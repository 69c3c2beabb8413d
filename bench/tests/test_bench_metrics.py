"""Tail, goodput and per-layer arithmetic of the metric readers over a
synthetic request log that includes drops."""

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchlib import spec  # noqa: E402
from benchlib.record import RequestLog, RunRecord, least_time, p95  # noqa: E402
from benchlib.tracing import TraceSummary  # noqa: E402

M = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 16, "d_ff": 128, "vocab_size": 256, "qkv_bias": True,
     "mlp": "gated_silu", "rope_theta": 1e4, "norm_eps": 1e-5,
     "tie_embeddings": False, "dtype": "bfloat16"}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
REF = spec.reference_module("dense_decoder")


def _req(uid, t_gen, admitted, first, step, n_out=4, b_total=0.5):
    times = [] if admitted is None else [first + i * step for i in range(n_out)]
    return RequestLog(uid=uid, t_gen=t_gen, arrival=t_gen + 0.01,
                      b_total=b_total, n_output=n_out, admitted=admitted,
                      token_times=times)


@pytest.fixture
def run():
    reqs = [_req(0, 0.0, 0.02, 0.05, 0.02),   # e2e 0.11
            _req(1, 0.1, 0.11, 0.15, 0.03),   # e2e 0.14
            _req(2, 0.2, 0.30, 0.40, 0.10),   # e2e 0.50: in budget (<=)
            _req(3, 0.3, 0.45, 0.60, 0.10),   # e2e 0.60: late
            _req(4, 0.4, None, 0.0, 0.0)]     # dropped
    return RunRecord(
        cell="synthetic", seconds=2.0, model=M, ref=REF, peaks=PEAKS, setup_s=12.5,
        sent=5, dropped=1, requests=reqs,
        prefill_calls=[(0.004, 8, True), (0.006, 8, False)],
        decode_calls=[(0.010, (8, 9), True), (0.020, (9,), False)],
        prefill_s_program=[0.003, 0.005],
        trace=TraceSummary(window_s=2.0, busy_s=1.5,
                           program_s={"prefill": 0.008, "decode": 0.05},
                           program_runs={"prefill": 1, "decode": 1},
                           device_ops=[], gaps=[]))


def read(name, run):
    return spec.metric_module(name).read(run)


def test_tails_are_over_served_requests_only(run):
    e2e = [0.11, 0.14, 0.50, 0.60]
    assert read("e2e_p95_ms", run) == pytest.approx(1e3 * np.percentile(e2e, 95))
    ttft = [0.05 - 0.01, 0.15 - 0.11, 0.40 - 0.21, 0.60 - 0.31]
    assert read("ttft_p95_ms", run) == pytest.approx(1e3 * np.percentile(ttft, 95))
    tpot = [0.02, 0.03, 0.10, 0.10]
    assert read("tpot_p95_ms", run) == pytest.approx(1e3 * np.percentile(tpot, 95))


def test_goodput_counts_drops_and_late_requests_as_misses(run):
    assert read("goodput_rps", run) == pytest.approx(3 / 2.0)
    assert read("drop_pct", run) == pytest.approx(20.0)


def test_admission_and_engine_readers(run):
    waits = [0.02 - 0.01, 0.11 - 0.11, 0.30 - 0.21, 0.45 - 0.31]
    assert read("queue_wait_p95_ms", run) == pytest.approx(1e3 * np.percentile(waits, 95))
    assert read("prefill_ms", run) == pytest.approx(4.0)
    assert read("decode_step_ms", run) == pytest.approx(15.0)
    assert read("setup_s", run) == 12.5
    assert read("device_idle_pct", run) == pytest.approx(25.0)


def test_mfu_and_roofline_readers(run):
    fp = 2 * REF.prefill_counts(M, 8)[0]
    fd = REF.decode_counts(M, (8, 9))[0] + REF.decode_counts(M, (9,))[0]
    assert read("mfu_pct.prefill", run) == pytest.approx(100 * fp / (0.010 * 1e12))
    assert read("mfu_pct.decode", run) == pytest.approx(100 * fd / (0.030 * 1e12))
    assert read("mfu_pct.overload", run) == pytest.approx(
        100 * (fp + fd) / (0.040 * 1e12))
    # only the traced calls count against the traced device time
    lp = least_time(*REF.prefill_counts(M, 8), PEAKS)
    ld = least_time(*REF.decode_counts(M, (8, 9)), PEAKS)
    assert read("prefill_roofline", run) == pytest.approx(100 * lp / 0.008)
    assert read("decode_roofline", run) == pytest.approx(100 * ld / 0.05)


def test_readers_without_their_source_return_nothing(run):
    run.trace = None
    for name in ("prefill_roofline", "decode_roofline", "device_idle_pct"):
        assert read(name, run) is None
    run.requests = [r for r in run.requests if r.admitted is None]
    run.prefill_calls, run.decode_calls, run.prefill_s_program = [], [], []
    for name in ("e2e_p95_ms", "ttft_p95_ms", "tpot_p95_ms", "queue_wait_p95_ms",
                 "prefill_ms", "decode_step_ms", "mfu_pct.prefill",
                 "mfu_pct.decode", "mfu_pct.overload"):
        assert read(name, run) is None, name
    assert read("goodput_rps", run) == 0.0
    assert p95([]) is None
