"""Nemotron-4's float32 reference (bench/reference/nemotron4.py) against
the served program at smoke size on the CPU: the engine's prefill, then
decode through the batched cache, with the benchmark's own weights; the
tolerance tells the published block from each departure the program used
to run; the counts by hand; and one whole run of the cell."""

import copy
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from benchlib import harness, spec  # noqa: E402
from repro.models import RuntimeFlags, build_model  # noqa: E402
from repro.serving import GenRequest, InferenceEngine  # noqa: E402

CONFIG = "nemotron-4-15b-pp4"
# test_bench_reference.py's small size; head_dim 16, so 8 dims rotate
SMALL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 1,
         "d_ff": 128, "vocab_size": 256}
TOL = 2e-4  # float32 program against float32 reference: summation order
ref = spec.reference_module("nemotron4")


def small_config(dtype: str = "float32") -> dict:
    conf = copy.deepcopy(spec.config(CONFIG))
    conf["overrides"] = {**conf["overrides"], **SMALL,
                         "vocab_pad_multiple": 64, "dtype": dtype}
    conf["model"].update(SMALL, head_dim=16, dtype=dtype)
    return conf


@pytest.fixture(scope="module")
def served():
    """Three requests over two slots (slots at mixed positions, one
    refilled): each request's tokens and the logits the engine served."""
    conf = small_config()
    m = conf["model"]
    model = build_model(harness.program_config(conf), RuntimeFlags(remat=False))
    params = ref.make_params(harness.key_from_seed(7), m)
    harness._check_layout(model, params)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, m["vocab_size"], n, dtype=np.int32) for n in (5, 9, 3)]
    eng = InferenceEngine(model, params, max_batch=2, max_seq=16)
    res = eng.generate([GenRequest(uid=i, prompt=jnp.asarray(p),
                                   max_new_tokens=6, keep_logits=True)
                        for i, p in enumerate(prompts)])
    rows = []
    for i, p in enumerate(prompts):
        toks = np.concatenate([p, np.asarray(res[i].tokens[:-1], np.int32)])
        got = np.stack([np.asarray(x, np.float32) for x in res[i].logits])
        rows.append((toks, len(p), got))
    return m, params, rows


def _ref_logits(params, m, rows):
    return [np.asarray(ref.logits(params, m, jnp.asarray(t[None]), n - 1))[0]
            for t, n, _ in rows]


def test_reference_matches_prefill_then_cached_decode(served):
    m, params, rows = served
    for want, (_, _, got) in zip(_ref_logits(params, m, rows), rows):
        assert got.shape == want.shape == (6, m["vocab_size"])
        np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


def _rms(m, params):
    return dict(m, norm="rms"), params


def _full_rope(m, params):
    return dict(m, partial_rotary_factor=1.0), params


def _no_bias(m, params):
    return m, jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if str(path[-1].key).endswith("_bias") else a, params)


@pytest.mark.parametrize("departure", [_rms, _full_rope, _no_bias],
                         ids=["rmsnorm", "full_rope", "no_beta"])
def test_each_departure_misses_the_tolerance(served, departure):
    m, params, rows = served
    dm, dparams = departure(m, params)
    for want, (_, _, got) in zip(_ref_logits(dparams, dm, rows), rows):
        assert not np.allclose(got, want, rtol=TOL, atol=TOL)


def test_program_mismatch_names_the_block():
    conf = spec.config(CONFIG)
    cfg = harness.program_config(conf)  # runs the block the file states
    assert (cfg.norm, cfg.rope_dim) == ("layernorm1p", 64)
    for key, other in (("norm", "rms"), ("partial_rotary_factor", 1.0),
                       ("mlp", "gated_silu")):
        m = dict(conf["model"], **{key: other})
        assert key in ref.program_mismatch(cfg, m)


def test_weights_follow_the_seed_and_the_program_layout():
    m = small_config("bfloat16")["model"]
    a = ref.make_params(harness.key_from_seed(2**33 + 5), m)
    b = ref.make_params(harness.key_from_seed(2**33 + 5), m)
    c = ref.make_params(harness.key_from_seed(5), m)
    assert all(jax.tree.leaves(jax.tree.map(lambda x, y: bool((x == y).all()), a, b)))
    assert not bool((a["layers"]["mlp"]["w1"] == c["layers"]["mlp"]["w1"]).all())
    assert jax.tree.map(lambda x: x.shape, a) == jax.tree.map(
        tuple, ref.param_shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    # gains 1 + g and biases b are drawn away from 0, so a path that drops
    # a bias or the 1 shows
    for name in ("attn_norm", "mlp_norm", "attn_norm_bias", "mlp_norm_bias"):
        assert float(jnp.std(a["layers"][name].astype(jnp.float32))) > 0.1


# d 8, 2 query heads of 4 over 1 kv head, d_ff 16, vocab 32, 2 layers
TINY = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
        "head_dim": 4, "d_ff": 16, "vocab_size": 32, "qkv_bias": False,
        "mlp": "relu2", "rope_theta": 1e4, "partial_rotary_factor": 0.5,
        "norm": "layernorm1p", "norm_eps": 1e-5, "tie_embeddings": False,
        "dtype": "bfloat16"}


def test_counts_by_hand():
    # q 8*2*4=64, k 32, v 32, o 64 -> 192; mlp 2*8*16=256
    assert ref.layer_matmul_params(TINY) == 448
    # + gain and bias of two norms: 4 * 8
    assert ref.layer_params(TINY) == 448 + 32
    flops, nbytes = ref.prefill_counts(TINY, 3)
    # per layer 2*448*3 matmul, attention 4*2*4*(1+2+3); head one row 2*8*32
    assert flops == 2 * (2 * 448 * 3 + 192) + 512
    # weights 2*480 + head 256 + final gain and bias 16 + 3 rows * 8;
    # kv written 3 tokens * 2 layers * (k, v) * 1 head * 4 dims; bf16
    assert nbytes == 2 * (2 * 480 + 256 + 16 + 24) + 2 * (3 * 2 * 2 * 4)
    flops, nbytes = ref.decode_counts(TINY, (5, 0))
    assert flops == 2 * (2 * 448 * 2 + 4 * 2 * 4 * 7) + 2 * 8 * 32 * 2
    assert nbytes == 2 * (2 * 480 + 256 + 16 + 16) + 2 * (5 + 2) * 16


def test_the_cell_runs_whole_and_is_correct():
    """bench/run.py's path at smoke size (the look for a chip skipped):
    the window is served, the sample agrees with the reference, and the
    end-to-end metrics the cell reports are read."""
    c = spec.cell("nemotron4_chat_steady")
    conf = copy.deepcopy(c.config)
    small = dict(SMALL, n_layers=2, d_model=128, n_heads=4, n_kv_heads=2)
    conf["overrides"] = {**conf["overrides"], **small, "vocab_pad_multiple": 64}
    conf["model"].update(small, head_dim=32)
    c.config = conf
    c.traffic = {**c.traffic, "n_input": 6, "n_output": 8, "rate_rps": 8.0}
    c.workload = {**c.workload, "max_seq": 16, "check": {
        **c.workload["check"], "min_compared_tokens": 32}}
    peaks = spec.load_json(spec.BENCH_DIR / "peaks.json")["devices"]["TPU v5 lite"]
    out = harness.run_cell(c, 2**31 + 91, 1.5, False, time.perf_counter(),
                           require=lambda n: jax.devices(), peaks=peaks)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    for name in ("setup_s", "goodput_rps", "tpot_p95_ms", "e2e_p95_ms"):
        assert name in out["metrics"], name
