"""The check that decides `correct`, driven through a whole run at smoke
size on the CPU (the look for a chip skipped): a sound run is correct, a
run whose served path is broken underneath is not, and the control, the
reference computed in fp8 in the program's place, fails the limit."""

import copy
import os
import sys
import time

import jax
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from benchlib import check, harness, spec  # noqa: E402

SEED = 2**31 + 77
# four layers, so that the fp8 control compounds its rounding as a deep
# stack does
SMALL = {"n_layers": 4, "d_model": 256, "n_heads": 8, "n_kv_heads": 2,
         "d_ff": 512, "vocab_size": 1024}
# the v5e row of the table stands in for the CPU's peaks in these runs
PEAKS = spec.load_json(spec.BENCH_DIR / "peaks.json")["devices"]["TPU v5 lite"]


def small_cell():
    c = spec.cell("glm4_ar_steady")
    conf = copy.deepcopy(c.config)
    conf["overrides"] = {**conf["overrides"], **SMALL, "vocab_pad_multiple": 64}
    conf["model"].update(SMALL, head_dim=32)
    c.config = conf
    c.traffic = {**c.traffic, "rate_rps": 20.0}
    return c


def run(fault=None):
    return harness.run_cell(small_cell(), SEED, 2.0, False, time.perf_counter(),
                            require=lambda n: jax.devices(), peaks=PEAKS,
                            fault=fault)


def alter_first_token(setup):
    """Each request's first token is changed where the prefill produces it."""
    eng = setup.engine
    submit = eng.submit
    vocab = setup.cell.config["model"]["vocab_size"]

    def faulty(req):
        slot = submit(req)
        toks = eng.results[req.uid].tokens
        toks[0] = (toks[0] + 1) % vocab
        return slot

    eng.submit = faulty


def cache_not_written(setup):
    """The decode step returns the cache it was given: tokens decoded after
    the prompt are never attended to."""
    eng = setup.engine
    decode = eng._decode
    eng._decode = lambda params, cache, tok, pos: (decode(params, cache, tok, pos)[0],
                                                   cache)


def test_sound_run_is_correct():
    out = run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 40
    assert list(out)[-1] == "checks"
    # the cell's end-to-end metrics, every one of them read
    assert set(out["metrics"]) == {m.name for m in small_cell().end_to_end}


@pytest.mark.parametrize("fault", [alter_first_token, cache_not_written])
def test_broken_served_path_is_not_correct(fault):
    out = run(fault)
    assert not out["correct"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_fp8_control_fails_the_limit():
    cell = small_cell()
    counter = harness.CompileCounter()
    setup = harness.set_up(cell, SEED)
    reqs, icc = harness.make_requests(setup, 2.0, SEED)
    win = harness.serve_window(setup, reqs, icc, counter)
    counter.close()
    harness.free_engine(setup)
    rows = harness.sample_rows(setup, win, SEED)
    m, lim = cell.config["model"], cell.workload["check"]
    prog = check.widest_gap(setup.ref, setup.params, m, rows, lim["ref_batch"])
    ctl = check.widest_gap(setup.ref, setup.params, m, rows, lim["ref_batch"],
                           quant="fp8")
    assert prog.max_gap <= lim["max_logit_gap"] < ctl.max_gap
    assert ctl.tokens == prog.tokens >= lim["min_compared_tokens"]
