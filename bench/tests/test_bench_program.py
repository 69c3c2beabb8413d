"""The program's spans and counters as the benchmark reads them: the idle
split by hand on a tiny trace, a CPU window served with a recorder against
the engine proxy's stamps and counts, and the script that reads them on
the chip refusing to run without one."""

import copy
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from benchlib import harness, program, spec, tracing  # noqa: E402
from repro.serving import ICCServer  # noqa: E402
from repro.telemetry import EventRecorder  # noqa: E402

# device busy [0, 20) [30, 40) [60, 61): 31 of the 70 ns that the ops and
# spans cover, so 39 idle
TINY = {
    "device": {
        "ops": [["fusion.1", 0, 10], ["fusion.2", 5, 15], ["dot.3", 30, 10],
                ["fusion.1", 60, 1]],
        "modules": [["jit_decode(7)", 0, 20], ["jit_prefill(3)", 30, 10],
                    ["jit_decode(7)", 60, 1]],
    },
    "host": [["step", 0, 25], ["admit", 26, 32], ["prefill", 28, 17],
             ["reap", 58, 1], ["step", 62, 8]],
}
SPANS = [
    ["engine.step", 0, 25], ["engine.step.dispatch", 0, 2],
    ["engine.step.sync", 2, 19], ["engine.step.update", 21, 2],
    ["engine.step.readback", 23, 2],
    ["icc.admit", 26, 32], ["engine.prefill", 28, 17],
    ["engine.prefill.dispatch", 28, 2], ["engine.prefill.sync", 30, 11],
    ["engine.splice", 41, 2], ["engine.prefill.update", 43, 2],
    ["icc.reap", 58, 1],
    ["engine.step", 62, 8], ["engine.step.dispatch", 62, 1],
    ["engine.step.sync", 63, 5], ["engine.step.readback", 68, 2],
]


def test_tiny_trace_split_by_hand():
    s = program.idle_split(TINY, SPANS)
    assert (s.window_ns, s.idle_ns) == (70, 39)
    # step 1: idle [20, 25) = sync 1, update 2, readback 2; step 2: all 8
    # of [62, 70) = dispatch 1, sync 5, readback 2
    assert (s.steps, s.step_idle_ns) == (2, 5 + 8)
    # prefill [28, 45) less [30, 40): dispatch 2, sync 1, splice 2, update 2
    assert (s.prefills, s.prefill_idle_ns) == (1, 7)
    assert s.self_idle_ns == {
        "engine.step": 0, "engine.step.dispatch": 1, "engine.step.sync": 6,
        "engine.step.update": 2, "engine.step.readback": 4,
        "engine.prefill": 0, "engine.prefill.dispatch": 2,
        "engine.prefill.sync": 1, "engine.splice": 2,
        "engine.prefill.update": 2,
        # [26, 28) and [45, 58); [58, 59); [25, 26) [59, 60) [61, 62)
        "icc.admit": 15, "icc.reap": 1, program.OUTSIDE: 3,
    }
    assert s.server_idle_ns == 15 + 1 + 3
    assert s.metrics() == {"step_idle_ms": pytest.approx(6.5e-6),
                           "prefill_idle_ms": pytest.approx(7e-6),
                           "server_idle_pct": pytest.approx(100 * 19 / 70)}
    # the three parts sum to the idle share the benchmark's reduction
    # reads over the same window
    sh = s.shares_pct()
    idle = 100 * tracing.summarize(TINY, window_s=70e-9).idle_share
    assert sh["step"] + sh["prefill"] + sh["server"] == pytest.approx(idle)
    assert sh["idle"] == pytest.approx(idle)


def test_idle_in_no_part_leaves_the_sum_short():
    """Server idle is read from the `icc.*` spans and the time outside
    every span, not left over from the whole: idle under a span of none of
    the three parts (here an `engine.*` span outside the engine's two
    calls) shows as a sum short of the whole."""
    stray = [sp for sp in SPANS if sp[0] != "icc.reap"] + [["engine.x", 58, 1]]
    s = program.idle_split(TINY, stray)
    assert s.self_idle_ns["engine.x"] == 1
    assert (s.step_idle_ns, s.prefill_idle_ns, s.server_idle_ns) == (13, 7, 18)
    sh = s.shares_pct()
    assert sh["idle"] - (sh["step"] + sh["prefill"] + sh["server"]) == \
        pytest.approx(100 * 1 / 70)


def test_program_spans_change_no_reading_of_the_trace():
    """`load_xplane` keeps the benchmark's own host spans by name, which
    no program span takes, so every existing reading stays."""
    names = {n for n, _, _ in SPANS}
    assert all(n.startswith(program.PREFIXES) for n in names)
    assert not names & set(tracing.HOST_SPANS)
    plain = tracing.summarize(TINY, window_s=70e-9)
    with_spans = tracing.summarize({**TINY, "program": SPANS}, window_s=70e-9)
    assert with_spans == plain


def test_no_device_activity_splits_nothing():
    empty = {"device": {"ops": [], "modules": []}, "host": []}
    assert program.idle_split(empty, SPANS) is None


def test_counter_metrics():
    from repro.serving import EngineCounters

    c = EngineCounters(steps=4, prefills=3, slot_steps=7, step_host_syncs=11)
    assert program.counter_metrics(c, max_batch=2) == {
        "host_syncs_per_step": 11 / 4,
        "batch_occupancy_pct": pytest.approx(100 * 7 / 8)}
    assert set(program.counter_metrics(EngineCounters(), 2).values()) == {None}


SMALL = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
         "d_ff": 256, "vocab_size": 512}
SEED = 2**31 + 91


def small_cell():
    c = spec.cell("glm4_ar_steady")
    conf = copy.deepcopy(c.config)
    conf["overrides"] = {**conf["overrides"], **SMALL, "vocab_pad_multiple": 64}
    conf["model"].update(SMALL, head_dim=32)
    c.config = conf
    c.traffic = {**c.traffic, "rate_rps": 20.0}
    return c


def test_recorded_window_agrees_with_the_proxy():
    """The program's queue wait (admit - arrival) and e2e equal the
    proxy's stamps for every request, and its counters the proxy's
    counts of calls and slots stepped."""
    setup = harness.set_up(small_cell(), SEED)
    reqs, icc = harness.make_requests(setup, 1.5, SEED)
    counter = harness.CompileCounter()
    rec = EventRecorder(sample_every_s=1e-6)
    try:
        with program.recording(rec):
            win = harness.serve_window(setup, reqs, icc, counter)
    finally:
        counter.close()
    assert harness.ICCServer is ICCServer  # restored after the block
    run = harness.record(setup, win, 1.5, 0.0, {}, None)
    served = run.served()
    assert len(served) == len(win.stats.e2e) > 10
    tel = rec.to_telemetry()
    row = {u: i for i, u in enumerate(tel["jobs"]["uid"])}
    for r in run.requests:
        i = row[r.uid]
        if r.admitted is None:
            assert tel["jobs"]["drop_reason"][i] == "infeasible"
            continue
        start, arrival = tel["jobs"]["t_start"][i], tel["jobs"]["t_arrival"][i]
        assert start - arrival == pytest.approx(r.admitted - r.arrival, abs=1e-12)
        if r.served:
            e2e = tel["jobs"]["t_complete"][i] - tel["jobs"]["t_gen"][i]
            assert e2e == pytest.approx(r.e2e, abs=1e-12)
            assert sum(rec.stage_breakdown(r.uid).values()) == \
                pytest.approx(r.e2e, abs=1e-9)
    c = setup.engine.counters
    assert c.prefills == len(win.proxy.prefill_calls)
    assert c.steps == len(win.proxy.decode_calls)
    assert c.slot_steps == sum(len(p) for _, p, _ in win.proxy.decode_calls)
    assert c.step_host_syncs == c.steps + c.slot_steps
    assert program.stall_p95_ms(rec, [r.uid for r in served]) >= 0.0
    # a sample per call, at the clock the proxy saw, covering its time
    walls = []
    for kind in ("prefill", "step"):
        s = rec.series[f"engine.{kind}"]
        calls = [(t, dt) for k, t, dt in win.proxy.calls if k == kind]
        assert s["t"] == [t for t, _ in calls]
        assert all(w >= dt for w, (_, dt) in zip(s["wall_s"], calls))
        walls += s["wall_s"]
    longest = program.longest_calls(rec)
    assert [c["wall_s"] for c in longest] == sorted(walls, reverse=True)[:3]
    totals = program.call_totals(rec)
    assert totals["engine.step"]["calls"] == c.steps
    assert totals["engine.prefill"]["wall_s"] == pytest.approx(
        sum(rec.series["engine.prefill"]["wall_s"]))


def test_trace_program_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "trace_program.py"),
         "--workload", "glm4_ar_steady", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3
    assert "TPU" in p.stderr and not p.stdout


CHILDREN = {
    "engine.step": {"engine.step.dispatch", "engine.step.sync",
                    "engine.step.update", "engine.step.readback"},
    "engine.prefill": {"engine.prefill.dispatch", "engine.prefill.sync",
                       "engine.splice", "engine.prefill.update"},
    "icc.admit": {"engine.prefill"},
}


def test_the_served_path_writes_its_spans(tmp_path):
    """Served under the profiler on the CPU, the program writes each span
    once per call, every child inside a parent of its own."""
    import glob

    import jax

    setup = harness.set_up(small_cell(), SEED)
    reqs, icc = harness.make_requests(setup, 0.3, SEED)
    eng = setup.engine
    eng.reset()
    srv = ICCServer(eng, est_latency=setup.est_latency)
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.run(icc)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = program.load_program_spans(path)
    names = [n for n, _, _ in spans]
    c = eng.counters
    assert names.count("engine.step") == c.steps > 0
    assert names.count("engine.prefill") == c.prefills == len(reqs)
    for parent, kids in CHILDREN.items():
        outer = [(s, s + d) for n, s, d in spans if n == parent]
        for kid in kids:
            inner = [(s, s + d) for n, s, d in spans if n == kid]
            if parent != "icc.admit":  # an admission may drop or find no slot
                assert len(inner) == len(outer), kid
            assert all(any(a <= s and e <= b for a, b in outer)
                       for s, e in inner), kid
    assert set(names) == set(CHILDREN) | set().union(*CHILDREN.values()) \
        | {"icc.reap"}


RECORDED = os.path.join(BENCH, "tests", "data", "trace_program_small.json")
RASTER_NS = 10


def test_recorded_program_trace_against_an_independent_count():
    """A TPU v5e trace with the program's spans: the idle split against
    a count on a 10 ns raster, each cell marked busy or idle and named by
    the innermost span painted over it."""
    import json

    import numpy as np

    with open(RECORDED) as f:
        tr = json.load(f)
    ops, spans = tr["device"]["ops"], tr["program"]
    names = [n for n, _, _ in spans]
    assert len(ops) > 100 and {"engine.step", "engine.prefill",
                               "icc.admit"} <= set(names)
    s = program.idle_split(tr, spans)
    t0 = min(x[1] for x in ops + spans)
    t1 = max(x[1] + x[2] for x in ops + spans)
    assert s.window_ns == pytest.approx(t1 - t0)

    def cells(start, dur):
        return (int((start - t0) // RASTER_NS),
                int((start + dur - t0) // RASTER_NS))

    n = cells(t1, 0)[0] + 1
    busy = np.zeros(n, bool)
    for _, st, d in ops:
        a, b = cells(st, d)
        busy[a:b] = True
    label = np.full(n, -1, np.int32)
    order = sorted(range(len(spans)), key=lambda i: (spans[i][1], -spans[i][2]))
    for i in order:  # parents first: an inner span paints over its parent
        a, b = cells(spans[i][1], spans[i][2])
        label[a:b] = i
    idle = ~busy
    by_name = {}
    for i, name in enumerate(names):
        by_name[name] = by_name.get(name, 0) + RASTER_NS * int(
            np.count_nonzero(idle & (label == i)))
    by_name[program.OUTSIDE] = RASTER_NS * int(np.count_nonzero(idle & (label < 0)))
    # each edge of a busy interval or a span moves by under one cell
    edges = 2 * (len(tracing.union((st, st + d) for _, st, d in ops)) + len(spans))
    tol = RASTER_NS * edges
    assert RASTER_NS * int(idle.sum()) == pytest.approx(s.idle_ns, abs=tol)
    assert set(by_name) == set(s.self_idle_ns)
    for name, ns in by_name.items():
        assert s.self_idle_ns[name] == pytest.approx(ns, abs=tol), name
    step = sum(v for k, v in by_name.items() if k.startswith("engine.step"))
    prefill = sum(v for k, v in by_name.items()
                  if k.startswith("engine.prefill") or k == "engine.splice")
    assert s.step_idle_ns == pytest.approx(step, abs=tol)
    assert s.prefill_idle_ns == pytest.approx(prefill, abs=tol)
    assert (s.steps, s.prefills) == (names.count("engine.step"),
                                     names.count("engine.prefill"))
    # the reduction the benchmark already has reads the same busy time
    assert tracing.busy_ns(tr) == pytest.approx(s.window_ns - s.idle_ns, abs=1)
