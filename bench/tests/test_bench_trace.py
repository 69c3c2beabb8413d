"""The reduction from a profiler trace to busy time, idle share, device
time per program and the breakdown: by hand on a tiny trace, and on a
small trace recorded on a TPU v5e (glm4_ar_steady) against an independent
count."""

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchlib import tracing  # noqa: E402

RECORDED = os.path.join(BENCH, "tests", "data", "trace_small.json")

# ops at [0, 10), [5, 20), [30, 40), [60, 61) ns: busy 20 + 10 + 1 = 31
TINY = {
    "device": {
        "ops": [["fusion.1", 0, 10], ["fusion.2", 5, 15], ["dot.3", 30, 10],
                ["fusion.1", 60, 1]],
        "modules": [["jit_decode(7)", 0, 20], ["jit_prefill(3)", 30, 10],
                    ["jit_dynamic_update_slice", 60, 1]],
    },
    "host": [["step", -5, 30], ["admit", 25, 30], ["prefill", 28, 14],
             ["reap", 41, 5]],
}


def test_tiny_trace_by_hand():
    s = tracing.summarize(TINY, window_s=100e-9)
    assert s.busy_s == pytest.approx(31e-9)
    assert s.idle_share == pytest.approx(0.69)
    assert s.program_s == pytest.approx({"prefill": 10e-9, "decode": 20e-9})
    assert s.program_runs == {"prefill": 1, "decode": 1}
    assert s.device_ops[0] == ("jit_decode:fusion.2", pytest.approx(15e-9))
    ops = dict(s.device_ops)
    assert ops["jit_decode:fusion.1"] == pytest.approx(10e-9)
    assert ops["jit_dynamic_update_slice:fusion.1"] == pytest.approx(1e-9)
    assert ops["jit_prefill:dot.3"] == pytest.approx(10e-9)
    # gaps: [20, 30) inside admit only -> admit; [40, 60) at 50: admit
    # (reap ended at 46)
    assert s.gaps == [("admit", pytest.approx(20e-9)), ("admit", pytest.approx(10e-9))]


def test_no_device_activity_reads_nothing():
    assert tracing.summarize({"device": {"ops": [], "modules": []}, "host": []},
                             1.0) is None


def _raster_busy_ns(events, t0, t1):
    """Busy nanoseconds by marking a 1 ns raster: independent of union()."""
    grid = np.zeros(int(t1 - t0) + 1, bool)
    for _, s, d in events:
        grid[int(s - t0):int(s - t0 + d)] = True
    return int(grid.sum())


def test_recorded_trace_against_an_independent_count():
    with open(RECORDED) as f:
        tr = json.load(f)
    ops = tr["device"]["ops"]
    assert len(ops) > 100 and tr["device"]["modules"] and tr["host"]
    t0 = min(s for _, s, _ in ops)
    t1 = max(s + d for _, s, d in ops)
    busy = tracing.busy_ns(tr)
    assert busy == pytest.approx(_raster_busy_ns(ops, t0, t1), abs=len(ops))
    s = tracing.summarize(tr, window_s=(t1 - t0) * 1e-9)
    assert 0.0 <= s.idle_share < 1.0
    for key, prefix in tracing.PROGRAMS.items():
        runs = [d for n, _, d in tr["device"]["modules"] if n.startswith(prefix)]
        assert s.program_runs[key] == len(runs) > 0
        assert s.program_s[key] == pytest.approx(sum(runs) * 1e-9)
    # a program's device time lies inside the device's busy time
    assert sum(s.program_s.values()) <= s.busy_s * 1.001
    assert all(name in tracing.HOST_SPANS + ("outside_spans",) for name, _ in s.gaps)
    assert len(s.device_ops) == 10 and len(s.gaps) <= 10
