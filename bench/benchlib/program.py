"""The program's own spans and counters, read beside the benchmark's.

The served path writes host spans into the profiler's trace
(`InferenceEngine`: `engine.prefill`, `engine.step` and their children;
`ICCServer`: `icc.admit`, `icc.reap`), counts steps, slots stepped and
device-to-host syncs (`InferenceEngine.counters`), and, given a
``recorder=``, books each request's stages and samples each engine call's
host seconds and thread CPU seconds.

This module reduces them to the per-layer numbers of a traced window:

    step_idle_ms          device idle inside `engine.step`, per step
    prefill_idle_ms       device idle inside `engine.prefill`, per admission
    server_idle_pct       device idle while no `engine.*` span is open
    host_syncs_per_step   step host syncs / steps
    batch_occupancy_pct   slots stepped / (steps x max_batch)
    prefill_stall_p95_ms  p95 over served requests of the `stall` stage

and puts the device's idle time down to the innermost program span open
over it. `tracing.load_xplane`, `summarize` and every reader of
`bench/metrics/` stay as they are. `harness.serve_window` takes no
recorder yet, so `recording` passes one to the server it builds; it and
`bench/trace_program.py` go once the harness passes the recorder itself.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import functools
from typing import Dict, Iterator, List, Optional, Sequence

from . import harness
from .record import p95
from .tracing import union

PREFIXES = ("engine.", "icc.")
OUTSIDE = "outside_spans"


def load_program_spans(path: str) -> List[list]:
    """The program's host spans in an `.xplane.pb`, as
    [[name, start_ns, dur_ns], ...] on the trace's clock."""
    from jax.profiler import ProfileData

    spans: List[list] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events if e.name.startswith(PREFIXES)]
    return spans


class _Busy:
    """Device busy time over any interval, from the union of op intervals."""

    def __init__(self, trace: dict):
        ev = trace["device"]["ops"] or trace["device"]["modules"]
        self.iv = union((float(s), float(s) + float(d)) for _, s, d in ev)
        self.starts = [s for s, _ in self.iv]
        self.cum = [0.0]
        for s, e in self.iv:
            self.cum.append(self.cum[-1] + e - s)

    def before(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        s, e = self.iv[i - 1]
        return self.cum[i - 1] + min(t, e) - s

    def idle(self, a: float, b: float) -> float:
        return (b - a) - (self.before(b) - self.before(a))


@dataclasses.dataclass
class IdleSplit:
    window_ns: float  # first to last device op or program span
    idle_ns: float  # device idle in the window
    steps: int  # engine.step spans
    step_idle_ns: float  # device idle inside them
    prefills: int  # engine.prefill spans
    prefill_idle_ns: float
    # device idle by the innermost program span open over it
    self_idle_ns: Dict[str, float]

    @property
    def server_idle_ns(self) -> float:
        """Device idle with no `engine.*` span open: inside `icc.*` spans
        but outside the engine's calls, or outside every program span."""
        return sum(v for k, v in self.self_idle_ns.items()
                   if k == OUTSIDE or k.startswith("icc."))

    def metrics(self) -> Dict[str, Optional[float]]:
        w = self.window_ns
        return {
            "step_idle_ms": 1e-6 * self.step_idle_ns / self.steps
            if self.steps else None,
            "prefill_idle_ms": 1e-6 * self.prefill_idle_ns / self.prefills
            if self.prefills else None,
            "server_idle_pct": 100.0 * self.server_idle_ns / w if w else None,
        }

    def shares_pct(self) -> Dict[str, float]:
        """Each part's idle as a share of the window, and the window's
        whole idle share. The parts are taken apart, so their sum falls
        short of the whole where idle lies in a span outside all three
        (one that does not nest, or an `engine.*` span outside the
        engine's two calls)."""
        w = self.window_ns
        return {"step": 100.0 * self.step_idle_ns / w,
                "prefill": 100.0 * self.prefill_idle_ns / w,
                "server": 100.0 * self.server_idle_ns / w,
                "idle": 100.0 * self.idle_ns / w}


def idle_split(trace: dict, spans: Sequence[list]) -> Optional[IdleSplit]:
    """Device idle time put down to the program's spans. Spans of one
    thread nest, so each span's self idle is its idle less its direct
    children's. None where the trace holds no device operation."""
    ev = trace["device"]["ops"] or trace["device"]["modules"]
    if not ev:
        return None
    busy = _Busy(trace)
    ivs = [(float(s), float(s) + float(d), n) for n, s, d in spans]
    t0 = min([s for _, s, _ in ev] + [s for s, _, _ in ivs])
    t1 = max([s + d for _, s, d in ev] + [e for _, e, _ in ivs])
    total = busy.idle(t0, t1)
    self_idle: Dict[str, float] = {}
    n = {"engine.step": 0, "engine.prefill": 0}
    inside = {"engine.step": 0.0, "engine.prefill": 0.0}
    # (end, name, idle, idle of direct children)
    stack: List[list] = []
    top_idle = 0.0

    def close(frame) -> None:
        nonlocal top_idle
        _, name, idle, kids = frame
        self_idle[name] = self_idle.get(name, 0.0) + idle - kids
        if stack:
            stack[-1][3] += idle
        else:
            top_idle += idle

    for s, e, name in sorted(ivs, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        idle = busy.idle(s, e)
        stack.append([e, name, idle, 0.0])
        if name in n:
            n[name] += 1
            inside[name] += idle
    while stack:
        close(stack.pop())
    self_idle[OUTSIDE] = total - top_idle
    return IdleSplit(window_ns=t1 - t0, idle_ns=total,
                     steps=n["engine.step"],
                     step_idle_ns=inside["engine.step"],
                     prefills=n["engine.prefill"],
                     prefill_idle_ns=inside["engine.prefill"],
                     self_idle_ns=self_idle)


def counter_metrics(counters, max_batch: int) -> Dict[str, Optional[float]]:
    """From `InferenceEngine.counters` over the window."""
    steps = counters.steps
    return {
        "host_syncs_per_step": counters.step_host_syncs / steps
        if steps else None,
        "batch_occupancy_pct": 100.0 * counters.slot_steps / (steps * max_batch)
        if steps else None,
    }


def stall_p95_ms(recorder, uids) -> Optional[float]:
    """p95 over the requests `uids` of the recorder's `stall` stage: time
    resident while other requests prefilled."""
    stalls = []
    for u in uids:
        st = recorder.stage_breakdown(u)
        if st is not None:
            stalls.append(st["stall"])
    v = p95(stalls)
    return None if v is None else 1e3 * v


def longest_calls(recorder, n: int = 3) -> List[dict]:
    """The n engine calls of most host time, with the thread's CPU seconds
    across each."""
    rows = []
    for track in ("engine.prefill", "engine.step"):
        s = recorder.series.get(track)
        if s is None:
            continue
        for i, t in enumerate(s["t"]):
            rows.append({"call": track, "t": t,
                         **{k: s[k][i] for k in s if k != "t"}})
    return sorted(rows, key=lambda r: -r["wall_s"])[:n]


def call_totals(recorder) -> Dict[str, dict]:
    """Per call kind: calls, host and thread CPU seconds."""
    out = {}
    for track in ("engine.prefill", "engine.step"):
        s = recorder.series.get(track)
        if s is None:
            continue
        out[track] = {"calls": len(s["t"]), "wall_s": sum(s["wall_s"]),
                      "cpu_s": sum(s["cpu_s"])}
    return out


@contextlib.contextmanager
def recording(recorder) -> Iterator[None]:
    """Within the block, the server `harness.serve_window` builds is given
    `recorder`."""
    server = harness.ICCServer
    harness.ICCServer = functools.partial(server, recorder=recorder)
    try:
        yield
    finally:
        harness.ICCServer = server

