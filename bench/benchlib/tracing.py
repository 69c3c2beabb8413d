"""From a profiler trace to device busy time, idle share, device time per
program and the breakdown.

`load_xplane` reads the `.xplane.pb` the profiler wrote into a compact form
that the rest works on, and that a small recorded trace keeps in a JSON
file for the tests:

    {"device": {"ops": [[name, start_ns, dur_ns], ...],
                "modules": [[name, start_ns, dur_ns], ...]},
     "host": [[span, start_ns, dur_ns], ...]}

`ops` are the device plane's "XLA Ops" events (HLO ops, named up to the
" = " of their HLO text; a loop's op spans its body's ops), `modules` its
"XLA Modules" events (one per run of a compiled program, named after the
jitted function: the engine's prefill is "jit_prefill(...)", its decode
"jit_decode(...)"), `host` the benchmark's own host spans. Device and host
timestamps share the trace's clock, to within about a millisecond on a
v5e. Only the first device is read: every cell of this benchmark runs on
one chip.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HOST_SPANS = ("admit", "prefill", "step", "reap")
PROGRAMS = {"prefill": "jit_prefill", "decode": "jit_decode"}
Interval = Tuple[float, float]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device = None
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and device is None \
                and not plane.name.startswith("/device:CPU"):
            device = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    device[key] = [[e.name.split(" = ")[0], e.start_ns,
                                    e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events if e.name in HOST_SPANS]
    return {"device": device or {"ops": [], "modules": []}, "host": host}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge [start, end) intervals."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _intervals(events: Sequence[list]) -> List[Interval]:
    return [(float(s), float(s) + float(d)) for _, s, d in events]


def busy_ns(trace: dict) -> float:
    """Nanoseconds in which some operation ran on the device."""
    ev = trace["device"]["ops"] or trace["device"]["modules"]
    return sum(e - s for s, e in union(_intervals(ev)))


def program_ns(trace: dict, prefix: str) -> Tuple[float, int]:
    """(device nanoseconds, runs) of the compiled program named prefix."""
    runs = [d for n, _, d in trace["device"]["modules"] if n.startswith(prefix)]
    return float(sum(runs)), len(runs)


def top_ops(trace: dict, n: int = 10) -> List[Tuple[str, float]]:
    """The n ops of most device time, each named "<program>:<op>" after
    the program run in which it starts."""
    mods = sorted(trace["device"]["modules"], key=lambda m: m[1])
    starts = [m[1] for m in mods]
    tot: Dict[str, float] = {}
    for name, s, d in trace["device"]["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        prog = "?"
        if i >= 0 and s < mods[i][1] + mods[i][2]:
            prog = mods[i][0].split("(")[0]
        key = f"{prog}:{name}"
        tot[key] = tot.get(key, 0.0) + float(d)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v * 1e-9) for k, v in best]


def _span_at(host: Sequence[list], t: float) -> str:
    """The innermost host span open at time t."""
    best: Optional[Tuple[float, str]] = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or d < best[0]):
            best = (d, name)
    return best[1] if best else "outside_spans"


def idle_gaps(trace: dict, n: int = 10) -> List[Tuple[str, float]]:
    """The n longest gaps between device activity, each named by the host
    span open at its middle."""
    ev = trace["device"]["ops"] or trace["device"]["modules"]
    busy = union(_intervals(ev))
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])]
    gaps.sort(key=lambda g: -g[0])
    return [(_span_at(trace["host"], (s + e) / 2), g * 1e-9)
            for g, s, e in gaps[:n]]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    program_s: Dict[str, float]  # device seconds per program key
    program_runs: Dict[str, int]
    device_ops: List[Tuple[str, float]]
    gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(trace: dict, window_s: float) -> Optional[TraceSummary]:
    """None where the trace holds no device operation."""
    if not trace["device"]["ops"] and not trace["device"]["modules"]:
        return None
    prog_s, prog_n = {}, {}
    for key, prefix in PROGRAMS.items():
        ns, runs = program_ns(trace, prefix)
        prog_s[key], prog_n[key] = ns * 1e-9, runs
    return TraceSummary(window_s=window_s, busy_s=busy_ns(trace) * 1e-9,
                        program_s=prog_s, program_runs=prog_n,
                        device_ops=top_ops(trace), gaps=idle_gaps(trace))
