#!/usr/bin/env python3
"""One window of a cell with the program's own spans and counters read.

    python3 bench/trace_program.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up and window are bench/run.py's (the check is not run). With
--trace 1 the server is given an `EventRecorder` and the profiler records
the part of the window that run.py's traced runs record; the last stdout
line then holds the program's per-layer numbers (bench/benchlib/program.py)
beside the cell's own readers, and stderr one line with the device's idle
time by innermost program span and the longest engine calls, with the
thread's CPU seconds across each. With --trace 0 neither
recorder nor profiler runs: the host times then give the cost of tracing.
Like run.py it needs a TPU and exits 3 without one.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "bench"))


def step_ms_between(calls, t0, t1):
    """Mean host ms of the proxy's `step` calls entered in [t0, t1) of the
    server clock."""
    v = [dt for kind, t, dt in calls if kind == "step" and t0 <= t < t1]
    return 1e3 * sum(v) / len(v) if v else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    from benchlib import harness, program, spec
    from benchlib.proxy import TraceWindow
    from benchlib.tracing import load_xplane, summarize
    from repro.telemetry import EventRecorder

    try:
        cell = spec.cell(args.workload)
        devices = harness.require_chips(cell.chips)
    except (spec.SpecError, harness.NoChip) as e:
        print(f"bench/trace_program.py: {e}; nothing was run", file=sys.stderr)
        return 3
    harness.use_cache()
    peaks = spec.peaks(devices[0].device_kind)
    counter = harness.CompileCounter()
    setup = harness.set_up(cell, args.seed)
    reqs, icc = harness.make_requests(setup, args.seconds, args.seed)
    lo = harness.TRACE_FROM * args.seconds
    hi = lo + min(harness.TRACE_SECONDS, 0.5 * args.seconds)
    traced = bool(args.trace)
    tw = TraceWindow(lo, hi) if traced else None
    # a sample per engine call: calls are never closer than 1 us
    rec = EventRecorder(sample_every_s=1e-6) if traced else None
    setup_s = time.perf_counter() - T_START
    with program.recording(rec):
        win = harness.serve_window(setup, reqs, icc, counter, tw)

    summary, split, spans = None, None, []
    if tw is not None:
        path = tw.xplane()
        if path and tw.done:
            trace = load_xplane(path)
            spans = program.load_program_spans(path)
            summary = summarize(trace, tw.wall[1] - tw.wall[0])
            split = program.idle_split(trace, spans)
        tw.cleanup()

    run = harness.record(setup, win, args.seconds, setup_s, peaks, summary)
    readers = {}
    for ms in (cell.per_layer if traced else []) + cell.end_to_end:
        v = spec.metric_module(ms.name).read(run)
        if v is not None:
            readers[ms.name] = v
    eng = setup.engine
    out = {"workload": cell.name, "seed": args.seed, "traced": traced,
           "device": {"kind": devices[0].device_kind, "count": len(devices)},
           "readers": readers,
           "step_ms_in_trace_interval": step_ms_between(win.proxy.calls, lo, hi),
           "counters": dataclasses.asdict(eng.counters),
           "program": program.counter_metrics(eng.counters,
                                              cell.workload["max_batch"])}
    if rec is not None:
        served = [r.uid for r in run.served()]
        out["program"]["prefill_stall_p95_ms"] = program.stall_p95_ms(rec, served)
        out["calls_over_50ms"] = [c for c in program.longest_calls(rec, 10**9)
                                  if c["wall_s"] > 0.05]
        out["call_totals"] = program.call_totals(rec)
    if spans:
        # engine calls of over 50 ms in the trace, with what ran inside
        # them for over a millisecond
        out["long_spans"] = [
            [[n, s, d] for n, s, d in spans
             if d > 1e6 and top[1] <= s and s + d <= top[1] + top[2]]
            for top in spans
            if top[0] in ("engine.step", "engine.prefill") and top[2] > 5e7]
    if split is not None:
        out["program"].update(split.metrics())
        out["idle_shares_pct"] = split.shares_pct()
        out["idle_by_span_ms"] = {
            name: {"total": 1e-6 * ns,
                   "per_step": 1e-6 * ns / split.steps if split.steps else None,
                   "per_prefill": 1e-6 * ns / split.prefills
                   if split.prefills else None}
            for name, ns in sorted(split.self_idle_ns.items())}
        print(json.dumps({"idle_by_span_ms": {
            k: v["total"] for k, v in out["idle_by_span_ms"].items()},
            "longest_calls": program.longest_calls(rec)}),
            file=sys.stderr, flush=True)
    out["window"] = win.diagnostics()
    counter.close()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
