#!/usr/bin/env python3
"""Find a cell's knee: the highest Poisson rate at which at least 95% of
the requests sent finish within b_total (drops count as misses).

    python3 bench/sweep.py --workload <cell> --rates 3,3.5,4 --seeds 1,2 --seconds 51

One process: the cell's set-up once, then, for each rate in ascending
order, one window per seed through the same server path as bench/run.py,
with the cell's mix at that rate. A rate's share is pooled over its seeds.
The knee is the highest rate below the first rate that fails, so a rate
that passes by chance above a failing one does not count; the sweep stops
at that first failure. Prints one JSON line per window and per rate and,
last, the knee. Run once on the chip when a cell is defined; the rate the
cell then uses is a number in its traffic file, and the sweep goes into
PERF.md.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import List, Optional, Tuple  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "bench"))

ALPHA = 0.95  # the paper's Def. 2 share


def knee(shares: List[Tuple[float, float]], alpha: float = ALPHA) -> Optional[float]:
    """The highest rate below the first failing one, over (rate, share)
    pairs; None when the lowest rate already fails."""
    best = None
    for rate, share in sorted(shares):
        if share < alpha:
            break
        best = rate
    return best


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seeds", required=True, help="comma-separated, two or more")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    from benchlib import harness, spec
    from benchlib.record import p95

    cell = spec.cell(args.workload)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench/sweep.py: {e}", file=sys.stderr)
        return 3
    harness.use_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    counter = harness.CompileCounter()
    setup = harness.set_up(cell, seeds[0])
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "est_latency_s": setup.est_latency, **setup.phases}),
          flush=True)
    shares = []
    for rate in sorted(float(r) for r in args.rates.split(",")):
        mix = {**cell.traffic, "rate_rps": rate}
        sent = ok = 0
        for seed in seeds:
            reqs, icc = harness.make_requests(setup, args.seconds, seed, mix)
            win = harness.serve_window(setup, reqs, icc, counter)
            rec = harness.record(setup, win, args.seconds, 0.0, {})
            served = rec.served()
            n_ok = sum(1 for r in served if r.e2e <= r.b_total)
            sent, ok = sent + len(reqs), ok + n_ok
            print(json.dumps({
                "rate_rps": rate, "seed": seed, "sent": len(reqs),
                "served": len(served), "dropped": win.stats.n_dropped,
                "in_budget": n_ok, "share_in_budget": n_ok / len(reqs),
                "e2e_p95_ms": 1e3 * (p95([r.e2e for r in served]) or float("nan")),
                "ttft_p95_ms": 1e3 * (p95([r.ttft for r in served]) or float("nan")),
                "tpot_p95_ms": 1e3 * (p95([r.tpot for r in served]) or float("nan")),
                **win.diagnostics()}), flush=True)
        shares.append((rate, ok / sent))
        print(json.dumps({"rate_rps": rate, "share_in_budget": ok / sent}), flush=True)
        if ok / sent < ALPHA:
            break
    print(json.dumps({"workload": args.workload, "knee_rps": knee(shares)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
