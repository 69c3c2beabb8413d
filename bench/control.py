#!/usr/bin/env python3
"""The readings a cell's check limit is set from: the program's widest
logit gap over many seeds, and the fp8 control's over a few.

    python3 bench/control.py --workload <cell> --seeds 1,...,12 --control-seeds 1,2,3 --seconds 6

One process, the cell's set-up once. For each seed: weights from that
seed, a short window at the cell's own load through the same server path
as bench/run.py, and the same sample of finished requests that a run
compares. The program's reading is the widest gap of its served tokens in
the float32 reference; the control's, for the control seeds, is the widest
gap of the tokens the reference computed in fp8 puts first at the same
positions. The limit in bench/workloads/<cell>.json lies between the
largest program reading and the smallest control reading (PERF.md).
bench/run.py never runs the control.
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "bench"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    import jax
    from benchlib import check, harness, spec

    cell = spec.cell(args.workload)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 3
    harness.use_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    m, lim = cell.config["model"], cell.workload["check"]
    counter = harness.CompileCounter()
    setup = harness.set_up(cell, seeds[0])
    prog, ctrl = [], []
    for seed in seeds:
        if seed != setup.seed:
            setup.engine.params = setup.params = None
            gc.collect()
            setup.params = jax.block_until_ready(
                setup.ref.make_params(harness.key_from_seed(seed), m))
            setup.engine.params, setup.seed = setup.params, seed
        reqs, icc = harness.make_requests(setup, args.seconds, seed)
        win = harness.serve_window(setup, reqs, icc, counter)
        rows = harness.sample_rows(setup, win, seed)
        p = check.widest_gap(setup.ref, setup.params, m, rows, lim["ref_batch"])
        row = {"seed": seed, "program_gap": p.max_gap, "tokens": p.tokens,
               "failed": harness.malformed(win, m["vocab_size"]),
               "compiles_in_window": win.compiles}
        prog.append(p.max_gap)
        if seed in ctl:
            q = check.widest_gap(setup.ref, setup.params, m, rows,
                                 lim["ref_batch"], quant="fp8")
            row["control_gap"] = q.max_gap
            ctrl.append(q.max_gap)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload,
                      "program_max": max(prog),
                      "control_min": min(ctrl) if ctrl else None,
                      "limit": lim["max_logit_gap"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
