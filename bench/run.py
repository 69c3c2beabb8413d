#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, in this process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's workloads; its configuration,
traffic mix, engine sizes, check limit and metric readers are files under
bench/, found by name (bench/benchlib/spec.py). Set-up makes the weights
from the seed, calibrates and warms up the cell's shapes; the window then
serves the requests that arrive in `--seconds` of the server's clock
through ICCServer and drains them; the check compares a sample of what was
served with the plain float32 reference.

With --trace 0 the result carries the cell's end-to-end metrics, with
--trace 1 its per-layer metrics, read from a profiler trace of part of the
window. The last line of stdout is the result object; the numbers compared
for `correct` are the last lines of stderr. Without a TPU, or with fewer
chips than the cell asks for, the run exits 3 and prints no result.

JAX's persistent compilation cache is kept in .jax_cache/ at the root of
the checkout (or where JAX_COMPILATION_CACHE_DIR says).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "bench"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        import repro
        from benchlib import harness, spec
    except ImportError as e:
        print(f"bench/run.py: the program under test is missing: {e}",
              file=sys.stderr)
        return 2
    if not repro.__file__.startswith(os.path.join(ROOT, "src") + os.sep):
        print(f"bench/run.py: repro imported from {repro.__file__}, not from "
              "this checkout", file=sys.stderr)
        return 2
    try:
        cell = spec.cell(args.workload)
        harness.require_chips(cell.chips)
    except (spec.SpecError, harness.NoChip) as e:
        print(f"bench/run.py: {e}; nothing was run", file=sys.stderr)
        return 3
    harness.use_cache()
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
