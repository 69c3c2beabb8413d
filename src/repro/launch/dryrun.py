import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) case.

The two lines above MUST run before any other import (jax locks the device
count at first init) — this file is the only place the 512 placeholder
devices exist; tests and benches see 1 CPU device.

Per case:
  * jit(step, in_shardings=..., donate=...).lower(*abstract_args)
  * .compile()                      -> proves the sharding config lowers
  * compiled.memory_analysis()      -> per-device bytes (fits / doesn't)
  * analyze_hlo(compiled.as_text()) -> trip-count-corrected FLOPs, dot
                                       traffic, collective bytes
  * derive_roofline(...)            -> the three §Roofline terms

Results land in benchmarks/results/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
  python -m repro.launch.dryrun --all --mesh single
  python -m repro.launch.dryrun --all --mesh both --skip-existing
"""

import argparse
import dataclasses
import json
import logging
import time
import traceback

import jax
from jax.sharding import NamedSharding

from .. import sharding as sh
from ..configs import list_configs
from .hlo_analysis import analyze_hlo
from .mesh import make_production_mesh
from .roofline import derive_roofline
from .specs import SHAPES, build_case, skip_reason

# package logger ("repro" tree): importable callers (tests, sweep drivers)
# capture/filter case diagnostics; the CLI entrypoint wires a handler that
# reproduces the historical "[dryrun] ..." console lines
logger = logging.getLogger("repro.launch.dryrun")

ASSIGNED = [
    "qwen1.5-110b", "qwen2-vl-72b", "mixtral-8x22b", "seamless-m4t-large-v2",
    "glm4-9b", "nemotron-4-15b", "zamba2-7b", "mistral-large-123b",
    "xlstm-1.3b", "llama4-scout-17b-a16e",
]


def run_case(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             rules_override=None, tag: str = "",
             microbatches: int = 1) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    label = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    t0 = time.time()
    from ..configs import get_config

    reason = skip_reason(get_config(arch), SHAPES[shape_name])
    if reason:
        rec = {"case": label, "status": "skipped", "reason": reason}
        _write(out_dir, label, rec)
        logger.info("%s: SKIP (%s)", label, reason.split(";")[0])
        return rec

    try:
        case = build_case(arch, shape_name, rules_override=rules_override,
                          microbatches=microbatches)
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = mesh.size
        with sh.use_mesh(mesh, case.rules):
            to_ns = lambda spec: jax.tree.map(lambda s: NamedSharding(mesh, s), spec)
            in_shardings = tuple(
                to_ns(sh.tree_specs(a, ax))
                for a, ax in zip(case.args, case.arg_axes)
            )
            jitted = jax.jit(
                case.step, in_shardings=in_shardings, donate_argnums=case.donate
            )
            lowered = jitted.lower(*case.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            ma = compiled.memory_analysis()
            cost = analyze_hlo(compiled.as_text())
        roof = derive_roofline(cost, case.cfg, case.shape, chips)
        rec = {
            "case": label,
            "status": "ok",
            "arch": arch,
            "shape": shape_name,
            "mesh": [
                {k: v for k, v in zip(mesh.axis_names, mesh.devices.shape)}
            ][0],
            "chips": chips,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory": {
                "argument_gb": ma.argument_size_in_bytes / 1e9,
                "output_gb": ma.output_size_in_bytes / 1e9,
                "temp_gb": ma.temp_size_in_bytes / 1e9,
                "peak_est_gb": (
                    ma.argument_size_in_bytes + ma.temp_size_in_bytes
                ) / 1e9,
                "fits_16gb": (
                    ma.argument_size_in_bytes + ma.temp_size_in_bytes
                ) / 1e9 <= 16.0,
            },
            "hlo_cost": {
                "flops_per_device": cost.flops,
                "dot_bytes_per_device": cost.dot_bytes,
                "collective_bytes": {
                    k: v for k, v in sorted(cost.collective_bytes.items())
                },
                "unknown_trip_counts": cost.unknown_trip_counts,
            },
            "roofline": roof.as_dict(),
        }
        dom = roof.dominant
        logger.info(
            "%s: OK compile=%.0fs mem=%.1fGB terms(c/m/x)=%.3f/%.3f/%.3fs "
            "dom=%s useful=%.2f",
            label, t_compile, rec["memory"]["peak_est_gb"],
            roof.compute_s, roof.memory_s, roof.collective_s,
            dom, roof.useful_ratio,
        )
    except Exception as e:  # a failure here is a bug in the sharding config
        rec = {
            "case": label,
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
        logger.error("%s: ERROR %s: %s", label, type(e).__name__, str(e)[:200])
    _write(out_dir, label, rec)
    return rec


def _write(out_dir: str, label: str, rec: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, label + ".json"), "w") as f:
        json.dump(rec, f, indent=1, default=float)


def main() -> None:
    # CLI entrypoint: surface the case log on the console exactly as the
    # historical prints did (no-op if the caller configured logging already)
    logging.basicConfig(level=logging.INFO, format="[dryrun] %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--tag", default="", help="variant tag for the JSON name")
    ap.add_argument("--rules", default=None,
                    choices=["train_sp", "decode_v2", "decode_v3", "train_fsdp", "train_ep_cp", "train_ep_cp_sp", "decode_v3_ep"],
                    help="hillclimb rule-set override")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    from .. import sharding as shmod

    rules_override = {
        None: None,
        "train_sp": shmod.TRAIN_RULES_SP,
        "decode_v2": shmod.DECODE_RULES_V2,
        "decode_v3": shmod.DECODE_RULES_V3,
        "train_fsdp": shmod.TRAIN_RULES_FSDP,
        "train_ep_cp": shmod.TRAIN_RULES_EP_CP,
        "train_ep_cp_sp": shmod.TRAIN_RULES_EP_CP_SP,
        "decode_v3_ep": shmod.DECODE_RULES_V3_EP,
    }[args.rules]
    archs = ASSIGNED if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_err = n_skip = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                label = f"{arch}__{shape}__{'multi' if mp else 'single'}" + (
                    f"__{args.tag}" if args.tag else ""
                )
                path = os.path.join(args.out, label + ".json")
                if args.skip_existing and os.path.exists(path):
                    prev = json.load(open(path))
                    if prev.get("status") in ("ok", "skipped"):
                        continue
                rec = run_case(
                    arch, shape, mp, args.out,
                    rules_override=rules_override, tag=args.tag,
                    microbatches=args.microbatches,
                )
                st = rec["status"]
                n_ok += st == "ok"
                n_err += st == "error"
                n_skip += st == "skipped"
    logger.info("done: %d ok, %d skipped, %d errors", n_ok, n_skip, n_err)
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
