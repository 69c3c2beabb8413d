"""Serving driver: ICC-scheduled continuous batching over a real model.

Generates a Poisson request trace (the paper's Table-I workload shape:
short prompts, short outputs), runs it through the engine under each
admission policy — ICC priority, then FIFO — and prints
satisfaction/latency stats.

  PYTHONPATH=src python -m repro.launch.serve --rate 20     # smoke, float32
  PYTHONPATH=src python -m repro.launch.serve --full-size   # registered config
  PYTHONPATH=src python -m repro.launch.serve --trace out.json

`--trace PATH` records each policy's served requests (lifecycle, stage
breakdown, per-call host and CPU time) and writes one Chrome trace per
policy, named PATH with the policy before its suffix (`out.priority.json`,
`out.fifo.json`); open them at https://ui.perfetto.dev.

`--full-size` serves the registered config in its own dtype; it needs an
accelerator that holds the whole model (`chip_smoke.py` serves one chip's
share of glm4-9b through `serve`).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from ..configs import get_config
from ..configs.base import ModelConfig
from ..models import RuntimeFlags, build_model
from ..models.model import Model
from ..serving import (
    GenRequest,
    GenResult,
    ICCRequest,
    ICCServer,
    InferenceEngine,
    ServeStats,
)
from ..serving.calibrate import measure_service_time
from ..telemetry import EventRecorder, write_chrome_trace
from .compile_cache import use_compile_cache


def build_trace(cfg, rate: float, duration: float, n_input: int,
                n_output: int, b_total: float, seed: int = 0,
                keep_logits: bool = False):
    rng = np.random.default_rng(seed)
    reqs, t, uid = [], 0.0, 0
    while t < duration:
        t += rng.exponential(1.0 / rate)
        prompt = jax.random.randint(
            jax.random.PRNGKey(uid), (n_input,), 0, cfg.vocab_size
        )
        reqs.append(
            ICCRequest(
                GenRequest(uid=uid, prompt=prompt, max_new_tokens=n_output,
                           keep_logits=keep_logits),
                t_gen=t,
                t_comm=float(rng.uniform(0.008, 0.03)),  # SLS-like comm spread
                b_total=b_total,
            )
        )
        uid += 1
    return reqs


def init_params(model: Model) -> dict:
    """Random params (seed 0) made on the default device by one jitted
    program.

    Under `jit` each float32 draw fuses with its cast to the config's
    dtype; drawn eagerly, a stacked full-width weight would first sit on
    the device whole in float32. The logical-axes tree stays out of the
    jitted output (its `Axes` leaves are not arrays)."""
    return jax.jit(lambda key: model.init(key)[0])(jax.random.PRNGKey(0))


@dataclasses.dataclass
class PolicyRun:
    """One policy's pass over the trace."""

    policy: str
    trace: List[ICCRequest]
    stats: ServeStats
    results: Dict[int, GenResult]  # the served requests, by uid
    warmup_s: float  # engine warm-up, compilation included
    serve_s: float


@dataclasses.dataclass
class ServeReport:
    model: Model
    params: dict
    init_s: float
    runs: List[PolicyRun]


def serve(
    cfg: ModelConfig,
    *,
    policies: Sequence[str] = ("priority", "fifo"),
    rate: float = 10.0,
    duration: float = 3.0,
    n_input: int = 15,
    n_output: int = 15,
    budget: float = 2.0,
    max_batch: int = 8,
    max_seq: Optional[int] = None,  # None -> n_input + n_output + 8
    keep_logits: bool = False,
    trace_path: Optional[str] = None,
) -> ServeReport:
    """Serve one seeded trace of `cfg` under each policy and print stats;
    with `trace_path`, write each policy's Chrome trace (module doc)."""
    model = build_model(cfg, RuntimeFlags(remat=False))
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(model))
    init_s = time.perf_counter() - t0
    print(f"[serve] {cfg.name}: init {init_s:.3f}s on {jax.default_backend()}")
    cal = measure_service_time(model, params, n_input, n_output)
    print(f"[serve] calibrated: prefill {cal['prefill_s']*1e3:.1f}ms "
          f"decode {cal['decode_s']*1e3:.1f}ms")

    runs = []
    for policy in policies:
        trace = build_trace(cfg, rate, duration, n_input, n_output, budget,
                            keep_logits=keep_logits)
        eng = InferenceEngine(model, params, max_batch=max_batch,
                              max_seq=max_seq or n_input + n_output + 8)
        t0 = time.perf_counter()
        eng.warmup(trace[0].req.prompt)
        warmup_s = time.perf_counter() - t0
        # every engine call sampled: calls are never closer than 1 us
        rec = EventRecorder(sample_every_s=1e-6) if trace_path else None
        srv = ICCServer(eng, policy=policy, est_latency=cal["total_s"],
                        recorder=rec)
        t0 = time.perf_counter()
        stats = srv.run(trace)
        serve_s = time.perf_counter() - t0
        if rec is not None:
            root, ext = os.path.splitext(trace_path)
            path = f"{root}.{policy}{ext or '.json'}"
            write_chrome_trace(rec.to_telemetry(
                meta={"arch": cfg.name, "policy": policy}), path)
            print(f"[serve] {policy}: trace written to {path}")
        e2e = np.array(stats.e2e) if stats.e2e else np.array([np.nan])
        print(
            f"[serve] {policy:8s}: {stats.n_total} reqs, "
            f"sat={stats.satisfaction:.3f} drop={stats.n_dropped} "
            f"p50={np.nanpercentile(e2e,50)*1e3:.0f}ms "
            f"p95={np.nanpercentile(e2e,95)*1e3:.0f}ms"
        )
        runs.append(PolicyRun(policy, trace, stats, dict(eng.results),
                              warmup_s, serve_s))
    return ServeReport(model, params, init_s, runs)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--rate", type=float, default=10.0, help="req/s")
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--n-input", type=int, default=15)
    ap.add_argument("--n-output", type=int, default=15)
    ap.add_argument("--budget", type=float, default=2.0, help="b_total (s)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--full-size", action="store_true",
                    help="registered config in its own dtype (needs a chip "
                         "that holds it)")
    ap.add_argument("--trace", metavar="PATH",
                    help="write a Chrome trace of each policy's requests")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch, smoke=not args.full_size)
    if not args.full_size:
        cfg = dataclasses.replace(cfg, dtype="float32")
    serve(cfg, rate=args.rate, duration=args.duration, n_input=args.n_input,
          n_output=args.n_output, budget=args.budget,
          max_batch=args.max_batch, trace_path=args.trace)


if __name__ == "__main__":
    main()
