"""Measured-latency calibration: close the loop between the REAL engine and
the paper's system-level simulator.

The paper's T_comp comes from the analytic roofline (Eq. 7/8). Beyond the
paper, we also calibrate a service-time table by timing the actual JAX
engine (prefill + N decode steps) and hand the measured callable to
core.simulator — the ICC-vs-MEC comparison then runs on real compute
latencies instead of modeled ones (EXPERIMENTS.md 'measured mode').
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ..core.scheduler import Job
from ..models.model import Model
from .engine import GenRequest, InferenceEngine

__all__ = ["measure_service_time", "measured_service_fn"]


def measure_service_time(
    model: Model,
    params: dict,
    n_input: int,
    n_output: int,
    max_seq: int = 256,
    repeats: int = 3,
) -> Dict[str, float]:
    """Time prefill + n_output decode steps at batch 1. Returns seconds.
    One engine, reset between repeats, so one packed copy of the params."""
    eng = InferenceEngine(model, params, max_batch=1, max_seq=max_seq)
    key = jax.random.PRNGKey(0)
    prompt = jax.random.randint(key, (n_input,), 0, model.cfg.vocab_size)
    # warmup (compile)
    eng.generate([GenRequest(uid=-1, prompt=prompt, max_new_tokens=n_output)])
    prefill_s, decode_s = [], []
    for r in range(repeats):
        eng.reset()
        res = eng.generate(
            [GenRequest(uid=r, prompt=prompt, max_new_tokens=n_output)]
        )[r]
        prefill_s.append(res.prefill_s)
        decode_s.append(res.decode_s)
    return {
        "prefill_s": min(prefill_s),
        "decode_s": min(decode_s),
        "total_s": min(p + d for p, d in zip(prefill_s, decode_s)),
    }


def measured_service_fn(
    model: Model, params: dict, n_input: int, n_output: int, **kw
) -> Tuple[Callable[[Job], float], Dict[str, float]]:
    """-> (service_time(job) for core.simulator, the measured table)."""
    t = measure_service_time(model, params, n_input, n_output, **kw)
    per_in = t["prefill_s"] / max(n_input, 1)
    per_out = t["decode_s"] / max(n_output, 1)

    def service_time(job: Job) -> float:
        return per_in * job.n_input + per_out * job.n_output

    return service_time, t
