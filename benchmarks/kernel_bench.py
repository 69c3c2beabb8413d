"""Kernel micro-benchmarks.

The wall-times below are those of the kernels' jnp oracles
(`repro.kernels.ref`, identical math) on the host backend, while the
analytic columns report the TPU-target tile economics (VMEM working set,
arithmetic intensity, roofline-expected time on v5e) derived from the
BlockSpec shapes — the numbers a TPU run would be judged against.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from repro.kernels import ref

V5E_FLOPS, V5E_BW = 197e12, 819e9


def timed(fn, *args, repeats=5):
    fn(*args)  # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def flash_cases():
    for B, H, K, S, dh in [(1, 8, 8, 1024, 128), (1, 8, 1, 4096, 128)]:
        q = jnp.ones((B, H, S, dh), jnp.bfloat16)
        k = jnp.ones((B, K, S, dh), jnp.bfloat16)
        v = jnp.ones((B, K, S, dh), jnp.bfloat16)
        flops = 4.0 * B * H * S * S * dh * 0.5  # causal half
        bytes_ = 2.0 * (B * H * S * dh + 2 * B * K * S * dh + B * H * S * dh)
        yield (
            f"flash_attention B{B}H{H}K{K}S{S}",
            lambda q=q, k=k, v=v: ref.flash_attention_ref(q, k, v, causal=True),
            flops,
            bytes_,
        )


def decode_cases():
    for B, H, K, Sc, dh in [(8, 32, 8, 32768, 128)]:
        q = jnp.ones((B, H, dh), jnp.bfloat16)
        k = jnp.ones((B, K, Sc, dh), jnp.bfloat16)
        v = jnp.ones((B, K, Sc, dh), jnp.bfloat16)
        kv_pos = jnp.broadcast_to(jnp.arange(Sc), (B, Sc)).astype(jnp.int32)
        pos = jnp.full((B,), Sc - 1, jnp.int32)
        flops = 4.0 * B * H * Sc * dh
        bytes_ = 2.0 * 2 * B * K * Sc * dh  # stream the KV cache
        yield (
            f"decode_attention B{B}H{H}Sc{Sc}",
            lambda q=q, k=k, v=v, kv=kv_pos, p=pos: ref.decode_attention_ref(
                q, k, v, kv, p
            ),
            flops,
            bytes_,
        )


def rmsnorm_cases():
    for rows, d in [(8192, 8192)]:
        x = jnp.ones((rows, d), jnp.bfloat16)
        g = jnp.ones((d,), jnp.float32)
        yield (
            f"rmsnorm {rows}x{d}",
            lambda x=x, g=g: ref.rmsnorm_ref(x, g),
            3.0 * rows * d,
            2.0 * 2 * rows * d,
        )


def run(out_dir: str = "benchmarks/results") -> list:
    rows = []
    for gen in (flash_cases, decode_cases, rmsnorm_cases):
        for name, fn, flops, bytes_ in gen():
            cpu_s = timed(jax.jit(fn))
            v5e_s = max(flops / V5E_FLOPS, bytes_ / V5E_BW)
            ai = flops / bytes_
            rows.append(
                {
                    "kernel": name,
                    "cpu_ref_ms": cpu_s * 1e3,
                    "tpu_roofline_us": v5e_s * 1e6,
                    "arith_intensity": ai,
                    "bound": "compute" if ai > V5E_FLOPS / V5E_BW else "memory",
                }
            )
            print(
                f"[kernels] {name:36s} cpu_ref={cpu_s*1e3:8.2f}ms "
                f"v5e_roofline={v5e_s*1e6:8.1f}us AI={ai:6.1f} "
                f"({rows[-1]['bound']}-bound)"
            )
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kernel_bench.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    run()
