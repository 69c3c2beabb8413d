"""Pallas TPU fused RMSNorm: rows tiled through VMEM, f32 reduction,
normalize + scale in one pass (one HBM read, one write)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rmsnorm"]


def _kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (br, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y.astype(o_ref.dtype) * g_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(
    x: jax.Array,  # (..., d)
    gamma: jax.Array,  # (d,)
    eps: float = 1e-5,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    shape = x.shape
    d = shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    br = min(block_rows, n)
    pad = (-n) % br
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=((n + pad) // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(((n + pad), d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(xf, gamma)
    return out[:n].reshape(shape)
