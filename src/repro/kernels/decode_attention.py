"""Pallas TPU flash-decoding: one query token against a long KV cache.

Grid (B, K, nk): one step reads one (bk, dh) block of one KV head and
scores it against all G = H / K query heads that share it, so each KV block
is streamed from HBM once per step, not once per query head. KV-sequence is
innermost/sequential; (m, l, acc) running softmax lives in VMEM scratch.
The query positions (B,) are scalar-prefetched into SMEM; cache-slot
validity comes from absolute positions streamed as (1, bk) tiles of a
(B, 1, Sc) array. Masking covers empty slots (pos < 0), future slots
(pos > q_pos) and the sliding window for ring caches.

Every block's last two dims are either whole array dims (G, dh, the
singleton row of kv_pos) or multiples of (8, 128) (bk), as the TPU
compiler requires.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention"]

NEG_INF = -1e30


def _kernel(
    pos_ref,  # (B,) int32 query positions, SMEM (scalar prefetch)
    q_ref,  # (1, 1, G, dh)
    k_ref, v_ref,  # (1, 1, bk, dh)
    kvpos_ref,  # (1, 1, bk) int32
    o_ref,  # (1, 1, G, dh)
    m_ref, l_ref, acc_ref,  # scratch (G, 1), (G, 1), (G, dh) f32
    *,
    nk: int,
    window: int,
    scale: float,
):
    b, ik = pl.program_id(0), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, dh)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (G, bk)

    qpos = pos_ref[b]
    kvpos = kvpos_ref[0]  # (1, bk)
    ok = (kvpos >= 0) & (kvpos <= qpos)
    if window > 0:
        ok &= kvpos > qpos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))  # (G, 1)
    # all-masked-so-far rows: exp(NEG_INF - NEG_INF) must not become 1
    p = jnp.where(m_new <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ik == nk - 1)
    def _fin():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "block_k", "interpret")
)
def decode_attention(
    q: jax.Array,  # (B, H, dh)
    k: jax.Array,  # (B, K, Sc, dh)
    v: jax.Array,  # (B, K, Sc, dh)
    kv_pos: jax.Array,  # (B, Sc) int32, -1 = empty
    pos: jax.Array,  # (B,) int32 query positions
    *,
    window: int = 0,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, dh = q.shape
    K, Sc = k.shape[1], k.shape[2]
    G = H // K
    bk = min(block_k, Sc)
    scale = 1.0 / math.sqrt(dh)

    pad = (-Sc) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    nk = (Sc + pad) // bk

    kv_spec = pl.BlockSpec((1, 1, bk, dh), lambda b, kh, ik, pos: (b, kh, ik, 0))
    qo_spec = pl.BlockSpec((1, 1, G, dh), lambda b, kh, ik, pos: (b, kh, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, window=window, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K, nk),
            in_specs=[
                qo_spec,
                kv_spec,
                kv_spec,
                pl.BlockSpec((1, 1, bk), lambda b, kh, ik, pos: (b, 0, ik)),
            ],
            out_specs=qo_spec,
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pos, q.reshape(B, K, G, dh), k, v, kv_pos[:, None, :])
    return out.reshape(B, H, dh)
