"""Pallas TPU flash attention (prefill/train): tiled online softmax.

Grid (B, H, nq, nk) — the KV dimension is innermost and sequential on TPU,
so the (m, l, acc) running-softmax state lives in VMEM scratch across the
nk steps of one (b, h, iq) tile. Block shapes are MXU-aligned multiples of
128 on the (q, kv) dims; dh rides along whole (128 for every assigned arch,
64 for seamless).

GQA is expressed in the BlockSpec index maps (KV block row h // G), so no
KV replication ever materializes in VMEM.

VMEM budget per step at (bq, bk, dh) = (128, 128, 128), bf16 in / f32 acc:
q 32 KB + k 32 KB + v 32 KB + acc/m/l ~65 KB + s/p 2x64 KB — well under
the ~16 MB/core VMEM of v5e.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref,  # (1, 1, bq, dh), (1, 1, bk, dh)
    o_ref,  # (1, 1, bq, dh)
    m_ref, l_ref, acc_ref,  # scratch: (bq,), (bq,), (bq, dh) f32
    *,
    bq: int,
    bk: int,
    nk: int,
    causal: bool,
    window: int,
    kv_len: int,
    scale: float,
):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, dh)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, dh)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = kpos < kv_len
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    # all-masked-so-far rows: exp(NEG_INF - NEG_INF) must not become 1
    p = jnp.where(m_new[:, None] <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[:, None]))
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + p.sum(axis=1)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ik == nk - 1)
    def _fin():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, H, Sq, dh)
    k: jax.Array,  # (B, K, Sk, dh)
    v: jax.Array,  # (B, K, Sk, dh)
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, dh = q.shape
    K, Sk = k.shape[1], k.shape[2]
    G = H // K
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    scale = 1.0 / math.sqrt(dh)

    def padded(x, blk, axis):
        pad = (-x.shape[axis]) % blk
        if pad == 0:
            return x
        w = [(0, 0)] * x.ndim
        w[axis] = (0, pad)
        return jnp.pad(x, w)

    qp = padded(q, bq, 2)
    kp = padded(k, bk, 2)
    vp = padded(v, bk, 2)
    nq = qp.shape[2] // bq
    nk = kp.shape[2] // bk

    out = pl.pallas_call(
        functools.partial(
            _kernel, bq=bq, bk=bk, nk=nk, causal=causal, window=window,
            kv_len=Sk, scale=scale,
        ),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :Sq]
