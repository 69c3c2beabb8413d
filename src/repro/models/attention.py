"""Grouped-query attention: train/prefill (naive or chunked-flash) + decode.

Two implementations of the score->softmax->mix core, chosen by the number
of keys alone (`core_for_keys`):

  * naive    — materializes (B, K, G, Sq, Sk) scores; up to NAIVE_MAX_KEYS.
  * chunked  — double-chunked online-softmax (flash attention in pure jnp):
               outer lax.map over query chunks, inner lax.scan over KV
               chunks carrying (m, l, acc). Peak memory O(CHUNK * CHUNK);
               above NAIVE_MAX_KEYS (the 32k/500k dry-run shapes).

Masking supports causal, sliding-window (Mixtral/long_500k serving variant)
and full (encoder / cross attention). GQA is native: q is shaped
(B, S, K, G, dh) against KV (B, S, K, dh).

Serving packs each self-attention block's Q/K/V weights into one 2-D leaf
(`pack_self_attention`): a (d, N) weight is read by one dot as the MLP's
are, where the 3-D (d, heads, dh) weights are each staged through a copy
before their dot. `_project_qkv` takes whichever layout it is given.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import constrain, shard_count
from .common import Initializer
from .rope import apply_mrope, apply_rope, text_mrope_positions

__all__ = [
    "init_attention",
    "attention_forward",
    "decode_attention",
    "attention_core",
    "core_for_keys",
    "pack_self_attention",
    "packed_blocks",
]

NEG_INF = -1e30
# naive attention up to this many keys, chunked attention above
NAIVE_MAX_KEYS = 2048
# query and key chunk of chunked attention
CHUNK = 1024


def init_attention(init: Initializer, cfg: ModelConfig) -> dict:
    d, H, K, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": init.param("wq", (d, H, dh), ("p_embed", "p_heads", None)),
        "wk": init.param("wk", (d, K, dh), ("p_embed", "p_kv_heads", None)),
        "wv": init.param("wv", (d, K, dh), ("p_embed", "p_kv_heads", None)),
        "wo": init.param("wo", (H, dh, d), ("p_heads", None, "p_embed"),
                         scale=1.0 / math.sqrt(H * dh)),
    }
    if cfg.qkv_bias:
        p["bq"] = init.param("bq", (H, dh), ("p_heads", None), zeros=True)
        p["bk"] = init.param("bk", (K, dh), ("p_kv_heads", None), zeros=True)
        p["bv"] = init.param("bv", (K, dh), ("p_kv_heads", None), zeros=True)
    return p


# ---------------------------------------------------------------------------
# the serving layout: Q, K and V packed into one matrix
# ---------------------------------------------------------------------------

# param keys of self-attention blocks; "cross_attn" projects its K/V from
# the encoder memory (`project_kv`), so it keeps separate projections
SELF_ATTENTION = ("attn", "self_attn")


@jax.jit
def _pack_heads(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """q (..., H, dh), k and v (..., K, dh) -> (..., K * (H/K + 2) * dh):
    for each KV head its H/K query heads (h = k * G + g), then its K, then
    its V."""
    *lead, H, dh = q.shape
    K = k.shape[-2]
    qkv = jnp.concatenate(
        [q.reshape(*lead, K, H // K, dh), k[..., None, :], v[..., None, :]],
        axis=-2,
    )
    return qkv.reshape(*lead, K * (H // K + 2) * dh)


def _pack_block(p: dict) -> dict:
    """One self-attention block (leading layer axes kept) with wq, wk, wv
    replaced by wqkv (..., d, K * (G + 2) * dh), and bq, bk, bv by bqkv.
    Left as it is where already packed, or where the active mesh would
    split the packed columns inside a KV head's group."""
    if "wq" not in p:
        return p
    *_, H, dh = p["wq"].shape
    K = p["wk"].shape[-2]
    if K % shard_count(K * (H // K + 2) * dh, "p_kv_heads"):
        return p
    out = {k: v for k, v in p.items() if k not in ("wq", "wk", "wv")}
    out["wqkv"] = _pack_heads(p["wq"], p["wk"], p["wv"])
    if "bq" in p:
        for b in ("bq", "bk", "bv"):
            del out[b]
        out["bqkv"] = _pack_heads(p["bq"], p["bk"], p["bv"])
    return out


def pack_self_attention(params: dict) -> dict:
    """The params with every self-attention block packed (`_pack_block`).
    Every other leaf is the input's own array, not a copy."""

    def walk(t):
        if not isinstance(t, dict):
            return t
        return {k: _pack_block(v) if k in SELF_ATTENTION else walk(v)
                for k, v in t.items()}

    return walk(params)


def packed_blocks(params: dict) -> int:
    """How many attention blocks hold their Q, K and V packed: a stacked
    leaf counts each of its layers."""
    if not isinstance(params, dict):
        return 0
    if "wqkv" in params:
        return math.prod(params["wqkv"].shape[:-2])
    return sum(packed_blocks(v) for v in params.values())


# ---------------------------------------------------------------------------
# score/softmax/mix cores
# ---------------------------------------------------------------------------


def _mask_bias(
    q_pos: jax.Array,  # (Sq,) or (B, Sq)
    k_pos: jax.Array,  # (Sk,) or (B, Sk)
    causal: bool,
    window: int,
) -> jax.Array:
    """Additive bias (..., Sq, Sk); k_pos < 0 marks invalid (padding) slots."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    ok = kp >= 0
    if causal:
        ok &= kp <= qp
    if window > 0:
        ok &= kp > qp - window
    return jnp.where(ok, 0.0, NEG_INF)


def naive_attention(
    q: jax.Array,  # (B, Sq, K, G, dh)
    k: jax.Array,  # (B, Sk, K, dh)
    v: jax.Array,  # (B, Sk, K, dh)
    q_pos: jax.Array,  # (B, Sq)
    k_pos: jax.Array,  # (B, Sk)
    causal: bool,
    window: int,
) -> jax.Array:
    dh = q.shape[-1]
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)
    s = s / math.sqrt(dh)
    bias = _mask_bias(q_pos, k_pos, causal, window)  # (B, Sq, Sk)
    s = s + bias[:, None, None]
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    # fully-masked rows emit 0 (matches the online-softmax l=0 convention)
    any_valid = (bias > NEG_INF / 2).any(-1)  # (B, Sq)
    p = p * any_valid[:, None, None, :, None].astype(p.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", p, v)


def chunked_attention(
    q: jax.Array,  # (B, Sq, K, G, dh)
    k: jax.Array,  # (B, Sk, K, dh)
    v: jax.Array,  # (B, Sk, K, dh)
    q_pos: jax.Array,  # (B, Sq)
    k_pos: jax.Array,  # (B, Sk)
    causal: bool,
    window: int,
    q_block: int = CHUNK,
    kv_block: int = CHUNK,
) -> jax.Array:
    """Flash-style double-chunked attention with online softmax."""
    B, Sq, K, G, dh = q.shape
    Sk = k.shape[1]
    qc = min(q_block, Sq)
    kc = min(kv_block, Sk)
    scale = 1.0 / math.sqrt(dh)

    # Pad to chunk multiples; padded KV slots get k_pos = -1 (masked).
    def pad_to(x, mult, axis, value=0):
        pad = (-x.shape[axis]) % mult
        if pad == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths, constant_values=value)

    qp = pad_to(q, qc, 1)
    qposp = pad_to(q_pos, qc, 1, value=0)
    kp_ = pad_to(k, kc, 1)
    vp = pad_to(v, kc, 1)
    kposp = pad_to(k_pos, kc, 1, value=-1)
    nq, nk = qp.shape[1] // qc, kp_.shape[1] // kc

    q_blocks = qp.reshape(B, nq, qc, K, G, dh).transpose(1, 0, 2, 3, 4, 5)
    qpos_blocks = qposp.reshape(B, nq, qc).transpose(1, 0, 2)
    k_blocks = kp_.reshape(B, nk, kc, K, dh).transpose(1, 0, 2, 3, 4)
    v_blocks = vp.reshape(B, nk, kc, K, dh).transpose(1, 0, 2, 3, 4)
    kpos_blocks = kposp.reshape(B, nk, kc).transpose(1, 0, 2)

    def one_q_block(args):
        qb, qposb = args  # (B, qc, K, G, dh), (B, qc)

        def kv_step(carry, xs):
            m, l, acc = carry
            kb, vb, kposb = xs  # (B, kc, K, dh), ..., (B, kc)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qb, kb).astype(jnp.float32)
            s = s * scale + _mask_bias(qposb, kposb, causal, window)[:, None, None]
            m_new = jnp.maximum(m, s.max(-1))
            # all-masked-so-far rows: exp(NEG_INF - NEG_INF) would be 1
            p = jnp.where(
                m_new[..., None] <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[..., None])
            )
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(vb.dtype), vb
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, qc), jnp.float32)
        a0 = jnp.zeros((B, K, G, qc, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (k_blocks, v_blocks, kpos_blocks)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4)  # (B, qc, K, G, dh)

    out_blocks = jax.lax.map(one_q_block, (q_blocks, qpos_blocks))  # (nq, B, qc, ...)
    out = out_blocks.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * qc, K, G, dh)
    return out[:, :Sq].astype(q.dtype)


def core_for_keys(n_keys: int) -> str:
    """The attention core `attention_core` runs over n_keys keys."""
    return "naive" if n_keys <= NAIVE_MAX_KEYS else "chunked"


def attention_core(q, k, v, q_pos, k_pos, causal, window) -> jax.Array:
    if core_for_keys(int(k.shape[1])) == "chunked":
        return chunked_attention(q, k, v, q_pos, k_pos, causal, window)
    return naive_attention(q, k, v, q_pos, k_pos, causal, window)


# ---------------------------------------------------------------------------
# full layers
# ---------------------------------------------------------------------------


def _project_qkv(
    p: dict,
    x: jax.Array,  # (B, S, d)
    cfg: ModelConfig,
    positions: Optional[jax.Array],  # (B, S) or None (NoPE)
    mrope_positions: Optional[jax.Array] = None,  # (3, B, S)
    rope_flag: Optional[jax.Array] = None,  # traced scalar: 1=RoPE, 0=NoPE (iRoPE)
):
    if "wqkv" in p:  # packed (`pack_self_attention`): one dot
        B, S, _ = x.shape
        K, G = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
        qkv = jnp.einsum("bsd,dn->bsn", x, p["wqkv"])
        if cfg.qkv_bias:
            qkv = qkv + p["bqkv"]
        # The barrier keeps the dot's output in its own (b, s, n) layout.
        # Fused with the reshape below, the TPU compiler lays it out head
        # major and then copies the whole weight, transposed, before the
        # dot on every layer.
        qkv = jax.lax.optimization_barrier(qkv)
        qkv = qkv.reshape(B, S, K, G + 2, cfg.head_dim)
        q = qkv[:, :, :, :G].reshape(B, S, K * G, cfg.head_dim)
        k, v = qkv[:, :, :, G], qkv[:, :, :, G + 1]
    else:
        q = jnp.einsum("bsd,dkh->bskh", x,
                       p["wq"].reshape(cfg.d_model, -1, cfg.head_dim))
        k = jnp.einsum("bsd,dkh->bskh", x, p["wk"])
        v = jnp.einsum("bsd,dkh->bskh", x, p["wv"])
        if cfg.qkv_bias:
            q = q + p["bq"].reshape(-1, cfg.head_dim)
            k = k + p["bk"]
            v = v + p["bv"]
    if positions is not None:
        with jax.named_scope("rope"):
            if cfg.mrope_sections:
                m = mrope_positions
                if m is None:
                    m = text_mrope_positions(positions)
                qr = apply_mrope(q, m, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections)
                kr = apply_mrope(k, m, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections)
            else:
                qr = apply_rope(q, positions, cfg.head_dim, cfg.rope_theta, cfg.rope_dim)
                kr = apply_rope(k, positions, cfg.head_dim, cfg.rope_theta, cfg.rope_dim)
            if rope_flag is None:
                q, k = qr, kr
            else:  # traced per-layer iRoPE selection (inside lax.scan)
                q = jnp.where(rope_flag, qr, q)
                k = jnp.where(rope_flag, kr, k)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    v = constrain(v, ("batch", "seq", "kv_heads", None))
    return q, k, v


def project_kv(
    p: dict, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """K/V projections only (cross-attention memory, no RoPE).
    x: (B, S, d) -> k, v: (B, S, K, dh)."""
    k = jnp.einsum("bsd,dkh->bskh", x, p["wk"])
    v = jnp.einsum("bsd,dkh->bskh", x, p["wv"])
    if cfg.qkv_bias:
        k = k + p["bk"]
        v = v + p["bv"]
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    v = constrain(v, ("batch", "seq", "kv_heads", None))
    return k, v


def attention_forward(
    p: dict,
    x: jax.Array,  # (B, S, d)
    cfg: ModelConfig,
    positions: jax.Array,  # (B, S)
    *,
    causal: bool = True,
    window: int = 0,
    use_rope: bool = True,
    rope_flag: Optional[jax.Array] = None,
    mrope_positions: Optional[jax.Array] = None,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    cross_pos: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Returns (out (B,S,d), (k, v) for cache collection).

    cross_kv: precomputed (k, v) for cross attention (enc-dec decoder);
    q is still projected from x, mask is full.
    """
    B, S, _ = x.shape
    K, G = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    q, k, v = _project_qkv(
        p, x, cfg, positions if use_rope else None, mrope_positions, rope_flag
    )
    if cross_kv is not None:
        k, v = cross_kv
        k_pos = cross_pos
        causal, window = False, 0
    else:
        k_pos = positions
    qg = q.reshape(B, S, K, G, cfg.head_dim)
    out = attention_core(qg, k, v, positions, k_pos, causal, window)
    out = out.reshape(B, S, cfg.n_heads, cfg.head_dim)
    y = jnp.einsum("bsnh,nhd->bsd", out, p["wo"])
    return constrain(y, ("batch", "seq_res", "embed")), (k, v)


def decode_attention(
    p: dict,
    x: jax.Array,  # (B, d) — one new token per sequence
    cfg: ModelConfig,
    pos: jax.Array,  # (B,) current position index
    cache_k: jax.Array,  # (B, Sc, K, dh)
    cache_v: jax.Array,
    cache_pos: jax.Array,  # (B, Sc) absolute positions in cache, -1 = empty
    *,
    window: int = 0,
    use_rope: bool = True,
    rope_flag: Optional[jax.Array] = None,
    cross: bool = False,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One decode step. Returns (out (B, d), (k_new, v_new) to be written by
    the caller — except for cross attention, where the cache is static).

    The fresh token's K/V are *not* concatenated onto the (possibly
    sequence-sharded) cache; its score is merged through a two-part online
    softmax so the cache keeps its sharding layout untouched.
    """
    B = x.shape[0]
    K, G, dh = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim
    scale = 1.0 / math.sqrt(dh)
    q, k, v = _project_qkv(
        p,
        x[:, None, :],
        cfg,
        pos[:, None] if use_rope else None,
        rope_flag=rope_flag,
    )
    qg = q.reshape(B, K, G, dh)

    # Scores over the cache: (B, K, G, Sc).
    s_c = jnp.einsum("bkgh,bskh->bkgs", qg, cache_k).astype(jnp.float32) * scale
    valid = cache_pos >= 0
    if not cross:
        valid &= cache_pos <= pos[:, None]
    if window > 0:
        valid &= cache_pos > (pos[:, None] - window)
    s_c = jnp.where(valid[:, None, None, :], s_c, NEG_INF)

    if cross:
        p_c = jax.nn.softmax(s_c, axis=-1)
        out = jnp.einsum("bkgs,bskh->bkgh", p_c.astype(cache_v.dtype), cache_v)
    else:
        # Fresh token attends to itself too (slot not yet written).
        s_s = (
            jnp.einsum("bkgh,bkh->bkg", qg, k[:, 0]).astype(jnp.float32) * scale
        )[..., None]
        m = jnp.maximum(s_c.max(-1, keepdims=True), s_s)
        p_c = jnp.exp(s_c - m)
        p_s = jnp.exp(s_s - m)
        l = p_c.sum(-1, keepdims=True) + p_s
        out = jnp.einsum("bkgs,bskh->bkgh", (p_c / l).astype(cache_v.dtype), cache_v)
        out = out + (p_s / l).astype(v.dtype) * v[:, 0][:, :, None, :]

    out = out.reshape(B, cfg.n_heads, dh)
    y = jnp.einsum("bnh,nhd->bd", out, p["wo"])
    return constrain(y, ("batch", "embed")), (k[:, 0], v[:, 0])
