"""xLSTM blocks: mLSTM (matrix memory, chunked-parallel) and sLSTM (scalar
memory, sequential). [arXiv:2405.04517]

mLSTM cell (per head, value dim Pv, key dim Pk):

    C_t = f_t C_{t-1} + i_t v_t k_t^T        C: (Pv, Pk)
    n_t = f_t n_{t-1} + i_t k_t
    h_t = (C_t q_t) / max(|n_t . q_t|, exp(-m_t))

with exp input gate / sigmoid forget gate and running stabilizer m_t.
Train/prefill uses the chunkwise form (intra-chunk quadratic + lax.scan
carry of (C, n, m) across chunks — same TPU shape as the SSD scan);
decode is the plain recurrence.

sLSTM is inherently sequential (scalar memories with block-diagonal
recurrent gate matrices); its forward is a lax.scan over time. xLSTM-1.3b
places one sLSTM block every `slstm_every` layers.

Block structure follows the paper: mLSTM blocks are post-up-projection
(Mamba-style: up x2, conv, q/k/v, cell, gated down-projection, no separate
FFN); sLSTM blocks are pre-up-projection (cell at d_model, then a gated
4/3-factor FFN).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import constrain
from .common import Initializer, rms_norm

# steps per chunk of the chunkwise mLSTM forward
CHUNK = 256

__all__ = [
    "init_mlstm",
    "mlstm_forward",
    "mlstm_decode_step",
    "init_mlstm_state",
    "init_slstm",
    "slstm_forward",
    "slstm_decode_step",
    "init_slstm_state",
    "slstm_ffn_dim",
]


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------


def init_mlstm(init: Initializer, cfg: ModelConfig) -> dict:
    d, di = cfg.d_model, cfg.d_inner
    nh = cfg.n_heads
    cw = cfg.ssm_conv
    return {
        "w_up": init.param("w_up", (d, di), ("p_embed", "p_inner")),
        "w_z": init.param("w_z", (d, di), ("p_embed", "p_inner")),
        "conv": init.param("conv", (cw, di), (None, "p_inner"), scale=0.5),
        "wq": init.param("wq", (di, di), ("p_inner", None)),
        "wk": init.param("wk", (di, di), ("p_inner", None)),
        "wv": init.param("wv", (di, di), ("p_inner", "p_inner")),
        "w_if": init.param("w_if", (di, 2 * nh), ("p_inner", None), scale=0.01),
        "b_if": init.param("b_if", (2 * nh,), (None,), zeros=True),
        "skip": init.param("skip", (di,), ("p_inner",), ones=True),
        "norm": init.param("norm", (di,), ("p_inner",), ones=True),
        "w_down": init.param("w_down", (di, d), ("p_inner", "p_embed")),
    }


def init_mlstm_state(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16) -> dict:
    nh, P = cfg.n_heads, cfg.d_inner // cfg.n_heads
    cw, di = cfg.ssm_conv, cfg.d_inner
    return {
        "C": jnp.zeros((batch, nh, P, P), jnp.float32),
        "n": jnp.zeros((batch, nh, P), jnp.float32),
        "m": jnp.full((batch, nh), -1e30, jnp.float32),
        "conv": jnp.zeros((batch, cw - 1, di), dtype),
    }


def _mlstm_proj(p: dict, x: jax.Array, cfg: ModelConfig, conv_prior=None):
    """x (B,S,d) -> q,k,v (B,S,nh,P), gates (B,S,nh), z (B,S,di), raw conv in."""
    from .mamba2 import _causal_conv  # same depthwise conv helper

    B, S, _ = x.shape
    nh, P = cfg.n_heads, cfg.d_inner // cfg.n_heads
    up = jnp.einsum("bsd,de->bse", x, p["w_up"])
    z = jnp.einsum("bsd,de->bse", x, p["w_z"])
    c = _causal_conv(up, p["conv"], conv_prior)
    q = jnp.einsum("bse,ef->bsf", c, p["wq"]).reshape(B, S, nh, P)
    k = jnp.einsum("bse,ef->bsf", c, p["wk"]).reshape(B, S, nh, P) / math.sqrt(P)
    v = jnp.einsum("bse,ef->bsf", up, p["wv"]).reshape(B, S, nh, P)
    gates = jnp.einsum("bse,eg->bsg", c, p["w_if"]).astype(jnp.float32) + p["b_if"]
    ipre, fpre = gates[..., :nh], gates[..., nh:]
    return q, k, v, ipre, fpre, z, up, c


def mlstm_forward(
    p: dict,
    x: jax.Array,  # (B, S, d)
    cfg: ModelConfig,
    chunk: Optional[int] = None,  # None: CHUNK
    state: dict = None,
) -> Tuple[jax.Array, dict]:
    B, S, _ = x.shape
    nh, P, di = cfg.n_heads, cfg.d_inner // cfg.n_heads, cfg.d_inner
    Q = min(chunk or CHUNK, S)
    pad = (-S) % Q
    prior = state or {}

    q, k, v, ipre, fpre, z, up_raw, conv_out = _mlstm_proj(
        p, x, cfg, prior.get("conv")
    )
    logf = jax.nn.log_sigmoid(fpre)  # (B, S, nh)

    def padq(a, fill=0.0):
        if pad == 0:
            return a
        w = [(0, 0)] * a.ndim
        w[1] = (0, pad)
        return jnp.pad(a, w, constant_values=fill)

    # padded steps: logf = 0 (no decay), ipre = -inf (no input)
    qp, kp, vp = padq(q), padq(k), padq(v)
    ip, fp = padq(ipre, -1e30), padq(logf, 0.0)
    Sp = S + pad
    nc = Sp // Q
    qc = qp.reshape(B, nc, Q, nh, P)
    kc = kp.reshape(B, nc, Q, nh, P)
    vc = vp.reshape(B, nc, Q, nh, P)
    ic = ip.reshape(B, nc, Q, nh)
    cum = jnp.cumsum(fp.reshape(B, nc, Q, nh), axis=2)  # inclusive log-decay

    # intra-chunk: D_ij = cum_i - cum_j + ipre_j for j <= i
    ii, jj = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]
    causal = jj <= ii
    D = cum[:, :, :, None, :] - cum[:, :, None, :, :] + ic[:, :, None, :, :]
    D = jnp.where(causal[None, None, :, :, None], D, -1e30)  # (B,nc,i,j,nh)
    m_intra = D.max(axis=3)  # (B, nc, i, nh)

    def carry_step(carry, xs):
        C_hat, n_hat, m_prev = carry  # scaled state: actual = hat * exp(m_prev)
        qx, kx, vx, Dx, mx, cumx, icx = xs
        # mx: intra max (B, i, nh); inter contribution magnitude cum_i + m_prev
        m_i = jnp.maximum(mx, cumx + m_prev[:, None, :])  # (B, i, nh)
        w_intra = jnp.exp(Dx - m_i[:, :, None, :])  # (B, i, j, nh)
        w_inter = jnp.exp(cumx + m_prev[:, None, :] - m_i)  # (B, i, nh)
        sq = jnp.einsum("bihp,bjhp->bhij", qx, kx).astype(jnp.float32)
        num = jnp.einsum("bhij,bijh,bjhp->bihp", sq, w_intra, vx.astype(jnp.float32))
        # C_hat is (B, nh, P_value, P_key): contract q over the KEY dim.
        num = num + jnp.einsum(
            "bihk,bhvk,bih->bihv", qx.astype(jnp.float32), C_hat, w_inter
        )
        nvec = jnp.einsum("bijh,bjhp->bihp", w_intra, kx.astype(jnp.float32))
        nvec = nvec + w_inter[..., None] * n_hat[:, None]
        qn = jnp.einsum("bihp,bihp->bih", qx.astype(jnp.float32), nvec)
        denom = jnp.maximum(jnp.abs(qn), jnp.exp(-m_i))
        h = num / denom[..., None]  # (B, i, nh, P)
        # chunk-end state update
        cum_Q = cumx[:, -1, :]  # (B, nh)
        d_end = cum_Q[:, None, :] - cumx + icx  # (B, j, nh)
        m_end = jnp.maximum(cum_Q + m_prev, d_end.max(axis=1))
        w_end = jnp.exp(d_end - m_end[:, None, :])  # (B, j, nh)
        C_new = jnp.exp(cum_Q + m_prev - m_end)[:, :, None, None] * C_hat
        C_new = C_new + jnp.einsum(
            "bjh,bjhp,bjhr->bhpr", w_end, vx.astype(jnp.float32), kx.astype(jnp.float32)
        )
        n_new = jnp.exp(cum_Q + m_prev - m_end)[:, :, None] * n_hat
        n_new = n_new + jnp.einsum("bjh,bjhp->bhp", w_end, kx.astype(jnp.float32))
        return (C_new, n_new, m_end), h

    C0 = prior.get("C")
    if C0 is None:
        C0 = jnp.zeros((B, nh, P, P), jnp.float32)
        n0 = jnp.zeros((B, nh, P), jnp.float32)
        m0 = jnp.full((B, nh), -1e30, jnp.float32)
    else:
        n0, m0 = prior["n"], prior["m"]

    xs = (
        qc.transpose(1, 0, 2, 3, 4),
        kc.transpose(1, 0, 2, 3, 4),
        vc.transpose(1, 0, 2, 3, 4),
        D.transpose(1, 0, 2, 3, 4),
        m_intra.transpose(1, 0, 2, 3),
        cum.transpose(1, 0, 2, 3),
        ic.transpose(1, 0, 2, 3),
    )
    (C_f, n_f, m_f), hs = jax.lax.scan(carry_step, (C0, n0, m0), xs)
    h = hs.transpose(1, 0, 2, 3, 4).reshape(B, Sp, nh, P)[:, :S]

    # per-head norm, learnable skip (conv path), output gate, down-projection
    h = h.reshape(B, S, di).astype(x.dtype)
    h = rms_norm(h, p["norm"], cfg.norm_eps)
    h = h + p["skip"] * conv_out
    h = h * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", h, p["w_down"])
    out = constrain(out, ("batch", "seq", "embed"))

    cw = cfg.ssm_conv
    up_prior = prior.get("conv")
    if up_prior is None:
        up_prior = jnp.zeros((B, cw - 1, di), x.dtype)
    new_conv = jnp.concatenate([up_prior, up_raw], axis=1)[:, -(cw - 1) :]
    return out, {"C": C_f, "n": n_f, "m": m_f, "conv": new_conv}


def mlstm_decode_step(
    p: dict, x: jax.Array, state: dict, cfg: ModelConfig
) -> Tuple[jax.Array, dict]:
    B = x.shape[0]
    nh, P, di = cfg.n_heads, cfg.d_inner // cfg.n_heads, cfg.d_inner
    q, k, v, ipre, fpre, z, up_raw, conv_out = _mlstm_proj(
        p, x[:, None, :], cfg, state["conv"]
    )
    q, k, v = q[:, 0], k[:, 0], v[:, 0]  # (B, nh, P)
    ipre, logf = ipre[:, 0], jax.nn.log_sigmoid(fpre[:, 0])  # (B, nh)

    m_new = jnp.maximum(logf + state["m"], ipre)
    f_eff = jnp.exp(logf + state["m"] - m_new)
    i_eff = jnp.exp(ipre - m_new)
    C = state["C"] * f_eff[..., None, None] + i_eff[..., None, None] * jnp.einsum(
        "bhp,bhr->bhpr", v.astype(jnp.float32), k.astype(jnp.float32)
    )
    n = state["n"] * f_eff[..., None] + i_eff[..., None] * k.astype(jnp.float32)
    num = jnp.einsum("bhpr,bhr->bhp", C, q.astype(jnp.float32))
    qn = jnp.einsum("bhp,bhp->bh", n, q.astype(jnp.float32))
    denom = jnp.maximum(jnp.abs(qn), jnp.exp(-m_new))
    h = (num / denom[..., None]).reshape(B, di).astype(x.dtype)
    h = rms_norm(h, p["norm"], cfg.norm_eps)
    h = h + p["skip"] * conv_out[:, 0]
    h = h * jax.nn.silu(z[:, 0])
    out = jnp.einsum("be,ed->bd", h, p["w_down"])
    new_conv = jnp.concatenate([state["conv"][:, 1:], up_raw], axis=1)
    return out, {"C": C, "n": n, "m": m_new, "conv": new_conv}


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------


def slstm_ffn_dim(cfg: ModelConfig) -> int:
    f = int(cfg.d_model * 4 / 3)
    return ((f + 127) // 128) * 128


def init_slstm(init: Initializer, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    nh = cfg.n_heads
    dh = d // nh
    f = slstm_ffn_dim(cfg)
    return {
        "w_gates": init.param("w_gates", (d, 4 * d), ("p_embed", None)),
        "b_gates": init.param("b_gates", (4 * d,), (None,), zeros=True),
        # block-diagonal recurrent matrices, one (dh, dh) block per head/gate
        "r_gates": init.param("r_gates", (4, nh, dh, dh), (None, None, None, None),
                              scale=1.0 / math.sqrt(dh)),
        "norm": init.param("norm", (d,), ("p_embed",), ones=True),
        "ffn_w1": init.param("ffn_w1", (d, f), ("p_embed", "p_ffn")),
        "ffn_w3": init.param("ffn_w3", (d, f), ("p_embed", "p_ffn")),
        "ffn_w2": init.param("ffn_w2", (f, d), ("p_ffn", "p_embed")),
    }


def init_slstm_state(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> dict:
    d = cfg.d_model
    return {
        "h": jnp.zeros((batch, d), jnp.float32),
        "c": jnp.zeros((batch, d), jnp.float32),
        "n": jnp.zeros((batch, d), jnp.float32),
        "m": jnp.full((batch, d), -1e30, jnp.float32),
    }


def _slstm_cell(p, carry, g_x, cfg: ModelConfig):
    """One time step. carry: (h, c, n, m) each (B, d); g_x: (B, 4d) input-side
    gate preactivations for this step."""
    h, c, n, m = carry
    B = h.shape[0]
    d = cfg.d_model
    nh = cfg.n_heads
    dh = d // nh
    hb = h.reshape(B, nh, dh).astype(jnp.float32)
    rec = jnp.einsum("bhe,ghef->bghf", hb, p["r_gates"].astype(jnp.float32))
    g = g_x.reshape(B, 4, d).astype(jnp.float32) + rec.reshape(B, 4, d)
    ipre, fpre, zpre, opre = g[:, 0], g[:, 1], g[:, 2], g[:, 3]
    logf = jax.nn.log_sigmoid(fpre)
    m_new = jnp.maximum(logf + m, ipre)
    i_eff = jnp.exp(ipre - m_new)
    f_eff = jnp.exp(logf + m - m_new)
    c_new = f_eff * c + i_eff * jnp.tanh(zpre)
    n_new = f_eff * n + i_eff
    h_new = jax.nn.sigmoid(opre) * c_new / jnp.maximum(n_new, 1e-6)
    return (h_new, c_new, n_new, m_new)


def slstm_forward(
    p: dict,
    x: jax.Array,  # (B, S, d)
    cfg: ModelConfig,
    state: dict = None,
) -> Tuple[jax.Array, dict]:
    B, S, d = x.shape
    st = state or init_slstm_state(cfg, B)
    g_x = jnp.einsum("bsd,dg->bsg", x, p["w_gates"]) + p["b_gates"]

    def step(carry, g):
        new = _slstm_cell(p, carry, g, cfg)
        return new, new[0]

    carry0 = (st["h"], st["c"], st["n"], st["m"])
    (h, c, n, m), hs = jax.lax.scan(step, carry0, g_x.transpose(1, 0, 2))
    y = hs.transpose(1, 0, 2).astype(x.dtype)  # (B, S, d)
    y = rms_norm(y, p["norm"], cfg.norm_eps)
    # gated FFN (GELU, 4/3 factor)
    hmid = jax.nn.gelu(jnp.einsum("bsd,df->bsf", y, p["ffn_w1"]))
    hmid = hmid * jnp.einsum("bsd,df->bsf", y, p["ffn_w3"])
    out = jnp.einsum("bsf,fd->bsd", hmid, p["ffn_w2"])
    out = constrain(out, ("batch", "seq", "embed"))
    return out, {"h": h, "c": c, "n": n, "m": m}


def slstm_decode_step(
    p: dict, x: jax.Array, state: dict, cfg: ModelConfig
) -> Tuple[jax.Array, dict]:
    g_x = jnp.einsum("bd,dg->bg", x, p["w_gates"]) + p["b_gates"]
    carry = (state["h"], state["c"], state["n"], state["m"])
    h, c, n, m = _slstm_cell(p, carry, g_x, cfg)
    y = rms_norm(h.astype(x.dtype), p["norm"], cfg.norm_eps)
    hmid = jax.nn.gelu(jnp.einsum("bd,df->bf", y, p["ffn_w1"]))
    hmid = hmid * jnp.einsum("bd,df->bf", y, p["ffn_w3"])
    out = jnp.einsum("bf,fd->bd", hmid, p["ffn_w2"])
    return out, {"h": h, "c": c, "n": n, "m": m}
