"""Mamba2 (SSD) mixer: chunked parallel scan for train/prefill, O(1)-state
recurrence for decode. [arXiv:2405.21060, as used by Zamba2's backbone]

State-space recurrence per head h with state size N and head dim P:

    h_t = a_t * h_{t-1} + B_t (dt_t x_t)^T        h: (P, N)
    y_t = h_t C_t + D * x_t                        a_t = exp(-exp(A_log) dt_t)

The chunked ("SSD") algorithm splits the sequence into chunks of Q steps:
within a chunk the contribution is a masked quadratic form (decay kernel
L_ij = exp(cum_i - cum_j)); across chunks a (P, N) state is carried by a
`lax.scan`. Inputs x/B/C pass through a short causal depthwise conv whose
rolling (cw-1)-sample context is part of the decode state.

TPU adaptation: the inner quadratic term is an MXU-friendly (Q x Q) matmul
per head; the cross-chunk carry is the only sequential dependency, so the
HLO contains one scan of length S/Q regardless of model depth.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import constrain
from .common import Initializer, rms_norm

# steps per chunk of the chunked forward
CHUNK = 256

__all__ = ["init_mamba2", "mamba2_forward", "mamba2_decode_step", "init_mamba_state"]


def init_mamba2(init: Initializer, cfg: ModelConfig) -> dict:
    d, di, N = cfg.d_model, cfg.d_inner, cfg.ssm_state
    nh = cfg.n_ssm_heads
    cw = cfg.ssm_conv
    return {
        "w_x": init.param("w_x", (d, di), ("p_embed", "p_inner")),
        "w_z": init.param("w_z", (d, di), ("p_embed", "p_inner")),
        "w_B": init.param("w_B", (d, N), ("p_embed", None)),
        "w_C": init.param("w_C", (d, N), ("p_embed", None)),
        "w_dt": init.param("w_dt", (d, nh), ("p_embed", "p_inner")),
        "dt_bias": init.param("dt_bias", (nh,), ("p_inner",), zeros=True),
        "A_log": init.param("A_log", (nh,), ("p_inner",), zeros=True),
        "D": init.param("D", (nh,), ("p_inner",), ones=True),
        "conv_x": init.param("conv_x", (cw, di), (None, "p_inner"), scale=0.5),
        "conv_B": init.param("conv_B", (cw, N), (None, None), scale=0.5),
        "conv_C": init.param("conv_C", (cw, N), (None, None), scale=0.5),
        "norm": init.param("norm", (di,), ("p_inner",), ones=True),
        "w_out": init.param("w_out", (di, d), ("p_inner", "p_embed")),
    }


def _causal_conv(x: jax.Array, w: jax.Array, prior: jax.Array = None) -> jax.Array:
    """Depthwise causal conv + SiLU. x: (B, S, D), w: (W, D); prior:
    (B, W-1, D) rolling context from previous tokens (zeros if None)."""
    W = w.shape[0]
    if prior is None:
        prior = jnp.zeros((x.shape[0], W - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([prior, x], axis=1)
    out = sum(xp[:, i : i + x.shape[1]] * w[i] for i in range(W))
    return jax.nn.silu(out)


def _gates(p: dict, x: jax.Array):
    """Raw (pre-conv) projections: xi/z (B,S,di), B/C (B,S,N), dt (B,S,nh)."""
    xi = jnp.einsum("...d,de->...e", x, p["w_x"])
    z = jnp.einsum("...d,de->...e", x, p["w_z"])
    Bp = jnp.einsum("...d,dn->...n", x, p["w_B"])
    Cp = jnp.einsum("...d,dn->...n", x, p["w_C"])
    dt = jax.nn.softplus(
        jnp.einsum("...d,dh->...h", x, p["w_dt"]).astype(jnp.float32) + p["dt_bias"]
    )
    return xi, z, Bp, Cp, dt


def init_mamba_state(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16) -> dict:
    nh, P, N = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    cw, di = cfg.ssm_conv, cfg.d_inner
    return {
        "h": jnp.zeros((batch, nh, P, N), jnp.float32),
        "conv_x": jnp.zeros((batch, cw - 1, di), dtype),
        "conv_B": jnp.zeros((batch, cw - 1, N), dtype),
        "conv_C": jnp.zeros((batch, cw - 1, N), dtype),
    }


def mamba2_forward(
    p: dict,
    x: jax.Array,  # (B, S, d)
    cfg: ModelConfig,
    chunk: Optional[int] = None,  # None: CHUNK
    state: dict = None,  # continue from a previous state (or None = fresh)
) -> Tuple[jax.Array, dict]:
    """Full-sequence chunked forward. Returns (y (B,S,d), final state)."""
    B, S, _ = x.shape
    nh, P, N = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    cw = cfg.ssm_conv
    Q = min(chunk or CHUNK, S)
    pad = (-S) % Q

    xi_raw, z, B_raw, C_raw, dt = _gates(p, x)
    prior = state or {}
    xi = _causal_conv(xi_raw, p["conv_x"], prior.get("conv_x"))
    Bp = _causal_conv(B_raw, p["conv_B"], prior.get("conv_B"))
    Cp = _causal_conv(C_raw, p["conv_C"], prior.get("conv_C"))
    xi = constrain(xi, ("batch", "seq", "inner"))

    if pad:
        xi_p = jnp.pad(xi, ((0, 0), (0, pad), (0, 0)))
        Bp = jnp.pad(Bp, ((0, 0), (0, pad), (0, 0)))
        Cp = jnp.pad(Cp, ((0, 0), (0, pad), (0, 0)))
        dt_p = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    else:
        xi_p, dt_p = xi, dt
    Sp = S + pad
    nc = Sp // Q

    xh = xi_p.reshape(B, nc, Q, nh, P)
    u = (xh.astype(jnp.float32) * dt_p.reshape(B, nc, Q, nh)[..., None]).astype(x.dtype)
    Bc = Bp.reshape(B, nc, Q, N)
    Cc = Cp.reshape(B, nc, Q, N)
    a_log = -jnp.exp(p["A_log"].astype(jnp.float32)) * dt_p  # (B, Sp, nh) <= 0
    # padded steps must not decay the carried state: a_log(pad) = 0 is correct
    a_log = a_log.reshape(B, nc, Q, nh)
    cum = jnp.cumsum(a_log, axis=2)  # inclusive log-decay prefix

    # Intra-chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) u_j
    sBC = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)  # (B, nc, Q, Q)
    ii, jj = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]
    causal = (jj <= ii).astype(jnp.float32)
    decay = jnp.exp(cum[:, :, :, None, :] - cum[:, :, None, :, :])  # (B,nc,i,j,nh)
    G = sBC[..., None] * decay * causal[..., None]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", G.astype(x.dtype), u)

    # Cross-chunk carry: state (B, nh, P, N) f32.
    chunk_decay = jnp.exp(cum[:, :, -1:, :] - cum)  # decay j -> chunk end
    state_in = jnp.einsum(
        "bcjn,bcjhp,bcjh->bchpn",
        Bc.astype(jnp.float32),
        u.astype(jnp.float32),
        chunk_decay,
    )
    total_decay = jnp.exp(cum[:, :, -1, :])  # (B, nc, nh)

    def carry_step(h, xs):
        s_in, tdec, c_chunk, cum_chunk = xs
        y_int = jnp.einsum(
            "bin,bhpn,bih->bihp", c_chunk.astype(jnp.float32), h, jnp.exp(cum_chunk)
        )
        return h * tdec[:, :, None, None] + s_in, y_int

    h0 = prior.get("h")
    if h0 is None:
        h0 = jnp.zeros((B, nh, P, N), jnp.float32)
    h_final, y_inter = jax.lax.scan(
        carry_step,
        h0,
        (
            state_in.transpose(1, 0, 2, 3, 4),
            total_decay.transpose(1, 0, 2),
            Cc.transpose(1, 0, 2, 3),
            cum.transpose(1, 0, 2, 3),
        ),
    )
    y_inter = y_inter.transpose(1, 0, 2, 3, 4)  # (B, nc, Q, nh, P)

    y = (y_intra.astype(jnp.float32) + y_inter).reshape(B, Sp, nh, P)[:, :S]
    y = y + p["D"].astype(jnp.float32)[:, None] * xi.reshape(B, S, nh, P).astype(
        jnp.float32
    )
    y = y.reshape(B, S, nh * P).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"])
    out = constrain(out, ("batch", "seq", "embed"))

    def roll_ctx(raw, old_key):
        prev = prior.get(old_key)
        if prev is None:
            prev = jnp.zeros((B, cw - 1, raw.shape[-1]), raw.dtype)
        return jnp.concatenate([prev, raw], axis=1)[:, -(cw - 1) :]

    new_state = {
        "h": h_final,
        "conv_x": roll_ctx(xi_raw, "conv_x"),
        "conv_B": roll_ctx(B_raw, "conv_B"),
        "conv_C": roll_ctx(C_raw, "conv_C"),
    }
    return out, new_state


def mamba2_decode_step(
    p: dict,
    x: jax.Array,  # (B, d) one token
    state: dict,
    cfg: ModelConfig,
) -> Tuple[jax.Array, dict]:
    """Single-token recurrent step; state as from `init_mamba_state`."""
    B = x.shape[0]
    nh, P, N = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    xi_raw, z, B_raw, C_raw, dt = _gates(p, x[:, None, :])
    xi = _causal_conv(xi_raw, p["conv_x"], prior=state["conv_x"])[:, 0]
    Bc = _causal_conv(B_raw, p["conv_B"], prior=state["conv_B"])[:, 0]
    Cc = _causal_conv(C_raw, p["conv_C"], prior=state["conv_C"])[:, 0]
    dt1 = dt[:, 0]  # (B, nh)

    a = jnp.exp(-jnp.exp(p["A_log"].astype(jnp.float32)) * dt1)  # (B, nh)
    xh = xi.reshape(B, nh, P).astype(jnp.float32)
    u = xh * dt1[..., None]
    h = state["h"] * a[..., None, None] + jnp.einsum(
        "bn,bhp->bhpn", Bc.astype(jnp.float32), u
    )
    y = jnp.einsum("bhpn,bn->bhp", h, Cc.astype(jnp.float32))
    y = y + p["D"].astype(jnp.float32)[:, None] * xh
    y = y.reshape(B, nh * P).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z[:, 0]), p["norm"], cfg.norm_eps)
    out = jnp.einsum("be,ed->bd", y, p["w_out"])
    new_state = {
        "h": h,
        "conv_x": jnp.concatenate([state["conv_x"][:, 1:], xi_raw], axis=1),
        "conv_B": jnp.concatenate([state["conv_B"][:, 1:], B_raw], axis=1),
        "conv_C": jnp.concatenate([state["conv_C"][:, 1:], C_raw], axis=1),
    }
    return out, new_state
