"""Mixture-of-experts MLP with capacity-based top-k dispatch (Mesh-TF style).

Mixtral-8x22B: 8 experts top-2; Llama-4-Scout: 16 experts top-1.

Dispatch: per batch-row groups. Tokens pick top-k experts; position within
each expert's buffer comes from a cumulative sum over the (token, k) slots;
tokens beyond the expert capacity C = ceil(S*k/E * capacity_factor) are
dropped (residual passthrough). Tokens reach the (B, E, C, d) expert
buffers by scatter and come back by gather.

Expert weights are tensor-parallel on the ffn axis inside every expert
(uniform, always divides); the experts axis itself is a hillclimb knob
(expert parallelism trades the dispatch einsums for all-to-alls).

Router aux outputs: load-balancing loss (Switch-style) and router z-loss.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import constrain
from .common import Initializer, activation_fn

__all__ = ["init_moe", "moe_forward", "expert_capacity"]


def expert_capacity(cfg: ModelConfig, seq: int) -> int:
    cap = int(seq * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, ((cap + 7) // 8) * 8)  # pad to 8 for TPU-friendly tiling


def init_moe(init: Initializer, cfg: ModelConfig) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    gated = cfg.activation in ("silu", "gelu")
    p = {
        "router": init.param("router", (d, E), ("p_embed", None)),
        "w1": init.param("w1", (E, d, f), ("p_experts", "p_embed", "p_ffn")),
        "w2": init.param("w2", (E, f, d), ("p_experts", "p_ffn", "p_embed")),
    }
    if gated:
        p["w3"] = init.param("w3", (E, d, f), ("p_experts", "p_embed", "p_ffn"))
    return p


def _route(p: dict, x: jax.Array, cfg: ModelConfig, C: int):
    """Top-k routing + capacity positions. Returns (gate_w, gate_idx,
    pos_sel, keep_k, probs) with shapes (B,S,k) / (B,S,E)."""
    E, k = cfg.n_experts, cfg.top_k
    B, S, _ = x.shape
    logits = jnp.einsum("bsd,de->bse", x, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, k)  # (B, S, k)
    if k > 1:
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    sel = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # (B, S, k, E)
    pos = jnp.cumsum(sel.reshape(B, S * k, E), axis=1) - 1
    pos = pos.reshape(B, S, k, E)
    keep = (pos < C) & (sel > 0)
    pos_sel = (pos * sel).sum(-1)  # (B, S, k)
    keep_k = keep.any(-1)  # (B, S, k)
    return gate_w, gate_idx, pos_sel, keep_k, sel, keep, probs, logits


def moe_forward(p: dict, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, dict]:
    """x: (B, S, d) -> (out (B, S, d), aux losses dict).

    Tokens move into (B, E, C, d) expert buffers via scatter and back via
    gather: O(T*d) data movement, no FLOPs beyond the expert matmuls. (The
    Mesh-TF one-hot einsum form, which tests compare against, spends
    O(T*E*C*d) FLOPs on dispatch and combine.)
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = expert_capacity(cfg, S)
    act = activation_fn(cfg.activation)
    gate_w, gate_idx, pos_sel, keep_k, sel, keep, probs, logits = _route(
        p, x, cfg, C
    )

    def experts(xe):  # (B, E, C, d) -> (B, E, C, d)
        h = jnp.einsum("becd,edf->becf", xe, p["w1"])
        if "w3" in p:
            h = act(h) * jnp.einsum("becd,edf->becf", xe, p["w3"])
        else:
            h = act(h)
        h = constrain(h, ("batch", "experts", None, "ffn"))
        return jnp.einsum("becf,efd->becd", h, p["w2"])

    e_idx = jnp.where(keep_k, gate_idx, E)  # OOB rows dropped by scatter
    c_idx = jnp.where(keep_k, pos_sel, 0)
    xk = jnp.broadcast_to(x[:, :, None, :], (B, S, k, d))

    # vmap over the batch row makes it an explicit scatter/gather
    # batching dim, so GSPMD keeps the data movement local to the
    # (data-sharded) batch instead of all-reducing buffers.
    def scatter_row(er, cr, xr):
        return jnp.zeros((E + 1, C, d), x.dtype).at[er, cr].set(xr, mode="drop")

    xe = jax.vmap(scatter_row)(e_idx, c_idx, xk)[:, :E]
    xe = constrain(xe, ("batch", "experts", None, "embed"))
    ye = experts(xe)

    def gather_row(yr, er, cr):
        return yr[jnp.minimum(er, E - 1), cr]

    yk = jax.vmap(gather_row)(ye, e_idx, c_idx)  # (B, S, k, d)
    out = jnp.einsum(
        "bskd,bsk->bsd", yk, gate_w.astype(x.dtype) * keep_k.astype(x.dtype)
    )
    out = constrain(out, ("batch", "seq_res", "embed"))

    # Switch-style load-balance loss + router z-loss. Each of the k picks
    # counts 1/k so a perfectly balanced router scores exactly 1.0.
    frac_tokens = jnp.mean(sel.astype(jnp.float32).sum(2), axis=(0, 1)) / k  # (E,)
    frac_prob = jnp.mean(probs, axis=(0, 1))
    lb_loss = E * jnp.sum(frac_tokens * frac_prob)
    z_loss = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return out, {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss}
