"""Encoder-decoder transformer (seamless-m4t backbone).

The speech frontend is stubbed per the assignment carve-out: the encoder
consumes precomputed frame embeddings (B, S_enc, d). The decoder is a
standard causal transformer with per-layer cross attention over the encoder
output. RoPE provides positions on both self-attention paths.

Decode caches: self-attention KV ring + *static* cross-attention KV
(projected once at prefill — the paper's N_input tokens map to encoder
frames here).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import Axes, constrain
from .attention import (
    attention_forward,
    decode_attention,
    init_attention,
    project_kv,
)
from .common import DTYPES, Initializer, RuntimeFlags, init_ctx, rms_norm
from .mlp import init_mlp, mlp_forward
from .transformer import _stack_init, logits_from_hidden

__all__ = [
    "init_encdec_params",
    "encdec_forward",
    "encdec_prefill",
    "encdec_decode",
    "init_encdec_cache",
    "encode",
]


def _init_enc_layer(init: Initializer, cfg: ModelConfig) -> dict:
    return {
        "attn_norm": init.param("attn_norm", (cfg.d_model,), ("p_embed",), ones=True),
        "attn": init_attention(init.child("attn"), cfg),
        "mlp_norm": init.param("mlp_norm", (cfg.d_model,), ("p_embed",), ones=True),
        "mlp": init_mlp(init.child("mlp"), cfg),
    }


def _init_dec_layer(init: Initializer, cfg: ModelConfig) -> dict:
    return {
        "self_norm": init.param("self_norm", (cfg.d_model,), ("p_embed",), ones=True),
        "self_attn": init_attention(init.child("self_attn"), cfg),
        "cross_norm": init.param("cross_norm", (cfg.d_model,), ("p_embed",), ones=True),
        "cross_attn": init_attention(init.child("cross_attn"), cfg),
        "mlp_norm": init.param("mlp_norm", (cfg.d_model,), ("p_embed",), ones=True),
        "mlp": init_mlp(init.child("mlp"), cfg),
    }


def init_encdec_params(
    cfg: ModelConfig, key: jax.Array, dtype=None
) -> Tuple[dict, dict]:
    dtype = dtype or DTYPES[cfg.dtype]
    keys = jax.random.split(key, 4)
    params: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}
    with init_ctx() as top_axes:
        top = Initializer(keys[0], dtype)
        params["embed"] = top.param(
            "embed", (cfg.padded_vocab, cfg.d_model), ("p_vocab", "p_embed"),
            scale=0.02,
        )
        params["enc_final_norm"] = top.param(
            "enc_final_norm", (cfg.d_model,), ("p_embed",), ones=True
        )
        params["final_norm"] = top.param(
            "final_norm", (cfg.d_model,), ("p_embed",), ones=True
        )
        params["lm_head"] = top.param(
            "lm_head", (cfg.d_model, cfg.padded_vocab), ("p_embed", "p_vocab")
        )
    axes.update(top_axes)
    params["enc_layers"], axes["enc_layers"] = _stack_init(
        lambda i: _init_enc_layer(i, cfg), keys[1], cfg.n_encoder_layers, dtype
    )
    params["dec_layers"], axes["dec_layers"] = _stack_init(
        lambda i: _init_dec_layer(i, cfg), keys[2], cfg.n_layers, dtype
    )
    return params, axes


def encode(
    params: dict, cfg: ModelConfig, rt: RuntimeFlags, enc_embeds: jax.Array
) -> jax.Array:
    """Bidirectional encoder over frame embeddings -> (B, S_enc, d)."""
    B, S, _ = enc_embeds.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = constrain(enc_embeds, ("batch", "seq", "embed"))

    def body(x, lp):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        a, _ = attention_forward(lp["attn"], h, cfg, positions, causal=False)
        x = x + a
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        return x + mlp_forward(lp["mlp"], h, cfg), None

    b = jax.checkpoint(body) if rt.remat else body
    x, _ = jax.lax.scan(b, x, params["enc_layers"])
    return rms_norm(x, params["enc_final_norm"], cfg.norm_eps)


def _dec_stack(
    params, cfg, rt, x, positions, enc_out, enc_pos, collect_cache: bool
):
    """Decoder layers over (B, S, d) with cross attention on enc_out."""

    def body(x, lp):
        h = rms_norm(x, lp["self_norm"], cfg.norm_eps)
        a, kv = attention_forward(lp["self_attn"], h, cfg, positions, causal=True)
        x = x + a
        h = rms_norm(x, lp["cross_norm"], cfg.norm_eps)
        ckv = project_kv(lp["cross_attn"], enc_out, cfg)
        c, _ = attention_forward(
            lp["cross_attn"], h, cfg, positions,
            cross_kv=ckv, cross_pos=enc_pos,
        )
        x = x + c
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + mlp_forward(lp["mlp"], h, cfg)
        ys = (kv, ckv) if collect_cache else None
        return x, ys

    b = jax.checkpoint(body) if rt.remat else body
    return jax.lax.scan(b, x, params["dec_layers"])


def encdec_forward(
    params: dict,
    cfg: ModelConfig,
    rt: RuntimeFlags,
    enc_embeds: jax.Array,  # (B, S_enc, d)
    dec_tokens: jax.Array,  # (B, S_dec)
) -> Tuple[jax.Array, dict]:
    """Teacher-forced forward. Returns (logits (B, S_dec, V), aux)."""
    enc_out = encode(params, cfg, rt, enc_embeds)
    B, Se, _ = enc_out.shape
    Sd = dec_tokens.shape[1]
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))
    positions = jnp.broadcast_to(jnp.arange(Sd, dtype=jnp.int32), (B, Sd))
    x = jnp.take(params["embed"], dec_tokens, axis=0)
    x = constrain(x, ("batch", "seq", "embed"))
    x, _ = _dec_stack(params, cfg, rt, x, positions, enc_out, enc_pos, False)
    return logits_from_hidden(params, cfg, x), {}


def init_encdec_cache(
    cfg: ModelConfig, batch: int, cache_len: int, enc_len: int, dtype=None
) -> Tuple[dict, dict]:
    dtype = dtype or DTYPES[cfg.dtype]
    L, K, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    kv_ax = Axes(("layers", "kv_batch", "kv_seq", "kv_heads", None))
    cache = {
        "k": jnp.zeros((L, batch, cache_len, K, dh), dtype),
        "v": jnp.zeros((L, batch, cache_len, K, dh), dtype),
        "pos": jnp.full((batch, cache_len), -1, jnp.int32),
        "cross_k": jnp.zeros((L, batch, enc_len, K, dh), dtype),
        "cross_v": jnp.zeros((L, batch, enc_len, K, dh), dtype),
        "cross_pos": jnp.zeros((batch, enc_len), jnp.int32),
    }
    axes = {
        "k": kv_ax,
        "v": kv_ax,
        "pos": Axes(("kv_batch", "kv_seq")),
        "cross_k": kv_ax,
        "cross_v": kv_ax,
        "cross_pos": Axes(("kv_batch", "kv_seq")),
    }
    return cache, axes


def encdec_prefill(
    params: dict,
    cfg: ModelConfig,
    rt: RuntimeFlags,
    enc_embeds: jax.Array,
    dec_tokens: jax.Array,
) -> Tuple[jax.Array, dict]:
    enc_out = encode(params, cfg, rt, enc_embeds)
    B, Se, _ = enc_out.shape
    Sd = dec_tokens.shape[1]
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))
    positions = jnp.broadcast_to(jnp.arange(Sd, dtype=jnp.int32), (B, Sd))
    x = jnp.take(params["embed"], dec_tokens, axis=0)
    x = constrain(x, ("batch", "seq", "embed"))
    x, kvs = _dec_stack(params, cfg, rt, x, positions, enc_out, enc_pos, True)
    (k, v), (ck, cv) = kvs
    cache = {
        "k": k, "v": v, "pos": positions,
        "cross_k": ck, "cross_v": cv, "cross_pos": enc_pos,
    }
    return logits_from_hidden(params, cfg, x[:, -1]), cache


def encdec_decode(
    params: dict,
    cfg: ModelConfig,
    cache: dict,
    token: jax.Array,  # (B,)
    pos: jax.Array,  # (B,)
) -> Tuple[jax.Array, dict]:
    x = jnp.take(params["embed"], token, axis=0)
    x = constrain(x, ("batch", "embed"))
    Sc = cache["k"].shape[2]
    slot = pos % Sc
    bidx = jnp.arange(x.shape[0])

    def body(x, xs):
        lp, ck, cv, crk, crv = xs
        h = rms_norm(x, lp["self_norm"], cfg.norm_eps)
        a, (kn, vn) = decode_attention(
            lp["self_attn"], h, cfg, pos, ck, cv, cache["pos"]
        )
        x = x + a
        h = rms_norm(x, lp["cross_norm"], cfg.norm_eps)
        c, _ = decode_attention(
            lp["cross_attn"], h, cfg, pos, crk, crv, cache["cross_pos"],
            cross=True,
        )
        x = x + c
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + mlp_forward(lp["mlp"], h, cfg)
        ck = ck.at[bidx, slot].set(kn)
        cv = cv.at[bidx, slot].set(vn)
        return x, (ck, cv)

    xs = (
        params["dec_layers"], cache["k"], cache["v"],
        cache["cross_k"], cache["cross_v"],
    )
    x, (k_new, v_new) = jax.lax.scan(body, x, xs)
    new_cache = dict(cache, k=k_new, v=v_new,
                     pos=cache["pos"].at[bidx, slot].set(pos))
    return logits_from_hidden(params, cfg, x), new_cache
