"""Decoder-only stacks for all assigned families.

Uniform families (dense / vlm / moe) scan one pre-norm residual block over
stacked per-layer params — compile time is depth-independent (88-layer
mistral-large lowers as one block + lax.scan).

Grouped families re-use the same scan with a supergroup pattern:

  * hybrid (zamba2): groups of `shared_attn_every` Mamba2 layers followed by
    ONE weight-shared attention+MLP block (+ a trailing remainder group).
  * ssm (xlstm): groups of (slstm_every - 1) mLSTM blocks + 1 sLSTM block.

Caches:

  * attention: {"k","v": (L, B, Sc, K, dh), "pos": (B, Sc)}; sliding-window
    serving uses the same buffers as a ring (slot = pos % Sc).
  * hybrid: mamba states (L, B, ...) + shared-attn caches (n_groups, ...).
  * ssm: mLSTM (C, n, m, conv) + sLSTM (h, c, n, m) states per group.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import Axes, constrain
from .attention import attention_forward, decode_attention, init_attention
from .common import DTYPES, Initializer, RuntimeFlags, init_ctx, init_norm, norm
from .mamba2 import (
    init_mamba2,
    init_mamba_state,
    mamba2_decode_step,
    mamba2_forward,
)
from .mlp import init_mlp, mlp_forward
from .moe import init_moe, moe_forward
from .xlstm import (
    init_mlstm,
    init_mlstm_state,
    init_slstm,
    init_slstm_state,
    mlstm_decode_step,
    mlstm_forward,
    slstm_decode_step,
    slstm_forward,
)

__all__ = [
    "init_decoder_params",
    "decoder_forward",
    "decoder_prefill",
    "decoder_decode",
    "init_decode_cache",
    "logits_from_hidden",
    "embed_inputs",
]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _collect_axes(fn: Callable[[Initializer], dict], dtype) -> dict:
    """Run `fn` once abstractly to collect the logical-axes tree."""
    with init_ctx() as col:
        jax.eval_shape(lambda k: fn(Initializer(k, dtype)), jax.random.PRNGKey(0))
    return col


def _stack_init(
    fn: Callable[[Initializer], dict], key: jax.Array, n: int, dtype
) -> Tuple[dict, dict]:
    """vmap `fn` over `n` layer keys; axes get a leading (unsharded) layer
    axis. Returns (stacked params, axes tree)."""
    axes1 = _collect_axes(fn, dtype)
    axes = jax.tree.map(
        lambda ax: Axes((None,) + tuple(ax)),
        axes1,
        is_leaf=lambda x: isinstance(x, Axes),
    )
    keys = jax.random.split(key, n)
    params = jax.vmap(lambda k: fn(Initializer(k, dtype)))(keys)
    return params, axes


def _iro_flags(cfg: ModelConfig, n: int) -> Optional[jax.Array]:
    """Per-layer RoPE flags for iRoPE (1.0 = RoPE, 0.0 = NoPE)."""
    if not cfg.nope_interval:
        return None
    idx = jnp.arange(n)
    return ((idx + 1) % cfg.nope_interval != 0).astype(jnp.float32)


def _init_attn_block(init: Initializer, cfg: ModelConfig) -> dict:
    sub = init_norm(init, "attn_norm", cfg)
    a = init.child("attn")
    sub["attn"] = init_attention(a, cfg)
    sub.update(init_norm(init, "mlp_norm", cfg))
    if cfg.n_experts:
        m = init.child("moe")
        sub["moe"] = init_moe(m, cfg)
    else:
        m = init.child("mlp")
        sub["mlp"] = init_mlp(m, cfg)
    return sub


def _init_mamba_block(init: Initializer, cfg: ModelConfig) -> dict:
    return {
        **init_norm(init, "norm", cfg),
        "mamba": init_mamba2(init.child("mamba"), cfg),
    }


def _init_mlstm_block(init: Initializer, cfg: ModelConfig) -> dict:
    return {
        **init_norm(init, "norm", cfg),
        "mlstm": init_mlstm(init.child("mlstm"), cfg),
    }


def _init_slstm_block(init: Initializer, cfg: ModelConfig) -> dict:
    return {
        **init_norm(init, "norm", cfg),
        **init_norm(init, "ffn_norm", cfg),
        "slstm": init_slstm(init.child("slstm"), cfg),
    }


def _group_shape(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_groups, group_size, remainder) for grouped families."""
    if cfg.family == "hybrid":
        g = cfg.shared_attn_every
    elif cfg.family == "ssm":
        g = cfg.slstm_every
    else:
        return (0, 0, cfg.n_layers)
    n_groups = cfg.n_layers // g
    return n_groups, g, cfg.n_layers - n_groups * g


def init_decoder_params(
    cfg: ModelConfig, key: jax.Array, dtype=None
) -> Tuple[dict, dict]:
    """Returns (params, logical-axes tree with matching structure)."""
    dtype = dtype or DTYPES[cfg.dtype]
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}

    with init_ctx() as top_axes:
        top = Initializer(keys[0], dtype)
        # Embed table exists even for embeds_input archs: their *prompt*
        # arrives as frontend embeddings, but generated tokens still need
        # text embeddings during decode.
        params["embed"] = top.param(
            "embed", (cfg.padded_vocab, cfg.d_model), ("p_vocab", "p_embed"),
            scale=0.02,
        )
        params.update(init_norm(top, "final_norm", cfg))
        if not cfg.tie_embeddings:
            params["lm_head"] = top.param(
                "lm_head", (cfg.d_model, cfg.padded_vocab), ("p_embed", "p_vocab")
            )
    axes.update(top_axes)

    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        params["layers"], axes["layers"] = _stack_init(
            lambda i: _init_attn_block(i, cfg), keys[1], cfg.n_layers, dtype
        )
    elif fam == "hybrid":
        ng, gs, rem = _group_shape(cfg)
        grouped, gaxes = _stack_init(
            lambda i: _init_mamba_block(i, cfg), keys[1], ng * gs, dtype
        )
        params["mamba_groups"] = jax.tree.map(
            lambda x: x.reshape((ng, gs) + x.shape[1:]), grouped
        )
        axes["mamba_groups"] = jax.tree.map(
            lambda ax: Axes((None,) + tuple(ax)),
            gaxes,
            is_leaf=lambda x: isinstance(x, Axes),
        )
        if rem:
            params["mamba_rest"], axes["mamba_rest"] = _stack_init(
                lambda i: _init_mamba_block(i, cfg), keys[2], rem, dtype
            )
        with init_ctx() as sa:
            params["shared"] = _init_attn_block(Initializer(keys[3], dtype), cfg)
        axes["shared"] = sa
    elif fam == "ssm":
        ng, gs, rem = _group_shape(cfg)
        assert rem == 0, "xlstm stack must divide into (mLSTM*, sLSTM) groups"
        params["mlstm_groups"], maxes = _stack_init(
            lambda i: _init_mlstm_block(i, cfg), keys[1], ng * (gs - 1), dtype
        )
        params["mlstm_groups"] = jax.tree.map(
            lambda x: x.reshape((ng, gs - 1) + x.shape[1:]), params["mlstm_groups"]
        )
        axes["mlstm_groups"] = jax.tree.map(
            lambda ax: Axes((None,) + tuple(ax)),
            maxes,
            is_leaf=lambda x: isinstance(x, Axes),
        )
        params["slstm_blocks"], axes["slstm_blocks"] = _stack_init(
            lambda i: _init_slstm_block(i, cfg), keys[2], ng, dtype
        )
    else:
        raise ValueError(f"family {fam} handled by encdec.py, not here")
    return params, axes


# ---------------------------------------------------------------------------
# shared forward pieces
# ---------------------------------------------------------------------------


def embed_inputs(params: dict, cfg: ModelConfig, inputs: jax.Array) -> jax.Array:
    """tokens (B, S) int -> (B, S, d); (B, S, d) frontend embeds pass through."""
    if inputs.ndim == 3:
        return constrain(inputs, ("batch", "seq", "embed"))
    x = jnp.take(params["embed"], inputs, axis=0)
    return constrain(x, ("batch", "seq", "embed"))


def logits_from_hidden(params: dict, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    with jax.named_scope("final_norm"):
        h = norm(h, params, "final_norm", cfg)
    w = params.get("lm_head")
    if w is None:  # tied embeddings
        w = params["embed"].T
    with jax.named_scope("lm_head"):
        logits = jnp.einsum("...d,dv->...v", h, w)
    ax = ("batch", "seq", "vocab") if logits.ndim == 3 else ("batch", "vocab")
    return constrain(logits, ax)


def _attn_block_apply(
    lp: dict,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    rope_flag: Optional[jax.Array],
    window: int,
    mrope_positions=None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array], dict]:
    """Pre-norm attention(+MLP/MoE) residual block. Returns (x, (k,v), aux).

    The residual stream is pinned to the "seq_res" logical axis at the
    block boundaries — unsharded by default, model-axis-sharded under the
    sequence-parallel rule set (TRAIN_RULES_SP)."""
    x = constrain(x, ("batch", "seq_res", "embed"))
    with jax.named_scope("attn_norm"):
        h = norm(x, lp, "attn_norm", cfg)
    with jax.named_scope("attention"):
        a, kv = attention_forward(
            lp["attn"], h, cfg, positions,
            causal=True, window=window, rope_flag=rope_flag,
            mrope_positions=mrope_positions,
        )
    x = constrain(x + a, ("batch", "seq_res", "embed"))
    with jax.named_scope("mlp_norm"):
        h = norm(x, lp, "mlp_norm", cfg)
    if "moe" in lp:
        with jax.named_scope("moe"):
            m, aux = moe_forward(lp["moe"], h, cfg)
    else:
        with jax.named_scope("mlp"):
            m, aux = mlp_forward(lp["mlp"], h, cfg), {}
    return constrain(x + m, ("batch", "seq_res", "embed")), kv, aux


def _attn_block_decode(
    lp: dict,
    x: jax.Array,  # (B, d)
    cfg: ModelConfig,
    pos: jax.Array,  # (B,)
    cache_k, cache_v, cache_pos,
    rope_flag,
    window: int,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array], dict]:
    with jax.named_scope("attn_norm"):
        h = norm(x, lp, "attn_norm", cfg)
    with jax.named_scope("attention"):
        a, kv = decode_attention(
            lp["attn"], h, cfg, pos, cache_k, cache_v, cache_pos,
            window=window, rope_flag=rope_flag,
        )
    x = x + a
    with jax.named_scope("mlp_norm"):
        h = norm(x, lp, "mlp_norm", cfg)
    if "moe" in lp:
        with jax.named_scope("moe"):
            hm, aux = moe_forward(lp["moe"], h[:, None, :], cfg)
        m = hm[:, 0]
    else:
        with jax.named_scope("mlp"):
            m, aux = mlp_forward(lp["mlp"], h, cfg), {}
    return x + m, kv, aux


def _sum_aux(acc: dict, aux: dict) -> dict:
    for k, v in aux.items():
        acc[k] = acc.get(k, 0.0) + v
    return acc


# ---------------------------------------------------------------------------
# uniform (dense / vlm / moe) stack
# ---------------------------------------------------------------------------


def _uniform_stack(
    params, cfg, rt, x, positions, mrope_positions, collect_cache: bool
):
    flags = _iro_flags(cfg, cfg.n_layers)
    window = cfg.window
    aux0 = {"moe_lb_loss": jnp.float32(0.0), "moe_z_loss": jnp.float32(0.0)} \
        if cfg.n_experts else {}

    def body(carry, xs):
        x, aux = carry
        lp = xs if flags is None else xs[0]
        fl = None if flags is None else xs[1]
        fn = _attn_block_apply
        if rt.remat:
            fn = jax.checkpoint(fn, static_argnums=(2, 5))
        x, kv, a = fn(lp, x, cfg, positions, fl, window, mrope_positions)
        aux = _sum_aux(dict(aux), a)
        ys = kv if collect_cache else None
        return (x, aux), ys

    xs = params["layers"] if flags is None else (params["layers"], flags)
    (x, aux), kvs = jax.lax.scan(body, (x, aux0), xs)
    return x, aux, kvs


def _uniform_decode(params, cfg, x, pos, cache):
    flags = _iro_flags(cfg, cfg.n_layers)
    window = cfg.window
    Sc = cache["k"].shape[2]
    slot = pos % Sc  # ring-buffer slot (full cache: pos < Sc)
    bidx = jnp.arange(x.shape[0])

    def body(x, xs):
        if flags is None:
            lp, ck, cv = xs
            fl = None
        else:
            lp, ck, cv, fl = xs
        x, (kn, vn), _ = _attn_block_decode(
            lp, x, cfg, pos, ck, cv, cache["pos"], fl, window
        )
        ck = ck.at[bidx, slot].set(kn)
        cv = cv.at[bidx, slot].set(vn)
        return x, (ck, cv)

    xs = (params["layers"], cache["k"], cache["v"])
    if flags is not None:
        xs = xs + (flags,)
    x, (k_new, v_new) = jax.lax.scan(body, x, xs)
    new_pos = cache["pos"].at[bidx, slot].set(pos)
    return x, {"k": k_new, "v": v_new, "pos": new_pos}


# ---------------------------------------------------------------------------
# hybrid (zamba2) stack
# ---------------------------------------------------------------------------


def _hybrid_stack(params, cfg, rt, x, positions, collect_cache: bool):
    ng, gs, rem = _group_shape(cfg)
    window = cfg.window

    def mamba_layer(carry, lp):
        x = carry
        h = norm(x, lp, "norm", cfg)
        y, st = mamba2_forward(lp["mamba"], h, cfg)
        ys = st if collect_cache else None
        return x + y, ys

    def group_body(carry, glp):
        x, _aux = carry
        x, sts = jax.lax.scan(mamba_layer, x, glp)
        x, kv, a = _attn_block_apply(
            params["shared"], x, cfg, positions, None, window
        )
        return (x, _sum_aux(dict(_aux), a)), (sts, kv if collect_cache else None)

    gb = group_body
    if rt.remat:
        gb = jax.checkpoint(group_body)
    (x, aux), (mamba_states, kvs) = jax.lax.scan(
        gb, (x, {}), params["mamba_groups"]
    )
    rest_states = None
    if rem:
        x, rest_states = jax.lax.scan(mamba_layer, x, params["mamba_rest"])
    return x, aux, (mamba_states, rest_states, kvs)


def _hybrid_decode(params, cfg, x, pos, cache):
    ng, gs, rem = _group_shape(cfg)
    window = cfg.window
    Sc = cache["k"].shape[2]
    slot = pos % Sc
    bidx = jnp.arange(x.shape[0])

    def mamba_layer(carry, xs):
        x = carry
        lp, st = xs
        h = norm(x, lp, "norm", cfg)
        y, st_new = mamba2_decode_step(lp["mamba"], h, st, cfg)
        return x + y, st_new

    def group_body(carry, xs):
        x = carry
        glp, gst, ck, cv = xs
        x, st_new = jax.lax.scan(mamba_layer, x, (glp, gst))
        x, (kn, vn), _ = _attn_block_decode(
            params["shared"], x, cfg, pos, ck, cv, cache["pos"], None, window
        )
        ck = ck.at[bidx, slot].set(kn)
        cv = cv.at[bidx, slot].set(vn)
        return x, (st_new, ck, cv)

    x, (mstates, k_new, v_new) = jax.lax.scan(
        group_body, x, (params["mamba_groups"], cache["mamba"], cache["k"], cache["v"])
    )
    rest = cache.get("rest")
    if rest is not None:
        x, rest = jax.lax.scan(mamba_layer, x, (params["mamba_rest"], rest))
    new_pos = cache["pos"].at[bidx, slot].set(pos)
    out_cache = {"mamba": mstates, "k": k_new, "v": v_new, "pos": new_pos}
    if rest is not None:
        out_cache["rest"] = rest
    return x, out_cache


# ---------------------------------------------------------------------------
# ssm (xlstm) stack
# ---------------------------------------------------------------------------


def _ssm_stack(params, cfg, rt, x, collect_cache: bool):
    def mlstm_layer(carry, lp):
        x = carry
        h = norm(x, lp, "norm", cfg)
        y, st = mlstm_forward(lp["mlstm"], h, cfg)
        return x + y, st if collect_cache else None

    def group_body(carry, xs):
        x = carry
        glp, slp = xs
        x, msts = jax.lax.scan(mlstm_layer, x, glp)
        h = norm(x, slp, "norm", cfg)
        y, sst = slstm_forward(slp["slstm"], h, cfg)
        # slstm block: cell + its own gated FFN applied inside slstm_forward
        x = x + y
        return x, (msts, sst if collect_cache else None)

    gb = jax.checkpoint(group_body) if rt.remat else group_body
    x, (mstates, sstates) = jax.lax.scan(
        gb, x, (params["mlstm_groups"], params["slstm_blocks"])
    )
    return x, {}, (mstates, sstates)


def _ssm_decode(params, cfg, x, cache):
    def mlstm_layer(carry, xs):
        x = carry
        lp, st = xs
        h = norm(x, lp, "norm", cfg)
        y, st_new = mlstm_decode_step(lp["mlstm"], h, st, cfg)
        return x + y, st_new

    def group_body(carry, xs):
        x = carry
        glp, slp, gmst, gsst = xs
        x, mst = jax.lax.scan(mlstm_layer, x, (glp, gmst))
        h = norm(x, slp, "norm", cfg)
        y, sst = slstm_decode_step(slp["slstm"], h, gsst, cfg)
        return x + y, (mst, sst)

    x, (mstates, sstates) = jax.lax.scan(
        group_body,
        x,
        (params["mlstm_groups"], params["slstm_blocks"], cache["mlstm"], cache["slstm"]),
    )
    return x, {"mlstm": mstates, "slstm": sstates}


# ---------------------------------------------------------------------------
# public entry points (decoder-only families)
# ---------------------------------------------------------------------------


def decoder_forward(
    params: dict,
    cfg: ModelConfig,
    rt: RuntimeFlags,
    inputs: jax.Array,  # (B,S) tokens or (B,S,d) embeds
    positions: Optional[jax.Array] = None,
    mrope_positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, dict]:
    """Full forward to logits (train / eval). Returns (logits, aux)."""
    B, S = inputs.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = embed_inputs(params, cfg, inputs)
    if cfg.family in ("dense", "vlm", "moe"):
        x, aux, _ = _uniform_stack(
            params, cfg, rt, x, positions, mrope_positions, collect_cache=False
        )
    elif cfg.family == "hybrid":
        x, aux, _ = _hybrid_stack(params, cfg, rt, x, positions, collect_cache=False)
    elif cfg.family == "ssm":
        x, aux, _ = _ssm_stack(params, cfg, rt, x, collect_cache=False)
    else:
        raise ValueError(cfg.family)
    return logits_from_hidden(params, cfg, x), aux


def init_decode_cache(
    cfg: ModelConfig, batch: int, cache_len: int, dtype=None
) -> Tuple[dict, dict]:
    """Zero-initialized decode cache + logical axes tree.

    cache_len: KV capacity (== seq_len, or window size for ring caches).
    """
    dtype = dtype or DTYPES[cfg.dtype]
    K, dh = cfg.n_kv_heads, cfg.head_dim
    ng, gs, rem = _group_shape(cfg)
    cache: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}
    kv_ax = Axes(("layers", "kv_batch", "kv_seq", "kv_heads", None))

    def attn_cache(n_layers):
        cache["k"] = jnp.zeros((n_layers, batch, cache_len, K, dh), dtype)
        cache["v"] = jnp.zeros((n_layers, batch, cache_len, K, dh), dtype)
        cache["pos"] = jnp.full((batch, cache_len), -1, jnp.int32)
        axes["k"] = kv_ax
        axes["v"] = kv_ax
        axes["pos"] = Axes(("kv_batch", "kv_seq"))

    if cfg.family in ("dense", "vlm", "moe"):
        attn_cache(cfg.n_layers)
    elif cfg.family == "hybrid":
        attn_cache(ng)
        st1 = init_mamba_state(cfg, batch, dtype)

        def stack_state(n):
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), st1
            )

        cache["mamba"] = stack_state(ng * gs)
        cache["mamba"] = jax.tree.map(
            lambda x: x.reshape((ng, gs) + x.shape[1:]), cache["mamba"]
        )
        maxes = {
            "h": Axes((None, None, "kv_batch", "inner", None, None)),
            "conv_x": Axes((None, None, "kv_batch", None, "inner")),
            "conv_B": Axes((None, None, "kv_batch", None, None)),
            "conv_C": Axes((None, None, "kv_batch", None, None)),
        }
        axes["mamba"] = maxes
        if rem:
            cache["rest"] = stack_state(rem)
            axes["rest"] = {
                k: Axes(tuple(v)[1:]) for k, v in maxes.items()
            }
    elif cfg.family == "ssm":
        m1 = init_mlstm_state(cfg, batch, dtype)
        s1 = init_slstm_state(cfg, batch, dtype)
        cache["mlstm"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (ng, gs - 1) + x.shape).copy(), m1
        )
        cache["slstm"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (ng,) + x.shape).copy(), s1
        )
        axes["mlstm"] = {
            "C": Axes((None, None, "kv_batch", None, "inner", None)),
            "n": Axes((None, None, "kv_batch", None, "inner")),
            "m": Axes((None, None, "kv_batch", None)),
            "conv": Axes((None, None, "kv_batch", None, "inner")),
        }
        axes["slstm"] = {
            "h": Axes((None, "kv_batch", None)),
            "c": Axes((None, "kv_batch", None)),
            "n": Axes((None, "kv_batch", None)),
            "m": Axes((None, "kv_batch", None)),
        }
    else:
        raise ValueError(cfg.family)
    return cache, axes


def decoder_prefill(
    params: dict,
    cfg: ModelConfig,
    rt: RuntimeFlags,
    inputs: jax.Array,
    positions: Optional[jax.Array] = None,
    mrope_positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, dict]:
    """Process the prompt; returns (last-position logits (B, V), cache)."""
    B, S = inputs.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = embed_inputs(params, cfg, inputs)

    if cfg.family in ("dense", "vlm", "moe"):
        x, aux, kvs = _uniform_stack(
            params, cfg, rt, x, positions, mrope_positions, collect_cache=True
        )
        k, v = kvs  # (L, B, S, K, dh)
        cache = {"k": k, "v": v, "pos": positions}
    elif cfg.family == "hybrid":
        x, aux, (msts, rest, kvs) = _hybrid_stack(
            params, cfg, rt, x, positions, collect_cache=True
        )
        k, v = kvs
        cache = {"k": k, "v": v, "pos": positions, "mamba": msts}
        if rest is not None:
            cache["rest"] = rest
    elif cfg.family == "ssm":
        x, aux, (msts, ssts) = _ssm_stack(params, cfg, rt, x, collect_cache=True)
        cache = {"mlstm": msts, "slstm": ssts}
    else:
        raise ValueError(cfg.family)

    logits = logits_from_hidden(params, cfg, x[:, -1])
    return logits, cache


def decoder_decode(
    params: dict,
    cfg: ModelConfig,
    cache: dict,
    token: jax.Array,  # (B,) int tokens or (B, d) embeds
    pos: jax.Array,  # (B,)
) -> Tuple[jax.Array, dict]:
    """One decode step: returns (logits (B, V), updated cache)."""
    if cfg.embeds_input and token.ndim == 2:
        x = token
    else:
        x = jnp.take(params["embed"], token, axis=0)
    x = constrain(x, ("batch", "embed"))
    if cfg.family in ("dense", "vlm", "moe"):
        x, cache = _uniform_decode(params, cfg, x, pos, cache)
    elif cfg.family == "hybrid":
        x, cache = _hybrid_decode(params, cfg, x, pos, cache)
    elif cfg.family == "ssm":
        x, cache = _ssm_decode(params, cfg, x, cache)
    else:
        raise ValueError(cfg.family)
    return logits_from_hidden(params, cfg, x), cache
