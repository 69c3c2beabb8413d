"""Shared building blocks for the model zoo.

Parameters are plain nested dicts of jnp arrays. Every parameter is created
through `Param.make` inside an `init_ctx()` so the *logical sharding axes*
of each array are recorded in a parallel tree (same structure, `Axes`
leaves) — single source of truth for `in_shardings` at lower time.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..sharding import Axes, constrain

__all__ = [
    "DTYPES",
    "Initializer",
    "init_ctx",
    "make_param",
    "axes_of",
    "rms_norm",
    "layer_norm",
    "init_norm",
    "norm",
    "dense",
    "activation_fn",
    "RuntimeFlags",
]

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}


@dataclasses.dataclass(frozen=True)
class RuntimeFlags:
    """How the model runs apart from what it computes: whether each layer
    is wrapped in `jax.checkpoint` (activation checkpointing, for training)."""

    remat: bool = True


# --------------------------------------------------------------------------
# Param creation with logical-axis recording
# --------------------------------------------------------------------------

_AXES_STACK: list = []


@contextlib.contextmanager
def init_ctx():
    """Collect logical axes for params created within. Yields a dict that is
    filled with an axes-tree mirroring the params returned by the block."""
    col: Dict[str, Any] = {}
    _AXES_STACK.append(col)
    try:
        yield col
    finally:
        _AXES_STACK.pop()


def _record(path: Tuple[str, ...], axes: Axes) -> None:
    if not _AXES_STACK:
        return
    node = _AXES_STACK[-1]
    for k in path[:-1]:
        node = node.setdefault(k, {})
    node[path[-1]] = axes


class Initializer:
    """Splittable PRNG + path tracking for nested param dicts."""

    def __init__(self, key: jax.Array, dtype, path: Tuple[str, ...] = ()):
        self.key = key
        self.dtype = dtype
        self.path = path

    def child(self, name: str) -> "Initializer":
        self.key, sub = jax.random.split(self.key)
        return Initializer(sub, self.dtype, self.path + (name,))

    def param(
        self,
        name: str,
        shape: Sequence[int],
        axes: Sequence[Optional[str]],
        scale: Optional[float] = None,
        zeros: bool = False,
        ones: bool = False,
    ) -> jax.Array:
        assert len(shape) == len(axes), (name, shape, axes)
        _record(self.path + (name,), Axes(axes))
        if ones:
            return jnp.ones(shape, self.dtype)
        if zeros:
            return jnp.zeros(shape, self.dtype)
        self.key, sub = jax.random.split(self.key)
        if scale is None:
            scale = 1.0 / math.sqrt(shape[0])  # fan-in on leading dim
        return (jax.random.normal(sub, shape, jnp.float32) * scale).astype(self.dtype)


def make_param(init: Initializer, *a, **k) -> jax.Array:
    return init.param(*a, **k)


def axes_of(col: Dict[str, Any]):
    return col


# --------------------------------------------------------------------------
# Elementary ops
# --------------------------------------------------------------------------


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dt) * gamma


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float) -> jax.Array:
    """LayerNorm in float32, gain and bias included; back in x's dtype."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(dt)


def init_norm(init: Initializer, name: str, cfg) -> Dict[str, jax.Array]:
    """The leaves of one norm site as `cfg.norm` lays them out: a gain
    `name` (ones for RMSNorm; zeros for LayerNorm1p, whose gain is
    1 + gamma) and, for LayerNorm1p, a bias `name + "_bias"` beside it."""
    d = (cfg.d_model,)
    if cfg.norm == "layernorm1p":
        return {name: init.param(name, d, ("p_embed",), zeros=True),
                name + "_bias": init.param(name + "_bias", d, ("p_embed",),
                                           zeros=True)}
    if cfg.norm != "rms":
        raise ValueError(f"unknown norm {cfg.norm!r}")
    return {name: init.param(name, d, ("p_embed",), ones=True)}


def norm(x: jax.Array, p: dict, name: str, cfg) -> jax.Array:
    """The configured norm of x with the leaves `init_norm` made in p."""
    if cfg.norm == "layernorm1p":
        gain = 1.0 + p[name].astype(jnp.float32)
        return layer_norm(x, gain, p[name + "_bias"], cfg.norm_eps)
    return rms_norm(x, p[name], cfg.norm_eps)


def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    y = jnp.einsum("...d,df->...f", x, w)
    if b is not None:
        y = y + b
    return y


def activation_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name!r}")
