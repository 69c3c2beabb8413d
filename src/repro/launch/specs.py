"""Assigned input shapes -> abstract step arguments (ShapeDtypeStruct).

The four assigned shapes:

    train_4k     seq   4,096  global_batch 256  (training)
    prefill_32k  seq  32,768  global_batch  32  (inference prefill)
    decode_32k   seq  32,768  global_batch 128  (decode, KV cache = seq)
    long_500k    seq 524,288  global_batch   1  (long-context decode)

`build_case(arch, shape)` resolves applicability (`skip_reason`), the
sliding-window serving variant of full-attention archs at 500k, and
activation checkpointing for training, and returns everything the dry-run
and the launch scripts need: the step callable, abstract args, and
logical-axes trees for sharding. Nothing here allocates device memory.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs import ModelConfig, get_config
from ..models import Model, RuntimeFlags, build_model
from ..models.common import DTYPES
from ..sharding import (
    Axes,
    DECODE_RULES,
    PREFILL_RULES,
    TRAIN_RULES,
    AxisRules,
)
from ..training import AdamWConfig, adamw_init
from ..training.loop import make_train_step

__all__ = [
    "SHAPES",
    "ShapeSpec",
    "Case",
    "build_case",
    "applicable",
    "skip_reason",
    "input_specs",
]

# sliding window used by the long_500k serving variant of full-attention archs
LONG_WINDOW = 8192


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str  # train | prefill | decode
    seq: int
    batch: int


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}


def skip_reason(cfg: ModelConfig, shape: ShapeSpec) -> Optional[str]:
    """The one (arch x shape) case that is not built, and why."""
    if shape.name == "long_500k" and cfg.n_encoder_layers:
        return (
            "long_500k x enc-dec (seamless): 500k source frames through a "
            "full-attention encoder has no sub-quadratic variant in this "
            "family, and a sliding window would change what the encoder "
            "computes."
        )
    return None


def applicable(cfg: ModelConfig, shape: ShapeSpec) -> bool:
    return skip_reason(cfg, shape) is None


def _config_for(cfg: ModelConfig, shape: ShapeSpec) -> ModelConfig:
    if (shape.name == "long_500k" and cfg.family in ("dense", "vlm", "moe")
            and not cfg.window):
        # full-attention families run the sliding-window serving variant;
        # mixtral's native SWA (4096) already bounds the cache.
        return dataclasses.replace(cfg, window=LONG_WINDOW)
    return cfg


def _cache_len(cfg: ModelConfig, shape: ShapeSpec) -> int:
    if cfg.window:
        return min(shape.seq, cfg.window)
    return shape.seq


@dataclasses.dataclass
class Case:
    """One (arch x shape) dry-run/driver case (abstract, zero allocation)."""

    arch: str
    cfg: ModelConfig
    shape: ShapeSpec
    model: Model
    rules: AxisRules
    step: Callable  # the function to jit
    args: tuple  # abstract ShapeDtypeStruct args
    arg_axes: tuple  # logical-axes trees matching args
    donate: Tuple[int, ...] = ()


def _abstract_init(model: Model) -> Tuple[Any, Any]:
    box = {}

    def f(k):
        p, a = model.init(k)
        box["axes"] = a
        return p

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, box["axes"]


def _abstract_cache(model: Model, batch: int, cache_len: int, enc_len: int = 0):
    box = {}

    def f():
        c, a = model.init_cache(batch, cache_len, enc_len=enc_len)
        box["axes"] = a
        return c

    shapes = jax.eval_shape(f)
    return shapes, box["axes"]


def _batch_inputs(cfg: ModelConfig, shape: ShapeSpec, with_labels: bool):
    """Abstract train/prefill inputs + axes for one architecture."""
    B, S = shape.batch, shape.seq
    dt = DTYPES[cfg.dtype]
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    tok_ax = Axes(("batch", "seq"))
    emb = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
    emb_ax = Axes(("batch", "seq", "embed"))
    if cfg.n_encoder_layers:
        batch = {"enc_embeds": emb, "dec_tokens": tok}
        axes = {"enc_embeds": emb_ax, "dec_tokens": tok_ax}
    elif cfg.embeds_input:
        batch, axes = {"embeds": emb}, {"embeds": emb_ax}
    else:
        batch, axes = {"tokens": tok}, {"tokens": tok_ax}
    if with_labels:
        batch["labels"] = tok
        axes["labels"] = tok_ax
    return batch, axes


def input_specs(arch: str, shape_name: str):
    """ShapeDtypeStruct stand-ins for every input of the (arch x shape)
    step function — weak-type-correct, shardable, zero allocation. For a
    training step that is (params, opt_state, {tokens, labels}); for
    decode it is (params, cache, token, pos)."""
    return build_case(arch, shape_name).args


def build_case(
    arch: str,
    shape_name: str,
    opt_cfg: Optional[AdamWConfig] = None,
    rules_override: Optional[AxisRules] = None,
    microbatches: int = 1,
) -> Case:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    if reason:
        raise ValueError(f"skipped: {reason}")
    cfg = _config_for(cfg, shape)
    model = build_model(cfg, RuntimeFlags(remat=shape.kind == "train"))
    pshapes, paxes = _abstract_init(model)

    if shape.kind == "train":
        opt_cfg = opt_cfg or AdamWConfig()
        opt_shapes = jax.eval_shape(adamw_init, pshapes)
        opt_axes = {"mu": paxes, "nu": paxes, "step": Axes(())}
        batch, batch_axes = _batch_inputs(cfg, shape, with_labels=True)
        step = make_train_step(model, opt_cfg, microbatches=microbatches)
        return Case(
            arch, cfg, shape, model, rules_override or TRAIN_RULES, step,
            (pshapes, opt_shapes, batch), (paxes, opt_axes, batch_axes),
            donate=(0, 1),
        )

    if shape.kind == "prefill":
        batch, batch_axes = _batch_inputs(cfg, shape, with_labels=False)
        prompt = batch if cfg.n_encoder_layers else next(iter(batch.values()))
        prompt_axes = batch_axes if cfg.n_encoder_layers else next(iter(batch_axes.values()))

        def prefill_step(params, p):
            return model.prefill(params, p)

        return Case(
            arch, cfg, shape, model, rules_override or PREFILL_RULES,
            prefill_step, (pshapes, prompt), (paxes, prompt_axes),
        )

    # decode
    B = shape.batch
    clen = _cache_len(cfg, shape)
    enc_len = shape.seq if cfg.n_encoder_layers else 0
    cshapes, caxes = _abstract_cache(model, B, clen, enc_len)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)

    def decode_step(params, cache, token, p):
        return model.decode(params, cache, token, p)

    return Case(
        arch, cfg, shape, model, rules_override or DECODE_RULES, decode_step,
        (pshapes, cshapes, tok, pos),
        (paxes, caxes, Axes(("batch",)), Axes(("batch",))),
        donate=(1,),
    )
