"""Rule-based logical-axis sharding.

Model code never names mesh axes. Arrays (params and activations) carry
*logical* axis names ("batch", "ffn", "kv_seq", ...); a rule table maps each
logical name to an ordered tuple of mesh axes. `spec_for()` resolves a
concrete shape to a `PartitionSpec`, enforcing

  * divisibility — a dim is only sharded by a (prefix of the) mesh-axis
    tuple whose total size divides it, else it falls back to replication,
  * uniqueness — a mesh axis is consumed at most once per spec,

so every (arch x shape x mesh) combination lowers: the worst case is
replication, never a crash.

Use:

    with use_mesh(mesh, TRAIN_RULES):
        spec = spec_for((256, 4096, 8192), ("batch", "seq", "embed"))
        x = constrain(x, ("batch", "seq", "embed"))   # no-op outside ctx
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "AxisRules",
    "TRAIN_RULES",
    "PREFILL_RULES",
    "DECODE_RULES",
    "use_mesh",
    "current_mesh",
    "spec_for",
    "sharding_for",
    "shard_count",
    "constrain",
    "tree_specs",
    "Axes",
]

# logical name -> ordered mesh-axis candidates (joined, in order, while they
# divide the dim). Missing name == replicated.
AxisRules = Dict[str, Tuple[str, ...]]

# ---------------------------------------------------------------------------
# Rule presets.
#
# Activation axes: batch, seq, embed, heads, kv_heads, head_dim, ffn, vocab,
#                  experts, capacity, kv_seq, inner, state
# Param axes are prefixed p_ where their placement differs from the
# activation of the same name (FSDP: shard params' embed dim over the data
# axis; they are all-gathered on use).
# ---------------------------------------------------------------------------

TRAIN_RULES: AxisRules = {
    # activations ("seq_res" = the residual stream between blocks; mapping
    # it to ("model",) turns on Megatron-style sequence parallelism:
    # norms/elementwise run seq-sharded, GSPMD inserts all-gather before
    # attention/MLP matmuls and reduce-scatter after — and, crucially, the
    # remat-saved layer boundaries shrink by the model-axis size)
    "batch": ("pod", "data"),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "inner": ("model",),
    "vocab": ("model",),
    "experts": (),
    # params (TP on model axis + FSDP on data axis along p_embed)
    "p_embed": ("data",),
    "p_vocab": ("model",),
    "p_heads": ("model",),
    "p_kv_heads": ("model",),
    "p_ffn": ("model",),
    "p_inner": ("model",),
    "p_experts": (),
}

# Hillclimbed train rules: + sequence-parallel residual stream.
TRAIN_RULES_SP: AxisRules = dict(TRAIN_RULES, seq_res=("model",))

# Pure-FSDP training (ZeRO-3 style): batch shards over the WHOLE mesh, no
# tensor parallelism; every parameter shards 256-way along its embed dim and
# is all-gathered per layer. For models whose per-layer weights are smaller
# than the per-device activation slab of TP (e.g. mistral-large at global
# batch == chip count) this removes the dominant activation all-reduces.
TRAIN_RULES_FSDP: AxisRules = {
    "batch": ("pod", "data", "model"),
    "heads": (), "kv_heads": (), "ffn": (), "inner": (), "vocab": (),
    "experts": (),
    "p_embed": ("data", "model"),
    "p_vocab": (), "p_heads": (), "p_kv_heads": (), "p_ffn": (),
    "p_inner": (), "p_experts": (),
}

# Expert-parallel MoE: experts live one-per-model-rank (all-to-all
# dispatch), heads and ffn are not tensor-parallel, batch is data-parallel
# only. The canonical MoE sharding for archs whose expert count matches
# the model axis (llama4-scout: 16 experts).
TRAIN_RULES_EP_CP: AxisRules = {
    **TRAIN_RULES,
    "experts": ("model",),
    "p_experts": ("model",),
    "heads": (), "kv_heads": (), "ffn": (),
    "p_heads": (), "p_kv_heads": (), "p_ffn": (),
}

# ... + sequence-parallel residual (activation-memory variant).
TRAIN_RULES_EP_CP_SP: AxisRules = dict(TRAIN_RULES_EP_CP, seq_res=("model",))

# Serving-prefill: identical placement (weights stationary, batch DP).
PREFILL_RULES: AxisRules = dict(TRAIN_RULES)

# Serving-decode: KV cache dominates; shard cache sequence over the model
# axis (flash-decoding style context parallelism) and batch over data.
DECODE_RULES: AxisRules = dict(
    TRAIN_RULES,
    kv_seq=("model",),
    kv_batch=("pod", "data"),
)

# Hillclimbed decode rules: per-token activations REPLICATED over the data
# axis (they are tiny), so GSPMD reshards activations through the 2D-sharded
# weights instead of all-gathering ~13 GB of FSDP weights per decoded token.
DECODE_RULES_V2: AxisRules = {
    **DECODE_RULES,
    "batch": (),
    "heads": ("model",),
}

# V3: additionally shard the per-token activations' EMBED dim over the data
# axis (matching the FSDP weight layout), so every matmul contracts locally
# and only (B, d)-sized partial sums cross the links — no weight gathers.
DECODE_RULES_V3: AxisRules = {
    **DECODE_RULES_V2,
    "embed": ("data",),
}

# V3 + expert-parallel decode: expert weights resident one-per-model-rank
# (no FSDP gathers of expert tensors), token movement via all-to-all.
DECODE_RULES_V3_EP: AxisRules = {
    **DECODE_RULES_V3,
    "experts": ("model",),
    "p_experts": ("model",),
}


@dataclasses.dataclass(frozen=True)
class _Ctx:
    mesh: Mesh
    rules: AxisRules


_ctx: contextvars.ContextVar[Optional[_Ctx]] = contextvars.ContextVar(
    "sharding_ctx", default=None
)


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: AxisRules):
    """Activate (mesh, rules) for spec resolution and constraints."""
    token = _ctx.set(_Ctx(mesh, rules))
    try:
        with mesh:
            yield
    finally:
        _ctx.reset(token)


def current_mesh() -> Optional[Mesh]:
    c = _ctx.get()
    return c.mesh if c is not None else None


def _resolve_dim(dim: int, name: Optional[str], ctx: _Ctx, used: set):
    """Longest prefix of the rule tuple that exists in the mesh, divides
    `dim`, and does not reuse a mesh axis."""
    if name is None:
        return None
    cand = ctx.rules.get(name, ())
    chosen = []
    size = 1
    for ax in cand:
        if ax not in ctx.mesh.shape or ax in used:
            continue
        nxt = size * ctx.mesh.shape[ax]
        if dim % nxt != 0:
            break
        chosen.append(ax)
        size = nxt
    if not chosen:
        return None
    used.update(chosen)
    return tuple(chosen) if len(chosen) > 1 else chosen[0]


def spec_for(shape: Sequence[int], axes: Sequence[Optional[str]]) -> P:
    """Resolve logical axes for a concrete shape to a PartitionSpec."""
    ctx = _ctx.get()
    if ctx is None:
        return P()
    assert len(shape) == len(axes), (shape, axes)
    used: set = set()
    return P(*[_resolve_dim(d, a, ctx, used) for d, a in zip(shape, axes)])


def sharding_for(shape: Sequence[int], axes: Sequence[Optional[str]]) -> Optional[NamedSharding]:
    ctx = _ctx.get()
    if ctx is None:
        return None
    return NamedSharding(ctx.mesh, spec_for(shape, axes))


def shard_count(dim: int, name: str) -> int:
    """How many shards the active rules split a dim of size `dim` with
    logical name `name` into: 1 outside a mesh or where it is replicated."""
    ctx = _ctx.get()
    if ctx is None:
        return 1
    ax = _resolve_dim(dim, name, ctx, set())
    if ax is None:
        return 1
    return math.prod(ctx.mesh.shape[a] for a in (ax if isinstance(ax, tuple) else (ax,)))


def constrain(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint under an active mesh; identity otherwise."""
    ctx = _ctx.get()
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec_for(x.shape, axes))
    )


class Axes(tuple):
    """Logical-axis annotation for one array. Deliberately NOT a registered
    pytree node, so an axes tree (same structure as a param tree, `Axes`
    leaves) maps 1:1 onto array leaves under jax.tree.map."""

    def __repr__(self) -> str:  # pragma: no cover
        return f"Axes{tuple.__repr__(self)}"


def tree_specs(arrays_tree, axes_tree):
    """Map (arrays, logical-axes) trees -> PartitionSpec tree.

    `arrays_tree` leaves need `.shape` (jax.Array or ShapeDtypeStruct);
    `axes_tree` has matching structure with `Axes` leaves.
    """
    return jax.tree.map(
        lambda arr, ax: spec_for(arr.shape, ax), arrays_tree, axes_tree
    )
