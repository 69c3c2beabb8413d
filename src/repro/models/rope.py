"""Rotary position embeddings: standard RoPE (whole or partial head) and
Qwen2-VL M-RoPE.

M-RoPE splits the head_dim/2 frequency bands into sections driven by
(temporal, height, width) position streams; text tokens carry identical
(t, h, w) so M-RoPE degrades to RoPE for pure text. [arXiv:2409.12191]
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["rope_freqs", "apply_rope", "apply_mrope", "text_mrope_positions"]


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """(head_dim/2,) inverse frequencies."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def _rotate(x: jax.Array, angles: jax.Array) -> jax.Array:
    """x: (..., S, H, D); angles: broadcastable to (..., S, 1, D/2)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(dt)


def apply_rope(
    x: jax.Array, positions: jax.Array, head_dim: int, theta: float,
    rope_dim: int = 0,
) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32. Rotates the first
    `rope_dim` dims of each head (0: all of them), rotate-half, with the
    frequencies taken over those dims; the rest pass through (partial
    rotary, as Nemotron's partial_rotary_factor)."""
    r = rope_dim or head_dim
    inv = rope_freqs(r, theta)  # (r/2,)
    angles = positions[..., None, None].astype(jnp.float32) * inv  # (B,S,1,r/2)
    if r == head_dim:
        return _rotate(x, angles)
    return jnp.concatenate([_rotate(x[..., :r], angles), x[..., r:]], -1)


def apply_mrope(
    x: jax.Array,
    positions3: jax.Array,  # (3, B, S): t / h / w position streams
    head_dim: int,
    theta: float,
    sections: Tuple[int, ...],
) -> jax.Array:
    """Qwen2-VL multimodal RoPE; sections sum to head_dim//2."""
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    inv = rope_freqs(head_dim, theta)  # (D/2,)
    # Pick, per frequency band, which positional stream drives it.
    sec_id = jnp.repeat(
        jnp.arange(len(sections)), jnp.array(sections), total_repeat_length=half
    )  # (D/2,) static
    # Gather the driving stream per band via one-hot (n_sections is tiny).
    onehot = jax.nn.one_hot(sec_id, len(sections), dtype=jnp.float32)  # (D/2, 3)
    pos = jnp.einsum("kbs,dk->bsd", positions3.astype(jnp.float32), onehot)  # (B,S,D/2)
    angles = pos[..., None, :] * inv  # (B, S, 1, D/2)
    return _rotate(x, angles)


def text_mrope_positions(positions: jax.Array) -> jax.Array:
    """(B, S) -> (3, B, S): text tokens share t=h=w=pos."""
    return jnp.broadcast_to(positions[None], (3,) + positions.shape)
