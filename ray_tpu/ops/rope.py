"""Rotary position embeddings (RoPE), Llama convention.

Frequencies are computed once per step in fp32 and applied to q/k. The
half-split rotation (rotate_half) is used rather than interleaved pairs —
it lowers to two slices + concat which XLA vectorizes cleanly on the VPU.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp


def rope_frequencies(
    head_dim: int,
    max_len: int,
    theta: float = 10000.0,
    *,
    positions: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Return (cos, sin) of shape [max_len, head_dim//2] (fp32), or of
    ``positions``' shape + [head_dim//2] when positions are given."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if positions is None:
        positions = jnp.arange(max_len, dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """Apply RoPE to ``x`` of shape [..., seq, heads, head_dim].

    ``cos``/``sin`` have shape [seq, head_dim//2] (broadcast over batch and
    heads) or [..., seq, head_dim//2] with angles of their own for each
    leading index (broadcast over heads).
    """
    dtype = x.dtype
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(dtype)
