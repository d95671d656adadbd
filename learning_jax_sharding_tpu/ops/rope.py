"""Rotary position embeddings (RoPE).

Not in the reference — its attention has no position signal at all and the
composed transformer added learned absolute embeddings. RoPE is the modern
alternative a complete framework needs: positions enter as a rotation of each
(q, k) head-dim pair, so relative offsets are encoded multiplicatively and
generation can run past the training length without a learned table.

TPU notes: the rotation is a pure elementwise map (VPU work) that XLA fuses
into the surrounding projection matmuls; angles are computed in fp32 and the
rotated values cast back to the input dtype (bf16-safe, same upcast reasoning
as the reference's softmax, `/root/reference/case6_attention.py:121-122`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rope_angles(
    positions: jax.Array, head_dim: int, theta: float = 10_000.0
) -> tuple[jax.Array, jax.Array]:
    """Per-position rotation ``(cos, sin)`` of shape ``positions.shape + (head_dim/2,)``.

    Args:
        positions: integer absolute positions, any shape (typically ``(S,)``).
        head_dim: per-head width; must be even (pairs are rotated).
        theta: base wavelength (10k, the standard choice).
    """
    if head_dim % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {head_dim}")
    freqs = theta ** (
        -jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    )  # (head_dim/2,)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float = 10_000.0,
    *, interleave: bool = False,
) -> jax.Array:
    """Rotate ``x`` of shape ``(B, S, N, H)`` by its absolute positions.

    ``positions`` is ``(S,)`` or ``(B, S)``. Pairing follows the split-half
    convention (x[..., :H/2] with x[..., H/2:]), matching the common
    NeoX/LLaMA layout; ``interleave`` rotates the neighbours
    ``(x[..., 2i], x[..., 2i+1])`` in place instead (the GPT-J / DeepSeek
    layout the latent-attention models publish).
    """
    h = x.shape[-1]
    cos, sin = rope_angles(positions, h, theta)  # (..., S, H/2)
    # Broadcast over batch (if positions were (S,)) and heads.
    if cos.ndim == 2:  # (S, H/2) → (1, S, 1, H/2)
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:  # (B, S, H/2) → (B, S, 1, H/2)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    if interleave:
        x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
        rotated = jnp.stack(
            [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
        ).reshape(x.shape)
        return rotated.astype(x.dtype)
    x1, x2 = x[..., : h // 2].astype(jnp.float32), x[..., h // 2 :].astype(jnp.float32)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.astype(x.dtype)
