"""Rotary position embeddings (RoPE).

Not in the reference — its attention has no position signal at all and the
composed transformer added learned absolute embeddings. RoPE is the modern
alternative a complete framework needs: positions enter as a rotation of each
(q, k) head-dim pair, so relative offsets are encoded multiplicatively and
generation can run past the training length without a learned table.

Two variants a config may ask for: a PARTIAL rotation (``rotary_dim``: the
first values of a head rotate, the rest pass) and YaRN's scaled frequencies
(:class:`Yarn`), as the ``laguna`` family's global layers publish them.

TPU notes: the rotation is a pure elementwise map (VPU work) that XLA fuses
into the surrounding projection matmuls; angles are computed in fp32 and the
rotated values cast back to the input dtype (bf16-safe, same upcast reasoning
as the reference's softmax, `/root/reference/case6_attention.py:121-122`).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN's scaled rotary frequencies (arXiv 2309.00071; the
    ``rope_type: yarn`` keys of a ``config.json``): frequencies that turn
    fewer than ``beta_slow`` times over ``original_max_positions`` are
    divided by ``factor``, those that turn more than ``beta_fast`` times are
    kept, a linear ramp blends the ones between, and the rotation's cos and
    sin are multiplied by ``attention_factor``."""

    factor: float
    original_max_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float | None = None   # None: 0.1 ln(factor) + 1

    @property
    def scale(self) -> float:
        if self.attention_factor is not None:
            return self.attention_factor
        return 0.1 * math.log(self.factor) + 1.0 if self.factor > 1 else 1.0


def rope_frequencies(dim: int, theta: float, yarn: Yarn | None = None) -> np.ndarray:
    """The ``dim / 2`` inverse frequencies of a rotation over ``dim`` values
    (float32, host-side: they depend on the configuration alone). With
    ``yarn`` the correction range is truncated to whole dimensions, as the
    published YaRN code does."""
    if dim % 2:
        raise ValueError(f"RoPE needs an even number of rotated dims, got {dim}")
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = 1.0 / pos
    if yarn is not None:
        def corr(turns):
            return dim * math.log(
                yarn.original_max_positions / (turns * 2 * math.pi)
            ) / (2 * math.log(theta))

        low = max(math.floor(corr(yarn.beta_fast)), 0)
        high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
        ramp = np.clip(
            (np.arange(dim // 2) - low) / ((high - low) or 0.001), 0.0, 1.0
        )
        # 0: kept (extrapolated), 1: divided by factor (interpolated).
        freqs = freqs / yarn.factor * ramp + freqs * (1.0 - ramp)
    return freqs.astype(np.float32)


def rope_angles(
    positions: jax.Array, head_dim: int, theta: float = 10_000.0,
    yarn: Yarn | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-position rotation ``(cos, sin)`` of shape ``positions.shape + (head_dim/2,)``.

    Args:
        positions: integer absolute positions, any shape (typically ``(S,)``).
        head_dim: the rotated width; must be even (pairs are rotated).
        theta: base wavelength (10k, the standard choice).
        yarn: scaled frequencies and a factor on cos and sin (:class:`Yarn`).
    """
    if yarn is None:
        if head_dim % 2:
            raise ValueError(f"RoPE needs an even head_dim, got {head_dim}")
        freqs = theta ** (
            -jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
        )  # (head_dim/2,)
        angles = positions.astype(jnp.float32)[..., None] * freqs
        return jnp.cos(angles), jnp.sin(angles)
    freqs = jnp.asarray(rope_frequencies(head_dim, theta, yarn))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles) * yarn.scale, jnp.sin(angles) * yarn.scale


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float = 10_000.0,
    *, interleave: bool = False, rotary_dim: int | None = None,
    yarn: Yarn | None = None,
) -> jax.Array:
    """Rotate ``x`` of shape ``(B, S, N, H)`` by its absolute positions.

    ``positions`` is ``(S,)`` or ``(B, S)``. Pairing follows the split-half
    convention (x[..., :H/2] with x[..., H/2:]), matching the common
    NeoX/LLaMA layout; ``interleave`` rotates the neighbours
    ``(x[..., 2i], x[..., 2i+1])`` in place instead (the GPT-J / DeepSeek
    layout the latent-attention models publish). ``rotary_dim``: PARTIAL
    rotation, the first ``rotary_dim`` values of a head are rotated (split
    half within them, frequencies over that width) and the rest pass
    through unchanged; ``yarn``: :class:`Yarn` frequencies and scale.
    """
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        rot = apply_rope(
            x[..., :rotary_dim], positions, theta, interleave=interleave,
            yarn=yarn,
        )
        return jnp.concatenate([rot, x[..., rotary_dim:]], axis=-1)
    h = x.shape[-1]
    cos, sin = rope_angles(positions, h, theta, yarn)  # (..., S, H/2)
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
