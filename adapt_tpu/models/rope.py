"""Rotary position embedding: the rotation every attention kind
shares, and the frequencies a model's configuration gives it.

``apply_rope`` rotates by ``base ** (-2i / d)`` unless the caller
hands it the frequencies; :func:`yarn_frequencies` are DeepSeek-V3's
(YaRN, arXiv:2309.00071: the slow dimensions interpolated by
``factor``, the fast ones left alone, a linear ramp between), which a
latent-attention block's spec names (``models/mla.LatentSpec.yarn``).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class YarnSpec:
    """``rope_scaling`` of type ``yarn`` as a configuration states it."""

    factor: float
    original_max: int  # original_max_position_embeddings
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 * mscale * ln(factor) + 1`` (1 where nothing is
    stretched)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, base: float, yarn: YarnSpec) -> jax.Array:
    """The ``dim // 2`` rotation frequencies under YaRN. A dimension
    that turns more than ``beta_fast`` times over the original context
    keeps ``base ** (-2i / dim)``; one that turns fewer than
    ``beta_slow`` times is divided by ``factor``; the ramp between is
    linear in the dimension's index."""

    def correction_dim(rotations: float) -> float:
        return dim * math.log(
            yarn.original_max / (rotations * 2 * math.pi)
        ) / (2 * math.log(base))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001  # the reference implementation's guard
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = base ** (-2.0 * i / dim)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    # ramp 0: a fast dimension, kept; ramp 1: a slow one, interpolated.
    return plain * (1.0 - ramp) + plain / yarn.factor * ramp


def apply_rope(x: jax.Array, positions: jax.Array,
               base: float = 10000.0, freqs: jax.Array | None = None,
               mscale: float = 1.0) -> jax.Array:
    """Rotary position embedding over (b, heads, s, head_dim) with
    explicit ``positions`` ((s,) shared or (b, s) per row — per-row
    LOGICAL positions keep ragged rows bitwise-equal to their solo
    runs). Rotate-half convention; head_dim must be even. Computed in
    f32 and cast back (rotation is a unitary mix — doing it in bf16
    would cost precision every cached step). ``freqs`` (head_dim // 2,)
    replaces ``base``'s geometric ladder (:func:`yarn_frequencies`);
    ``mscale`` multiplies cos and sin (YaRN's ``mscale /
    mscale_all_dim``)."""
    hd = x.shape[-1]
    half = hd // 2
    if freqs is None:
        freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = jnp.asarray(positions, jnp.float32)
    if pos.ndim == 1:
        angles = pos[None, :, None] * freqs  # (1, s, half)
    else:
        angles = pos[:, :, None] * freqs  # (b, s, half)
    cos = jnp.cos(angles)[:, None, :, :]  # (b|1, 1, s, half)
    sin = jnp.sin(angles)[:, None, :, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)
