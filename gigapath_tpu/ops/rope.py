"""Rotary positions with YaRN's blended frequencies.

The decoupled rotary part of latent attention (DeepSeek-V2/V3 and the models
built on them): ``dim`` features of a query head and of the one key head all
heads share are rotated in pairs by ``position * f_i``. YaRN ("YaRN: Efficient
Context Window Extension", Peng et al. 2023) keeps the fast pairs' published
frequency, divides the slow pairs' by ``factor``, and blends the pairs in
between by a linear ramp over the pair index::

    f_i     = theta ** (-2 i / dim),                         i = 0 .. dim/2 - 1
    corr(b) = dim ln(original / (2 pi b)) / (2 ln theta)
    low, high = floor(corr(beta_fast)), ceil(corr(beta_slow)),  in [0, dim - 1]
    ramp_i  = clip((i - low) / (high - low), 0, 1)
    f_i    <- f_i / factor * ramp_i + f_i * (1 - ramp_i)

and the softmax scale of the attention that reads them is multiplied by
``yarn_mscale(factor, mscale_all_dim) ** 2``. The pairs are interleaved,
``(x[2i], x[2i+1])``, as the DeepSeek-V3 reference implementation rotates
them, and :func:`apply_rope_interleaved` leaves them where they lie: a pair's
partner is fetched by a product with a signed ``[dim, dim]`` permutation (exact
in any float type: one +-1 a column), not by a stride-2 slice along the lanes,
which costs a TPU a dozen relayout passes over the array.

:func:`apply_rope_halfsplit` is the other pairing, ``(x[i], x[i + dim / 2])``
(the non-interleaved rotation DeepSeek-V3.2's lightning indexer gives its
queries and its key): the two halves are contiguous slices, so plain slicing
does it.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def yarn_correction_range(dim: int, theta: float, original: int,
                          beta_fast: float, beta_slow: float) -> Tuple[int, int]:
    """``(low, high)``: the pair indices between which the ramp rises."""

    def corr(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    return max(math.floor(corr(beta_fast)), 0), min(math.ceil(corr(beta_slow)), dim - 1)


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The ``dim / 2`` blended frequencies, float32 (``factor`` 1: plain RoPE)."""
    freqs = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor != 1:
        low, high = yarn_correction_range(dim, theta, original, beta_fast, beta_slow)
        ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
        freqs = freqs / factor * ramp + freqs * (1.0 - ramp)
    return freqs.astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 mscale ln(factor) + 1`` (1 where nothing is stretched)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_tables(positions: jnp.ndarray, inv_freq) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(cos, sin)`` of ``positions [...] x inv_freq [dim / 2]``, float32."""
    angles = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq, jnp.float32)
    return jnp.cos(angles), jnp.sin(angles)


def _pair_swap(dim: int) -> np.ndarray:
    """``x @ swap`` is ``(-x[1], x[0], -x[3], x[2], ...)``."""
    swap = np.zeros((dim, dim), np.float32)
    even = np.arange(0, dim, 2)
    swap[even + 1, even] = -1.0
    swap[even, even + 1] = 1.0
    return swap


def apply_rope_interleaved(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate the interleaved pairs of ``x [B, L, H, dim]`` by the tables
    ``[L, dim / 2]``: ``out[2i] = x[2i] cos_i - x[2i+1] sin_i``, ``out[2i+1] =
    x[2i] sin_i + x[2i+1] cos_i``. Float32 inside, ``x``'s type out."""
    partner = jnp.einsum("blhd,de->blhe", x, jnp.asarray(_pair_swap(x.shape[-1]), x.dtype),
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
    cos, sin = (jnp.repeat(t, 2, axis=-1)[:, None, :] for t in (cos, sin))
    return (x.astype(jnp.float32) * cos + partner * sin).astype(x.dtype)


def apply_rope_halfsplit(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate the pairs ``(x[i], x[i + dim / 2])`` of ``x [B, L, H, dim]`` by
    the tables ``[L, dim / 2]``: ``out[i] = x[i] cos_i - x[i + dim/2] sin_i``,
    ``out[i + dim/2] = x[i] sin_i + x[i + dim/2] cos_i``. Float32 inside,
    ``x``'s type out."""
    lo, hi = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos], axis=-1).astype(x.dtype)
