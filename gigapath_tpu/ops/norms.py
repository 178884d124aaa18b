"""Normalization layers.

LayerNorm is plain ``flax.linen.LayerNorm`` — XLA fuses it for free, which
replaces the reference's optional ``apex.normalization.FusedLayerNorm``
(``multihead_attention.py:10-13`` et al.). RMSNorm has parity with reference
``torchscale/component/rms_norm.py`` (fp32 accumulation, optional affine).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn


class RMSNorm(nn.Module):
    dim: int
    eps: float = 1e-6
    elementwise_affine: bool = True
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        normed = normed.astype(x.dtype)
        if self.elementwise_affine:
            weight = self.param("weight", nn.initializers.ones, (self.dim,), self.param_dtype)
            normed = normed * weight.astype(normed.dtype)
        return normed
