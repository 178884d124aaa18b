"""Quantized matmul: jnp reference tier + a Pallas TPU tier, and the
``QuantDense`` flax twin of ``nn.Dense`` that routes through them.

Numerics contract (both tiers, identical by construction): the int8/fp8
weight tile is cast to bf16 **inside** the kernel (int8 magnitudes
<= 127 and e4m3 values are exact in bf16), the activation rides bf16,
and the MXU accumulates in f32 (``preferred_element_type``) — bf16
operand tiles, f32 accumulation, so the quantized grid arithmetic is
EXACT and the only approximation anywhere is the weight quantization
itself (qtensor.py). The per-output-channel scale folds into the
epilogue as one row-broadcast multiply. The f32 path (``nn.Dense``)
stays the fallback and parity oracle, selected by leaving the quant
mode empty.

Tier dispatch follows the repo's kernel-flag discipline: the default
tier is the jnp reference formulation (XLA fuses it well and it runs
everywhere); the Pallas tier engages only when the caller passes
``use_pallas`` (``GIGAPATH_QUANT_PALLAS``, read ONCE host-side by the
tile-encoder factory — never here) and the geometry is MXU-tileable
(K and N multiples of 128).
Untileable geometries silently use the reference tier — same fallback
shape as ``flash_attention``'s ``PALLAS_MIN_SEQ`` routing.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gigapath_tpu.ops import flash_attention
from gigapath_tpu.quant.qtensor import (
    QTensor,
    base_mode,
    normalize_mode,
    quantize_per_channel,
)

_LANE = 128


# ---------------------------------------------------------------------------
# jnp reference tier
# ---------------------------------------------------------------------------

def q_matmul_reference(x: jnp.ndarray, qt: QTensor) -> jnp.ndarray:
    """``[..., K] x QTensor([K, N])`` -> f32 ``[..., N]`` — the default
    tier and the numerics spec the Pallas tier must reproduce."""
    y = jax.lax.dot_general(
        x.astype(jnp.bfloat16),
        qt.data.astype(jnp.bfloat16),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return y * qt.scale  # [1, N] row broadcast (per-output-channel)


# ---------------------------------------------------------------------------
# Pallas tier
# ---------------------------------------------------------------------------

def _q_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk):
    """Blocked matmul cell: grid (nm, nn, nk); x [bm, bk] bf16,
    w [bk, bn] int8/fp8 (cast to bf16 in-cell — exact), f32 scratch
    accumulator, per-channel scale applied once at the last k step."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        x_ref[:], w_ref[:].astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _finalize():
        o_ref[:] = acc_ref[:] * s_ref[:]


def q_matmul_pallas(x: jnp.ndarray, qt: QTensor, *, block_m: int = 256,
                    block_n: int = 256, block_k: int = 256,
                    interpret: bool = False) -> jnp.ndarray:
    """Pallas tier: requires ``K % 128 == 0 and N % 128 == 0`` (the MXU
    lane quantum); the row axis pads to ``block_m`` and slices back."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = qt.data.shape[-1]
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, K).astype(jnp.bfloat16)
    bm = min(block_m, max(_round_up(m, 8), 8))
    bk = min(block_k, K)
    bn = min(block_n, N)
    while K % bk:
        bk //= 2
    while N % bn:
        bn //= 2
    mp = _round_up(m, bm)
    if mp != m:
        x2 = jnp.pad(x2, ((0, mp - m), (0, 0)))
    nm, nn, nk = mp // bm, N // bn, K // bk
    scale = jnp.broadcast_to(qt.scale.astype(jnp.float32), (1, N))
    out = pl.pallas_call(
        functools.partial(_q_matmul_kernel, nk=nk),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((mp, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="q_matmul",
    )(x2, qt.data, scale)
    return out[:m].reshape(*lead, N)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pallas_eligible(x: jnp.ndarray, qt: QTensor) -> bool:
    return x.shape[-1] % _LANE == 0 and qt.data.shape[-1] % _LANE == 0


def q_matmul(x: jnp.ndarray, qt: QTensor, *,
             use_pallas: Optional[bool] = None,
             interpret: bool = False) -> jnp.ndarray:
    """The quantized matmul entry: f32 out, tier per the module doc.

    ``use_pallas`` is the caller's already-read flag value
    (``GIGAPATH_QUANT_PALLAS``) — this function NEVER reads the
    environment (gigalint GL001)."""
    if use_pallas is None:
        use_pallas = False
    if (use_pallas and (flash_attention._on_tpu() or interpret)
            and _pallas_eligible(x, qt)):
        return q_matmul_pallas(x, qt, interpret=interpret)
    return q_matmul_reference(x, qt)


# ---------------------------------------------------------------------------
# the flax Dense twin
# ---------------------------------------------------------------------------

class QuantDense(nn.Module):
    """``nn.Dense`` with a quantized-weight forward.

    Param names and shapes are IDENTICAL to ``nn.Dense`` ("kernel"
    ``[in, features]``, "bias" ``[features]``), so every existing
    checkpoint path — timm conversion, orbax restore, the sharding-rule
    registry's name lists — works unchanged; only the forward differs:
    the kernel is quantized in-graph through the ONE sanctioned helper
    (per-channel absmax, qtensor.py) and consumed by :func:`q_matmul`.
    The quantize lives inside the traced program on purpose — it is
    what makes the flag-on/flag-off programs distinct jit entries
    (pinned by tests/test_quant.py), and XLA constant-folds it when the
    params are donated/baked. ``mode`` empty is refused: the f32 path
    is ``nn.Dense`` itself (the caller's branch), never a silent
    QuantDense pass-through.
    """

    features: int
    mode: str
    use_bias: bool = True
    use_pallas: bool = False  # the factory's GIGAPATH_QUANT_PALLAS read
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        mode = base_mode(normalize_mode(self.mode))
        if not mode:
            raise ValueError(
                "QuantDense requires a quant mode; use nn.Dense for the "
                "f32 path"
            )
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (x.shape[-1], self.features),
            self.param_dtype,
        )
        qt = quantize_per_channel(kernel, mode, axis=-1)
        y = q_matmul(x, qt, use_pallas=self.use_pallas)
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros, (self.features,),
                self.param_dtype,
            )
            y = y + bias.astype(jnp.float32)
        out_dtype = self.dtype or x.dtype
        return y.astype(out_dtype)
