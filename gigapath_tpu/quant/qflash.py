"""Quantized flash attention: int8 Q/K logits with f32 online softmax.

The '+attn' rider of the quantized tile tier (``GIGAPATH_QUANT_TILE=
int8+attn``): on top of the quantized projections (qmatmul.py), the
attention logits themselves are computed from dynamically-quantized
int8 Q and K — one symmetric absmax scale per (batch, head), folded
with the softmax temperature into a single scalar multiply of the f32
logits tile. V stays bf16 (the PV matmul is where f32 statistics
already protect the sum), the softmax statistics stay f32, and the op
returns the same ``(out, lse)`` contract every attention tier in this
repo emits — so the branch-fusion/partial-combine machinery is
oblivious to the quantization.

Same numerics discipline as qmatmul.py: int8 operand tiles cast to
bf16 in-cell (exact — |q| <= 127), MXU f32 accumulation, so the int8
grid arithmetic is exact and the only approximation is the activation
quantization. The f32 ``attention_with_lse`` stays the fallback and
parity oracle.

Tiers: jnp reference by default; a Pallas online-softmax kernel
(base-2 hot loop, running-max floor — the pallas_flash.py numerics)
behind the caller's ``use_pallas`` (``GIGAPATH_QUANT_PALLAS``, read by
the tile-encoder factory) when the sequence is block-aligned. The ViT
tile sequence (197 = 1 cls + 196 patches) is NOT 128-aligned, so the
tile encoder rides the reference tier.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gigapath_tpu.ops import flash_attention
from gigapath_tpu.ops.pallas_flash import LANES, LN2, LOG2E, M_FLOOR
from gigapath_tpu.quant.qtensor import quantize_dynamic


# ---------------------------------------------------------------------------
# jnp reference tier
# ---------------------------------------------------------------------------

def q_flash_attention_reference(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    scale: Optional[float] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[B, L, H, D] q/k/v -> (out [B, L, H, D], lse [B, H, L])."""
    B, Lq, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    qh = q.transpose(0, 2, 1, 3)  # [B, H, L, D]
    kh = k.transpose(0, 2, 1, 3)
    qq = quantize_dynamic(qh)
    kq = quantize_dynamic(kh)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk",
        qq.data.astype(jnp.bfloat16),
        kq.data.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )
    # fold both activation scales + the softmax temperature into one
    # [B, H, 1, 1] multiply of the f32 logits
    logits = logits * (qq.scale * kq.scale.reshape(B, H, 1, 1) * scale)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)  # [B, H, Lq]
    probs = jnp.exp(logits - lse[..., None])
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype), lse


# ---------------------------------------------------------------------------
# Pallas tier
# ---------------------------------------------------------------------------

def _qflash_kernel(s_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_ref, l_ref, acc_ref, *, block_q, block_k):
    """Online-softmax cell: grid (B, H, nq, nk); int8 q/k blocks, the
    combined (sq*sk*scale*log2e) scalar from SMEM, pallas_flash's
    base-2 running-max numerics."""
    b, h = pl.program_id(0), pl.program_id(1)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    s_ = jax.lax.dot_general(
        q_ref[0, 0].astype(jnp.bfloat16), k_ref[0, 0].astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * s_ref[b, h]  # log2-unit logits

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
    pp = jnp.exp2(s_ - m_new)
    alpha = jnp.exp2(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(pp, axis=-1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        pp.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[:, :1] = m_new
    l_ref[:, :1] = l_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        safe_l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        val = (m_ref[:, :1] + jnp.log2(safe_l)) * LN2  # natural-log lse
        lse_ref[0, 0] = jnp.broadcast_to(val, (block_q, LANES))


def q_flash_attention_pallas(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    scale: Optional[float] = None, block_q: int = 128,
    block_k: int = 128, interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pallas tier; requires L divisible by the block sizes."""
    B, L, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    block_q = min(block_q, L)
    block_k = min(block_k, L)
    assert L % block_q == 0 and L % block_k == 0, (L, block_q, block_k)
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3).astype(jnp.bfloat16)
    qq = quantize_dynamic(qh)
    kq = quantize_dynamic(kh)
    combined = (
        qq.scale * kq.scale * jnp.float32(scale * LOG2E)
    ).reshape(B, H)
    nq, nk = L // block_q, L // block_k
    spec_q = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0),
                          memory_space=pltpu.VMEM)
    spec_k = pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, j, 0),
                          memory_space=pltpu.VMEM)
    lse_spec = pl.BlockSpec((1, 1, block_q, LANES),
                            lambda b, h, i, j: (b, h, i, 0),
                            memory_space=pltpu.VMEM)
    with jax.named_scope("kernel_fwd"):
        out, lse = pl.pallas_call(
            functools.partial(_qflash_kernel, block_q=block_q, block_k=block_k),
            grid=(B, H, nq, nk),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      spec_q, spec_k, spec_k],
            out_specs=[spec_q, lse_spec],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, L, D), q.dtype),
                jax.ShapeDtypeStruct((B, H, L, LANES), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, D), jnp.float32),
            ],
            interpret=interpret,
            name="q_flash_fwd",
        )(combined, qq.data, kq.data, vh)
    return out.transpose(0, 2, 1, 3), lse[..., 0]


def q_flash_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    scale: Optional[float] = None, use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The quantized attention entry: tier per the module doc;
    ``use_pallas`` is the caller's snapshotted flag value (never an env
    read here — gigalint GL001)."""
    L = q.shape[1]
    if (use_pallas and (flash_attention._on_tpu() or interpret)
            and L % 128 == 0 and q.shape == k.shape):
        return q_flash_attention_pallas(
            q, k, v, scale=scale, interpret=interpret
        )
    return q_flash_attention_reference(q, k, v, scale=scale)
