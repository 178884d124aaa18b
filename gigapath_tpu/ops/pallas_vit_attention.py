"""Pallas TPU attention core for short sequences over a PACKED QKV output.

The ViT tile encoder's attention is 24 heads of 64 over 197 tokens: a
``[197, 197]`` problem per head that never needs to leave VMEM. Written as
``qkv.reshape(B, N, 3, H, hd)``, three slices, two einsums and a softmax,
XLA spends more time moving q, k, v and the output between layouts and
making three trips over float32 ``[B, H, N, N]`` scores in HBM than on the
GEMMs' 2 % of FLOPs it serves (PERF.md §5-6, PR 26). This kernel reads the
``[B, N, 3*D]`` array as the qkv GEMM wrote it (columns ordered
``[3][H][hd]``, timm's order) and writes the ``[B, N, D]`` array the output
projection reads, heads side by side in the lane dimension:

- one grid step per batch row; the row's whole q, k and v column blocks
  (``[N, D]`` each, three index maps over the same array) sit in VMEM, and
  a block equal to the array's ``N`` needs no padding in HBM;
- heads are taken a 128-lane group at a time with no lane shift: for heads
  narrower than 128 the other heads' lanes of q are zeroed, so the
  contraction over the group's 128 lanes is one head's ``q k^T`` (the MXU
  is 128 deep either way), and ``p v`` over the group's 128 value lanes
  keeps each head's own columns;
- the arithmetic is ``ops.attention.attention_with_lse``'s: float32
  logits, the scale on the float32 logits, float32 row max and sum,
  probabilities cast to the value dtype before PV. No online softmax (one
  key block) and no lse output (the ViT discards it).

Forward only: no cell, driver or recipe trains the ViT, so the custom VJP
recomputes the jnp form from the saved qkv and differentiates that.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from gigapath_tpu.ops.attention import attention_with_lse
from gigapath_tpu.ops.common import round_up

LANES = 128
# What one grid step may hold of the 16 MiB a kernel is given by default:
# the double-buffered q/k/v/out blocks and the float32 score temporaries.
_VMEM_BUDGET = 12 * 1024 * 1024


def _group_width(head_dim: int) -> int:
    """Lanes handled together: one wide head, or the heads sharing 128."""
    return max(head_dim, LANES)


def _vmem_bytes(n: int, d: int, itemsize: int) -> int:
    rows, keys = round_up(n, 16), round_up(n, LANES)
    blocks = 2 * (3 + 1) * rows * d * itemsize  # q, k, v, out, double-buffered
    scores = 4 * rows * keys * 4                # s, p, p in the value dtype, slack
    return blocks + scores


def fits(shape, num_heads: int, dtype) -> bool:
    """The shape gate: whether :func:`packed_qkv_attention` takes a packed
    ``[B, N, 3*D]`` array of this shape and dtype. Heads must tile the
    128-lane groups exactly and the whole-``N`` blocks must fit VMEM."""
    if len(shape) != 3 or shape[-1] % 3:
        return False
    n, d = shape[1], shape[2] // 3
    if d % num_heads:
        return False
    hd = d // num_heads
    if not (LANES % hd == 0 or hd % LANES == 0) or d % _group_width(hd):
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    return _vmem_bytes(n, d, jnp.dtype(dtype).itemsize) <= _VMEM_BUDGET


def packed_qkv_attention_jnp(qkv: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """The jnp form of the same operation: what runs where the kernel does
    not, and what the kernel's backward differentiates."""
    B, N, D3 = qkv.shape
    D = D3 // 3
    qkv = qkv.reshape(B, N, 3, num_heads, D // num_heads)
    out, _ = attention_with_lse(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
    return out.reshape(B, N, D)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, head_dim, scale):
    D = q_ref.shape[2]
    width = _group_width(head_dim)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    for g in range(D // width):
        cols = slice(g * width, (g + 1) * width)
        q, k, v = q_ref[0, :, cols], k_ref[0, :, cols], v_ref[0, :, cols]
        out = None
        for j in range(width // head_dim):
            if width > head_dim:  # this head's lanes of the group
                mine = (lane >= j * head_dim) & (lane < (j + 1) * head_dim)
                qj = jnp.where(mine, q, jnp.zeros_like(q))
            else:
                qj = q
            s = jax.lax.dot_general(
                qj, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale
            p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            l = jnp.sum(p, axis=-1, keepdims=True)
            o = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) / l
            out = o if out is None else jnp.where(mine, o, out)
        o_ref[0, :, cols] = out.astype(o_ref.dtype)


def _fwd(qkv, num_heads, interpret):
    # imported here: the model imports this module for its shape gate, and a
    # CPU-only import path should not load Pallas for that
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, N, D3 = qkv.shape
    D = D3 // 3
    hd = D // num_heads
    with jax.named_scope("kernel_fwd"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, head_dim=hd, scale=hd**-0.5),
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, N, D), lambda b: (b, 0, 0)),  # q columns
                pl.BlockSpec((1, N, D), lambda b: (b, 0, 1)),  # k columns
                pl.BlockSpec((1, N, D), lambda b: (b, 0, 2)),  # v columns
            ],
            out_specs=pl.BlockSpec((1, N, D), lambda b: (b, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, N, D), qkv.dtype),
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
            interpret=interpret,
            name="vit_attn_fwd",
        )(qkv, qkv, qkv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _attention(qkv, num_heads, interpret):
    return _fwd(qkv, num_heads, interpret)


def _attention_fwd(qkv, num_heads, interpret):
    return _fwd(qkv, num_heads, interpret), qkv


def _attention_bwd(num_heads, interpret, qkv, g):
    _, vjp = jax.vjp(functools.partial(packed_qkv_attention_jnp, num_heads=num_heads), qkv)
    return vjp(g)


_attention.defvjp(_attention_fwd, _attention_bwd)


@functools.partial(jax.jit, static_argnames=("num_heads", "interpret"))
def packed_qkv_attention(qkv: jnp.ndarray, num_heads: int, interpret: bool = False):
    """Softmax attention over packed ``qkv [B, N, 3*D]`` -> ``[B, N, D]``.

    One module-level jitted function, so a model's blocks, which call it
    with the same shapes, share one trace and one lowering. The caller
    checks :func:`fits` first."""
    assert fits(qkv.shape, num_heads, qkv.dtype), (qkv.shape, num_heads, qkv.dtype)
    return _attention(qkv, num_heads, interpret)
