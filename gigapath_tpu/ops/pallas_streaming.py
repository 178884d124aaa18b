"""Pallas TPU tier for the streaming-fold pair partial (fwd + bwd).

The streaming chunked prefill's inner loop —
:func:`gigapath_tpu.ops.streaming_prefill.pair_partial_attention` — is a
jnp formulation that materializes a dense ``[H, cq, ck]`` boolean
segment/phase/validity mask per chunk pair before the softmax touches a
single logit. At the paper-scale operating point (10^5-10^6 tiles per
slide, every chunk pair of every branch of every layer) that mask is
pure overhead: it is a function of nothing but iota comparisons the
kernel grid can evaluate per block.

This module is the FlashAttention-style replacement (the same treatment
``pallas_flash.py`` gave the dense path):

- forward: one kernel per (batch, head, q-block) running the base-2
  online softmax over key blocks, with the segment / dilation-phase /
  ragged-``valid_len`` masks computed IN-KERNEL from
  ``broadcasted_iota`` against the chunks' global offsets — no dense
  mask tensor ever exists in the compiled program (the golden ledger's
  ``jaxpr.mask`` column pins this at 0 vs the jnp control's nonzero
  count);
- backward: dQ and dK/dV kernels recomputing probabilities from the
  stored LSE (the ``_branch_bwd_core`` discipline), with one twist the
  branch VJPs don't need: ``combine_partials`` DIFFERENTIATES through
  the lse output, so the incoming ``dlse`` cotangent folds into the
  delta term (``ds = p * (dp - (delta - dlse))``) instead of being
  dropped;
- the chunks' global offsets, the ragged valid length, and the true
  (unpadded) block extents travel as ONE dynamic int32 SMEM array, so a
  single compiled executable serves every chunk pair of a branch class
  — the fold loop never retraces on chunk position.

Numerics contract vs the jnp oracle: covered query rows match fwd 1e-5
/ grads 1e-4. Fully-masked rows produce ``out = 0`` in both
formulations; their lse is a large-negative SENTINEL in both (~ -7e19
here via the ``M_FLOOR`` underflow discipline, ~ -1e30 in the oracle)
and the two interoperate identically downstream: ``combine_partials``
folds either in with weight ``exp(sentinel - lse) == 0`` and
``fuse_branch_partials`` gives either zero fusion weight. Parity tests
therefore compare lse on covered rows and the fused OUTPUT everywhere.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gigapath_tpu.ops.common import round_up
from gigapath_tpu.ops.pallas_flash import (
    LANES,
    LN2,
    LOG2E,
    M_FLOOR,
    NEG_INF,
    bwd_blocks,
)

# Chunk blocks are small next to the dense path's sequences (the 16k
# smoke geometry folds 2048-token chunks), so the flash default of
# 1024x1024 — fp32 logits tile 4 MB, well under the 16 MB VMEM budget —
# is also the fold's default.
DEFAULT_FOLD_BLOCK = 1024

# layout of the dynamic int32 SMEM info array (ONE executable serves
# every chunk pair): global q offset, global k offset, ragged valid
# length (sentinel INT32_MAX = no ragged tail), true q rows, true k rows
_INFO_Q0, _INFO_K0, _INFO_VALID, _INFO_CQ, _INFO_CK = range(5)
_NO_VALID = np.int32(2**31 - 1)


def fold_blocks(flags) -> Tuple[int, int]:
    """(block_q, block_k) of the fold kernel: the carrier's
    ``fold_block_q``/``fold_block_k`` where set, else the default."""
    return (int(flags.fold_block_q or DEFAULT_FOLD_BLOCK),
            int(flags.fold_block_k or DEFAULT_FOLD_BLOCK))


# ---------------------------------------------------------------------------
# in-kernel masks
# ---------------------------------------------------------------------------

def _pair_masks(info_ref, i, j, phase, *, segment_len, ratio,
                block_q, block_k):
    """(row_ok [bq,1], col_ok [1,bk], seg_ok [bq,bk]) from iota
    comparisons against the SMEM scalars — the dense ``[H, cq, ck]``
    mask of the jnp oracle, re-expressed as three per-block predicates
    that never materialize outside VMEM."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0) + i * block_q
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1) + j * block_k
    t = info_ref[_INFO_Q0] + rows  # global query positions
    u = info_ref[_INFO_K0] + cols  # global key positions
    # local bounds first: padded rows/cols sit at global positions that
    # could otherwise pass the segment/lattice tests
    row_ok = (rows < info_ref[_INFO_CQ]) \
        & (((t % segment_len) % ratio) == phase)
    col_ok = (cols < info_ref[_INFO_CK]) \
        & (((u % segment_len) % ratio) == phase) \
        & (u < info_ref[_INFO_VALID])
    seg_ok = (t // segment_len) == (u // segment_len)
    return row_ok, col_ok, seg_ok


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(info_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref,
                *, scale, segment_len, ratio, hpg, block_q, block_k):
    h = pl.program_id(1)
    i, j = pl.program_id(2), pl.program_id(3)
    phase = h // hpg

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # scale (with log2(e) folded in: the hot loop runs exp2) applied to
    # the small q block, not the [bq, bk] logits — the pallas_flash
    # discipline
    q = (q_ref[0, 0].astype(jnp.float32) * (scale * LOG2E)).astype(q_ref.dtype)
    k = k_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (BQ, BK), log2 units

    row_ok, col_ok, seg_ok = _pair_masks(
        info_ref, i, j, phase,
        segment_len=segment_len, ratio=ratio,
        block_q=block_q, block_k=block_k,
    )
    # select BEFORE the running max (a post-hoc zero-multiply would see
    # inf * 0 = NaN); M_FLOOR keeps m_new finite for fully-masked rows
    # so exp2(NEG_INF - m_new) underflows to exactly 0.0 in fp32
    s = jnp.where(seg_ok & row_ok & col_ok, s, NEG_INF)

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp2(s - m_new)
    # padded key rows of V are exact zeros (the wrapper zero-pads) and p
    # is exactly 0 there — no NaN hazard, no extra select needed
    v = v_ref[0, 0]
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if pl.num_programs(3) == 1:
        # single k block: no online carry — skip the acc rescale
        l_new = jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = pv
    else:
        alpha = jnp.exp2(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + pv
    m_ref[:, :1] = m_new
    l_ref[:, :1] = l_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        # natural-log lse from the base-2 running stats, carried at
        # LANES width (TPU tiling); the wrapper slices lane 0
        lse_ref[0, 0] = jnp.broadcast_to(
            (m_ref[:, :1] + jnp.log2(safe_l)) * LN2, (block_q, LANES)
        )


# ---------------------------------------------------------------------------
# backward kernels (stored-LSE recompute, the _branch_bwd_core discipline)
# ---------------------------------------------------------------------------

def _dq_kernel(info_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc,
               *, scale, segment_len, ratio, hpg, block_q, block_k):
    h = pl.program_id(1)
    i, j = pl.program_id(2), pl.program_id(3)
    phase = h // hpg

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (scale * LOG2E)
    row_ok, col_ok, seg_ok = _pair_masks(
        info_ref, i, j, phase,
        segment_len=segment_len, ratio=ratio,
        block_q=block_q, block_k=block_k,
    )
    # masking BEFORE the exp (inf * 0 = NaN in the gradients otherwise);
    # masked/padded rows carry lse = 0 from the wrapper's pad, and
    # exp2(NEG_INF - 0) is exactly 0 — their p rows vanish
    p = jnp.exp2(
        jnp.where(seg_ok & row_ok & col_ok, s, NEG_INF)
        - lse_ref[0, 0][:, :1] * LOG2E
    )
    dp = jax.lax.dot_general(
        do_ref[0, 0].astype(jnp.float32), v.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    # delta arrives PRE-FOLDED with the lse cotangent:
    # delta' = rowsum(do * out) - dlse  (combine_partials differentiates
    # through lse, unlike the branch VJPs that drop it)
    ds = p * (dp - delta_ref[0, 0][:, :1])
    dq_acc[:] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(info_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale, segment_len, ratio, hpg, block_q, block_k):
    h = pl.program_id(1)
    j, i = pl.program_id(2), pl.program_id(3)  # grid: (B, H, nk, nq)
    phase = h // hpg

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    do = do_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (scale * LOG2E)
    row_ok, col_ok, seg_ok = _pair_masks(
        info_ref, i, j, phase,
        segment_len=segment_len, ratio=ratio,
        block_q=block_q, block_k=block_k,
    )
    p = jnp.exp2(
        jnp.where(seg_ok & row_ok & col_ok, s, NEG_INF)
        - lse_ref[0, 0][:, :1] * LOG2E
    )  # (BQ, BK)
    dv_acc[:] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )  # (BK, D)
    dp = jax.lax.dot_general(
        do, v_ref[0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (BQ, BK)
    ds = p * (dp - delta_ref[0, 0][:, :1])
    dk_acc[:] += jax.lax.dot_general(
        ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (BK, D)

    @pl.when(i == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# impls ([B, H, c, D] head-major layout; padding handled here)
# ---------------------------------------------------------------------------

def _pad_rows(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """[B, H, c, ...] zero-padded to n rows on axis 2."""
    if x.shape[2] == n:
        return x
    pads = [(0, 0), (0, 0), (0, n - x.shape[2])] + [(0, 0)] * (x.ndim - 3)
    return jnp.pad(x, pads)


def _blocks_for(cq: int, ck: int, block_q: int, block_k: int):
    bq = min(block_q, round_up(cq, LANES))
    bk = min(block_k, round_up(ck, LANES))
    return bq, bk, round_up(cq, bq), round_up(ck, bk)


def _fwd_impl(info, q, k, v, segment_len, ratio, block_q, block_k,
              interpret):
    B, H, cq, D = q.shape
    ck = k.shape[2]
    scale = D ** -0.5
    bq, bk, cqp, ckp = _blocks_for(cq, ck, block_q, block_k)
    qp = _pad_rows(q, cqp)
    kp, vp = _pad_rows(k, ckp), _pad_rows(v, ckp)
    nq, nk = cqp // bq, ckp // bk
    hpg = -(-H // ratio)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, segment_len=segment_len, ratio=ratio,
        hpg=hpg, block_q=bq, block_k=bk,
    )
    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0),
                          memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0),
                          memory_space=pltpu.VMEM)
    info_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    with jax.named_scope("kernel_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid=(B, H, nq, nk),
            in_specs=[info_spec, q_spec, k_spec, k_spec],
            out_specs=[
                q_spec,
                pl.BlockSpec((1, 1, bq, LANES), lambda b, h, i, j: (b, h, i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, cqp, D), q.dtype),
                jax.ShapeDtypeStruct((B, H, cqp, LANES), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, D), jnp.float32),
            ],
            interpret=interpret,
            name="stream_fold",
        )(info, qp, kp, vp)
    return out[:, :, :cq], lse[:, :, :cq, 0]


def _bwd_impl(info, q, k, v, lse, delta, do, segment_len, ratio,
              block_q, block_k, interpret):
    B, H, cq, D = q.shape
    ck = k.shape[2]
    scale = D ** -0.5
    bq, bk = bwd_blocks(block_q)
    bk = min(bk, block_k)
    bq, bk, cqp, ckp = _blocks_for(cq, ck, bq, bk)
    qp = _pad_rows(q, cqp)
    kp, vp = _pad_rows(k, ckp), _pad_rows(v, ckp)
    dop = _pad_rows(do, cqp)
    # lse/delta carried at LANES width; padded q rows get lse = 0, which
    # is harmless: their mask rows are all-False, so p = exp2(NEG_INF -
    # 0) = 0 and nothing leaks into dk/dv
    lsep = jnp.broadcast_to(
        _pad_rows(lse[..., None], cqp), (B, H, cqp, LANES)
    )
    deltap = jnp.broadcast_to(
        _pad_rows(delta[..., None], cqp), (B, H, cqp, LANES)
    )
    nq, nk = cqp // bq, ckp // bk
    hpg = -(-H // ratio)

    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0),
                          memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0),
                          memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, 1, bq, LANES), lambda b, h, i, j: (b, h, i, 0),
                            memory_space=pltpu.VMEM)
    info_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    with jax.named_scope("kernel_dq"):
        dq = pl.pallas_call(
            functools.partial(
                _dq_kernel, scale=scale, segment_len=segment_len, ratio=ratio,
                hpg=hpg, block_q=bq, block_k=bk,
            ),
            grid=(B, H, nq, nk),
            in_specs=[info_spec, q_spec, k_spec, k_spec, q_spec, vec_spec,
                      vec_spec],
            out_specs=[q_spec],
            out_shape=[jax.ShapeDtypeStruct((B, H, cqp, D), q.dtype)],
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            interpret=interpret,
            name="stream_fold_dq",
        )(info, qp, kp, vp, dop, lsep, deltap)[0]

    # grid (B, H, nk, nq): index maps see (b, h, j, i)
    q_spec_kv = pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0),
                             memory_space=pltpu.VMEM)
    k_spec_kv = pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0),
                             memory_space=pltpu.VMEM)
    vec_spec_kv = pl.BlockSpec(
        (1, 1, bq, LANES), lambda b, h, j, i: (b, h, i, 0),
        memory_space=pltpu.VMEM,
    )
    with jax.named_scope("kernel_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(
                _dkv_kernel, scale=scale, segment_len=segment_len, ratio=ratio,
                hpg=hpg, block_q=bq, block_k=bk,
            ),
            grid=(B, H, nk, nq),
            in_specs=[info_spec, q_spec_kv, k_spec_kv, k_spec_kv, q_spec_kv,
                      vec_spec_kv, vec_spec_kv],
            out_specs=[k_spec_kv, k_spec_kv],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, ckp, D), k.dtype),
                jax.ShapeDtypeStruct((B, H, ckp, D), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, D), jnp.float32),
            ],
            interpret=interpret,
            name="stream_fold_dkv",
        )(info, qp, kp, vp, dop, lsep, deltap)
    return dq[:, :, :cq], dk[:, :, :ck], dv[:, :, :ck]


# ---------------------------------------------------------------------------
# custom VJP
# ---------------------------------------------------------------------------

def _pair_fwd_rule(segment_len, ratio, block_q, block_k, interpret,
                   info, q, k, v):
    out, lse = _fwd_impl(
        info, q, k, v, segment_len, ratio, block_q, block_k, interpret
    )
    return (out, lse), (info, q, k, v, out, lse)


def _pair_bwd_rule(segment_len, ratio, block_q, block_k, interpret,
                   res, cotangents):
    info, q, k, v, out, lse = res
    do, dlse = cotangents
    # the lse output IS differentiated downstream (combine_partials
    # merges through it), so its cotangent folds into the delta term:
    # ds = p * (dp - (rowsum(do*out) - dlse))
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ) - dlse.astype(jnp.float32)
    dq, dk, dv = _bwd_impl(
        info, q, k, v, lse, delta, do, segment_len, ratio,
        block_q, block_k, interpret,
    )
    # int32 info carries no gradient: float0 cotangent (the repo's
    # integer-residual idiom, pallas_dilated/_dilated_branch_bwd)
    info_ct = np.zeros(info.shape, dtype=jax.dtypes.float0)
    return info_ct, dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _pair_with_lse(segment_len, ratio, block_q, block_k, interpret,
                   info, q, k, v):
    return _fwd_impl(
        info, q, k, v, segment_len, ratio, block_q, block_k, interpret
    )


_pair_with_lse.defvjp(_pair_fwd_rule, _pair_bwd_rule)


# ---------------------------------------------------------------------------
# public wrapper (the pair_partial_attention contract)
# ---------------------------------------------------------------------------

def pallas_pair_partial(
    q_blk: jnp.ndarray,
    k_blk: jnp.ndarray,
    v_blk: jnp.ndarray,
    q0,
    k0,
    *,
    segment_len: int,
    ratio: int,
    valid_len=None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pallas twin of
    :func:`~gigapath_tpu.ops.streaming_prefill.pair_partial_attention`:
    ``(out [B,cq,H,D] q-dtype, lse [B,H,cq] f32)`` of one dilated branch
    restricted to one resident key chunk, masks computed in-kernel.

    ``q0``/``k0``/``valid_len`` are DYNAMIC int32 scalars packed into
    one SMEM array, so one compiled executable serves every chunk pair
    of the same block shapes. Kernels run on the head-major
    ``[B, H, c, D]`` layout (Mosaic's (8, 128) tiling rule); this
    wrapper transposes, like the flash wrapper.
    """
    B, cq, H, Dh = q_blk.shape
    ck = k_blk.shape[1]
    valid = _NO_VALID if valid_len is None \
        else jnp.asarray(valid_len, jnp.int32)
    info = jnp.stack([
        jnp.asarray(q0, jnp.int32),
        jnp.asarray(k0, jnp.int32),
        jnp.asarray(valid, jnp.int32),
        jnp.int32(cq),
        jnp.int32(ck),
    ])
    q4 = q_blk.transpose(0, 2, 1, 3)
    k4 = k_blk.transpose(0, 2, 1, 3)
    v4 = v_blk.transpose(0, 2, 1, 3)
    out, lse = _pair_with_lse(
        int(segment_len), int(ratio),
        int(block_q or DEFAULT_FOLD_BLOCK),
        int(block_k or DEFAULT_FOLD_BLOCK),
        bool(interpret), info, q4, k4, v4,
    )
    return out.transpose(0, 2, 1, 3), lse
