"""Pallas TPU flash attention with LSE output (forward + backward).

The one hard kernel (SURVEY §7.3): everything in LongNet leans on a fused
attention that also emits the log-sum-exp, because dilated attention's
branch recombination needs it (reference ``dilated_attention.py:119-128``
consumes the LSE the flash-attn CUDA kernel returns). This is the TPU
replacement for that CUDA dependency:

- forward: online-softmax blocks over K/V, carrying (m, l, acc) in VMEM
  scratch across the innermost grid dimension; emits ``(out, lse)``;
- backward: two kernels — dQ (grid over Q blocks, loop K) and dK/dV (grid
  over K blocks, loop Q) — recomputing probabilities from the saved LSE
  rather than storing the attention matrix.

Performance notes (PERF.md §5 / §6 hold the v5e readings):
- kernels run on ``[B, H, L, D]`` layout with ``(1, 1, block_q, D)`` blocks —
  the only layout whose trailing block dims satisfy Mosaic's (8, 128)
  tiling rule for head counts > 1; the public API stays ``[B, L, H, D]``
  and the wrapper transposes (XLA folds the relayout into the surrounding
  projection reshapes);
- two forward bodies, one arithmetic (:func:`plan_fwd_body` picks from the
  mask and the block; the tests hold them equal to the bit): the serial one,
  a QK^T -> softmax -> PV chain a grid step, and the overlapped one, which
  cuts the query block's rows into independent chains and emits the next
  chain's QK^T before the current chain's softmax. At keys of 192 and blocks
  of 1,024 the serial body took 5.5 us a block, this one 4.55, the MXU's
  passes 4.09 (PR 37); "the inner loop is VPU-bound" was read at heads of 48;
- the softmax scale, with log2(e), is folded into the small q block, and the
  online softmax runs in base 2 (``exp2``: one VPU pass a logit fewer than
  ``exp``); the emitted lse is converted back to natural log;
- masked slots rely on exp2 underflow instead of a second ``where``: the
  running max is floored at ``M_FLOOR`` so ``exp2(NEG_INF - m)`` is exactly
  0.0 in fp32: fully-masked rows give out=0 and an lse sentinel of ~ -7e19
  (ignored by the branch fusion) without extra per-element work;
- head_dim is NOT padded, and sequence length is zero-padded to the block
  size with padded *keys masked* in every kernel; ragged per-(batch,head)
  key counts (``kv_len``) are masked the same way from an SMEM table.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Floor for the running softmax max: low enough to never clip real logits,
# high enough that exp(NEG_INF - M_FLOOR) == 0.0 exactly in fp32.
M_FLOOR = -1e20
LANES = 128
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453
# 1024x1024 blocks measured ~2.3x faster than 512x1024 on the LongNet branch
# shapes (v5e, head_dim 48): fewer K/V restreams per q row and fuller MXU
# rows; fp32 logits block = 4 MB, comfortably under the 16 MB VMEM budget.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024

# Backward-pass logits budget, in block_q*block_k ELEMENTS. The backward
# kernels keep ~2.5 live fp32 (block_q, block_k) tiles on the scoped-vmem
# stack (s, p/dp, ds — measured: 20.12 MB scoped at 1408x1408, i.e. 2.54
# tiles), vs the forward's ~2. A block pair is bwd-safe when ~2.6 live
# tiles fit under the 16 MB limit with headroom: 2.6 * 4 B * budget
# <= 14 MB  =>  budget <= ~1.35M elements. 1024x1024 (1.05M, the default)
# passes; 1408x1408 (1.98M, round 3's single-block choice) does not — that
# exact overflow shipped a HEAD whose own benchmark crashed (the round-3 driver run).
_BWD_LOGITS_BUDGET = 1_350_000


def bwd_blocks(fwd_block: int) -> Tuple[int, int]:
    """Backward block sizes (block_q, block_k) given the forward's block.

    Keeps block_q = the forward block (so the q/do/lse/delta arrays need no
    extra padding beyond the forward's), then shrinks block_k until the
    backward's live fp32 logits tiles fit the scoped-vmem budget — the two
    kernels take block_q/block_k independently, and nothing forces the
    backward to share the forward's block (the branch VJP re-dilates
    anyway)."""
    if fwd_block * fwd_block <= _BWD_LOGITS_BUDGET:
        return fwd_block, fwd_block
    # contract is total: even the thinnest k block must fit the budget
    assert fwd_block * LANES <= _BWD_LOGITS_BUDGET, fwd_block
    bk = _BWD_LOGITS_BUDGET // fwd_block // LANES * LANES
    return fwd_block, bk


from gigapath_tpu.ops.common import round_up  # noqa: E402  (re-export)

_round_up = round_up  # internal alias


def _fwd_kernel(q_ref, k_ref, v_ref, kvlen_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale, causal, block_q, block_k):
    b, h, sg = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    i, j = pl.program_id(3), pl.program_id(4)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _online_step(masked: bool):
        # scale (with log2(e) folded in: the hot loop runs exp2, one fewer
        # VPU pass per logit than exp) applied to q: block_q*D elements
        # instead of block_q*block_k
        q = (q_ref[0, 0, 0].astype(jnp.float32) * (scale * LOG2E)).astype(q_ref.dtype)
        k = k_ref[0, 0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (BQ, BK), in log2 units

        if causal:
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + j * block_k
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + i * block_q
            s = jnp.where(cols > rows, NEG_INF, s)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        if masked:
            # kv-length masking as a per-COLUMN select (the mask depends
            # only on the column, so it broadcasts from one [1, bk] row).
            # A select, not an additive bias: masked keys can be REAL
            # activations (alignment padding becomes nonzero after the
            # first residual layer) or — on the flat path — out-of-bounds
            # DMA garbage that may be non-finite, and NaN + NEG_INF stays
            # NaN where the select yields exactly NEG_INF. Masking must
            # precede the running max; M_FLOOR keeps m_new finite even for
            # fully-masked rows, so exp2(NEG_INF - m_new) underflows to 0.
            col_ok = (
                jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1) + j * block_k
                < kvlen_ref[b, h, sg]
            )
            s = jnp.where(col_ok, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        v = v_ref[0, 0, 0]
        if masked:
            # masked key rows of V can be OOB garbage on the flat path
            # (non-finite bits); p is exactly 0 there but 0 * NaN = NaN in
            # the PV contraction, so V itself must be zeroed
            row_ok = (
                jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0) + j * block_k
                < kvlen_ref[b, h, sg]
            )
            v = jnp.where(row_ok, v, 0)
        if pl.num_programs(4) == 1:
            # single k block: no online carry — skip the acc rescale and
            # write the stats once (saves two [bq, 1] scratch stores and an
            # alpha pass on every single-segment branch)
            l_new = jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[:] = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            alpha = jnp.exp2(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        # single-lane stats stores (the broadcast-to-128-lane form wrote
        # 128x the bytes per step)
        m_ref[:, :1] = m_new
        l_ref[:, :1] = l_new

    # full key blocks skip the col-bias pass entirely (one fewer VPU pass
    # over the [bq, bk] tile — the inner loop is VPU-bound); only the block
    # straddling the valid-key boundary pays for masking
    def visited(block_has_keys):
        # a key block wholly above the diagonal (its first key after the q
        # block's last row) is not visited: no compute here, and no copy,
        # because _fwd_impl's index map names the diagonal block again
        return block_has_keys & (j * block_k < (i + 1) * block_q) if causal else block_has_keys

    @pl.when(visited((j + 1) * block_k <= kvlen_ref[b, h, sg]))
    def _compute_full():
        _online_step(masked=False)

    @pl.when(visited(
        (j * block_k < kvlen_ref[b, h, sg])
        & ((j + 1) * block_k > kvlen_ref[b, h, sg])
    ))
    def _compute_partial():
        _online_step(masked=True)

    @pl.when(j == pl.num_programs(4) - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0, 0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        # natural-log lse recovered from the base-2 running stats; carried
        # at LANES width (TPU tiling needs a 128-lane last dim); the
        # wrapper slices lane 0
        lse_ref[0, 0, 0] = jnp.broadcast_to(
            (m_ref[:, :1] + jnp.log2(safe_l)) * LN2, (block_q, LANES)
        )


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kvlen_ref, dq_ref, dq_acc,
               *, scale, causal, block_q, block_k, flat=False):
    b, h, sg = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    i, j = pl.program_id(3), pl.program_id(4)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(j * block_k < kvlen_ref[b, h, sg])
    def _compute():
        q = q_ref[0, 0, 0]
        k = k_ref[0, 0, 0]
        v = v_ref[0, 0, 0]
        col_ok = (
            jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1) + j * block_k
            < kvlen_ref[b, h, sg]
        )
        if flat:
            # flat mode reads the unpadded arrays: masked key rows can be
            # OOB garbage (possibly non-finite), and 0 * NaN = NaN inside
            # the contractions — zero K/V rows before any matmul touches
            # them (padded mode's masked rows are provably zero already)
            krow_ok = (
                jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0) + j * block_k
                < kvlen_ref[b, h, sg]
            )
            k = jnp.where(krow_ok, k, 0)
            v = jnp.where(krow_ok, v, 0)
        # log2-units recompute (exp2 is one fewer VPU pass than exp); the
        # natural-log lse is rescaled on its [bq, 1] column, not per logit
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (scale * LOG2E)
        # masking BEFORE the exp: a post-hoc zero-multiply would compute
        # exp of unbounded masked logits — inf * 0 = NaN in the gradients
        p = jnp.exp2(
            jnp.where(col_ok, s, NEG_INF) - lse_ref[0, 0, 0][:, :1] * LOG2E
        )
        if causal:
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + j * block_k
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + i * block_q
            p = jnp.where(cols > rows, 0.0, p)

        dp = jax.lax.dot_general(
            do_ref[0, 0, 0].astype(jnp.float32), v.astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0, 0][:, :1])
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(j == pl.num_programs(4) - 1)
    def _finalize():
        dq_ref[0, 0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kvlen_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale, causal, block_q, block_k, flat=False):
    b, h, sg = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    j, i = pl.program_id(3), pl.program_id(4)  # grid: (B, H, S, nk, nq)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(j * block_k < kvlen_ref[b, h, sg])
    def _compute():
        q = q_ref[0, 0, 0]
        k = k_ref[0, 0, 0]
        do = do_ref[0, 0, 0].astype(jnp.float32)
        if flat:
            # flat self-attention: valid q rows == valid key rows per
            # segment; OOB q/do rows are garbage and would pollute the
            # dk/dv row-sums through the transposed contractions
            qrow_ok = (
                jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0) + i * block_q
                < kvlen_ref[b, h, sg]
            )
            q = jnp.where(qrow_ok, q, 0)
            do = jnp.where(qrow_ok, do, 0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (scale * LOG2E)  # (BQ, BK), log2 units (see _dq_kernel)
        col_ok = (
            jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1) + j * block_k
            < kvlen_ref[b, h, sg]
        )
        p = jnp.exp2(
            jnp.where(col_ok, s, NEG_INF) - lse_ref[0, 0, 0][:, :1] * LOG2E
        )  # (BQ, BK)
        if flat:
            # OOB q rows carry garbage lse — their p rows must be exact 0
            p = jnp.where(qrow_ok, p, 0.0)
        if causal:
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + j * block_k
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + i * block_q
            p = jnp.where(cols > rows, 0.0, p)

        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (BK, D)
        dp = jax.lax.dot_general(
            do, v_ref[0, 0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        ds = p * (dp - delta_ref[0, 0, 0][:, :1])
        if flat:
            ds = jnp.where(qrow_ok, ds, 0.0)
        dk_acc[:] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (BK, D)

    @pl.when(i == pl.num_programs(4) - 1)
    def _finalize():
        dk_ref[0, 0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _kvlen_array(kv_lens, B: int, H: int, S: int, Lk: int) -> jnp.ndarray:
    """[B, H, S] int32 valid-key counts (None = all valid).

    Accepts a static tuple/np array OR a *traced* jnp array: the kernels
    read the counts from SMEM at runtime (``pl.when`` on SMEM scalars), so
    dynamic per-batch padding needs no retrace — only the shapes are
    static."""
    if kv_lens is None:
        return jnp.asarray(np.full((B, H, S), Lk, np.int32))
    if isinstance(kv_lens, (jax.Array, jax.core.Tracer)):
        return kv_lens.reshape(B, H, S).astype(jnp.int32)
    return jnp.asarray(np.asarray(kv_lens, np.int32).reshape(B, H, S))


def _pad_seg(x: jnp.ndarray, M: int) -> jnp.ndarray:
    """[B, H, S, M0, D] zero-padded to M on the per-segment axis."""
    if x.shape[3] == M:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, M - x.shape[3]), (0, 0)))


def _fwd_impl(q, k, v, kv_lens, causal, scale, block_q, block_k, interpret, body=None):
    """Segment-batched flash forward on [B, H, S, M, D] -> (out, lse [B,H,S,M]).

    Each of the S segments attends independently (block-diagonal attention);
    the segment axis is a grid dimension. ``k`` / ``v`` may carry ``H / group``
    heads (grouped KV heads): the K/V index map sends query head ``h`` to KV
    head ``h // group``, so a KV head is read from where it lies and never
    repeated in memory. ``v`` may be narrower or wider than ``q`` and ``k``
    (latent attention: keys of 192 beside values of 128): the value block, the
    output and the accumulator then take ``v``'s width. ``body``: ``None`` for
    the body :func:`plan_fwd_body` names; tests and probes pass ``"serial"``
    or a :class:`FwdPlan` to hold the bodies to each other.
    """
    B, H, S, Mq, D = q.shape
    Mk, Dv = k.shape[3], v.shape[4]
    group = H // k.shape[1]
    block_q = min(block_q, _round_up(Mq, LANES))
    block_k = min(block_k, _round_up(Mk, LANES))
    Mqp, Mkp = _round_up(Mq, block_q), _round_up(Mk, block_k)
    qp, kp, vp = _pad_seg(q, Mqp), _pad_seg(k, Mkp), _pad_seg(v, Mkp)
    nq, nk = Mqp // block_q, Mkp // block_k
    kvlen = _kvlen_array(kv_lens, B, H, S, Mk)

    plan = plan_fwd_body("causal" if causal else None, block_q, nq * nk) if body is None else body
    if plan != "serial" and plan.body == "overlap":  # else the serial call below, as it always was
        return _fwd_overlap(qp, kp, vp, kvlen, Mq, causal, scale, block_q, block_k, plan.rows, interpret)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k)
    def kv_index(b, h, s, i, j):
        if causal:  # past the diagonal: the block already there, so no new copy
            j = jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)
        # h itself where H_kv = H: that program lowers to the text it always had
        return (b, h // group if group > 1 else h, s, j, 0)

    q_spec = pl.BlockSpec((1, 1, 1, block_q, D), lambda b, h, s, i, j: (b, h, s, i, 0), memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, 1, 1, block_k, D), kv_index, memory_space=pltpu.VMEM)
    # the same spec objects where v is as wide as k: that program lowers to the text it always had
    v_spec = k_spec if Dv == D else pl.BlockSpec(
        (1, 1, 1, block_k, Dv), kv_index, memory_space=pltpu.VMEM)
    o_spec = q_spec if Dv == D else pl.BlockSpec(
        (1, 1, 1, block_q, Dv), lambda b, h, s, i, j: (b, h, s, i, 0), memory_space=pltpu.VMEM)
    kvlen_spec = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole (B,H,S) array; indexed by program_id
    with jax.named_scope("kernel_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid=(B, H, S, nq, nk),
            in_specs=[q_spec, k_spec, v_spec, kvlen_spec],
            out_specs=[
                o_spec,
                pl.BlockSpec((1, 1, 1, block_q, LANES), lambda b, h, s, i, j: (b, h, s, i, 0), memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, S, Mqp, Dv), q.dtype),
                jax.ShapeDtypeStruct((B, H, S, Mqp, LANES), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, Dv), jnp.float32),
            ],
            interpret=interpret,
            name="flash_fwd",
        )(qp, kp, vp, kvlen)
    return out[:, :, :, :Mq], lse[:, :, :, :Mq, 0]


def _bwd_impl(q, k, v, lse, delta, do, kv_lens, causal, scale, block_q, block_k, interpret):
    B, H, S, Mq, D = q.shape
    Mk = k.shape[3]
    block_q = min(block_q, _round_up(Mq, LANES))
    block_k = min(block_k, _round_up(Mk, LANES))
    Mqp, Mkp = _round_up(Mq, block_q), _round_up(Mk, block_k)
    qp, kp, vp = _pad_seg(q, Mqp), _pad_seg(k, Mkp), _pad_seg(v, Mkp)
    dop = _pad_seg(do, Mqp)
    # lse/delta carried at LANES width for TPU tiling; padded q rows get
    # lse=0, which is harmless (their p rows multiply masked ds/do = 0)
    lsep = jnp.broadcast_to(
        jnp.pad(lse, ((0, 0), (0, 0), (0, 0), (0, Mqp - Mq)))[..., None],
        (B, H, S, Mqp, LANES),
    )
    deltap = jnp.broadcast_to(
        jnp.pad(delta, ((0, 0), (0, 0), (0, 0), (0, Mqp - Mq)))[..., None],
        (B, H, S, Mqp, LANES),
    )
    nq, nk = Mqp // block_q, Mkp // block_k
    kvlen = _kvlen_array(kv_lens, B, H, S, Mk)

    q_spec = pl.BlockSpec((1, 1, 1, block_q, D), lambda b, h, s, i, j: (b, h, s, i, 0), memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, 1, 1, block_k, D), lambda b, h, s, i, j: (b, h, s, j, 0), memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, 1, 1, block_q, LANES), lambda b, h, s, i, j: (b, h, s, i, 0), memory_space=pltpu.VMEM)
    kvlen_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    with jax.named_scope("kernel_dq"):
        dq = pl.pallas_call(
            functools.partial(
                _dq_kernel, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k,
            ),
            grid=(B, H, S, nq, nk),
            in_specs=[q_spec, k_spec, k_spec, q_spec, vec_spec, vec_spec, kvlen_spec],
            out_specs=[q_spec],
            out_shape=[jax.ShapeDtypeStruct((B, H, S, Mqp, D), q.dtype)],
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            interpret=interpret,
            name="flash_dq",
        )(qp, kp, vp, dop, lsep, deltap, kvlen)[0]

    # grid (B, H, S, nk, nq): index maps see (b, h, s, j, i)
    q_spec_kv = pl.BlockSpec((1, 1, 1, block_q, D), lambda b, h, s, j, i: (b, h, s, i, 0), memory_space=pltpu.VMEM)
    k_spec_kv = pl.BlockSpec((1, 1, 1, block_k, D), lambda b, h, s, j, i: (b, h, s, j, 0), memory_space=pltpu.VMEM)
    vec_spec_kv = pl.BlockSpec((1, 1, 1, block_q, LANES), lambda b, h, s, j, i: (b, h, s, i, 0), memory_space=pltpu.VMEM)
    with jax.named_scope("kernel_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(
                _dkv_kernel, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k,
            ),
            grid=(B, H, S, nk, nq),
            in_specs=[q_spec_kv, k_spec_kv, k_spec_kv, q_spec_kv, vec_spec_kv, vec_spec_kv, kvlen_spec],
            out_specs=[k_spec_kv, k_spec_kv],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, S, Mkp, D), k.dtype),
                jax.ShapeDtypeStruct((B, H, S, Mkp, D), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ],
            interpret=interpret,
            name="flash_dkv",
        )(qp, kp, vp, dop, lsep, deltap, kvlen)
    return (
        dq[:, :, :, :Mq],
        dk[:, :, :, :Mk],
        dv[:, :, :, :Mk],
    )


# ---------------------------------------------------------------------------
# flat (zero-pad) segment path
# ---------------------------------------------------------------------------


def _flat_specs(g, D):
    """Specs over flat [B, H, 1, L, D] views: segment s = row block s
    (block size g) on the L axis, exploiting Pallas auto-masking for the
    non-divisible tail — the branch needs NO pads, reshapes, or slices at
    all (OOB reads are masked in-kernel, OOB writes dropped). The size-1
    third dim keeps the block rank identical to the segmented path so the
    kernels are shared verbatim."""
    q_spec = pl.BlockSpec(
        (1, 1, 1, g, D), lambda b, h, s, i, j: (b, h, 0, s, 0),
        memory_space=pltpu.VMEM,
    )
    lse_spec = pl.BlockSpec(
        (1, 1, 1, g, LANES), lambda b, h, s, i, j: (b, h, 0, s, 0),
        memory_space=pltpu.VMEM,
    )
    return q_spec, lse_spec


def _flat_fwd_impl(q, k, v, g, real_len, causal, interpret):
    """Flat segment flash: [B, H, L, D] -> (out [B, H, L, D], lse [B, H, L]).

    Segment s attends within itself; g is the segment length (one q and one
    k block per segment — requires g small enough for a single block)."""
    B, H, L, D = q.shape
    S = _round_up(L, g) // g
    kvlen = np.clip(real_len - np.arange(S) * g, 0, g).astype(np.int32)
    kvlen = jnp.asarray(np.broadcast_to(kvlen[None, None], (B, H, S)))
    q_spec, lse_spec = _flat_specs(g, D)
    q5, k5, v5 = q[:, :, None], k[:, :, None], v[:, :, None]
    kernel = functools.partial(
        _fwd_kernel, scale=D ** -0.5, causal=causal, block_q=g, block_k=g
    )
    with jax.named_scope("kernel_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid=(B, H, S, 1, 1),
            in_specs=[q_spec, q_spec, q_spec, pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=[q_spec, lse_spec],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, 1, L, D), q.dtype),
                jax.ShapeDtypeStruct((B, H, 1, L, LANES), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((g, LANES), jnp.float32),
                pltpu.VMEM((g, LANES), jnp.float32),
                pltpu.VMEM((g, D), jnp.float32),
            ],
            interpret=interpret,
            name="flash_fwd",
        )(q5, k5, v5, kvlen)
    return out[:, :, 0], lse[:, :, 0, :, 0]


def _flat_bwd_impl(q, k, v, lse, delta, do, g, real_len, causal, interpret):
    B, H, L, D = q.shape
    S = _round_up(L, g) // g
    kvlen = np.clip(real_len - np.arange(S) * g, 0, g).astype(np.int32)
    kvlen = jnp.asarray(np.broadcast_to(kvlen[None, None], (B, H, S)))
    if g * g > _BWD_LOGITS_BUDGET:
        # The forward's zero-glue single block is bwd-unsafe above ~1161
        # (see _BWD_LOGITS_BUDGET): re-segment into the padded [B,H,S,g,D]
        # layout and run the generic backward with a bwd-safe asymmetric
        # block pair. Glue (one pad + reshape per array) only ever runs in
        # training, where the backward's 2x FLOPs dominate it anyway.
        # Zeroing do/delta rows beyond real_len reproduces the flat=True
        # kernels' qrow masking: those rows' out is garbage by contract, so
        # they must contribute nothing to dk/dv (and get dq = 0) — without
        # this, gradient semantics would flip across the budget threshold
        # for callers whose cotangent touches rows in [real_len, L).
        if real_len < L:
            row_ok = (jnp.arange(L) < real_len)[None, None, :]
            do = jnp.where(row_ok[..., None], do, 0)
            delta = jnp.where(row_ok, delta, 0)
        Lp = S * g

        def seg(x):
            if Lp != L:
                x = jnp.pad(x, ((0, 0), (0, 0), (0, Lp - L)) + ((0, 0),) * (x.ndim - 3))
            return x.reshape(B, H, S, g, *x.shape[3:])

        bq, bk = bwd_blocks(g)
        dq5, dk5, dv5 = _bwd_impl(
            seg(q), seg(k), seg(v), seg(lse), seg(delta), seg(do),
            kvlen, causal, D ** -0.5, bq, bk, interpret,
        )
        undo = lambda x5: x5.reshape(B, H, Lp, D)[:, :, :L]
        return undo(dq5), undo(dk5), undo(dv5)
    # lse/delta carried at LANES width for TPU tiling
    lseL = jnp.broadcast_to(lse[:, :, None, :, None], (B, H, 1, L, LANES))
    deltaL = jnp.broadcast_to(delta[:, :, None, :, None], (B, H, 1, L, LANES))
    q_spec, lse_spec = _flat_specs(g, D)
    q5, k5, v5, do5 = q[:, :, None], k[:, :, None], v[:, :, None], do[:, :, None]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    scale = D ** -0.5

    with jax.named_scope("kernel_dq"):
        dq = pl.pallas_call(
            functools.partial(
                _dq_kernel, scale=scale, causal=causal, block_q=g, block_k=g,
                flat=True,
            ),
            grid=(B, H, S, 1, 1),
            in_specs=[q_spec, q_spec, q_spec, q_spec, lse_spec, lse_spec, smem],
            out_specs=[q_spec],
            out_shape=[jax.ShapeDtypeStruct((B, H, 1, L, D), q.dtype)],
            scratch_shapes=[pltpu.VMEM((g, D), jnp.float32)],
            interpret=interpret,
            name="flash_dq",
        )(q5, k5, v5, do5, lseL, deltaL, kvlen)[0]

    with jax.named_scope("kernel_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(
                _dkv_kernel, scale=scale, causal=causal, block_q=g, block_k=g,
                flat=True,
            ),
            grid=(B, H, S, 1, 1),
            in_specs=[q_spec, q_spec, q_spec, q_spec, lse_spec, lse_spec, smem],
            out_specs=[q_spec, q_spec],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, 1, L, D), k.dtype),
                jax.ShapeDtypeStruct((B, H, 1, L, D), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((g, D), jnp.float32),
                pltpu.VMEM((g, D), jnp.float32),
            ],
            interpret=interpret,
            name="flash_dkv",
        )(q5, k5, v5, do5, lseL, deltaL, kvlen)
    return dq[:, :, 0], dk[:, :, 0], dv[:, :, 0]


def _flat_fwd_rule(g, real_len, causal, interpret, q, k, v):
    out, lse = _flat_fwd_impl(q, k, v, g, real_len, causal, interpret)
    return (out, lse), (q, k, v, out, lse)


def _flat_bwd_rule(g, real_len, causal, interpret, res, cotangents):
    q, k, v, out, lse = res
    do, _dlse = cotangents
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    return _flat_bwd_impl(
        q, k, v, lse, delta, do, g, real_len, causal, interpret
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flat_with_lse(g, real_len, causal, interpret, q, k, v):
    return _flat_fwd_impl(q, k, v, g, real_len, causal, interpret)


_flat_with_lse.defvjp(_flat_fwd_rule, _flat_bwd_rule)

# g (= block) beyond this exceeds the per-cell VMEM budget (fp32 logits
# tile g^2 plus blocks and stats)
FLAT_MAX_SEGMENT = 1408


def flat_segment_flash(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    segment_len: int,
    real_len: Optional[int] = None,
    is_causal: bool = False,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Zero-glue segmented flash on flat [B, H, L, D] (undilated branches).

    Each ``segment_len`` chunk attends within itself; the ragged tail rides
    Pallas OOB auto-masking + the kvlen select, so the caller needs no
    pads/reshapes — the dominant XLA glue of short-segment branches.
    Requires ``segment_len % 8 == 0`` and ``segment_len <= FLAT_MAX_SEGMENT``.
    """
    B, H, L, D = q.shape
    assert segment_len % 8 == 0 and segment_len <= FLAT_MAX_SEGMENT
    rl = L if real_len is None else min(int(real_len), L)
    return _flat_with_lse(segment_len, rl, is_causal, interpret, q, k, v)


def _scale_of(scale, q):
    return q.shape[-1] ** -0.5 if scale is None else scale


def _flash_fwd_rule(kv_lens, causal, interpret, block_q, block_k, scale, q, k, v):
    out, lse = _fwd_impl(
        q, k, v, kv_lens, causal, _scale_of(scale, q), block_q, block_k, interpret
    )
    return (out, lse), (q, k, v, out, lse)


def _flash_bwd_rule(kv_lens, causal, interpret, block_q, block_k, scale, res, cotangents):
    q, k, v, out, lse = res
    do, _dlse = cotangents  # no gradient flows through the lse output
    if v.shape[-1] != q.shape[-1]:
        raise NotImplementedError(
            "pallas flash attention: the backward kernels take one width for q, k and v; "
            f"values of {v.shape[-1]} beside keys of {q.shape[-1]} are forward only "
            "(differentiate the jnp tier, flash_attention(..., use_pallas=False))")
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [B, H, S, Mq]
    group = q.shape[1] // k.shape[1]
    if group > 1:  # the backward kernels take one KV head per query head
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    dq, dk, dv = _bwd_impl(
        q, k, v, lse, delta, do, kv_lens, causal, _scale_of(scale, q),
        block_q, block_k, interpret,
    )
    if group > 1:  # a KV head's gradient: the sum over the query heads that read it
        dk, dv = (d.reshape(d.shape[0], -1, group, *d.shape[2:]).sum(2) for d in (dk, dv))
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _flash_with_lse(kv_lens, causal, interpret, block_q, block_k, scale, q, k, v):
    out, lse = _fwd_impl(
        q, k, v, kv_lens, causal, _scale_of(scale, q),
        block_q, block_k, interpret,
    )
    return out, lse


_flash_with_lse.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# NOTE: the segment-batched entry point for dilated attention is the
# branch-level custom VJP in ops/dilated_attention.py (_branch_pallas),
# which calls _fwd_impl/_bwd_impl directly with (possibly traced) kvlen
# arrays — there is deliberately no second segment-level wrapper here.


def pallas_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    is_causal: bool = False,
    kv_len=None,
    interpret: bool = False,
    scale: Optional[float] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Flash attention on [B, L, H, D] -> (out [B,L,H,D], lse [B,H,L]).

    ``k`` / ``v`` may be ``[B, L, H_kv, D]`` with ``H_kv`` dividing ``H``
    (grouped KV heads). ``v`` may be ``[B, L, H_kv, Dv]`` with a width of its
    own, which is then ``out``'s (forward only). ``scale`` multiplies the
    logits (``None``: ``D ** -0.5``, ``D`` the keys' width).

    ``kv_len``: optional static [B, H] array-like of per-(batch, head)
    valid key counts (trace-time constants — this wrapper's custom VJP
    carries them as nondiff args; for TRACED counts use the branch-level
    VJP in ops/dilated_attention.py, whose kvlen is a runtime argument).

    Kernels run on ``[B, H, S, M, D]`` blocks with a single segment — the
    head-major layout whose trailing block dims satisfy Mosaic's (8, 128)
    tiling rule — and the wrapper transposes (XLA folds the relayout into
    surrounding reshapes).
    """
    B, Lq, H, D = q.shape
    kv_lens = None
    if kv_len is not None:
        kv_lens = tuple(int(x) for x in np.asarray(kv_len).reshape(B * H))
    q5 = q.transpose(0, 2, 1, 3)[:, :, None]
    k5 = k.transpose(0, 2, 1, 3)[:, :, None]
    v5 = v.transpose(0, 2, 1, 3)[:, :, None]
    out, lse = _flash_with_lse(
        kv_lens, is_causal, interpret, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, scale, q5, k5, v5
    )
    return out[:, :, 0].transpose(0, 2, 1, 3), lse[:, :, 0]


# ---------------------------------------------------------------------------
# the overlapped forward body: causal flash and the core over selected keys
# ---------------------------------------------------------------------------


class FwdPlan(NamedTuple):
    """Which forward body a call takes, from its mask and its shapes alone."""

    body: str  # "overlap" | "serial": the kernel is <name>_overlap or <name>
    rows: int  # query rows a chain (one QK^T -> softmax -> PV)


# the overlapped call's three step tables lie in SMEM, 12 bytes a visited pair of
# the 1 MiB a v5e core has: a causal 262,144 tokens (65,536 pairs of blocks, 32,896
# at or below the diagonal) compile for it, 524,288 tokens (131,328) do not
_MAX_BLOCK_PAIRS = 1 << 16


def plan_fwd_body(mask: Optional[str], block_q: int, pairs: int) -> FwdPlan:
    """The forward body of a call whose query blocks hold ``block_q`` rows and
    whose grid holds ``pairs`` (query block, key block) pairs, visited or not,
    whatever the heads' number, grouping and widths (one head a step: at
    1,024 x 1,024 and keys of 192 a second head's blocks leave the default
    16 MiB of scoped VMEM, PERF.md §6 PR 37).

    ``mask`` "causal" (an iota compare on the blocks that straddle the
    diagonal) or "selection" (an int8 tile of chosen keys on every visited
    block): the overlapped body over the visited blocks alone, the block's
    rows in up to four chains of at least 128 (under that a chain streams
    fewer rows past a key tile than the tile took to load). ``None`` (every
    key up to the valid count: the slide encoder's undilated and head-major
    branches) and a grid past the step tables' room stay on the serial body
    and on the text it always lowered to."""
    if mask is None or pairs > _MAX_BLOCK_PAIRS:
        return FwdPlan("serial", block_q)
    return FwdPlan("overlap", max(block_q // 4, min(block_q, LANES)))


def _fwd_kernel_overlap(qi_ref, kj_ref, last_ref, q_ref, k_ref, v_ref, aux_ref, o_ref, *rest,
                        scale, mask, block_q, block_k, rows, nk):
    """The overlapped forward: grid ``(..., steps)`` over the visited (query
    block, key block) pairs, which the three prefetched tables name row by
    row (``last_ref``: 1 on a row's last pair); blocks ``(1, ..., 1, block,
    D)``, one head a step as :func:`_fwd_kernel`.

    The serial body is one MXU -> VPU -> MXU chain a step (QK^T, softmax,
    PV) and steps through the key blocks above the diagonal too, each for
    nothing. This body cuts the query block's rows into ``block_q / rows``
    independent chains and emits the next chain's QK^T before the current
    chain's softmax, so that a softmax has a product beside it that does not
    wait for it (the order of emission is what the scheduler follows:
    PERF.md §6, PR 35). The q block is scaled once a row of key blocks, into
    a scratch of its own type. Chains cut rows; the keys a chain leaves out
    are those no row of it may see, whose ``p`` was an exact zero: a row's
    operations and their order are the serial body's, to the bit.

    ``mask``: ``None`` | ``"causal"`` (``aux_ref``: the valid-key counts in
    SMEM, one per leading grid index; the iota compare only on blocks that
    straddle the diagonal, none wholly below it) | ``"selection"``
    (``aux_ref``: the ``(1, block_q, block_k)`` int8 tile of chosen keys,
    nothing above the diagonal, widened a chain at a time). With a mask and
    ``block_q == block_k`` the block on the diagonal is cut a chain: rows
    ``r0 .. r0 + rows`` of it take the keys up to column ``r0 + rows``.
    ``rest``: the ``(1, ..., block_q, LANES)`` lse block where the call writes
    one, then the running max, sum, accumulator and the scaled q block."""
    *lse_ref, m_ref, l_ref, acc_ref, qs_ref = rest
    lead = (0,) * (len(q_ref.shape) - 2)
    ids = tuple(pl.program_id(d) for d in range(len(lead)))
    t = pl.program_id(len(lead))
    i, j = qi_ref[t], kj_ref[t]
    chains = range(0, block_q, rows)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        # log2(e) folded into the scale: exp2 instead of exp in the hot loop
        qs_ref[:] = (q_ref[lead].astype(jnp.float32) * (scale * LOG2E)).astype(qs_ref.dtype)

    def logits(r0, diagonal):
        # the aligned diagonal block: no row of the chain sees a later column
        n = min(block_k, round_up(r0 + rows, LANES)) if diagonal and block_q == block_k \
            else block_k
        return jax.lax.dot_general(
            qs_ref[pl.ds(r0, rows), :], k_ref[lead + (pl.ds(0, n), slice(None))],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )  # [rows, n], in log2 units

    def consume(r0, s, v, diagonal, ragged):
        rs, n = pl.ds(r0, rows), s.shape[1]
        # selects, not additive biases, and all before the running max
        # (_fwd_kernel says why); M_FLOOR under the max keeps p at exactly 0
        # in a row that has no key yet
        if mask == "selection":
            s = jnp.where(aux_ref[0, rs, pl.ds(0, n)].astype(jnp.int32) != 0, s, NEG_INF)
        elif diagonal:
            cols = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1) + j * block_k
            at = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0) + (i * block_q + r0)
            s = jnp.where(cols > at, NEG_INF, s)
        if ragged:
            col_ok = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) + j * block_k < aux_ref[ids]
            s = jnp.where(col_ok, s, NEG_INF)
        m_prev = m_ref[rs, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v[:n], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if nk == 1:  # no online carry: no rescale of what is still zero
            l_new = jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[rs, :] = pv
        else:
            alpha = jnp.exp2(m_prev - m_new)
            l_new = l_ref[rs, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[rs, :] = acc_ref[rs, :] * alpha + pv
        # single-lane stats stores
        m_ref[rs, :1] = m_new
        l_ref[rs, :1] = l_new

    def step(diagonal=False, ragged=False):
        v = v_ref[lead]
        if ragged:  # masked value rows can be garbage, and 0 * NaN = NaN in PV
            row_ok = (
                jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0) + j * block_k
                < aux_ref[ids]
            )
            v = jnp.where(row_ok, v, 0)
        nxt = logits(chains[0], diagonal)
        for n, r0 in enumerate(chains):
            cur = nxt
            if n + 1 < len(chains):
                nxt = logits(chains[n + 1], diagonal)
            consume(r0, cur, v, diagonal, ragged)

    # which form of the step a block takes: (on the diagonal?, where) x (some
    # keys past the valid count?, where)
    if mask is None:
        places = [(False, True)]
    else:  # only a block that straddles the diagonal holds a pair to mask
        straddles = (j + 1) * block_k - 1 > i * block_q
        places = [(False, ~straddles), (True, straddles)]
    if mask == "selection":
        counts = [(False, True)]
    else:
        kv = aux_ref[ids]
        counts = [(False, (j + 1) * block_k <= kv),
                  (True, (j * block_k < kv) & ((j + 1) * block_k > kv))]
    for diagonal, here in places:
        for ragged, so in counts:
            pl.when(here & so)(functools.partial(step, diagonal=diagonal, ragged=ragged))

    @pl.when(last_ref[t] == 1)
    def _finalize():
        safe_l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[lead] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        if lse_ref:  # natural log from the base-2 stats, at LANES width
            lse_ref[0][lead] = jnp.broadcast_to(
                (m_ref[:, :1] + jnp.log2(safe_l)) * LN2, (block_q, LANES)
            )


def _visited_steps(nq, nk, block_q, block_k, masked):
    """``(qi, kj, last)`` int32 ``[steps]``: the (query block, key block) pairs a
    call computes, row by row, and a 1 on each row's last pair: every pair
    without a mask; with one, the pairs with a key at or below the diagonal."""
    qi, kj = np.divmod(np.arange(nq * nk, dtype=np.int32), np.int32(nk))
    if masked:
        keep = kj * block_k < (qi + 1) * block_q
        qi, kj = qi[keep], kj[keep]
    last = np.append(qi[1:] != qi[:-1], True).astype(np.int32)
    return jnp.asarray(qi), jnp.asarray(kj), jnp.asarray(last)


def fwd_call_overlap(q, k, v, aux, *, mask, name, scale, block_q, block_k, rows, lse,
                     interpret):
    """The overlapped forward over ``q [*lead, Mq, D]``, ``k [*lead_kv, Mk,
    D]``, ``v [*lead_kv, Mk, Dv]`` (``Mq`` / ``Mk`` whole blocks; the second
    leading axis the heads, ``k`` / ``v`` with a divisor of ``q``'s), ``aux``
    the mask's operand (:func:`_fwd_kernel_overlap`): ``out [*lead, Mq, Dv]``
    and, with ``lse``, the ``[*lead, Mq, LANES]`` float32 log-sum-exp. The call
    is ``<name>_overlap``: the name in a trace says which body ran."""
    *lead, Mq, D = q.shape
    Mk, Dv = k.shape[-2], v.shape[-1]
    group = q.shape[1] // k.shape[1]
    n, nk = len(lead), Mk // block_k
    tables = _visited_steps(Mq // block_q, nk, block_q, block_k, mask is not None)

    def spec(block, width, table, kv_head=False):
        def index(*args):  # grid indices, then the prefetched tables
            ids, t = list(args[:n]), args[n]
            if kv_head and group > 1:
                ids[1] = ids[1] // group
            return (*ids, args[n + 1 + table][t], 0)

        return pl.BlockSpec((1,) * n + (block, width), index, memory_space=pltpu.VMEM)

    if mask == "selection":  # one [Mq, Mk] selection a batch row, for every head
        aux_spec = pl.BlockSpec(
            (1, block_q, block_k), lambda *args: (args[0], args[n + 1][args[n]], args[n + 2][args[n]]),
            memory_space=pltpu.VMEM)
    else:  # the whole table of valid-key counts; indexed by program_id
        aux_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(
        _fwd_kernel_overlap, scale=scale, mask=mask, block_q=block_q, block_k=block_k,
        rows=rows, nk=nk)
    with jax.named_scope("kernel_fwd"):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(*lead, tables[0].shape[0]),
                in_specs=[spec(block_q, D, 0), spec(block_k, D, 1, True),
                          spec(block_k, Dv, 1, True), aux_spec],
                out_specs=[spec(block_q, Dv, 0)] + ([spec(block_q, LANES, 0)] if lse else []),
                scratch_shapes=[
                    pltpu.VMEM((block_q, LANES), jnp.float32),
                    pltpu.VMEM((block_q, LANES), jnp.float32),
                    pltpu.VMEM((block_q, Dv), jnp.float32),
                    pltpu.VMEM((block_q, D), q.dtype),
                ],
            ),
            out_shape=[jax.ShapeDtypeStruct((*lead, Mq, Dv), q.dtype)]
            + ([jax.ShapeDtypeStruct((*lead, Mq, LANES), jnp.float32)] if lse else []),
            interpret=interpret,
            name=f"{name}_overlap",
        )(*tables, q, k, v, aux)


def _fwd_overlap(qp, kp, vp, kvlen, Mq, causal, scale, block_q, block_k, rows, interpret):
    """:func:`_fwd_impl`'s overlapped arm, over its padded arrays. Down here and
    not inside it: a kernel's bytecode holds its call stack's line numbers, and
    with ``_fwd_impl``'s serial call left on its lines the non-causal paths
    lower to the text they always had, to the byte (PERF.md §6, PR 37)."""
    out, lse = fwd_call_overlap(
        qp, kp, vp, kvlen, mask="causal" if causal else None, name="flash_fwd", scale=scale,
        block_q=block_q, block_k=block_k, rows=rows, lse=True, interpret=interpret)
    return out[:, :, :, :Mq], lse[:, :, :, :Mq, 0]
