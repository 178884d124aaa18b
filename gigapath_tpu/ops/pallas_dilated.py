"""Fused dilated-attention branch kernel (phase-major layout), fwd + bwd.

Second-generation Pallas path for LongNet dilated attention (the reference's
``torchscale/component/dilated_attention.py`` branch loop). The first
generation ran a segment-grid flash kernel on a head-major ``[B, H, S, M, D]``
layout; profiling showed the kernel itself was fine but the XLA glue around it
(BLHD<->BHLD relayouts with a 48-wide minor dim, per-branch dilation
selects/scatters, and the mega-fusions XLA built across them) cost more than
the attention math. This kernel removes that glue by construction:

- Activations stay ``[B, L, E]`` (E = H*Dh, 128-lane aligned) end to end.
  Per branch, dense tensors are packed into a DIAGONAL-ONLY phase-major
  layout ``[B, S, r, H/r, Mp, Dh]`` holding just the (phase == band) data
  — 1/r of the dense volume — by small Pallas copy kernels (static-phase
  strided row extraction + static lane slices, measured 3.5x faster than
  the round-3 XLA 7-D transpose whose 48-minor reshape re-tiled at
  T(2,128) and materialized all r^2 (phase, band) blocks).
- A dilated branch with ratio ``r`` makes head band ``p`` (heads
  ``p*H/r .. (p+1)*H/r - 1``) attend exactly the tokens of phase ``p``
  (positions ``s*g + p + r*j``, ``dense_to_sparse`` in the reference) —
  the packed layout's index maps deliver that directly: dilation costs
  nothing inside the attention kernel.
- One head per grid cell — grid ``(B, S, r, nq, hb, nk)`` with ``[block,
  Dh]`` blocks whose lane range the head grid index picks via the packed
  array's head dim. (Unrolling a band's heads over lane slices of a single
  ``[block, E/r]`` tile was ~1.6x slower: Mosaic lane shuffles.)
- The unpack kernel writes off-band lanes of the dense result as exact
  zeros — the branch's cover pattern — so no separate cover-mask select
  exists anywhere, and the cross-branch fusion gives uncovered slots
  weight 0 through the NEG_INF lse. Gradients at those slots are genuinely
  zero, which the same zero-fill provides in the backward.
- The log-sum-exp per (token, head) — required by the cross-branch fusion
  (reference ``dilated_attention.py:119-128``) — is emitted compactly as
  ``[B, S, r, M, LANES]`` with one lane per band head.

Same numerics as ``pallas_flash.py``: fp32 online softmax (base-2 in the
forward: log2(e) folds into the q scale so the hot loop runs ``exp2``),
running max floored at ``M_FLOOR`` so masked/padded slots underflow to
exactly 0 and fully-masked rows produce out=0 / lse ~ -7e19, ragged tails
masked from an SMEM table of per-(segment, phase) valid counts with
fully-masked key blocks skipped.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gigapath_tpu.ops.pallas_flash import (  # shared kernel numerics
    LANES,
    LN2,
    LOG2E,
    M_FLOOR,
    NEG_INF,
    round_up as _round_up,
)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, kvlen_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale, causal,
                block_q, block_k):
    # grid (B, S, r, nq, hb, nk): one head-band slice per cell — blocks are
    # [block, Dh] lane slices picked by the head index in the BlockSpecs, so
    # the body never slices lanes (Mosaic lane shuffles measured ~1.6x the
    # whole kernel cost when heads were unrolled over an [block, W] tile)
    b, s, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    i, t, j = pl.program_id(3), pl.program_id(4), pl.program_id(5)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _online_step(masked: bool):
        # log2(e) folded into the scale: exp2 instead of exp in the hot loop
        qh = (q_ref[0, 0, 0, 0].astype(jnp.float32) * (scale * LOG2E)).astype(
            q_ref.dtype
        )  # [bq, Dh]
        s_ = jax.lax.dot_general(
            qh, k_ref[0, 0, 0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk], in log2 units
        if masked:
            # select, not additive bias, masking BEFORE the running max
            # (same rationale as pallas_flash._fwd_kernel: masked slots can
            # hold real activations after residual layers)
            col_ok = (
                jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1) + j * block_k
                < kvlen_ref[b, s, p]
            )
            s_ = jnp.where(col_ok, s_, NEG_INF)
        if causal:
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + j * block_k
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + i * block_q
            s_ = jnp.where(cols > rows, NEG_INF, s_)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
        pp = jnp.exp2(s_ - m_new)
        if pl.num_programs(5) == 1:
            # single k block: no online carry — skip the acc rescale and
            # write the stats once
            l_new = jnp.sum(pp, axis=-1, keepdims=True)
            acc_ref[:] = jax.lax.dot_general(
                pp.astype(v_ref.dtype), v_ref[0, 0, 0, 0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            alpha = jnp.exp2(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(pp, axis=-1, keepdims=True)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                pp.astype(v_ref.dtype), v_ref[0, 0, 0, 0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        # single-lane stats stores (a broadcast-to-128-lane store writes
        # 128x the bytes per step)
        m_ref[:, :1] = m_new
        l_ref[:, :1] = l_new

    # full key blocks skip the col-mask VPU pass entirely; only the block
    # straddling the valid-key boundary pays for masking
    @pl.when((j + 1) * block_k <= kvlen_ref[b, s, p])
    def _compute_full():
        _online_step(masked=False)

    @pl.when(
        (j * block_k < kvlen_ref[b, s, p])
        & ((j + 1) * block_k > kvlen_ref[b, s, p])
    )
    def _compute_partial():
        _online_step(masked=True)

    @pl.when(j == pl.num_programs(5) - 1)
    def _finalize():
        safe_l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0, 0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        # natural-log lse from the base-2 stats, written into lane t of the
        # shared [bq, LANES] block. The block persists in VMEM across the
        # (t, j) iterations of one i, so each head deposits its lane; lanes
        # beyond the band's heads keep the t=0 fill (sliced off outside).
        val = (m_ref[:, :1] + jnp.log2(safe_l)) * LN2  # [bq, 1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, LANES), 1)

        @pl.when(t == 0)
        def _first_head():
            lse_ref[0, 0, 0] = jnp.where(lane == 0, val, NEG_INF)

        @pl.when(t > 0)
        def _later_head():
            lse_ref[0, 0, 0] = jnp.where(lane == t, val, lse_ref[0, 0, 0])


def _fwd_kernel_pipe(q_ref, k_ref, v_ref, kvlen_ref, o_ref, lse_ref,
                     m_ref, l_ref, acc_ref, s_bufs, *, scale,
                     block_q, block_k, hb, nk):
    """Software-pipelined forward: grid (B, S, r, nq, hb*nk + 1).

    The serial kernel's body is a strict MXU -> VPU -> MXU dependence
    chain (QK^T, softmax, PV), so the VPU softmax serializes behind the
    MXU and cells measure ~1.7-1.9x over the Dh=48 shape bound
    (PERFORMANCE.md round-4 decomposition). This variant restructures the
    chain across grid steps: step n computes cell n's logits (MXU, into a
    parity scratch) and consumes cell n-1's logits (VPU softmax + PV) —
    every body opens with a big MXU matmul that is data-independent of
    the VPU chain that follows, which is the opportunity the serial body
    never gives the Mosaic scheduler. Cells are the flattened (head,
    k-block) steps of one q block; v/out index maps lag one step. The
    round-3 in-cell k-split (memory: rejected, 2.83->3.05 ms) differs
    materially: its two softmax chains shared the running (m, l) carry,
    so the "independent" matmul was bracketed by dependent VPU work.

    Non-causal only (the fused path's production use); the serial kernel
    remains for causal and as the default until the on-chip A/B decides.
    """
    b, s, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n = pl.program_id(4)
    total = hb * nk
    kv = kvlen_ref[b, s, p]
    j_p = jax.lax.rem(n, nk)
    t_c = jax.lax.div(n - 1, nk)
    j_c = jax.lax.rem(n - 1, nk)

    # ---- produce: cell n's logits into the parity scratch (MXU) ----
    @pl.when((n < total) & (j_p * block_k < kv))
    def _produce():
        qh = (q_ref[0, 0, 0, 0].astype(jnp.float32) * (scale * LOG2E)).astype(
            q_ref.dtype
        )
        s_bufs[jax.lax.rem(n, 2)] = jax.lax.dot_general(
            qh, k_ref[0, 0, 0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # ---- consume: cell n-1's logits (VPU softmax + PV matmul) ----
    @pl.when((n >= 1) & (j_c == 0))
    def _init():
        m_ref[:] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _consume(masked: bool):
        s_ = s_bufs[jax.lax.rem(n - 1, 2)]
        if masked:
            col_ok = (
                jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
                + j_c * block_k
                < kv
            )
            s_ = jnp.where(col_ok, s_, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
        pp = jnp.exp2(s_ - m_new)
        if nk == 1:
            # single k block per head: no online carry (see _fwd_kernel)
            l_new = jnp.sum(pp, axis=-1, keepdims=True)
            acc_ref[:] = jax.lax.dot_general(
                pp.astype(v_ref.dtype), v_ref[0, 0, 0, 0],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )
        else:
            alpha = jnp.exp2(m_prev - m_new)
            l_new = l_ref[:, :1] * alpha + jnp.sum(pp, axis=-1, keepdims=True)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                pp.astype(v_ref.dtype), v_ref[0, 0, 0, 0],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )
        m_ref[:, :1] = m_new
        l_ref[:, :1] = l_new

    @pl.when((n >= 1) & ((j_c + 1) * block_k <= kv))
    def _consume_full():
        _consume(masked=False)

    @pl.when((n >= 1) & (j_c * block_k < kv) & ((j_c + 1) * block_k > kv))
    def _consume_partial():
        _consume(masked=True)

    @pl.when((n >= 1) & (j_c == nk - 1))
    def _finalize():
        safe_l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0, 0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        val = (m_ref[:, :1] + jnp.log2(safe_l)) * LN2
        lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, LANES), 1)

        @pl.when(t_c == 0)
        def _first_head():
            lse_ref[0, 0, 0] = jnp.where(lane == 0, val, NEG_INF)

        @pl.when(t_c > 0)
        def _later_head():
            lse_ref[0, 0, 0] = jnp.where(lane == t_c, val, lse_ref[0, 0, 0])


def _fwd_impl_pipe(q6, k6, v6, kvlen, scale, heads, head_dim,
                   block_q, block_k, interpret):
    """Pipelined forward dispatch: same contract as _fwd_impl (non-causal).

    block_k may differ from block_q (a shallower k block deepens the
    pipeline); the k/v packed arrays are zero-padded to a block_k multiple
    — padded blocks are skipped by the kvlen guards."""
    B, S, r, hb, M, Dh = q6.shape
    Mk = k6.shape[4]
    assert hb == heads and Dh == head_dim, (hb, heads, Dh, head_dim)
    Mkp = _round_up(Mk, block_k)
    if Mkp != Mk:
        pad = ((0, 0), (0, 0), (0, 0), (0, 0), (0, Mkp - Mk), (0, 0))
        k6 = jnp.pad(k6, pad)
        v6 = jnp.pad(v6, pad)
    nq, nk = M // block_q, Mkp // block_k
    total = hb * nk

    def t_p(n):
        return jnp.minimum(n // nk, hb - 1)

    def cell_c(n):
        tc = jnp.clip((n - 1) // nk, 0, hb - 1)
        jc = jnp.clip(n - 1 - tc * nk, 0, nk - 1)
        return tc, jc

    spec_q = pl.BlockSpec(
        (1, 1, 1, 1, block_q, head_dim),
        lambda b, s, p, i, n: (b, s, p, t_p(n), i, 0),
        memory_space=pltpu.VMEM,
    )
    spec_k = pl.BlockSpec(
        (1, 1, 1, 1, block_k, head_dim),
        # j clamped: at the drain step (n == hb*nk) no produce executes but
        # the index must still name a real block
        lambda b, s, p, i, n: (
            b, s, p, t_p(n), jnp.minimum(n - t_p(n) * nk, nk - 1), 0,
        ),
        memory_space=pltpu.VMEM,
    )
    def v_map(b, s, p, i, n):
        tc, jc = cell_c(n)
        return (b, s, p, tc, jc, 0)

    spec_v = pl.BlockSpec(
        (1, 1, 1, 1, block_k, head_dim), v_map, memory_space=pltpu.VMEM,
    )

    def o_map(b, s, p, i, n):
        tc, _ = cell_c(n)
        return (b, s, p, tc, i, 0)

    spec_o = pl.BlockSpec(
        (1, 1, 1, 1, block_q, head_dim), o_map, memory_space=pltpu.VMEM,
    )
    lse_spec = pl.BlockSpec(
        (1, 1, 1, block_q, LANES), lambda b, s, p, i, n: (b, s, p, i, 0),
        memory_space=pltpu.VMEM,
    )
    kernel = functools.partial(
        _fwd_kernel_pipe, scale=scale,
        block_q=block_q, block_k=block_k, hb=hb, nk=nk,
    )
    with jax.named_scope("kernel_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid=(B, S, r, nq, total + 1),
            in_specs=[spec_q, spec_k, spec_v, pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=[spec_o, lse_spec],
            out_shape=[
                jax.ShapeDtypeStruct(q6.shape, q6.dtype),
                jax.ShapeDtypeStruct((B, S, r, M, LANES), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, head_dim), jnp.float32),
                pltpu.VMEM((2, block_q, block_k), jnp.float32),
            ],
            interpret=interpret,
            name="dilated_fwd_pipe",
        )(q6, k6, v6, kvlen)
    return out, lse


def _fwd_impl(q6, k6, v6, kvlen, causal, scale, heads, head_dim,
              block_q, block_k, interpret):
    B, S, r, hb, M, Dh = q6.shape
    Mk = k6.shape[4]
    nq, nk = M // block_q, Mk // block_k
    assert hb == heads and Dh == head_dim, (hb, heads, Dh, head_dim)

    spec_q = pl.BlockSpec(
        (1, 1, 1, 1, block_q, head_dim),
        lambda b, s, p, i, t, j: (b, s, p, t, i, 0),
        memory_space=pltpu.VMEM,
    )
    spec_k = pl.BlockSpec(
        (1, 1, 1, 1, block_k, head_dim),
        lambda b, s, p, i, t, j: (b, s, p, t, j, 0),
        memory_space=pltpu.VMEM,
    )
    lse_spec = pl.BlockSpec(
        (1, 1, 1, block_q, LANES), lambda b, s, p, i, t, j: (b, s, p, i, 0),
        memory_space=pltpu.VMEM,
    )
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k,
    )
    with jax.named_scope("kernel_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid=(B, S, r, nq, heads, nk),
            in_specs=[spec_q, spec_k, spec_k, pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=[spec_q, lse_spec],
            out_shape=[
                jax.ShapeDtypeStruct(q6.shape, q6.dtype),
                jax.ShapeDtypeStruct((B, S, r, M, LANES), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, head_dim), jnp.float32),
            ],
            interpret=interpret,
            name="dilated_fwd",
        )(q6, k6, v6, kvlen)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _lane(vec_block, t, block_q):
    """Extract lane ``t`` (a traced grid index) of a [bq, LANES] block as
    [bq, 1]: mask-and-rowsum, no dynamic lane slicing."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, LANES), 1)
    return jnp.sum(jnp.where(lane == t, vec_block, 0.0), axis=1, keepdims=True)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kvlen_ref,
               dq_ref, dq_acc, *, scale, causal, block_q, block_k):
    b, s, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    i, t, j = pl.program_id(3), pl.program_id(4), pl.program_id(5)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute(masked: bool):
        qh = q_ref[0, 0, 0, 0]
        kh = k_ref[0, 0, 0, 0]
        # base-2 recompute (exp2 = one fewer VPU pass per logit than exp);
        # the natural-log lse rescales on its [bq, 1] column, not per logit
        s_ = jax.lax.dot_general(
            qh, kh, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (scale * LOG2E)
        if masked:
            col_ok = (
                jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1) + j * block_k
                < kvlen_ref[b, s, p]
            )
            s_ = jnp.where(col_ok, s_, NEG_INF)
        pp = jnp.exp2(s_ - _lane(lse_ref[0, 0, 0], t, block_q) * LOG2E)
        if causal:
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + j * block_k
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + i * block_q
            pp = jnp.where(cols > rows, 0.0, pp)
        dp = jax.lax.dot_general(
            do_ref[0, 0, 0, 0].astype(jnp.float32),
            v_ref[0, 0, 0, 0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        ds = pp * (dp - _lane(delta_ref[0, 0, 0], t, block_q))
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(kh.dtype), kh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    # full key blocks skip the col-mask pass (see _fwd_kernel)
    @pl.when((j + 1) * block_k <= kvlen_ref[b, s, p])
    def _compute_full():
        _compute(masked=False)

    @pl.when(
        (j * block_k < kvlen_ref[b, s, p])
        & ((j + 1) * block_k > kvlen_ref[b, s, p])
    )
    def _compute_partial():
        _compute(masked=True)

    @pl.when(j == pl.num_programs(5) - 1)
    def _finalize():
        dq_ref[0, 0, 0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kvlen_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                block_q, block_k):
    b, s, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    j, t, i = pl.program_id(3), pl.program_id(4), pl.program_id(5)  # grid: (B, S, r, nk, hb, nq)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute(masked: bool):
        qh = q_ref[0, 0, 0, 0]
        kh = k_ref[0, 0, 0, 0]
        s_ = jax.lax.dot_general(
            qh, kh, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (scale * LOG2E)  # base-2 units (see _dq_kernel)
        if masked:
            col_ok = (
                jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1) + j * block_k
                < kvlen_ref[b, s, p]
            )
            s_ = jnp.where(col_ok, s_, NEG_INF)
        pp = jnp.exp2(s_ - _lane(lse_ref[0, 0, 0], t, block_q) * LOG2E)
        if causal:
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + j * block_k
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + i * block_q
            pp = jnp.where(cols > rows, 0.0, pp)
        do_h = do_ref[0, 0, 0, 0].astype(jnp.float32)
        dv_acc[:] += jax.lax.dot_general(
            pp, do_h, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do_h, v_ref[0, 0, 0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = pp * (dp - _lane(delta_ref[0, 0, 0], t, block_q))
        dk_acc[:] += jax.lax.dot_general(
            ds, qh.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when((j + 1) * block_k <= kvlen_ref[b, s, p])
    def _compute_full():
        _compute(masked=False)

    @pl.when(
        (j * block_k < kvlen_ref[b, s, p])
        & ((j + 1) * block_k > kvlen_ref[b, s, p])
    )
    def _compute_partial():
        _compute(masked=True)

    @pl.when(i == pl.num_programs(5) - 1)
    def _finalize():
        dk_ref[0, 0, 0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, 0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _dq_kernel_pipe(q_ref, k_ref, v_ref, kc_ref, do_ref, lse_ref, delta_ref,
                    kvlen_ref, dq_ref, dq_acc, s_bufs, dp_bufs, *, scale,
                    block_q, block_k, hb, nk):
    """Software-pipelined dQ: grid (B, S, r, nq, hb*nk + 1).

    Step n computes BOTH of cell n's matmuls that feed the VPU chain —
    s_n = (q*scale)@k_n^T and dp_n = do@v_n^T — into parity scratches,
    then consumes cell n-1: p = exp2(s - lse), ds = p*(dp - delta) (VPU)
    and dq_acc += ds@k (MXU, via the LAGGED second k input kc_ref). Same
    restructuring rationale as _fwd_kernel_pipe. Non-causal only."""
    b, s, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n = pl.program_id(4)
    total = hb * nk
    kv = kvlen_ref[b, s, p]
    j_p = jax.lax.rem(n, nk)
    t_c = jax.lax.div(n - 1, nk)
    j_c = jax.lax.rem(n - 1, nk)

    @pl.when((n < total) & (j_p * block_k < kv))
    def _produce():
        qh = (q_ref[0, 0, 0, 0].astype(jnp.float32) * (scale * LOG2E)).astype(
            q_ref.dtype
        )
        par = jax.lax.rem(n, 2)
        s_bufs[par] = jax.lax.dot_general(
            qh, k_ref[0, 0, 0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp_bufs[par] = jax.lax.dot_general(
            do_ref[0, 0, 0, 0].astype(jnp.float32),
            v_ref[0, 0, 0, 0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )

    @pl.when((n >= 1) & (j_c == 0))
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _consume(masked: bool):
        par = jax.lax.rem(n - 1, 2)
        s_ = s_bufs[par]
        if masked:
            col_ok = (
                jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
                + j_c * block_k
                < kv
            )
            s_ = jnp.where(col_ok, s_, NEG_INF)
        pp = jnp.exp2(s_ - _lane(lse_ref[0, 0, 0], t_c, block_q) * LOG2E)
        ds = pp * (dp_bufs[par] - _lane(delta_ref[0, 0, 0], t_c, block_q))
        kh = kc_ref[0, 0, 0, 0]
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(kh.dtype), kh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when((n >= 1) & ((j_c + 1) * block_k <= kv))
    def _consume_full():
        _consume(masked=False)

    @pl.when((n >= 1) & (j_c * block_k < kv) & ((j_c + 1) * block_k > kv))
    def _consume_partial():
        _consume(masked=True)

    @pl.when((n >= 1) & (j_c == nk - 1))
    def _finalize():
        dq_ref[0, 0, 0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel_pipe(q_ref, k_ref, v_ref, qc_ref, doc_ref, do_ref, lse_ref,
                     delta_ref, kvlen_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                     s_bufs, dp_bufs, *, scale, block_q, block_k, hb, nq):
    """Software-pipelined dK/dV: grid (B, S, r, nk, hb*nq + 1).

    Per k block j, the flattened (head, q-block) steps pipeline: step n
    produces s_n = (q*scale)@k^T and dp_n = do@v^T (MXU), consumes cell
    n-1's p/ds (VPU) + the dv/dk accumulation matmuls against the LAGGED
    q/do inputs (qc_ref/doc_ref). lse/delta index maps lag too. Non-causal
    only."""
    b, s, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    j = pl.program_id(3)
    n = pl.program_id(4)
    total = hb * nq
    kv = kvlen_ref[b, s, p]
    t_c = jax.lax.div(n - 1, nq)
    i_c = jax.lax.rem(n - 1, nq)

    @pl.when((n < total) & (j * block_k < kv))
    def _produce():
        qh = (q_ref[0, 0, 0, 0].astype(jnp.float32) * (scale * LOG2E)).astype(
            q_ref.dtype
        )
        par = jax.lax.rem(n, 2)
        s_bufs[par] = jax.lax.dot_general(
            qh, k_ref[0, 0, 0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp_bufs[par] = jax.lax.dot_general(
            do_ref[0, 0, 0, 0].astype(jnp.float32),
            v_ref[0, 0, 0, 0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )

    @pl.when((n >= 1) & (i_c == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _consume(masked: bool):
        par = jax.lax.rem(n - 1, 2)
        s_ = s_bufs[par]
        if masked:
            col_ok = (
                jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
                + j * block_k
                < kv
            )
            s_ = jnp.where(col_ok, s_, NEG_INF)
        pp = jnp.exp2(s_ - _lane(lse_ref[0, 0, 0], t_c, block_q) * LOG2E)
        do_h = doc_ref[0, 0, 0, 0].astype(jnp.float32)
        dv_acc[:] += jax.lax.dot_general(
            pp, do_h, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = pp * (dp_bufs[par] - _lane(delta_ref[0, 0, 0], t_c, block_q))
        dk_acc[:] += jax.lax.dot_general(
            ds, qc_ref[0, 0, 0, 0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when((n >= 1) & ((j + 1) * block_k <= kv))
    def _consume_full():
        _consume(masked=False)

    @pl.when((n >= 1) & (j * block_k < kv) & ((j + 1) * block_k > kv))
    def _consume_partial():
        _consume(masked=True)

    @pl.when((n >= 1) & (i_c == nq - 1))
    def _finalize():
        dk_ref[0, 0, 0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, 0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _pipe_bwd_block_k(block_q: int, override: Optional[int]) -> int:
    """k block for the pipelined backward: the parity scratches double the
    live fp32 logits tiles (~6 at peak: s2, dp2, pp, ds), so cap
    bq*bk <= 512k elements (~12 MB across 6 tiles). ``override`` comes
    from the PipelineFlags snapshot (GIGAPATH_PIPE_BWD_BLOCK_K), read
    once at dispatch — never from the environment here, where the value
    would be baked into the jit cache invisibly (gigalint GL001)."""
    if override:
        return max(LANES, min(override, block_q))
    bk = 512
    while bk > LANES and block_q * bk > 512 * 1024:
        bk //= 2
    return min(bk, block_q)


def _bwd_impl_pipe(q6, k6, v6, do6, lse, delta, kvlen, scale,
                   heads, head_dim, block_q, block_k, interpret):
    """Pipelined backward dispatch: same contract as _bwd_impl (non-causal).
    k/v padded to a block_k multiple; padded blocks skipped by kvlen."""
    B, S, r, hb, M, Dh = q6.shape
    Mk = k6.shape[4]
    Mkp = _round_up(Mk, block_k)
    if Mkp != Mk:
        pad = ((0, 0), (0, 0), (0, 0), (0, 0), (0, Mkp - Mk), (0, 0))
        k6p = jnp.pad(k6, pad)
        v6p = jnp.pad(v6, pad)
    else:
        k6p, v6p = k6, v6
    nq, nk = M // block_q, Mkp // block_k
    total_q = hb * nk

    def t_p(n):
        return jnp.minimum(n // nk, hb - 1)

    def cell_c(n, inner):
        tc = jnp.clip((n - 1) // inner, 0, hb - 1)
        jc = jnp.clip(n - 1 - tc * inner, 0, inner - 1)
        return tc, jc

    # ---- dQ: grid (B, S, r, nq, hb*nk + 1) ----
    spec_q = pl.BlockSpec(
        (1, 1, 1, 1, block_q, head_dim),
        lambda b, s, p, i, n: (b, s, p, t_p(n), i, 0),
        memory_space=pltpu.VMEM,
    )
    spec_k_prod = pl.BlockSpec(
        (1, 1, 1, 1, block_k, head_dim),
        lambda b, s, p, i, n: (
            b, s, p, t_p(n), jnp.minimum(n - t_p(n) * nk, nk - 1), 0,
        ),
        memory_space=pltpu.VMEM,
    )

    def kc_map(b, s, p, i, n):
        tc, jc = cell_c(n, nk)
        return (b, s, p, tc, jc, 0)

    spec_k_cons = pl.BlockSpec(
        (1, 1, 1, 1, block_k, head_dim), kc_map, memory_space=pltpu.VMEM,
    )

    def dq_map(b, s, p, i, n):
        tc, _ = cell_c(n, nk)
        return (b, s, p, tc, i, 0)

    spec_dq = pl.BlockSpec(
        (1, 1, 1, 1, block_q, head_dim), dq_map, memory_space=pltpu.VMEM,
    )
    vec_spec = pl.BlockSpec(
        (1, 1, 1, block_q, LANES), lambda b, s, p, i, n: (b, s, p, i, 0),
        memory_space=pltpu.VMEM,
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    with jax.named_scope("kernel_dq"):
        dq = pl.pallas_call(
            functools.partial(
                _dq_kernel_pipe, scale=scale,
                block_q=block_q, block_k=block_k, hb=hb, nk=nk,
            ),
            grid=(B, S, r, nq, total_q + 1),
            in_specs=[spec_q, spec_k_prod, spec_k_prod, spec_k_cons, spec_q,
                      vec_spec, vec_spec, smem],
            out_specs=[spec_dq],
            out_shape=[jax.ShapeDtypeStruct(q6.shape, q6.dtype)],
            scratch_shapes=[
                pltpu.VMEM((block_q, head_dim), jnp.float32),
                pltpu.VMEM((2, block_q, block_k), jnp.float32),
                pltpu.VMEM((2, block_q, block_k), jnp.float32),
            ],
            interpret=interpret,
            name="dilated_dq_pipe",
        )(q6, k6p, v6p, k6p, do6, lse, delta, kvlen)[0]

    # ---- dK/dV: grid (B, S, r, nk, hb*nq + 1) ----
    total_kv = hb * nq

    def t_p_kv(n):
        return jnp.minimum(n // nq, hb - 1)

    spec_q_prod = pl.BlockSpec(
        (1, 1, 1, 1, block_q, head_dim),
        lambda b, s, p, j, n: (
            b, s, p, t_p_kv(n), jnp.minimum(n - t_p_kv(n) * nq, nq - 1), 0,
        ),
        memory_space=pltpu.VMEM,
    )

    def qc_map(b, s, p, j, n):
        tc, ic = cell_c(n, nq)
        return (b, s, p, tc, ic, 0)

    spec_q_cons = pl.BlockSpec(
        (1, 1, 1, 1, block_q, head_dim), qc_map, memory_space=pltpu.VMEM,
    )
    spec_k_kv = pl.BlockSpec(
        (1, 1, 1, 1, block_k, head_dim),
        lambda b, s, p, j, n: (b, s, p, t_p_kv(n), j, 0),
        memory_space=pltpu.VMEM,
    )

    def dk_map(b, s, p, j, n):
        tc, _ = cell_c(n, nq)
        return (b, s, p, tc, j, 0)

    spec_dk = pl.BlockSpec(
        (1, 1, 1, 1, block_k, head_dim), dk_map, memory_space=pltpu.VMEM,
    )

    def vec_c_map(b, s, p, j, n):
        _, ic = cell_c(n, nq)
        return (b, s, p, ic, 0)

    vec_spec_c = pl.BlockSpec(
        (1, 1, 1, block_q, LANES), vec_c_map, memory_space=pltpu.VMEM,
    )
    with jax.named_scope("kernel_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(
                _dkv_kernel_pipe, scale=scale,
                block_q=block_q, block_k=block_k, hb=hb, nq=nq,
            ),
            grid=(B, S, r, nk, total_kv + 1),
            in_specs=[spec_q_prod, spec_k_kv, spec_k_kv, spec_q_cons, spec_q_cons,
                      spec_q_prod, vec_spec_c, vec_spec_c, smem],
            out_specs=[spec_dk, spec_dk],
            out_shape=[
                jax.ShapeDtypeStruct(k6p.shape, k6.dtype),
                jax.ShapeDtypeStruct(v6p.shape, v6.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, head_dim), jnp.float32),
                pltpu.VMEM((block_k, head_dim), jnp.float32),
                pltpu.VMEM((2, block_q, block_k), jnp.float32),
                pltpu.VMEM((2, block_q, block_k), jnp.float32),
            ],
            interpret=interpret,
            name="dilated_dkv_pipe",
        )(q6, k6p, v6p, q6, do6, do6, lse, delta, kvlen)
    if Mkp != Mk:
        dk = dk[:, :, :, :, :Mk]
        dv = dv[:, :, :, :, :Mk]
    return dq, dk, dv


class PipelineFlags(NamedTuple):
    """One trace-stable snapshot of the attention kernels' dispatch switches.

    Read ONCE per public call (host side, at dispatch: an explicit
    ``flags=`` argument, else :func:`snapshot_flags`) and threaded through
    the custom_vjp as a static argument, so the forward and backward of one
    call can never observe different flag values, and no traced code reads
    the environment (gigalint GL001). Toggling a flag still only affects
    future traces — the jit cache keys on the traced program, not the
    environment; see the README flag table for the
    fresh-function-identity workaround.
    """

    pipelined_fwd: bool = False
    pipelined_bwd: bool = False
    pipe_block_k: Optional[int] = None  # None: VMEM-budget auto choice
    pipe_bwd_block_k: Optional[int] = None
    stream_fusion: bool = False
    # online dense branch fold (GIGAPATH_STREAMING_FUSION — the
    # memory-motivated near-namesake of stream_fusion above): fold
    # dilated branches into running (acc, m, l) instead of stacking all
    # branch outputs
    streaming_fusion: bool = False
    # ring-scheduled K/V exchange for gathered sequence-parallel branches
    # (ops/dilated_attention.py): per-shard memory O(local chunk) instead
    # of O(full segment), ppermute overlapped with partial attention
    ring_attn: bool = False
    # Pallas tier for the streaming-fold pair partial
    # (ops/pallas_streaming.py): in-kernel iota masks instead of the jnp
    # oracle's dense [H, cq, ck] mask tensors. False keeps the fold
    # byte-identical to the jnp path (the parity oracle)
    fold_pallas: bool = False
    # global fold block overrides (None: DEFAULT_FOLD_BLOCK auto choice)
    fold_block_q: Optional[int] = None
    fold_block_k: Optional[int] = None


# field -> environment twin, in field order
FLAG_ENV = {
    "pipelined_fwd": "GIGAPATH_PIPELINED_ATTN",
    "pipelined_bwd": "GIGAPATH_PIPELINED_BWD",
    "pipe_block_k": "GIGAPATH_PIPE_BLOCK_K",
    "pipe_bwd_block_k": "GIGAPATH_PIPE_BWD_BLOCK_K",
    "stream_fusion": "GIGAPATH_STREAM_FUSION",
    "streaming_fusion": "GIGAPATH_STREAMING_FUSION",
    "ring_attn": "GIGAPATH_RING_ATTN",
    "fold_pallas": "GIGAPATH_FOLD_PALLAS",
    "fold_block_q": "GIGAPATH_FOLD_BLOCK_Q",
    "fold_block_k": "GIGAPATH_FOLD_BLOCK_K",
}


def snapshot_flags() -> PipelineFlags:
    """Read GIGAPATH_PIPELINED_ATTN/_BWD, GIGAPATH_PIPE(_BWD)_BLOCK_K,
    GIGAPATH_STREAM_FUSION, GIGAPATH_STREAMING_FUSION, GIGAPATH_RING_ATTN,
    GIGAPATH_FOLD_PALLAS and GIGAPATH_FOLD_BLOCK_Q/_K from the
    environment, once."""
    import os

    from gigapath_tpu.ops.common import env_flag

    def _int(name: str) -> Optional[int]:
        # unset, empty and 0 all mean the auto choice, as every reader
        # of these fields takes them
        raw = os.environ.get(name, "").strip()
        return (int(raw) or None) if raw else None

    return PipelineFlags(
        pipelined_fwd=env_flag("GIGAPATH_PIPELINED_ATTN"),
        pipelined_bwd=env_flag("GIGAPATH_PIPELINED_BWD"),
        pipe_block_k=_int("GIGAPATH_PIPE_BLOCK_K"),
        pipe_bwd_block_k=_int("GIGAPATH_PIPE_BWD_BLOCK_K"),
        stream_fusion=env_flag("GIGAPATH_STREAM_FUSION"),
        streaming_fusion=env_flag("GIGAPATH_STREAMING_FUSION"),
        ring_attn=env_flag("GIGAPATH_RING_ATTN"),
        fold_pallas=env_flag("GIGAPATH_FOLD_PALLAS"),
        fold_block_q=_int("GIGAPATH_FOLD_BLOCK_Q"),
        fold_block_k=_int("GIGAPATH_FOLD_BLOCK_K"),
    )


def _bwd_impl(q6, k6, v6, do6, lse, delta, kvlen, causal, scale,
              heads, head_dim, block_q, block_k, interpret):
    B, S, r, hb, M, Dh = q6.shape
    Mk = k6.shape[4]
    nq, nk = M // block_q, Mk // block_k

    spec_q = pl.BlockSpec(
        (1, 1, 1, 1, block_q, head_dim),
        lambda b, s, p, i, t, j: (b, s, p, t, i, 0),
        memory_space=pltpu.VMEM,
    )
    spec_k = pl.BlockSpec(
        (1, 1, 1, 1, block_k, head_dim),
        lambda b, s, p, i, t, j: (b, s, p, t, j, 0),
        memory_space=pltpu.VMEM,
    )
    vec_spec = pl.BlockSpec(
        (1, 1, 1, block_q, LANES), lambda b, s, p, i, t, j: (b, s, p, i, 0),
        memory_space=pltpu.VMEM,
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    with jax.named_scope("kernel_dq"):
        dq = pl.pallas_call(
            functools.partial(
                _dq_kernel, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k,
            ),
            grid=(B, S, r, nq, heads, nk),
            in_specs=[spec_q, spec_k, spec_k, spec_q, vec_spec, vec_spec, smem],
            out_specs=[spec_q],
            out_shape=[jax.ShapeDtypeStruct(q6.shape, q6.dtype)],
            scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
            interpret=interpret,
            name="dilated_dq",
        )(q6, k6, v6, do6, lse, delta, kvlen)[0]

    # grid (B, S, r, nk, hb, nq): index maps see (b, s, p, j, t, i)
    spec_q_kv = pl.BlockSpec(
        (1, 1, 1, 1, block_q, head_dim),
        lambda b, s, p, j, t, i: (b, s, p, t, i, 0),
        memory_space=pltpu.VMEM,
    )
    spec_k_kv = pl.BlockSpec(
        (1, 1, 1, 1, block_k, head_dim),
        lambda b, s, p, j, t, i: (b, s, p, t, j, 0),
        memory_space=pltpu.VMEM,
    )
    vec_spec_kv = pl.BlockSpec(
        (1, 1, 1, block_q, LANES), lambda b, s, p, j, t, i: (b, s, p, i, 0),
        memory_space=pltpu.VMEM,
    )
    with jax.named_scope("kernel_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(
                _dkv_kernel, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k,
            ),
            grid=(B, S, r, nk, heads, nq),
            in_specs=[spec_q_kv, spec_k_kv, spec_k_kv, spec_q_kv,
                      vec_spec_kv, vec_spec_kv, smem],
            out_specs=[spec_k_kv, spec_k_kv],
            out_shape=[
                jax.ShapeDtypeStruct(k6.shape, k6.dtype),
                jax.ShapeDtypeStruct(v6.shape, v6.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, head_dim), jnp.float32),
                pltpu.VMEM((block_k, head_dim), jnp.float32),
            ],
            interpret=interpret,
            name="dilated_dkv",
        )(q6, k6, v6, do6, lse, delta, kvlen)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# phase-major packing + the differentiable branch op
# ---------------------------------------------------------------------------


def _branch_geometry(L: int, E: int, sl: int, r: int) -> Tuple[int, int, int, int, int, int]:
    """(g, S, gp, m, Mp, block): segment length/count, r-padded segment,
    sparse length, block-padded sparse length, block size.

    Block choice: one block when the whole sparse segment fits the VMEM
    budget; otherwise the candidate (multiple of 128) minimizing q-row
    padding — padded key blocks are skipped by the kernel, padded q rows are
    not. The cap keeps q/k/v/out double-buffered blocks plus the fp32 logits
    tile inside VMEM (W = E/r lanes per block row)."""
    g = min(sl, L)
    S = _round_up(L, g) // g
    gp = _round_up(g, r)
    m = gp // r
    # per-cell VMEM is dominated by the [bq, bk] fp32 logits/probs tiles
    # (blocks themselves are [b, Dh], tiny): 1024^2 blocks fit and are
    # ~2x faster than 512 on the LongNet shapes (fewer K/V restreams);
    # candidates below trade q-row padding against cell count
    cap = 1024
    single = _round_up(m, LANES)
    if single <= cap:
        block = single
    else:
        block = min(
            (512, 640, 768, 896, 1024),
            key=lambda b: (_round_up(m, b), -b),
        )
    Mp = _round_up(m, block)
    return g, S, gp, m, Mp, block


_COPY_WINDOW_BYTES = 3 * 2 ** 20


def _pack_bt(Mp: int, r: int, E: int, itemsize: int,
             budget: int = _COPY_WINDOW_BYTES) -> int:
    """Row-block size for the pack/unpack copy kernels: each cell holds a
    dense [bt*r, E] window in VMEM, double-buffered, beside its re-tiled
    [bt, r*E] copy and the packed blocks, so bt*r*E*itemsize must stay well
    under the budget (itemsize matters: the public op is dtype-generic, and
    fp32 doubles the footprint). Mp is always a multiple of 128 (block sizes
    are), so every candidate divides it.

    bt is a SUBLANE block dim (lanes are E, always full-width), so it may
    legally shrink below 128 down to the 8-row fp32 tile — which is what
    enforces the budget when r*E*itemsize is large: at the flagship r=16
    branch in fp32, bt=128 would be ~6.3 MB in + 6.3 MB out (~25 MB
    double-buffered, over the ~16 MB scoped-VMEM ceiling — the the round-3 driver run
    OOM class); bt=64 lands back inside the budget. 3 MiB and not 4: heads
    of 64 at r=8 (E=1024, bt=256) are exactly 4 MiB a window and compile to
    19.1 MB of scoped VMEM against the 16 MB there is (PR 29, compiled for
    a described v5e); every 3 MiB window of the flagship and of the heads
    of 96 fits. A lane split is NOT available here: the per-phase window is
    W = E/r lanes (48 at the flagship), and Mosaic only allows lane blocks
    that are 128-multiples or the whole dim."""
    bt = 512
    while bt > 8 and bt * r * E * itemsize > budget:
        bt //= 2
    while Mp % bt:
        bt //= 2
    if bt * r * E * itemsize > 8 * 2 ** 20:
        raise ValueError(
            f"pack/unpack row block [bt={bt}, r*E={r * E}] at itemsize "
            f"{itemsize} exceeds the VMEM copy budget even at the minimum "
            f"block height; use a narrower model width, smaller dilation "
            f"ratio, or a 2-byte dtype"
        )
    return bt


def _band_lanes(r, hb, Dh, E):
    """(phase, head, lane_start) of the diagonal band layout in a
    [bt, r*E] dense row-block: token ``j*r + p`` of a segment is row j,
    lanes ``[p*E, (p+1)*E)``, and band p's heads sit at sublanes
    ``p*W + t*Dh`` within the token (W = hb*Dh) — so phase/head extraction
    is pure static LANE slicing. The ONE place the layout math lives:
    both pack kernels extract with it and both unpack kernels rebuild
    with it (the padded-view and direct variants must never diverge)."""
    W = hb * Dh
    for p in range(r):
        base = p * E + p * W
        for t in range(hb):
            yield p, t, base + t * Dh


def _extract_bands(x, o_ref, r, hb, Dh):
    """[bt, r*E] dense row-block -> packed [.., p, t] blocks of o_ref.
    (The earlier per-phase variant extracted rows ``phase::r``, a stride-r
    sublane gather that measured ~5x over the bandwidth floor at r=2, and
    re-read the dense block once per phase on top.)"""
    E = x.shape[-1] // r
    for p, t, lane in _band_lanes(r, hb, Dh, E):
        o_ref[0, 0, p, t] = x[:, lane : lane + Dh]


def _assemble_bands(x_ref, r, hb, Dh, E, bt, dtype):
    """Packed [.., p, t] blocks -> one dense [bt, r*E] row-block, band
    lanes filled, every other lane exactly 0 (the branch's cover pattern,
    so no separate cover-mask select is needed)."""
    pieces = []
    cursor = 0
    for p, t, lane in _band_lanes(r, hb, Dh, E):
        if lane > cursor:
            pieces.append(jnp.zeros((bt, lane - cursor), dtype))
        pieces.append(x_ref[0, 0, p, t].astype(dtype))
        cursor = lane + Dh
    if r * E > cursor:
        pieces.append(jnp.zeros((bt, r * E - cursor), dtype))
    return jnp.concatenate(pieces, axis=-1)


def _sublane_tile(itemsize: int) -> int:
    """Rows of one HBM / VMEM tile: 8 of a 32-bit type, 16 of bfloat16."""
    return 32 // itemsize


def _copy_plan(L: int, g: int, S: int, r: int, Mp: int, E: int,
               itemsize: int) -> Tuple[str, int]:
    """(windows, bt): where the copy kernels' dense windows
    ``[s*g + i*bt*r, +bt*r)`` of the ``[B, L, E]`` activation sit, and the
    row block that goes with it, decided from shapes alone:

    ``"grid"``: on the block grid of ``bt*r`` rows (one segment, or a segment
    that is a whole number of blocks): plain blocked windows.
    ``"element"``: segment starts off that grid but on the sublane tile
    (the flagship's r=2 branch, g = 5,792 = 2^5 * 181): the kernel copies
    each window in or out by hand. Pack holds such a window twice over, so
    the row block is sized for half the budget.
    ``"padded"``: a segment start or the sequence end off the sublane tile
    with more than one segment (or a sequence shorter than one window): no
    window of the dense array can be moved legally, so the zero-padded
    ``[B, S, Mp, r*E]`` view is built by XLA.
    """
    bt = _pack_bt(Mp, r, E, itemsize)
    if S == 1 or g % (bt * r) == 0:
        return "grid", bt
    tile = _sublane_tile(itemsize)
    half = _pack_bt(Mp, r, E, itemsize, budget=_COPY_WINDOW_BYTES // 2)
    if g % tile or L % tile or half * r > L:
        return "padded", bt
    return ("grid" if g % (half * r) == 0 else "element"), half


def _valid_rows(g: int, L: int, s, i, rows: int):
    """How many of the ``rows`` dense rows of window (s, i) are tokens of
    segment s (inside the segment AND inside the sequence); may be <= 0.
    (``lax`` primitives on int32 scalars here and below, not ``jnp.minimum``
    or the ``*`` / ``-`` / ``//`` / ``<`` operators: inside a kernel each of
    those is a ``jit`` of its own to trace and lower, and the program
    holds 240 of these kernels.)"""
    in_segment = lax.min(np.int32(g), lax.sub(np.int32(L), lax.mul(s, np.int32(g))))
    return lax.sub(in_segment, lax.mul(i, np.int32(rows)))


def _emit_packed(x, valid, o_ref, *, r, hb, Dh, bt):
    """A dense [bt*r, E] window, of which the first ``valid`` rows are
    tokens of this segment -> all phases' [r, hb, bt, Dh] packed blocks:
    the rows-of-r-tokens -> r*E-lanes re-tile happens in VMEM. Rows past
    the segment's end (the NEXT segment's real tokens when Mp*r > g) or
    past L (whatever stands there, possibly non-finite) are zeroed by
    LOGICAL row index first: packed K/V MUST be exact zeros at padded
    slots or p=0 x NaN poisons the PV matmul. One straight-line body for
    full, partial and empty windows alike: a second copy of the band
    extraction under ``pl.when`` doubled what every one of the program's
    180 pack calls costs to trace and lower."""
    row = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    real = lax.lt(row, lax.broadcast_in_dim(valid, x.shape, ()))
    x = lax.select(real, x, lax.full_like(x, 0))
    _extract_bands(x.reshape(bt, r * x.shape[-1]), o_ref, r, hb, Dh)


def _pack_kernel(x_ref, o_ref, *, g, L, **kw):
    """Windows on the block grid: the dense [bt*r, E] block is read STRAIGHT
    off the [B, L, E] activation by the pipeline."""
    valid = _valid_rows(
        g, L, pl.program_id(1), pl.program_id(2), x_ref.shape[0]
    )
    _emit_packed(x_ref[...], valid, o_ref, **kw)


def _pack_kernel_element(x_hbm, o_ref, buf, sem, *, g, L, **kw):
    """Windows off the block grid: each is copied in by hand, the next
    step's while this one is re-tiled. A copy's height is static, so a
    window that reaches past L is read from ``L - rows`` instead, and
    lands that much higher in its ``[2*rows, E]`` slot: a window's rows
    always start at row ``rows`` of the slot, whatever stood before them
    (one copy per window whatever its valid height: a ``pl.when`` per
    height and site was a third of what these calls cost to lower)."""
    rows = buf.shape[1] // 2
    tile = _sublane_tile(buf.dtype.itemsize)
    one, zero = np.int32(1), np.int32(0)
    nB, nS, nb = (pl.num_programs(d) for d in range(3))
    step = lax.add(
        lax.mul(lax.add(lax.mul(pl.program_id(0), nS), pl.program_id(1)), nb),
        pl.program_id(2),
    )

    def copy_of(n):
        """(copy of grid step n's window into its slot, its valid rows)."""
        rest, i = lax.div(n, nb), lax.rem(n, nb)
        b, s = lax.div(rest, nS), lax.rem(rest, nS)
        start = lax.add(lax.mul(s, np.int32(g)), lax.mul(i, np.int32(rows)))
        source = lax.min(start, np.int32(L - rows))
        landing = lax.sub(np.int32(rows), lax.sub(start, source))
        slot = lax.rem(n, np.int32(2))
        copy = pltpu.make_async_copy(
            x_hbm.at[b, pl.ds(pl.multiple_of(source, tile), rows)],
            buf.at[slot, pl.ds(pl.multiple_of(landing, tile), rows)],
            sem.at[slot],
        )
        return copy, _valid_rows(g, L, s, i, rows)

    here, valid = copy_of(step)
    after, after_valid = copy_of(lax.add(step, one))

    @pl.when(lax.eq(step, zero))
    def _first():
        here.start()  # window (0, 0, 0) always holds tokens

    more = lax.lt(lax.add(step, one), lax.mul(lax.mul(nB, nS), nb))

    @pl.when(lax.bitwise_and(more, lax.gt(after_valid, zero)))
    def _prefetch():
        after.start()

    @pl.when(lax.gt(valid, zero))
    def _arrived():
        here.wait()

    _emit_packed(buf[lax.rem(step, np.int32(2)), rows:], valid, o_ref, **kw)


def _pack_kernel_padded(x_ref, o_ref, *, r, hb, Dh, bt):
    """One row-block [bt, r*E] of the zero-padded [B, S, Mp, r*E] view ->
    ALL phases' [r, hb, bt, Dh] packed blocks (see _band_lanes)."""
    _extract_bands(x_ref[0, 0], o_ref, r, hb, Dh)


def _unpack_rows(x_ref, r, hb, Dh, E, bt, dtype):
    """Packed [r, hb, bt, Dh] blocks -> the dense [bt*r, E] rows they
    cover, off-band lanes exact 0."""
    return _assemble_bands(x_ref, r, hb, Dh, E, bt, dtype).reshape(bt * r, E)


def _unpack_kernel(x_ref, o_ref, *, r, hb, Dh, bt):
    """Packed blocks -> a dense [bt*r, E] window written straight into the
    [B, L, E] output (windows on the block grid). The window that
    straddles L is truncated by its copy; windows that would START past
    L are not in the grid (clamping would slide them backward over valid
    rows)."""
    o_ref[...] = _unpack_rows(x_ref, r, hb, Dh, o_ref.shape[-1], bt, o_ref.dtype)


def _unpack_kernel_element(x_ref, o_hbm, buf, sem, *, r, hb, Dh, bt, g, L,
                           heights):
    """As :func:`_unpack_kernel` for windows off the block grid: the last
    window of a segment reaches into the next segment's rows (and the last
    segment's past L), so each window is assembled in VMEM and exactly its
    valid rows are copied out by hand: one of the static ``heights`` (a
    copy's height cannot be traced), none for an empty window. The copy is
    waited for in its own step; the packed blocks of the next step arrive
    meanwhile all the same."""
    rows = bt * r
    b, s, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    valid = lax.min(_valid_rows(g, L, s, i, rows), np.int32(rows))
    start = pl.multiple_of(
        lax.add(lax.mul(s, np.int32(g)), lax.mul(i, np.int32(rows))),
        _sublane_tile(buf.dtype.itemsize),
    )

    @pl.when(lax.gt(valid, np.int32(0)))
    def _write():
        buf[...] = _unpack_rows(x_ref, r, hb, Dh, buf.shape[-1], bt, buf.dtype)
        for h in heights:
            @pl.when(lax.eq(valid, np.int32(h)))
            def _copy_out(h=h):
                copy = pltpu.make_async_copy(
                    buf.at[pl.ds(0, h)],
                    o_hbm.at[b, pl.ds(start, h)], sem.at[0],
                )
                copy.start()
                copy.wait()


def _unpack_kernel_padded(x_ref, o_ref, *, r, hb, Dh, bt):
    """All phases' [r, hb, bt, Dh] packed blocks -> one row-block
    [bt, r*E] of the padded view."""
    E = o_ref.shape[-1] // r
    o_ref[0, 0] = _assemble_bands(x_ref, r, hb, Dh, E, bt, o_ref.dtype)


def _pad_segments(x: jnp.ndarray, g: int, S: int, gp2: int) -> jnp.ndarray:
    """[B, L, E] -> [B, S, gp2, E] (zero pads on the clean E-lane layout)."""
    B, L, E = x.shape
    if S * g != L:
        x = jnp.pad(x, ((0, 0), (0, S * g - L), (0, 0)))
    x = x.reshape(B, S, g, E)
    if gp2 != g:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, gp2 - g), (0, 0)))
    return x


@jax.named_scope("pack")
def _pack_phases(x: jnp.ndarray, g: int, S: int, r: int, Mp: int, H: int,
                 interpret: bool) -> jnp.ndarray:
    """[B, L, E] -> packed [B, S, r, hb, Mp, Dh] holding ONLY the diagonal
    (phase == band) data — 1/r of the dense volume. The old 7-D layout
    materialized all r^2 (phase, band) blocks and transposed the full
    tensor; the kernels only ever read the diagonal. One pallas_call,
    reading every dense byte at most once; the dense array is its operand
    as it stands (no XLA pad or relayout) unless :func:`_copy_plan`
    says ``"padded"``."""
    B, L, E = x.shape
    hb = H // r
    Dh = E // H
    windows, bt = _copy_plan(L, g, S, r, Mp, E, x.dtype.itemsize)
    rows = bt * r
    out_spec = pl.BlockSpec(
        (1, 1, r, hb, bt, Dh), lambda b, s, i: (b, s, 0, 0, i, 0),
        memory_space=pltpu.VMEM,
    )
    kw = dict(r=r, hb=hb, Dh=Dh, bt=bt)
    scratch = ()
    if windows == "padded":
        # [B, S, Mp, r*E]: rows are token groups of r, phases live on lanes
        x = _pad_segments(x, g, S, Mp * r).reshape(B, S, Mp, r * E)
        kernel = functools.partial(_pack_kernel_padded, **kw)
        in_spec = pl.BlockSpec(
            (1, 1, bt, r * E), lambda b, s, i: (b, s, i, 0),
            memory_space=pltpu.VMEM,
        )
    elif windows == "grid":
        # a window wholly past L is clamped to the last one that starts
        # inside (its rows are all zeroed, and an unchanged block index
        # is not copied again)
        per_seg, last = np.int32(g // rows), np.int32((L - 1) // rows)
        kernel = functools.partial(_pack_kernel, g=g, L=L, **kw)
        in_spec = pl.BlockSpec(
            (None, rows, E),
            lambda b, s, i: (b, lax.min(lax.add(lax.mul(s, per_seg), i), last), 0),
            memory_space=pltpu.VMEM,
        )
    else:
        kernel = functools.partial(_pack_kernel_element, g=g, L=L, **kw)
        in_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = (
            pltpu.VMEM((2, 2 * rows, E), x.dtype), pltpu.SemaphoreType.DMA((2,))
        )
    return pl.pallas_call(
        kernel,
        grid=(B, S, Mp // bt),
        in_specs=[in_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, r, hb, Mp, Dh), x.dtype),
        scratch_shapes=scratch,
        # one step after another: the element kernel fetches the next
        # step's window during this one
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3
        ),
        interpret=interpret,
        name="dilated_pack",
    )(x)


@jax.named_scope("unpack")
def _unpack_phases(p6: jnp.ndarray, L: int, E: int, g: int, S: int,
                   r: int, interpret: bool) -> jnp.ndarray:
    """Packed [B, S, r, hb, Mp, Dh] -> dense [B, L, E]; off-band lanes are
    written as exact zeros by the kernel, which writes the dense array
    itself (no XLA slice or relayout) unless :func:`_copy_plan` says
    ``"padded"``."""
    B, _, _, hb, Mp, Dh = p6.shape
    windows, bt = _copy_plan(L, g, S, r, Mp, E, p6.dtype.itemsize)
    rows = bt * r
    kw = dict(r=r, hb=hb, Dh=Dh, bt=bt)
    dense = jax.ShapeDtypeStruct((B, L, E), p6.dtype)
    if windows == "grid":
        # one step a dense window that STARTS inside L: cdiv(L, rows) of
        # them cover every row, and packed rows past them are padding
        if S == 1:
            packed_block = lambda b, d: (b, 0, 0, 0, d, 0)
        else:
            per_seg = np.int32(g // rows)
            packed_block = lambda b, d: (
                b, lax.div(d, per_seg), 0, 0, lax.rem(d, per_seg), 0
            )
        return pl.pallas_call(
            functools.partial(_unpack_kernel, **kw),
            grid=(B, -(-L // rows)),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, r, hb, bt, Dh), packed_block, memory_space=pltpu.VMEM,
                )
            ],
            out_specs=pl.BlockSpec(
                (None, rows, E), lambda b, d: (b, d, 0),
                memory_space=pltpu.VMEM,
            ),
            out_shape=dense,
            interpret=interpret,
            name="dilated_unpack",
        )(p6)
    in_spec = pl.BlockSpec(
        (1, 1, r, hb, bt, Dh), lambda b, s, i: (b, s, 0, 0, i, 0),
        memory_space=pltpu.VMEM,
    )
    if windows == "element":
        # the heights a window's valid part takes: whole, the last of a
        # segment, the last of the last segment
        valid = (min(g, L - s * g) - i * rows
                 for s in range(S) for i in range(Mp // bt))
        heights = tuple(sorted({min(h, rows) for h in valid if h > 0}))
        return pl.pallas_call(
            functools.partial(
                _unpack_kernel_element, g=g, L=L, heights=heights, **kw
            ),
            grid=(B, S, Mp // bt),
            in_specs=[in_spec],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=dense,
            scratch_shapes=[
                pltpu.VMEM((rows, E), p6.dtype), pltpu.SemaphoreType.DMA((1,)),
            ],
            interpret=interpret,
            name="dilated_unpack",
        )(p6)
    x = pl.pallas_call(
        functools.partial(_unpack_kernel_padded, **kw),
        grid=(B, S, Mp // bt),
        in_specs=[in_spec],
        out_specs=pl.BlockSpec(
            (1, 1, bt, r * E), lambda b, s, i: (b, s, i, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((B, S, Mp, r * E), p6.dtype),
        interpret=interpret,
        name="dilated_unpack",
    )(p6)
    x = x.reshape(B, S, Mp * r, E)
    return x[:, :, :g].reshape(B, S * g, E)[:, :L]


def _phase_kvlen(S: int, g: int, r: int, m: int, real_len: int) -> np.ndarray:
    """[S, r] valid sparse keys per (segment, phase): position
    ``s*g + p + r*j`` must be a real token and inside its segment."""
    seg = np.arange(S)[:, None]
    phase = np.arange(r)[None, :]
    in_seg = np.clip(real_len - seg * g, 0, g)
    counts = np.ceil((in_seg - phase) / r)
    return np.clip(counts, 0, m).astype(np.int32)


@jax.named_scope("unpack")
def _scatter_lse(lse5: jnp.ndarray, B: int, L: int, H: int, g: int, S: int,
                 r: int, m: int) -> jnp.ndarray:
    """Kernel lse [B, S, r, Mp, LANES] -> dense [B, H, L] with NEG_INF at
    (token, head) pairs the branch does not cover. Small fp32 data; plain
    jnp reshapes + a where."""
    hb = H // r  # heads per band
    lse = lse5[:, :, :, :m, :hb]  # [B, S, r(phase), m, hb]
    lse = lse.transpose(0, 2, 4, 1, 3).reshape(B, H, S, m)  # head h = p*hb + t
    # token t = s*g + j*r + p is covered by head h iff phase(h) == p
    phase_of_head = jax.lax.broadcasted_iota(jnp.int32, (H, r), 0) // hb
    cover = phase_of_head == jax.lax.broadcasted_iota(jnp.int32, (H, r), 1)
    dense = jnp.where(cover[None, :, None, None, :], lse[..., None], NEG_INF)
    dense = dense.reshape(B, H, S, m * r)[:, :, :, :g].reshape(B, H, S * g)
    return dense[:, :, :L]


def _branch_kvlen(B, S, g, r, m, real_len, vl_dyn):
    """[B, S, r] int32 valid sparse-key counts: the static table from
    ``real_len`` combined (by minimum) with optional TRACED per-batch
    valid lengths — the kernels read the counts from SMEM at runtime, so
    traced collate pad masks need no retrace and keep the fused path."""
    static = jnp.asarray(
        np.broadcast_to(_phase_kvlen(S, g, r, m, real_len)[None], (B, S, r))
    )
    if vl_dyn is None:
        return static
    from gigapath_tpu.ops.dilated_attention import dyn_sparse_counts

    # shared dynamic-masking formula; [B, r, S] -> the kernels' [B, S, r]
    counts = dyn_sparse_counts(vl_dyn, g, r, m, jnp.arange(r), S)
    return jnp.minimum(static, counts.transpose(0, 2, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _dilated_branch(q, k, v, vl_dyn, sl, r, H, real_len, causal, interpret,
                    flags):
    out, lse, _res = _dilated_branch_fwd_impl(
        q, k, v, vl_dyn, sl, r, H, real_len, causal, interpret, flags
    )
    return out, lse


def _pipe_block_k(block_q: int, override: Optional[int]) -> int:
    """k-block for the pipelined forward: the PipelineFlags override
    (GIGAPATH_PIPE_BLOCK_K, snapshotted at dispatch) or a default that
    keeps the two parity logits tiles + the exp2 temp inside the
    scoped-VMEM envelope at any legal block_q (<= 1408)."""
    bk = override if override else 512
    return max(LANES, min(bk, block_q))


def _branch_packed_fwd_impl(q, k, v, vl_dyn, sl, r, H, real_len, causal,
                            interpret, flags):
    """Shared forward core: dense [B, L, E] q/k/v -> PACKED
    ``(out6 [B, S, r, hb, Mp, Dh], lse5 [B, S, r, Mp, LANES])`` — the
    kernel-native layout, consumed either by the dense unpack/scatter pair
    (:func:`_dilated_branch_fwd_impl`) or directly by the streaming fusion
    epilogue (which never materializes the dense per-branch tensors)."""
    B, L, E = q.shape
    Dh = E // H
    g, S, gp, m, Mp, block = _branch_geometry(L, E, sl, r)
    q6 = _pack_phases(q, g, S, r, Mp, H, interpret)
    k6 = _pack_phases(k, g, S, r, Mp, H, interpret)
    v6 = _pack_phases(v, g, S, r, Mp, H, interpret)
    kvlen = _branch_kvlen(B, S, g, r, m, real_len, vl_dyn)
    hb = H // r
    if not causal and flags.pipelined_fwd:
        out6, lse5 = _fwd_impl_pipe(
            q6, k6, v6, kvlen, Dh ** -0.5, hb, Dh,
            block, _pipe_block_k(block, flags.pipe_block_k), interpret,
        )
    else:
        out6, lse5 = _fwd_impl(
            q6, k6, v6, kvlen, causal, Dh ** -0.5, hb, Dh, block, block,
            interpret,
        )
    return out6, lse5


def _dilated_branch_fwd_impl(q, k, v, vl_dyn, sl, r, H, real_len, causal,
                             interpret, flags):
    B, L, E = q.shape
    g, S, gp, m, Mp, block = _branch_geometry(L, E, sl, r)
    out6, lse5 = _branch_packed_fwd_impl(
        q, k, v, vl_dyn, sl, r, H, real_len, causal, interpret, flags
    )
    # off-band lanes come back as exact zeros from the unpack kernel — the
    # branch's cover pattern needs no separate select
    out = _unpack_phases(out6, L, E, g, S, r, interpret)
    lse = _scatter_lse(lse5, B, L, H, g, S, r, m)
    return out, lse, (out6, lse5)


def _dilated_branch_fwd(q, k, v, vl_dyn, sl, r, H, real_len, causal,
                        interpret, flags):
    out, lse, res = _dilated_branch_fwd_impl(
        q, k, v, vl_dyn, sl, r, H, real_len, causal, interpret, flags
    )
    # Residuals are the DENSE q/k/v (shared buffers across every branch of
    # the multi-branch op — XLA stores one copy) plus this branch's packed
    # (out, lse), 1/r of dense volume. Saving the packed q6/k6/v6 instead
    # kept ~3 full dense-sized copies alive per branch; the backward
    # re-packs with the same cheap kernels.
    return (out, lse), ((q, k, v, vl_dyn) + res, q.shape)


def _branch_bwd_core(q, k, v, vl_dyn, do6, out6, lse5, sl, r, H, real_len,
                     causal, interpret, flags):
    """Shared backward core: PACKED cotangent ``do6`` (plus the saved
    packed forward results) -> dense ``(dq, dk, dv, vl_ct)``. Callers:
    the dense branch VJP (packs its dense ``do`` first) and the packed
    branch VJP behind the streaming fusion epilogue (whose epilogue
    backward emits ``do6`` already packed — no dense round-trip)."""
    B, L, E = q.shape
    Dh = E // H
    hb = H // r
    g, S, gp, m, Mp, block = _branch_geometry(L, E, sl, r)
    q6 = _pack_phases(q, g, S, r, Mp, H, interpret)
    k6 = _pack_phases(k, g, S, r, Mp, H, interpret)
    v6 = _pack_phases(v, g, S, r, Mp, H, interpret)
    # delta = rowsum(do * out) per (token, head), in the kernel's lse
    # layout [B, S, r, Mp, LANES] — the packed arrays ARE the diagonal
    delta = (do6.astype(jnp.float32) * out6.astype(jnp.float32)).sum(axis=-1)
    delta = delta.transpose(0, 1, 2, 4, 3)  # [B, S, r, Mp, hb]
    delta = jnp.pad(delta, ((0, 0),) * 4 + ((0, LANES - hb),))
    kvlen = _branch_kvlen(B, S, g, r, m, real_len, vl_dyn)
    if not causal and flags.pipelined_bwd:
        dq6, dk6, dv6 = _bwd_impl_pipe(
            q6, k6, v6, do6, lse5, delta, kvlen, Dh ** -0.5,
            hb, Dh, block,
            _pipe_bwd_block_k(block, flags.pipe_bwd_block_k), interpret,
        )
    else:
        dq6, dk6, dv6 = _bwd_impl(
            q6, k6, v6, do6, lse5, delta, kvlen, causal, Dh ** -0.5,
            hb, Dh, block, block, interpret,
        )

    def undo(x6):
        # off-band lanes are exact zeros from the unpack kernel — which IS
        # the correct gradient there (the branch never reads those slots)
        return _unpack_phases(x6, L, E, g, S, r, interpret)

    vl_ct = (
        None if vl_dyn is None
        else np.zeros(vl_dyn.shape, dtype=jax.dtypes.float0)
    )
    return undo(dq6), undo(dk6), undo(dv6), vl_ct


def _dilated_branch_bwd(sl, r, H, real_len, causal, interpret, flags, saved,
                        cotangents):
    (q, k, v, vl_dyn, out6, lse5), (B, L, E) = saved
    do, _dlse = cotangents  # no gradient flows through the lse output
    g, S, gp, m, Mp, block = _branch_geometry(L, E, sl, r)
    do6 = _pack_phases(do, g, S, r, Mp, H, interpret)
    return _branch_bwd_core(
        q, k, v, vl_dyn, do6, out6, lse5, sl, r, H, real_len, causal,
        interpret, flags,
    )


_dilated_branch.defvjp(_dilated_branch_fwd, _dilated_branch_bwd)


def dilated_branch_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    sl: int,
    r: int,
    num_heads: int,
    *,
    real_len: Optional[int] = None,
    valid_len_dyn: Optional[jnp.ndarray] = None,
    is_causal: bool = False,
    interpret: bool = False,
    flags: Optional[PipelineFlags] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One dilated-attention branch on dense [B, L, E] activations.

    Returns ``(out [B, L, E], lse [B, H, L])`` where positions/heads not
    covered by this branch hold 0 / NEG_INF — ready for the cross-branch
    LSE-softmax fusion. Requires ``num_heads % r == 0`` and ``E % r == 0``
    (true for every LongNet config's power-of-two schedule).
    ``valid_len_dyn``: optional TRACED [B] suffix valid lengths (collate
    pad masks) — combined with the static masks in the kernels' SMEM
    valid-count tables at runtime.
    ``flags``: kernel-dispatch flag snapshot; by default the call reads
    the environment ONCE (:func:`snapshot_flags`). Pass an explicit
    :class:`PipelineFlags` to pin the dispatch independently of the
    environment.
    """
    B, L, E = q.shape
    assert E % num_heads == 0
    assert num_heads % r == 0 and E % r == 0, (num_heads, E, r)
    rl = L if real_len is None else min(int(real_len), L)
    if flags is None:
        flags = snapshot_flags()
    return _dilated_branch(
        q, k, v, valid_len_dyn, int(sl), int(r), num_heads, rl, is_causal,
        interpret, flags,
    )


# ---------------------------------------------------------------------------
# packed-boundary branch op (for the streaming fusion epilogue)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _dilated_branch_packed(q, k, v, vl_dyn, sl, r, H, real_len, causal,
                           interpret, flags):
    """Branch op with a PACKED output boundary: dense q/k/v in, packed
    ``(out6, lse5)`` out. Twin of :func:`_dilated_branch` whose backward
    accepts the cotangent *already in the packed layout* (the epilogue
    backward emits it there), so neither direction ever materializes the
    dense per-branch out/lse tensors."""
    out6, lse5 = _branch_packed_fwd_impl(
        q, k, v, vl_dyn, sl, r, H, real_len, causal, interpret, flags
    )
    return out6, lse5


def _dilated_branch_packed_fwd(q, k, v, vl_dyn, sl, r, H, real_len, causal,
                               interpret, flags):
    out6, lse5 = _branch_packed_fwd_impl(
        q, k, v, vl_dyn, sl, r, H, real_len, causal, interpret, flags
    )
    # Residuals mirror _dilated_branch_fwd: dense q/k/v (shared across
    # branches — XLA stores one copy) + this branch's packed results.
    return (out6, lse5), (q, k, v, vl_dyn, out6, lse5)


def _dilated_branch_packed_bwd(sl, r, H, real_len, causal, interpret, flags,
                               saved, cotangents):
    q, k, v, vl_dyn, out6, lse5 = saved
    do6, _dlse5 = cotangents  # no gradient flows through the lse output
    return _branch_bwd_core(
        q, k, v, vl_dyn, do6, out6, lse5, sl, r, H, real_len, causal,
        interpret, flags,
    )


_dilated_branch_packed.defvjp(_dilated_branch_packed_fwd,
                              _dilated_branch_packed_bwd)


def dilated_branch_attention_packed(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    sl: int,
    r: int,
    num_heads: int,
    *,
    real_len: Optional[int] = None,
    valid_len_dyn: Optional[jnp.ndarray] = None,
    is_causal: bool = False,
    interpret: bool = False,
    flags: Optional[PipelineFlags] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One dilated branch returning the PACKED phase-major results
    ``(out6 [B, S, r, hb, Mp, Dh], lse5 [B, S, r, Mp, LANES])`` — the
    streaming fusion epilogue's input contract. Same eligibility rules
    and ``flags`` default as :func:`dilated_branch_attention`."""
    B, L, E = q.shape
    assert E % num_heads == 0
    assert num_heads % r == 0 and E % r == 0, (num_heads, E, r)
    rl = L if real_len is None else min(int(real_len), L)
    if flags is None:
        flags = snapshot_flags()
    return _dilated_branch_packed(
        q, k, v, valid_len_dyn, int(sl), int(r), num_heads, rl, is_causal,
        interpret, flags,
    )


# ---------------------------------------------------------------------------
# streaming cross-branch fusion epilogue
# ---------------------------------------------------------------------------
#
# The dense fusion path scatters every branch's packed (out, lse) back to
# dense [B, L, E] / [B, H, L] (one re-tile pass per packed tensor,
# ~40-53 us each, plus the lse scatter) and only then runs the
# cross-branch LSE-softmax — the ~1.7 ms/layer residual glue of the
# round-4 decomposition. The epilogue below consumes every branch's
# results directly in the packed phase-major layout: for each dense token
# block it reads the covering (phase, band-head) lanes of each branch,
# folds them through an online softmax over the BRANCH axis (the same
# "combine partials via stored log-sum-exp" trick flash attention uses
# inside one kernel), and writes only the final fused [B, L, E] output.
# The per-branch dense out/lse tensors are never materialized.
#
# Alignment: a single epilogue pass needs every consumed branch to map a
# dense token block of BT tokens onto whole packed row blocks — i.e.
# r | BT, BT/r >= the 8-row fp32 sublane tile, and (for multi-segment
# branches) BT | g so blocks never straddle a segment boundary. Schedules
# whose branches cannot share one BT (the flagship's 5792-token segment:
# 2^5 * 181) are split into alignment CLASSES: one pass per class,
# chained through compact running state (acc [B, L, E] f32 + per-head
# (m, l) [B, L, H] f32), the last pass finalizing out = acc / l and the
# fused lse = m + log(l) (the backward's only residual besides the
# branch lse tables themselves).


class EpiloguePlan(NamedTuple):
    """Static geometry of one streaming-fusion epilogue instance. Hashable
    (participates in jit cache keys via the custom_vjp's nondiff args)."""

    L: int
    E: int
    H: int
    Dh: int
    branches: Tuple[Tuple[int, int, int, int, int], ...]  # (r, hb, S, g, Mp)
    classes: Tuple[Tuple[int, Tuple[int, ...]], ...]  # (BT_tokens, members)
    bwd_bt: Tuple[int, ...]  # per-branch backward packed-row block
    interpret: bool = False


_EPILOGUE_BT_CANDIDATES = (512, 256, 128, 64, 32, 16, 8)
# fwd per-cell fp32 dense temps: acc/m/l running state + 2 transient
# assemblies + the out block => keep ~6 [BT, E] fp32 buffers under budget
_EPILOGUE_VMEM_BUDGET = 10 * 2 ** 20


def _epilogue_bt_feasible(BT: int, r: int, S: int, g: int, Mp: int) -> bool:
    bt = BT // r
    return (
        BT % r == 0
        and bt >= 8
        and bt % 8 == 0
        and bt <= Mp
        and (S == 1 or g % BT == 0)
    )


def plan_stream_fusion(
    L: int, E: int, H: int,
    segment_lengths, dilated_ratios,
    interpret: bool = False,
) -> Optional[EpiloguePlan]:
    """Build the epilogue's static plan, or None when the schedule's
    geometry admits no legal blocking (callers fall back to the dense
    scatter + stacked fusion path, which stays the parity oracle)."""
    n = len(segment_lengths)
    if n < 2:
        return None
    Dh = E // H
    branches = []
    for sl, r in zip(segment_lengths, dilated_ratios):
        sl, r = int(sl), int(r)
        if H % r != 0 or E % r != 0:
            return None
        g, S, gp, m, Mp, block = _branch_geometry(L, E, sl, r)
        branches.append((r, H // r, S, g, Mp))

    def feasible(bi: int, BT: int) -> bool:
        r, hb, S, g, Mp = branches[bi]
        return _epilogue_bt_feasible(BT, r, S, g, Mp)

    # greedy alignment classes: largest BT covering the most branches
    # first; leftovers get their own (largest feasible) class each
    remaining = set(range(n))
    classes = []
    while remaining:
        best_bt, best_members = None, []
        for BT in _EPILOGUE_BT_CANDIDATES:
            members = [i for i in sorted(remaining) if feasible(i, BT)]
            if len(members) > len(best_members):
                best_bt, best_members = BT, members
        if not best_members:
            return None
        # shrink BT while the class's fp32 dense temps overflow the VMEM
        # budget (halving preserves feasibility only while bt stays >= 8)
        BT = best_bt

        def est(bt_tokens: int) -> int:
            state = 6 * bt_tokens * E * 4
            packed = sum(
                3 * bt_tokens * E * 4 // branches[i][0] for i in best_members
            )
            return state + packed

        while (
            est(BT) > _EPILOGUE_VMEM_BUDGET
            and BT // 2 >= 8
            and all(feasible(i, BT // 2) for i in best_members)
        ):
            BT //= 2
        classes.append((BT, tuple(best_members)))
        remaining -= set(best_members)

    # per-branch backward row blocks: the backward is one independent
    # pallas_call per branch over ITS packed rows, so only that branch's
    # own geometry constrains the block
    bwd_bt = []
    for r, hb, S, g, Mp in branches:
        bt = None
        for cand in (128, 64, 32, 16, 8):
            if (
                cand <= Mp
                and r * cand <= 512
                and (S == 1 or g % (cand * r) == 0)
            ):
                bt = cand
                break
        if bt is None:
            return None
        bwd_bt.append(bt)

    return EpiloguePlan(
        L=L, E=E, H=H, Dh=Dh,
        branches=tuple(branches),
        classes=tuple(classes),
        bwd_bt=tuple(bwd_bt),
        interpret=bool(interpret),
    )


def _head_lane_mask(H: int, E: int, Dh: int) -> jnp.ndarray:
    """Static [H, E] 0/1 matrix: lane e belongs to head e // Dh. Built from
    iotas on-device (host constants show up as per-step pred[] DMAs).
    One matmul against it expands per-head [*, H] stats to the [*, E]
    broadcast form; the transposed contraction (scaled by 1/Dh) compresses
    the lane-duplicated [*, E] form back to [*, H] exactly."""
    hh = jax.lax.broadcasted_iota(jnp.int32, (H, E), 0)
    ee = jax.lax.broadcasted_iota(jnp.int32, (H, E), 1)
    return (ee // Dh == hh).astype(jnp.float32)


def _expand_heads(x, mask):
    """[BT, H] -> [BT, E] (each head's value broadcast over its Dh lanes)."""
    return jax.lax.dot_general(
        x, mask, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _compress_heads(x, mask, Dh):
    """[BT, E] lane-duplicated -> [BT, H] (exact: mean over the Dh copies)."""
    return jax.lax.dot_general(
        x, mask, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (1.0 / Dh)


def _assemble_lse(l_ref, r, hb, Dh, E, bt):
    """Packed lse block [.., r, bt, LANES] -> dense row-block [bt, r*E]
    fp32 with the branch lse broadcast over each band head's Dh lanes and
    NEG_INF everywhere off-band — the lse twin of :func:`_assemble_bands`
    (same _band_lanes layout; the two must never diverge)."""
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, LANES), 1)
    pieces = []
    cursor = 0
    for p, t, lane in _band_lanes(r, hb, Dh, E):
        if lane > cursor:
            pieces.append(jnp.full((bt, lane - cursor), NEG_INF, jnp.float32))
        # lane t of the [bt, LANES] block, extracted mask-and-rowsum (no
        # lane shuffles — same idiom as the backward kernels' _lane)
        col = jnp.sum(
            jnp.where(lane_iota == t, l_ref[0, 0, p], 0.0),
            axis=1, keepdims=True,
        )
        pieces.append(jnp.broadcast_to(col, (bt, Dh)))
        cursor = lane + Dh
    if r * E > cursor:
        pieces.append(jnp.full((bt, r * E - cursor), NEG_INF, jnp.float32))
    return jnp.concatenate(pieces, axis=-1)


def _epilogue_fwd_kernel(*refs, brs, E, H, Dh, BT, first, final):
    """One dense [BT, E] token block: fold every class branch's packed
    (out, lse) into the running (acc, m, l) online softmax over branches.

    refs layout: per branch (out6 block, lse5 block); then, unless
    ``first``, the incoming (acc [BT,E] f32, m [BT,H] f32, l [BT,H] f32)
    state; then the outputs — (out [BT,E] dtype, fused_lse [BT,H] f32)
    when ``final``, else the outgoing (acc, m, l) state."""
    n = len(brs)
    pos = 2 * n
    mask = _head_lane_mask(H, E, Dh)
    acc = m_run = l_run = None
    if not first:
        acc_in, m_in, l_in = refs[pos:pos + 3]
        pos += 3
        acc = acc_in[0]
        m_run = _expand_heads(m_in[0], mask)
        l_run = _expand_heads(l_in[0], mask)
    out_refs = refs[pos:]

    for bi, (r, hb, bt) in enumerate(brs):
        o_ref, l_ref = refs[2 * bi], refs[2 * bi + 1]
        o_d = _assemble_bands(o_ref, r, hb, Dh, E, bt, jnp.float32)
        o_d = o_d.reshape(BT, E)
        l_d = _assemble_lse(l_ref, r, hb, Dh, E, bt).reshape(BT, E)
        if acc is None:
            acc, m_run, l_run = o_d, l_d, jnp.ones_like(l_d)
        else:
            m_new = jnp.maximum(m_run, l_d)
            a = jnp.exp(m_run - m_new)
            b_ = jnp.exp(l_d - m_new)
            acc = acc * a + o_d * b_
            l_run = l_run * a + b_
            m_run = m_new

    if final:
        o_out, lse_out = out_refs
        o_out[0] = (acc / l_run).astype(o_out.dtype)
        lse_out[0] = _compress_heads(m_run + jnp.log(l_run), mask, Dh)
    else:
        acc_out, m_out, l_out = out_refs
        acc_out[0] = acc
        m_out[0] = _compress_heads(m_run, mask, Dh)
        l_out[0] = _compress_heads(l_run, mask, Dh)


def _epilogue_pass_call(operands, geoms, B, plan, BT, first, final,
                        out_dtype):
    """One class pass: grid over (batch, dense token blocks)."""
    L, E, H, Dh = plan.L, plan.E, plan.H, plan.Dh
    NB = -(-L // BT)
    brs = []
    in_specs = []
    for (r, hb, S, g, Mp) in geoms:
        bt = BT // r
        brs.append((r, hb, bt))
        bps = g // BT if S > 1 else 0

        def o_map(b, i, bps=bps):
            if bps:
                return (b, i // bps, 0, 0, i % bps, 0)
            return (b, 0, 0, 0, i, 0)

        def l_map(b, i, bps=bps):
            if bps:
                return (b, i // bps, 0, i % bps, 0)
            return (b, 0, 0, i, 0)

        in_specs.append(pl.BlockSpec(
            (1, 1, r, hb, bt, Dh), o_map, memory_space=pltpu.VMEM,
        ))
        in_specs.append(pl.BlockSpec(
            (1, 1, r, bt, LANES), l_map, memory_space=pltpu.VMEM,
        ))
    dense_spec = pl.BlockSpec(
        (1, BT, E), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM,
    )
    stat_spec = pl.BlockSpec(
        (1, BT, H), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM,
    )
    if not first:
        in_specs += [dense_spec, stat_spec, stat_spec]
    if final:
        out_specs = [dense_spec, stat_spec]
        out_shape = [
            jax.ShapeDtypeStruct((B, L, E), out_dtype),
            jax.ShapeDtypeStruct((B, L, H), jnp.float32),
        ]
    else:
        out_specs = [dense_spec, stat_spec, stat_spec]
        out_shape = [
            jax.ShapeDtypeStruct((B, L, E), jnp.float32),
            jax.ShapeDtypeStruct((B, L, H), jnp.float32),
            jax.ShapeDtypeStruct((B, L, H), jnp.float32),
        ]
    kernel = functools.partial(
        _epilogue_fwd_kernel, brs=tuple(brs), E=E, H=H, Dh=Dh, BT=BT,
        first=first, final=final,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, NB),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=plan.interpret,
        name="dilated_epilogue_fwd",
    )(*operands)


def _epilogue_bwd_kernel(dy_ref, fl_ref, lse_ref, do_ref, *, r, hb, Dh, E,
                         bt, g, S, L):
    """One branch's packed cotangent block: d_out6 = w (x) extract(dY),
    where w = exp(lse_branch - fused_lse) re-derives the cross-branch
    softmax weight from the saved per-branch lse table and the fused
    (m + log l) residual — weights are constants in the backward
    (stop-gradient parity with the dense path / reference torch.no_grad).
    Rows past the real sequence (or the segment's dense extent) are
    zeroed by LOGICAL index, matching _pack_phases' zero padding — the
    downstream dK/dV kernels rely on padded query rows of do6 being
    exact zeros."""
    s = pl.program_id(1)
    i = pl.program_id(2)
    BT = bt * r
    H = r * hb
    mask = _head_lane_mask(H, E, Dh)
    fused = _expand_heads(fl_ref[0], mask)  # [BT, E]
    lse_d = _assemble_lse(lse_ref, r, hb, Dh, E, bt).reshape(BT, E)
    w = jnp.exp(lse_d - fused)
    x = dy_ref[0].astype(jnp.float32) * w
    rows = jax.lax.broadcasted_iota(jnp.int32, (BT, 1), 0) + i * BT
    limit = jnp.minimum(g, L - s * g)  # in-segment AND inside the sequence
    x = jnp.where(rows < limit, x, 0.0)
    _extract_bands(x.astype(do_ref.dtype).reshape(bt, r * E), do_ref,
                   r, hb, Dh)


def _epilogue_bwd_call(dy, fused_lse, lse5, geom, bt, plan):
    """One branch's backward pass: grid over (batch, segment, packed row
    blocks) — covering EVERY packed row (rows beyond the dense extent are
    written as exact zeros), so no uninitialized slot ever reaches the
    branch backward kernels."""
    L, E, Dh = plan.L, plan.E, plan.Dh
    r, hb, S, g, Mp = geom
    B = dy.shape[0]
    BT = bt * r
    bps = g // BT if S > 1 else 0

    def dense_map(b, s, i, bps=bps):
        if bps:
            return (b, s * bps + i, 0)
        return (b, i, 0)

    dy_spec = pl.BlockSpec((1, BT, E), dense_map, memory_space=pltpu.VMEM)
    fl_spec = pl.BlockSpec(
        (1, BT, r * hb), dense_map, memory_space=pltpu.VMEM,
    )
    lse_spec = pl.BlockSpec(
        (1, 1, r, bt, LANES), lambda b, s, i: (b, s, 0, i, 0),
        memory_space=pltpu.VMEM,
    )
    do_spec = pl.BlockSpec(
        (1, 1, r, hb, bt, Dh), lambda b, s, i: (b, s, 0, 0, i, 0),
        memory_space=pltpu.VMEM,
    )
    kernel = functools.partial(
        _epilogue_bwd_kernel, r=r, hb=hb, Dh=Dh, E=E, bt=bt, g=g, S=S, L=L,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, S, Mp // bt),
        in_specs=[dy_spec, fl_spec, lse_spec],
        out_specs=do_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, r, hb, Mp, Dh), dy.dtype),
        interpret=plan.interpret,
        name="dilated_epilogue_bwd",
    )(dy, fused_lse, lse5)


def _fusion_epilogue_fwd_impl(outs, lses, plan):
    B = outs[0].shape[0]
    out_dtype = outs[0].dtype
    ncls = len(plan.classes)
    state = None
    for ci, (BT, members) in enumerate(plan.classes):
        first, final = ci == 0, ci == ncls - 1
        geoms = [plan.branches[bi] for bi in members]
        operands = []
        for bi in members:
            operands += [outs[bi], lses[bi]]
        if not first:
            operands += list(state)
        state = _epilogue_pass_call(
            operands, geoms, B, plan, BT, first, final, out_dtype,
        )
    out, fused_lse = state
    return out, fused_lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fusion_epilogue(outs, lses, plan):
    """Fused cross-branch softmax over PACKED branch results -> dense
    [B, L, E]. Same math as the stacked dense fusion (softmax of the
    branch LSEs, NEG_INF at uncovered slots -> weight 0, all-uncovered
    slots -> 0 output), weights constant in the backward."""
    out, _ = _fusion_epilogue_fwd_impl(outs, lses, plan)
    return out


def _fusion_epilogue_fwd(outs, lses, plan):
    out, fused_lse = _fusion_epilogue_fwd_impl(outs, lses, plan)
    # residuals: the branches' packed lse tables (shared with the branch
    # ops' own residuals — XLA stores one copy) + the compact fused
    # (m + log l) per (token, head); no dense per-branch tensor is saved
    return out, (lses, fused_lse)


def _fusion_epilogue_bwd(plan, res, dy):
    lses, fused_lse = res
    d_outs = tuple(
        _epilogue_bwd_call(
            dy, fused_lse, lses[bi], plan.branches[bi], plan.bwd_bt[bi], plan,
        )
        for bi in range(len(plan.branches))
    )
    # the fusion weights are constants in the backward: zero cotangent
    # into every branch lse (packed shape — never a dense [B, H, L])
    d_lses = tuple(jnp.zeros(l.shape, l.dtype) for l in lses)
    return d_outs, d_lses


_fusion_epilogue.defvjp(_fusion_epilogue_fwd, _fusion_epilogue_bwd)


def dilated_attention_stream_fused(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_lengths,
    dilated_ratios,
    num_heads: int,
    *,
    real_len: Optional[int] = None,
    valid_len_dyn: Optional[jnp.ndarray] = None,
    is_causal: bool = False,
    interpret: bool = False,
    flags: Optional[PipelineFlags] = None,
    plan: Optional[EpiloguePlan] = None,
) -> jnp.ndarray:
    """Multi-branch dilated attention on dense [B, L, E] with the
    streaming fusion epilogue: every branch runs the packed-boundary op
    and the packed results flow straight into :func:`_fusion_epilogue` —
    no dense per-branch out/lse is ever materialized, forward or
    backward. Callers must have checked :func:`plan_stream_fusion`
    feasibility (pass the plan in to avoid recomputing it)."""
    B, L, E = q.shape
    if flags is None:
        flags = snapshot_flags()
    if plan is None or plan.interpret != bool(interpret):
        # a caller-built plan must agree with this call's interpret mode;
        # rebuilding is pure cheap Python
        plan = plan_stream_fusion(
            L, E, num_heads, segment_lengths, dilated_ratios,
            interpret=interpret,
        )
    assert plan is not None, "caller must gate on plan_stream_fusion"
    outs, lses = [], []
    for sl, r in zip(segment_lengths, dilated_ratios):
        with jax.named_scope(f"branch_r{int(r)}"):
            o6, l5 = dilated_branch_attention_packed(
                q, k, v, int(sl), int(r), num_heads,
                real_len=real_len, valid_len_dyn=valid_len_dyn,
                is_causal=is_causal, interpret=interpret, flags=flags,
            )
        outs.append(o6)
        lses.append(l5)
    with jax.named_scope("merge"):
        return _fusion_epilogue(tuple(outs), tuple(lses), plan)
