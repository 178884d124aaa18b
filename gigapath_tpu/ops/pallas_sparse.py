"""Pallas TPU kernels of learned sparse attention
(:mod:`gigapath_tpu.ops.sparse_index` has the mathematics and the dispatch).

- ``index_score``: ``I[t, s] = sum_h w[t, h] relu(q[t, h] . k[s])`` a ``[block_q,
  block_k]`` tile at a time. The heads of a query block go through the MXU in
  groups stacked along the rows (``[group * block_q, D] x [D, block_k]``: the
  key tile stays put while many rows stream past it), and each head's rows
  are weighted and summed on the VPU. Tiles wholly above the diagonal are
  neither computed nor fetched.
- ``index_select``: a block of query rows against all its keys in VMEM. The
  float32 scores become integer keys of the same order; 32 counting passes
  find the ``k``-th largest key of each row bit by bit; where more scores equal
  it than are wanted, ``log2 L`` more passes find the column up to which the
  equal ones are taken. Exact, no sort. Only the columns at or below the
  block's last row are read.
- ``sparse_attn``: no body of its own. The call is
  :func:`gigapath_tpu.ops.pallas_flash.fwd_call_overlap` with the mask mode
  "selection": the flash forward's overlapped body over the blocks at or
  below the diagonal, an int8 ``[block_q, block_k]`` tile of the selection in
  the causal compare's place (the selection has nothing above the diagonal)
  and no lse output; the kernel is ``sparse_attn_overlap``.

Forward only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gigapath_tpu.ops.common import round_up
from gigapath_tpu.ops.pallas_flash import LANES, fwd_call_overlap, plan_fwd_body

INT_MIN = -(2 ** 31)

SCORE_BLOCK_Q = 128
SCORE_BLOCK_K = 512
SCORE_HEAD_GROUP = 8
SELECT_ROWS = 64          # query rows a step (an int8 tile is 32 sublanes)
SELECT_CHUNK = 4096       # columns a counting step takes at once
ATTN_BLOCK_Q = 1024
ATTN_BLOCK_K = 1024


# --------------------------------------------------------------------- scores
def _score_kernel(q_ref, k_ref, w_ref, o_ref, *, heads, group, block_q, block_k):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j * block_k < (i + 1) * block_q)
    def _compute():
        k = k_ref[0]                                   # [bk, D]
        w = w_ref[0]                                   # [bq, H] float32
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for g in range(heads // group):
            rows = q_ref[0, g * group:(g + 1) * group].reshape(group * block_q, -1)
            s = jax.lax.dot_general(rows, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            for h in range(group):
                head = g * group + h
                acc = acc + jnp.maximum(s[h * block_q:(h + 1) * block_q], 0.0) \
                    * w[:, head:head + 1]
        o_ref[0] = acc


def index_score_fwd(q, k, w, *, block_q=SCORE_BLOCK_Q, block_k=SCORE_BLOCK_K,
                    interpret=False):
    """``q [B, L, H, D]``, ``k [B, L, D]``, ``w [B, L, H]`` float32 -> ``[B, L,
    L]`` float32; tiles above the diagonal are left as they were allocated."""
    B, L, H, D = q.shape
    block_q = min(block_q, round_up(L, LANES))
    block_k = min(block_k, round_up(L, LANES))
    Lq, Lk = round_up(L, block_q), round_up(L, block_k)
    group = SCORE_HEAD_GROUP if H % SCORE_HEAD_GROUP == 0 else 1
    qp = jnp.pad(q, ((0, 0), (0, Lq - L), (0, 0), (0, 0))).transpose(0, 2, 1, 3)   # [B, H, Lq, D]
    kp = jnp.pad(k, ((0, 0), (0, Lk - L), (0, 0)))
    wp = jnp.pad(w, ((0, 0), (0, Lq - L), (0, 0)))

    def k_index(b, i, j):   # above the diagonal: the tile already there, so no new copy
        return (b, jnp.minimum(j, ((i + 1) * block_q - 1) // block_k), 0)

    kernel = functools.partial(_score_kernel, heads=H, group=group, block_q=block_q,
                               block_k=block_k)
    with jax.named_scope("kernel_fwd"):
        out = pl.pallas_call(
            kernel,
            grid=(B, Lq // block_q, Lk // block_k),
            in_specs=[
                pl.BlockSpec((1, H, block_q, D), lambda b, i, j: (b, 0, i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, block_k, D), k_index, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, block_q, H), lambda b, i, j: (b, i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, block_q, block_k), lambda b, i, j: (b, i, j),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((B, Lq, Lk), jnp.float32),
            interpret=interpret,
            name="index_score",
        )(qp, kp, wp)
    return out[:, :L, :L]


# --------------------------------------------------------------------- select
def _select_kernel(s_ref, o_ref, key_ref, *, topk, rows, chunk, col_bits):
    i = pl.program_id(1)
    n_chunks = (i * rows + rows + chunk - 1) // chunk        # columns up to the block's last row
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) + i * rows
    want = jnp.minimum(row + 1, topk)                         # [rows, 1]

    def cols_of(c):
        return jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1) + c * chunk

    def fill(c, _):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        bits = jax.lax.bitcast_convert_type(s_ref[0, :, at], jnp.int32)
        # the floats' order, as integers; -0.0 (INT_MIN) is 0.0's equal, as floats compare
        key = jnp.where(bits >= 0, bits, jnp.where(bits == INT_MIN, 0, bits ^ 0x7FFFFFFF))
        key_ref[:, at] = jnp.where(cols_of(c) <= row, key, INT_MIN)
        return 0

    jax.lax.fori_loop(0, n_chunks, fill, 0)

    def count(pred):
        """Per row, over the block's columns: how many keys ``pred(key, cols)`` holds for."""
        def step(c, total):
            at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            hit = pred(key_ref[:, at], cols_of(c))
            return total + jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)

        return jax.lax.fori_loop(0, n_chunks, step, jnp.zeros((rows, 1), jnp.int32))

    def bit_step(b, tau):   # the largest value at least `want` keys reach, a bit at a time
        cand = tau + jnp.left_shift(jnp.int32(1), 31 - b)     # the first step wraps INT_MIN to 0
        return jnp.where(count(lambda key, _: key >= cand) >= want, cand, tau)

    tau = jax.lax.fori_loop(0, 32, bit_step, jnp.full((rows, 1), INT_MIN, jnp.int32))
    above = count(lambda key, _: key > tau)
    spare = want - above                                      # of the keys equal to tau, the lowest columns
    equal = count(lambda key, _: key == tau)

    def col_step(b, upto):   # the largest column bound below which fewer than `spare` equal keys lie
        cand = upto + jnp.left_shift(jnp.int32(1), col_bits - 1 - b)
        fewer = count(lambda key, cols: (key == tau) & (cols < cand)) < spare
        return jnp.where(fewer, cand, upto)

    upto = jax.lax.cond(
        jnp.max(equal - spare) > 0,
        lambda: jax.lax.fori_loop(0, col_bits, col_step, jnp.zeros((rows, 1), jnp.int32)),
        lambda: jnp.full((rows, 1), 2 ** 30, jnp.int32))

    o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    def write(c, _):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        key, cols = key_ref[:, at], cols_of(c)
        keep = ((key > tau) | ((key == tau) & (cols <= upto))) & (cols <= row)
        o_ref[0, :, at] = keep.astype(jnp.int32).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, n_chunks, write, 0)


def index_select_fwd(scores, topk, *, rows=SELECT_ROWS, chunk=SELECT_CHUNK, interpret=False):
    """``scores [B, L, L]`` float32 -> ``mask [B, L, L]`` int8, ``min(t + 1,
    topk)`` ones in row ``t``."""
    B, L, _ = scores.shape
    chunk = min(chunk, round_up(L, LANES))
    Lr, Lc = round_up(L, rows), round_up(L, chunk)
    sp = jnp.pad(scores, ((0, 0), (0, Lr - L), (0, Lc - L)))
    kernel = functools.partial(_select_kernel, topk=topk, rows=rows, chunk=chunk,
                               col_bits=max(Lc - 1, 1).bit_length())
    spec = pl.BlockSpec((1, rows, Lc), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM)
    with jax.named_scope("kernel_fwd"):
        mask = pl.pallas_call(
            kernel,
            grid=(B, Lr // rows),
            in_specs=[spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((B, Lr, Lc), jnp.int8),
            scratch_shapes=[pltpu.VMEM((rows, Lc), jnp.int32)],
            interpret=interpret,
            name="index_select",
        )(sp)
    return mask[:, :L, :L]


# ----------------------------------------------------------------------- core
def sparse_attn_fwd(q, k, v, mask, *, scale, block_q=ATTN_BLOCK_Q, block_k=ATTN_BLOCK_K,
                    interpret=False, body=None):
    """``q, k [B, L, H, D]``, ``v [B, L, H, Dv]``, ``mask [B, L, L]`` int8 with
    nothing above the diagonal -> ``[B, L, H, Dv]``. ``body``: a
    ``pallas_flash.FwdPlan`` in the planner's place (tests and probes)."""
    B, L, H, D = q.shape
    block_q = min(block_q, round_up(L, LANES))
    block_k = min(block_k, round_up(L, LANES))
    Lq, Lk = round_up(L, block_q), round_up(L, block_k)
    plan = body or plan_fwd_body("selection", block_q, (Lq // block_q) * (Lk // block_k))
    assert plan.body == "overlap", plan  # the serial flash body reads no selection

    def heads_first(x, length):
        return jnp.pad(x, ((0, 0), (0, length - L), (0, 0), (0, 0))).transpose(0, 2, 1, 3)

    out, = fwd_call_overlap(
        heads_first(q, Lq), heads_first(k, Lk), heads_first(v, Lk),
        jnp.pad(mask, ((0, 0), (0, Lq - L), (0, Lk - L))),
        mask="selection", name="sparse_attn", scale=scale, block_q=block_q, block_k=block_k,
        rows=plan.rows, lse=False, interpret=interpret)
    return out[:, :, :L].transpose(0, 2, 1, 3)
