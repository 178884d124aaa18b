"""Pallas TPU kernels of InfLLM-v2 block-sparse attention
(:mod:`gigapath_tpu.ops.block_sparse` has the mathematics, the tiles' lists
and the dispatch): ``block_score`` and ``block_sparse_attn``.

``block_score``: grid (batch, KV group, tile of ``SCORE_ROWS`` query
positions). The group's compressed keys stay in VMEM across its tiles; a
tile takes each head of the group in turn, ``Kc q^T`` with the units on the
sublanes and the positions on the lanes, the softmax over the visible units
down the sublanes in float32, summed over the heads; then each block's
maximum over the units that overlap it by strided reads of a VMEM copy (a
unit is ``block / stride`` rows from the last block's, and a block spans
``block / stride + kernel / stride - 1`` of them). It writes ``Bs^T [nb,
positions]``: no score of a unit ever reaches HBM.

``block_sparse_attn``:
grid (batch, KV group, query tile). A tile's rows are its ``tile`` query
positions times the group's heads, position-major, read by their BlockSpec
from ``q [B, L, H, d]`` as it lies, so that each key block is read once for
every head of the group. The group's keys and values are copied whole into
VMEM at its first tile (``2 x L x d`` bfloat16: 33.5 MB at 65,536 tokens and
``d`` 128) and stay there for all its tiles. The tile's list (one SMEM block
of ``LIST_WIDTH`` int32: its count, then its blocks in rising order) and the
matching bits (which of the tile's positions chose each block) drive a loop
of ``BLOCKS_PER_STEP`` blocks a step: their keys gathered from VMEM into one
``[BLOCKS_PER_STEP x block, d]`` operand, ``q k^T`` in float32, one mask from
a row's bit and the causal compare, the online softmax in float32, ``p v``
with ``p`` in the values' type. A block no row of the tile chose is never
read. Forward only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gigapath_tpu.ops.block_sparse import LIST_WIDTH
from gigapath_tpu.ops.common import round_up

BLOCKS_PER_STEP = 16
# query positions (lanes) a tile of the block scores
SCORE_ROWS = 128
# a masked score: finite, so that a row that has met no key of its own yet
# sums exp(0) for each masked one, and its first real key's correction,
# exp(_MASKED - m), takes all of it away again
_MASKED = -1e30
# the group's keys and values held in VMEM, of the chip's 128 MiB
_KV_VMEM_MAX = 64 << 20
_VMEM_MARGIN = 8 << 20
_SCORE_VMEM = 48 << 20
_LANES = 128


def fits(q_shape, k_shape, block: int, tile: int, width: int) -> bool:
    """Whether the kernel takes these shapes: a head of whole 128-lane lines,
    whole bfloat16 tiles of rows, key blocks of whole tiles, a list of
    ``width`` blocks that leaves a step's slack in ``LIST_WIDTH``, and the
    group's keys and values inside the VMEM they may have."""
    _, L, H, d = q_shape
    G = k_shape[2]
    Lk = round_up(L, block)
    return (d % 128 == 0 and H % G == 0 and (H // G) % 8 == 0 and (tile * (H // G)) % 16 == 0
            and block % 16 == 0 and width + BLOCKS_PER_STEP <= LIST_WIDTH
            and 2 * Lk * d * 2 <= _KV_VMEM_MAX)


def _kernel(list_ref, mask_ref, q_ref, k_hbm, v_hbm, o_ref, k_vmem, v_vmem, sem, *,
            block, scale, tile, per_step):
    f32 = jnp.float32
    b, g, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _load():
        copies = [pltpu.make_async_copy(k_hbm.at[b, g], k_vmem, sem.at[0]),
                  pltpu.make_async_copy(v_hbm.at[b, g], v_vmem, sem.at[1])]
        for copy in copies:
            copy.start()
        for copy in copies:
            copy.wait()

    _, heads, d = q_ref.shape
    rows = tile * heads
    q = q_ref[...].reshape(rows, d)
    row = lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // heads     # position in the tile
    row_bit = jnp.left_shift(jnp.int32(1), row)
    row_pos = i * tile + row
    keys = per_step * block
    lane = lax.broadcasted_iota(jnp.int32, (1, keys), 1)
    lane_block, lane_key = lane // block, lane % block

    def step(s, carry):
        m, l, acc = carry
        base = 1 + s * per_step
        starts = [pl.multiple_of(list_ref[base + j] * block, block) for j in range(per_step)]
        kt = jnp.concatenate([k_vmem[pl.ds(at, block), :] for at in starts], axis=0)
        vt = jnp.concatenate([v_vmem[pl.ds(at, block), :] for at in starts], axis=0)
        # each lane's key position and the bits of its block
        key_pos, key_bits = starts[0] + lane_key, jnp.full((1, keys), mask_ref[base], jnp.int32)
        for j in range(1, per_step):
            mine = lane_block == j
            key_pos = jnp.where(mine, starts[j] + lane_key, key_pos)
            key_bits = jnp.where(mine, mask_ref[base + j], key_bits)
        allowed = ((key_bits & row_bit) != 0) & (key_pos <= row_pos)    # [rows, keys]
        scores = lax.dot_general(q, kt, (((1,), (1,)), ((), ())), preferred_element_type=f32)
        scores = jnp.where(allowed, scores * scale, _MASKED)
        m_new = jnp.maximum(m, scores.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        l = alpha * l + p.sum(axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(vt.dtype), vt, preferred_element_type=f32)
        return m_new, l, acc

    steps = (list_ref[0] + per_step - 1) // per_step
    init = (jnp.full((rows, 1), _MASKED, f32), jnp.zeros((rows, 1), f32), jnp.zeros((rows, d), f32))
    _, l, acc = lax.fori_loop(0, steps, step, init)
    o_ref[...] = (acc / l).astype(o_ref.dtype).reshape(tile, heads, d)


@functools.partial(jax.jit, static_argnames=("block", "scale", "tile", "per_step", "interpret"))
def _attn_call(q, k, v, lists, masks, *, block, scale, tile, per_step, interpret):
    """``q [B, Lp, H, d]``, ``k``, ``v`` ``[B, G, Lk, d]``, ``lists`` /
    ``masks`` flat ``[B G tiles LIST_WIDTH]`` int32 -> ``[B, Lp, H, d]``. A
    jitted function of its own, so that the layers of a model share one trace
    and one lowering."""
    B, Lp, H, d = q.shape
    G, Lk = k.shape[1], k.shape[2]
    heads, tiles = H // G, Lp // tile

    def index_block(b, g, i):
        return ((b * G + g) * tiles + i,)

    smem = pl.BlockSpec((LIST_WIDTH,), index_block, memory_space=pltpu.SMEM)
    rows = pl.BlockSpec((None, tile, heads, d), lambda b, g, i: (b, i, g, 0),
                        memory_space=pltpu.VMEM)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    kernel = functools.partial(_kernel, block=block, scale=scale, tile=tile, per_step=per_step)
    with jax.named_scope("kernel_fwd"):
        return pl.pallas_call(
            kernel,
            grid=(B, G, tiles),
            in_specs=[smem, smem, rows, any_, any_],
            out_specs=rows,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((Lk, d), k.dtype), pltpu.VMEM((Lk, d), v.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=2 * Lk * d * k.dtype.itemsize + _VMEM_MARGIN),
            interpret=interpret,
            name="block_sparse_attn",
        )(lists, masks, q, k, v)


def block_sparse_attn_fwd(q, k, v, lists, masks, *, block, scale, tile, interpret=False):
    """``q [B, L, H, d]``, ``k``, ``v`` ``[B, L, G, d]``, the tiles' ``lists``
    and ``masks`` ``[B, G, tiles, LIST_WIDTH]`` (``block_sparse.tile_lists``)
    -> ``out [B, L, H, d]`` in ``q``'s type."""
    B, L, H, d = q.shape
    tiles = lists.shape[2]
    Lp, Lk = tiles * tile, round_up(L, block)
    q = jnp.pad(q, ((0, 0), (0, Lp - L), (0, 0), (0, 0)))
    k, v = (jnp.pad(a, ((0, 0), (0, Lk - L), (0, 0), (0, 0))).transpose(0, 2, 1, 3) for a in (k, v))
    out = _attn_call(q, k, v, lists.reshape(-1), masks.reshape(-1), block=block, scale=scale,
                     tile=tile, per_step=BLOCKS_PER_STEP, interpret=interpret)
    return out[:, :L]


def score_fits(q_shape, k_shape, kernel: int, stride: int, block: int) -> bool:
    """Whether ``block_score`` takes these shapes: a head of whole 128-lane
    lines, KV groups of whole heads, and a block and a window of whole
    steps."""
    _, L, H, d = q_shape
    G = k_shape[2]
    return (d % 128 == 0 and H % G == 0 and kernel % stride == 0 and block % stride == 0
            and L >= kernel)


def _score_kernel(q_ref, kc_ref, o_ref, pool_ref, *, kernel, stride, block, scale):
    f32 = jnp.float32
    i = pl.program_id(2)
    heads, rows, _ = q_ref.shape
    units = kc_ref.shape[0]
    nb = o_ref.shape[0]
    kc = kc_ref[...]
    t = i * rows + lax.broadcasted_iota(jnp.int32, (1, rows), 1)    # positions on the lanes
    j = lax.broadcasted_iota(jnp.int32, (units, 1), 0)              # units on the sublanes
    visible = stride * j + kernel - 1 <= t                          # [units, rows]
    total = jnp.zeros((units, rows), f32)
    for h in range(heads):
        s = lax.dot_general(kc, q_ref[h], (((1,), (1,)), ((), ())), preferred_element_type=f32)
        s = jnp.where(visible, s * scale, -jnp.inf)
        m = s.max(axis=0, keepdims=True)
        e = jnp.exp(s - jnp.where(m > -jnp.inf, m, 0.0))
        z = e.sum(axis=0, keepdims=True)
        total = total + e / jnp.where(z > 0, z, 1.0)
    # unit u at row 8 + u, -inf around it: block b's units are step b - before ..
    step, before = block // stride, kernel // stride - 1
    pool_ref[...] = jnp.full(pool_ref.shape, -jnp.inf, f32)
    pool_ref[pl.ds(8, units), :] = jnp.where(visible, total, -jnp.inf)
    pooled = pool_ref[pl.ds(8 - before, nb, stride=step), :]
    for w in range(1, step + before):
        pooled = jnp.maximum(pooled, pool_ref[pl.ds(8 - before + w, nb, stride=step), :])
    o_ref[...] = pooled


@functools.partial(jax.jit, static_argnames=("kernel", "stride", "block", "scale", "nb",
                                             "interpret"))
def _score_call(qh, kc, *, kernel, stride, block, scale, nb, interpret):
    """``qh [B, H, Lp, d]``, ``kc [B, G, units, d]`` -> ``Bs^T [B, G, nb,
    Lp]`` float32."""
    B, H, Lp, d = qh.shape
    G, units = kc.shape[1], kc.shape[2]
    heads, rows = H // G, SCORE_ROWS
    step, before = block // stride, kernel // stride - 1
    pool = round_up(max(8 + units, 8 - before + step * nb + before + 1), 8)
    body = functools.partial(_score_kernel, kernel=kernel, stride=stride, block=block, scale=scale)
    with jax.named_scope("kernel_fwd"):
        return pl.pallas_call(
            body,
            grid=(B, G, Lp // rows),
            in_specs=[pl.BlockSpec((None, heads, rows, d), lambda b, g, i: (b, g, i, 0),
                                   memory_space=pltpu.VMEM),
                      pl.BlockSpec((None, None, units, d), lambda b, g, i: (b, g, 0, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((None, None, nb, rows), lambda b, g, i: (b, g, 0, i),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((B, G, nb, Lp), jnp.float32),
            scratch_shapes=[pltpu.VMEM((pool, rows), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel"),
                vmem_limit_bytes=_SCORE_VMEM),
            interpret=interpret,
            name="block_score",
        )(qh, kc)


def block_score_fwd(q, kc, *, kernel, stride, block, scale, interpret=False):
    """``q [B, L, H, d]``, the compressed keys ``kc [B, M, G, d]`` in ``q``'s
    type -> ``Bs [B, G, L, ceil(L / block)]`` float32, as
    ``block_sparse.compressed_scores`` gives it."""
    B, L, H, d = q.shape
    M = kc.shape[1]
    nb = -(-L // block)
    Lp, units = round_up(L, SCORE_ROWS), round_up(M, _LANES)
    qh = jnp.pad(q, ((0, 0), (0, Lp - L), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kc = jnp.pad(kc, ((0, 0), (0, units - M), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    out = _score_call(qh, kc, kernel=kernel, stride=stride, block=block, scale=scale, nb=nb,
                      interpret=interpret)
    return out[..., :L].transpose(0, 1, 3, 2)
