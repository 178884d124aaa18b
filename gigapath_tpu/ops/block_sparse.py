"""InfLLM-v2 block-sparse attention (MiniCPM4's sparse attention, MiniCPM-SALA's
sparse layers): scores of key blocks from compressed keys, a top-k of blocks
a query and KV group, and a core that visits only the selected blocks.

With ``k`` and ``v`` ``[B, L, G, d]``, the queries of group ``g`` its ``H /
G`` heads, ``kernel`` / ``stride`` the compression window and step, ``block``
the key block::

    Kc[g, j]    = mean(k[g, stride j : stride j + kernel])   unit j visible to t iff stride j + kernel - 1 <= t
    P[t, g, j]  = sum over heads h of g of softmax_j(q[t, h] . Kc[g, j] * scale), over the visible j
    Bs[t, g, b] = max of P[t, g, j] over the visible units j that overlap block b   (-inf where none does)
    forced      = blocks b < init_blocks, and the blocks that hold max(0, t - window + 1) .. t
    S[t, g]     = the topk blocks b <= t // block of largest score, forced ones scored +inf,
                  ties to the lower b; a block whose score is -inf is never taken
    out[t, h]   = softmax over s <= t with s // block in S[t, g(h)] of (q[t, h] . k[g, s] * scale) v[g, s]

Three steps, each a function here: :func:`compressed_scores` (``Bs [B, G, L,
L / block]`` float32, a block of query rows at a time: ``[heads, L, L /
stride]`` never exists), :func:`select_blocks` (indices ``[B, G, L, topk]``
int32, ``-1`` where fewer blocks may be taken; exact as ``jax.lax.top_k``,
which puts the lower index first among equals) and
:func:`block_sparse_attention`, which hands the core, for each tile of
``TILE_Q`` query positions, the ascending list of the distinct blocks its
rows selected and, for each, which of the tile's positions selected it. The
core visits those blocks and no other: the Pallas kernel
(:mod:`gigapath_tpu.ops.pallas_block_sparse`, ``block_sparse_attn``) on a
TPU where its ``fits`` takes the shapes, the ``jnp`` tier (the same lists,
gathered) elsewhere. No ``[L, L]`` array exists on either tier.
:func:`infllm_attention` runs the three under the program's scope names, and
up to ``dense_len`` tokens a causal flash core in their place. Forward only.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from gigapath_tpu.ops import flash_attention as _gate
from gigapath_tpu.ops.common import round_up

# query positions a tile of the core: with the group's heads its rows
TILE_Q = 8
# entries of a tile's list: the count, then up to 1,023 blocks (one SMEM
# block of a flat 32-bit array, ops/moe/pallas_rows.py's INDEX_BLOCK)
LIST_WIDTH = 1024
# query rows a step of the jnp tier's block scores: [B, heads, rows, L / stride] float32
_SCORE_ROWS = 64


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    """The selection's sizes (MiniCPM4's ``sparse_config``)."""

    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192


def _use_pallas(use_pallas: Optional[bool]) -> bool:
    return _gate._on_tpu() if use_pallas is None else use_pallas


def compressed_keys(k: jnp.ndarray, kernel: int, stride: int) -> jnp.ndarray:
    """``k [B, L, G, d]`` -> ``Kc [B, M, G, d]`` float32, ``M = (L - kernel) //
    stride + 1`` units (0 where ``L < kernel``), each the mean of its window."""
    B, L, G, d = k.shape
    M = max((L - kernel) // stride + 1, 0)
    if M == 0:
        return jnp.zeros((B, 0, G, d), jnp.float32)
    per = kernel // stride
    n = M + per - 1
    sums = k[:, : n * stride].astype(jnp.float32).reshape(B, n, stride, G, d).sum(axis=2)
    return sum(sums[:, i:i + M] for i in range(per)) / kernel


def _pool(P: jnp.ndarray, nb: int, kernel: int, stride: int, block: int) -> jnp.ndarray:
    """``P [..., M]`` (-inf where not visible) -> ``[..., nb]``: block ``b``'s
    maximum over the units that overlap it, ``b (block / stride) - (kernel /
    stride - 1)`` to ``(b + 1) (block / stride) - 1``."""
    step, before = block // stride, kernel // stride - 1
    width = step + before
    after = max((nb - 1) * step + width - P.shape[-1] - before, 0)
    pads = [(0, 0, 0)] * (P.ndim - 1) + [(before, after, 0)]
    P = jax.lax.pad(P, jnp.float32(-jnp.inf), pads)
    pooled = jax.lax.reduce_window(P, -jnp.inf, jax.lax.max, (1,) * (P.ndim - 1) + (width,),
                                   (1,) * (P.ndim - 1) + (step,), "VALID")
    return pooled[..., :nb]


def compressed_scores(q: jnp.ndarray, k: jnp.ndarray, *, kernel: int, stride: int, block: int,
                      scale: float, use_pallas: Optional[bool] = None,
                      interpret: bool = False) -> jnp.ndarray:
    """``q [B, L, H, d]``, ``k [B, L, G, d]`` -> ``Bs [B, G, L, ceil(L /
    block)]`` float32, the block scores of the module docstring: the
    ``block_score`` kernel where the device gate says TPU and its
    ``score_fits`` takes the shapes, a block of query rows at a time on the
    ``jnp`` tier elsewhere. ``kernel`` and ``block`` are multiples of
    ``stride``."""
    if kernel % stride or block % stride:
        raise ValueError(f"kernel {kernel} and block {block} must be multiples of stride {stride}")
    B, L, H, d = q.shape
    G = k.shape[2]
    nb = -(-L // block)
    with jax.named_scope("compress"):
        kc = compressed_keys(k, kernel, stride).astype(q.dtype)          # [B, M, G, d]
    M = kc.shape[1]
    if M == 0:
        return jnp.full((B, G, L, nb), -jnp.inf, jnp.float32)
    if _use_pallas(use_pallas):
        from gigapath_tpu.ops import pallas_block_sparse

        if pallas_block_sparse.score_fits(q.shape, k.shape, kernel, stride, block):
            with jax.named_scope("score"):
                return pallas_block_sparse.block_score_fwd(
                    q, kc, kernel=kernel, stride=stride, block=block, scale=scale,
                    interpret=interpret)
    with jax.named_scope("score"):
        rows = min(_SCORE_ROWS, L)
        Lp = round_up(L, rows)
        qb = jnp.pad(q, ((0, 0), (0, Lp - L), (0, 0), (0, 0))).reshape(B, Lp // rows, rows, G,
                                                                         H // G, d)
        last = stride * jnp.arange(M) + kernel - 1                       # a unit's last position

        def one(args):
            i, q_blk = args                                              # [B, rows, G, r, d]
            t = i * rows + jnp.arange(rows)
            visible = last[None, :] <= t[:, None]                        # [rows, M]
            s = jnp.einsum("btgxd,bjgd->bgtxj", q_blk, kc,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(visible[None, None, :, None], s, -jnp.inf)
            m = s.max(axis=-1, keepdims=True)
            e = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
            z = e.sum(axis=-1, keepdims=True)
            P = (e / jnp.where(z > 0, z, 1.0)).sum(axis=3)               # [B, G, rows, M]
            return _pool(jnp.where(visible, P, -jnp.inf), nb, kernel, stride, block)

        out = jax.lax.map(one, (jnp.arange(Lp // rows), jnp.moveaxis(qb, 1, 0)))
        return jnp.moveaxis(out, 0, 2).reshape(B, G, Lp, nb)[:, :, :L]


def select_blocks(scores: jnp.ndarray, *, topk: int, block: int, init_blocks: int,
                  window: int) -> jnp.ndarray:
    """``Bs [B, G, L, nb]`` -> the selection ``[B, G, L, topk]`` int32: row
    ``t`` holds the blocks of the module docstring's ``S[t, g]`` in the order
    ``jax.lax.top_k`` gives them (forced blocks first, in rising order), then
    ``-1`` where fewer than ``topk`` blocks may be taken."""
    L, nb = scores.shape[2], scores.shape[3]
    t = jnp.arange(L)[:, None]
    b = jnp.arange(nb)[None, :]
    eligible = b <= t // block
    forced = eligible & ((b < init_blocks) | (b >= jnp.maximum(t - window + 1, 0) // block))
    ranked = jnp.where(forced, jnp.inf, jnp.where(eligible, scores, -jnp.inf))
    if nb < topk:
        ranked = jnp.pad(ranked, ((0, 0),) * 3 + ((0, topk - nb),), constant_values=-jnp.inf)
    values, index = jax.lax.top_k(ranked, topk)
    return jnp.where(values == -jnp.inf, -1, index).astype(jnp.int32)


def tile_lists(selected: jnp.ndarray, nb: int, tile: int = TILE_Q):
    """``selected [B, G, L, topk]`` -> ``(lists, masks)``, each ``[B, G,
    tiles, LIST_WIDTH]`` int32 with ``tiles = ceil(L / tile)``: ``lists[...,
    0]`` is how many distinct blocks the tile's positions selected, entries
    ``1 .. count`` those blocks in rising order; ``masks[..., 1 + i]`` has bit
    ``p`` set where the tile's position ``p`` selected block ``lists[..., 1 +
    i]``. Entries past the count are 0."""
    B, G, L, topk = selected.shape
    if min(tile * topk, nb) >= LIST_WIDTH:
        raise ValueError(f"a tile of {tile} x top-{topk} over {nb} blocks can name more than "
                         f"{LIST_WIDTH - 1} blocks")
    Lp = round_up(L, tile)
    sel = jnp.pad(selected, ((0, 0), (0, 0), (0, Lp - L), (0, 0)), constant_values=-1)
    sel = sel.reshape(B, G, Lp // tile, tile, topk)
    bit = (1 << jnp.arange(tile, dtype=jnp.int32))[:, None, None]
    # bit p of block b: one comparison a (position, choice, block), summed
    bits = jnp.where(sel[..., None] == jnp.arange(nb, dtype=jnp.int32), bit, 0).sum(
        axis=(3, 4), dtype=jnp.int32)                                    # [B, G, tiles, nb]
    present = bits != 0
    iota = jnp.arange(nb, dtype=jnp.int32)
    # the bits ride along the sort of the blocks: a gather of them by the
    # sorted order took as long on a v5e as the core itself
    order, bits = jax.lax.sort((jnp.where(present, iota, nb + iota), bits), dimension=3,
                               num_keys=1)
    width = min(tile * topk, nb)
    order, bits = order[..., :width], bits[..., :width]
    valid = order < nb
    blocks = jnp.where(valid, order, 0)
    masks = jnp.where(valid, bits, 0)
    count = present.sum(axis=-1, dtype=jnp.int32)[..., None]
    pad = ((0, 0),) * 3 + ((0, LIST_WIDTH - 1 - width),)
    lists = jnp.concatenate([count, jnp.pad(blocks, pad)], axis=-1)
    masks = jnp.concatenate([jnp.zeros_like(count), jnp.pad(masks, pad)], axis=-1)
    return lists, masks


def _attend_lists_jnp(q, k, v, lists, masks, *, width, block, scale, tile):
    """The ``jnp`` tier of the core: each tile gathers the ``width`` first
    blocks its list names and attends over them under its positions' bits and
    the causal mask, as the kernel does."""
    B, L, H, d = q.shape
    G, dv = k.shape[2], v.shape[-1]
    r = H // G
    tiles = lists.shape[2]
    nb = -(-L // block)
    Lp, Lk = tiles * tile, nb * block
    qt = jnp.pad(q, ((0, 0), (0, Lp - L), (0, 0), (0, 0))).reshape(B, tiles, tile, G, r, d)

    def by_block(a):  # [B, L, G, e] -> [B, G, nb, block, e]
        a = jnp.pad(a, ((0, 0), (0, Lk - L), (0, 0), (0, 0)))
        return a.reshape(B, nb, block, G, a.shape[-1]).transpose(0, 3, 1, 2, 4)

    kb, vb = by_block(k), by_block(v)
    names = lists[..., 1:1 + width]                                      # [B, G, tiles, width]
    bits = jnp.where(jnp.arange(width) < lists[..., :1], masks[..., 1:1 + width], 0)

    def one(args):
        i, q_i, names_i, bits_i = args          # [B, tile, G, r, d], [B, G, width] x 2
        keys = jnp.take_along_axis(kb, names_i[..., None, None], axis=2)  # [B, G, width, block, d]
        vals = jnp.take_along_axis(vb, names_i[..., None, None], axis=2)
        s = jnp.einsum("bpgxd,bgnkd->bgpxnk", q_i, keys, preferred_element_type=jnp.float32) * scale
        pos = i * tile + jnp.arange(tile)                                # [tile]
        key_pos = names_i[..., None] * block + jnp.arange(block)         # [B, G, width, block]
        member = (bits_i[:, :, None, :] >> jnp.arange(tile)[:, None]) & 1   # [B, G, tile, width]
        allowed = (member[..., None] == 1) & (key_pos[:, :, None] <= pos[:, None, None])
        s = jnp.where(allowed[:, :, :, None], s, -jnp.inf).reshape(B, G, tile, r, -1)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
        l = p.sum(axis=-1, keepdims=True)
        o = jnp.einsum("bgpxn,bgnd->bgpxd", p.astype(v.dtype), vals.reshape(B, G, -1, dv),
                       preferred_element_type=jnp.float32)
        return (o / jnp.where(l > 0, l, 1.0)).transpose(0, 2, 1, 3, 4)   # [B, tile, G, r, dv]

    out = jax.lax.map(one, (jnp.arange(tiles), jnp.moveaxis(qt, 1, 0),
                            jnp.moveaxis(names, 2, 0), jnp.moveaxis(bits, 2, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(B, Lp, H, dv)[:, :L].astype(q.dtype)


def block_sparse_attention(q, k, v, selected, *, block: int, scale: float,
                           use_pallas: Optional[bool] = None, interpret: bool = False):
    """``q [B, L, H, d]``, ``k``, ``v`` ``[B, L, G, d]``, the selection
    ``[B, G, L, topk]`` -> ``(out [B, L, H, dv], fetched [B] int32)``:
    attention of each query over the keys ``s <= t`` of its selected blocks;
    ``fetched`` counts the blocks the core visits, each once for every query
    position of its tile: the kernel's steps of ``BLOCKS_PER_STEP`` blocks
    that cover the tile's list (the last step's entries past the list
    included), or the ``jnp`` tier's ``width`` gathered blocks a tile."""
    L, tile = q.shape[1], TILE_Q
    nb = -(-L // block)
    width = min(tile * selected.shape[-1], nb)     # the most blocks a tile's list can name
    lists, masks = tile_lists(selected, nb, tile)
    if _use_pallas(use_pallas):
        from gigapath_tpu.ops import pallas_block_sparse

        if pallas_block_sparse.fits(q.shape, k.shape, block, tile, width):
            step = pallas_block_sparse.BLOCKS_PER_STEP
            steps = (lists[..., 0] + step - 1) // step
            fetched = steps.sum(axis=(1, 2), dtype=jnp.int32) * (step * tile)
            return pallas_block_sparse.block_sparse_attn_fwd(
                q, k, v, lists, masks, block=block, scale=scale, tile=tile,
                interpret=interpret), fetched
    fetched = jnp.full((q.shape[0],), lists.shape[1] * lists.shape[2] * width * tile, jnp.int32)
    with jax.named_scope("kernel_fwd"):
        out = _attend_lists_jnp(q, k, v, lists, masks, width=width, block=block, scale=scale,
                                tile=tile)
    return out, fetched


def selection_counts(selected: jnp.ndarray, block: int):
    """``selected [B, G, L, topk]`` -> ``(pairs, blocks)``, each ``[B]``
    int32: the (query, key) pairs ``s <= t`` the selection hands the core,
    summed over the KV groups, and the blocks it names."""
    L = selected.shape[2]
    t = jnp.arange(L, dtype=jnp.int32)[:, None]
    valid = selected >= 0
    keys = jnp.clip(t - block * selected + 1, 0, block)
    pairs = jnp.where(valid, keys, 0).sum(axis=(1, 2, 3), dtype=jnp.int32)
    return pairs, valid.sum(axis=(1, 2, 3), dtype=jnp.int32)


def infllm_attention(q, k, v, spec: SparseSpec, *, scale: float,
                     use_pallas: Optional[bool] = None, interpret: bool = False):
    """The layer's core under the program's scope names: ``q [B, L, H, d]``,
    ``k``, ``v`` ``[B, L, G, d]`` -> ``(out [B, L, H, dv], {"selected_pairs",
    "kv_blocks_fetched", "kv_blocks_selected"})``, the three ``[B]`` int32.
    Up to ``spec.dense_len`` tokens a causal flash core over every earlier
    key, whose counters count every causal pair and block."""
    B, L, H, _ = q.shape
    G = k.shape[2]
    if spec.init_blocks + -(-(spec.window_size - 1) // spec.block_size) + 1 > spec.topk:
        raise ValueError(f"{spec}: the forced blocks can outnumber the top {spec.topk}")
    if L <= spec.dense_len:
        with jax.named_scope("attn_core"):
            out, _ = _gate.flash_attention(q, k, v, is_causal=True, scale=scale)
        blocks = G * int(np.sum(np.arange(L) // spec.block_size + 1))
        counts = (G * L * (L + 1) // 2, blocks, blocks)
        full = {name: jnp.full((B,), n, jnp.int32) for name, n in
                zip(("selected_pairs", "kv_blocks_fetched", "kv_blocks_selected"), counts)}
        return out, full
    with jax.named_scope("block_score"):
        scores = compressed_scores(q, k, kernel=spec.kernel_size, stride=spec.kernel_stride,
                                   block=spec.block_size, scale=scale, use_pallas=use_pallas,
                                   interpret=interpret)
    with jax.named_scope("block_select"):
        selected = select_blocks(scores, topk=spec.topk, block=spec.block_size,
                                 init_blocks=spec.init_blocks, window=spec.window_size)
        pairs, named = selection_counts(selected, spec.block_size)
    with jax.named_scope("attn_core"):
        out, fetched = block_sparse_attention(q, k, v, selected, block=spec.block_size,
                                              scale=scale, use_pallas=use_pallas,
                                              interpret=interpret)
    return out, {"selected_pairs": pairs, "kv_blocks_fetched": fetched,
                 "kv_blocks_selected": named}
