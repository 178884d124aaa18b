"""Learned sparse attention: index scores, an exact top-k a query, and a
causal core over the selected keys (DeepSeek-V3.2's "lightning indexer" in
front of latent attention).

Three steps, each with a ``jnp`` tier (any backend) and a Pallas tier (a TPU,
:mod:`gigapath_tpu.ops.pallas_sparse`), chosen by the library's one device
gate::

    I[t, s] = sum_h w[t, h] * relu(q[t, h] . k[s])          float32, one key for all heads
    S_t     = the min(t + 1, topk) keys s <= t of largest I[t, s]; ties to the lower s
    out[t]  = softmax over s in S_t of (q[t] . k[s] * scale) v[s]

:func:`index_scores` never holds ``[heads, L, L]``: a block of query rows at a
time on the ``jnp`` tier, a ``[block_q, block_k]`` tile on the kernel's.
:func:`select_topk` is exact: the selection is the set ``jax.lax.top_k``
returns (which puts the lower index first among equals; -0.0 is taken as
0.0, so that equal means what it means for floats), handed on as a
``[B, L, L]`` int8 mask, 1 where ``s`` is in ``S_t``; nothing above the
diagonal is ever 1. The kernel finds each row's ``k``-th largest score by
bisection over the 32 bits of an order-preserving integer key, then, among
the scores equal to it, the lowest columns by bisection over the column
index. :func:`sparse_attention` is a causal flash core that reads the mask a
tile at a time beside the keys; it visits every key block at or below the
diagonal (a query's keys are wherever its scores put them), so its cost is a
dense causal core's, and what the selection saves is what a kernel that
visits only the selected keys would save. Such skipping exists for a selection
by key block (:mod:`gigapath_tpu.ops.block_sparse`, whose ``block_sparse_attn``
visits the blocks a tile of queries chose and no other); for this per-key
selection it does not yet.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from gigapath_tpu.ops import flash_attention as _gate

# query rows a step of the jnp tier's index scores: [rows, heads, L] float32 is held
_JNP_SCORE_ROWS = 128


def _use_pallas(use_pallas: Optional[bool], length: int) -> bool:
    if use_pallas is None:
        return _gate._on_tpu() and length >= _gate.PALLAS_MIN_SEQ
    return use_pallas


def index_scores(q: jnp.ndarray, k: jnp.ndarray, w: jnp.ndarray, *,
                 use_pallas: Optional[bool] = None, interpret: bool = False) -> jnp.ndarray:
    """``q [B, L, H, D]``, ``k [B, L, D]`` (one key for all heads), ``w [B, L,
    H]`` float32 -> ``I [B, L, L]`` float32. Only ``s <= t`` means anything:
    the kernel leaves whole tiles above the diagonal unwritten, and
    :func:`select_topk` reads none of it."""
    B, L, H, D = q.shape
    if _use_pallas(use_pallas, L):
        from gigapath_tpu.ops.pallas_sparse import index_score_fwd

        return index_score_fwd(q, k, w.astype(jnp.float32), interpret=interpret)
    with jax.named_scope("kernel_fwd"):
        rows = min(_JNP_SCORE_ROWS, L)
        pad = -L % rows
        qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(B, -1, rows, H, D)
        wb = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, pad), (0, 0))).reshape(B, -1, rows, H)

        def block(args):
            q_blk, w_blk = args                                        # [B, rows, H, D], [B, rows, H]
            s = jnp.einsum("bthd,bsd->bths", q_blk, k, preferred_element_type=jnp.float32)
            return jnp.einsum("bths,bth->bts", jax.nn.relu(s), w_blk,
                              precision=jax.lax.Precision.HIGHEST)

        out = jax.lax.map(block, (qb.swapaxes(0, 1), wb.swapaxes(0, 1)))  # [blocks, B, rows, L]
        return out.swapaxes(0, 1).reshape(B, L + pad, L)[:, :L]


def select_topk(scores: jnp.ndarray, topk: int, *, use_pallas: Optional[bool] = None,
                interpret: bool = False) -> jnp.ndarray:
    """``scores [B, L, L]`` float32 -> ``mask [B, L, L]`` int8: row ``t`` has
    exactly ``min(t + 1, topk)`` ones, on the keys ``s <= t`` of largest score,
    ties to the lower ``s``."""
    B, L, _ = scores.shape
    if _use_pallas(use_pallas, L):
        from gigapath_tpu.ops.pallas_sparse import index_select_fwd

        return index_select_fwd(scores, topk, interpret=interpret)
    with jax.named_scope("kernel_fwd"):
        t = jnp.arange(L)
        causal = t[None, :] <= t[:, None]
        k = min(topk, L)
        scores = jnp.where(scores == 0, 0.0, scores)       # -0.0 is 0.0's equal, not its lesser
        _, chosen = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), k)      # [B, L, k]
        real = jnp.arange(k)[None, :] <= t[:, None]                            # the first t + 1 are keys
        mask = jnp.zeros((B, L, L), jnp.int8)
        return mask.at[jnp.arange(B)[:, None, None], t[None, :, None], chosen].max(
            jnp.broadcast_to(real, chosen.shape).astype(jnp.int8))


def sparse_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray, *,
                     scale: float, use_pallas: Optional[bool] = None,
                     interpret: bool = False) -> jnp.ndarray:
    """``q, k [B, L, H, D]``, ``v [B, L, H, Dv]``, ``mask [B, L, L]`` int8
    (one selection for all heads) -> ``[B, L, H, Dv]``: every query attends
    to the keys its row of ``mask`` names and to no other. A row with no key
    gives 0."""
    if _use_pallas(use_pallas, q.shape[1]):
        from gigapath_tpu.ops.pallas_sparse import sparse_attn_fwd

        return sparse_attn_fwd(q, k, v, mask, scale=scale, interpret=interpret)
    with jax.named_scope("kernel_fwd"):
        s = jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32) * scale
        keep = (mask != 0)[:, None]
        s = jnp.where(keep, s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(keep, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
        p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        return jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(v.dtype)


def selected_pairs(mask: jnp.ndarray) -> jnp.ndarray:
    """How many (query, key) pairs the selection handed the core, a sequence:
    the ones of ``mask [B, L, L]``, counted on the device. ``[B]`` int32."""
    return jnp.sum(mask, axis=(1, 2), dtype=jnp.int32)


def sparse_index_attention(
    q_index: jnp.ndarray, k_index: jnp.ndarray, w_index: jnp.ndarray,
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *, topk: int, scale: float,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The three steps under the scopes the trace is read by (``indexer/score``
    stands inside the caller's ``indexer`` scope): ``(out [B, L, H, Dv], pairs
    selected [B] int32, the selection [B, L, L] int8)``."""
    with jax.named_scope("indexer"), jax.named_scope("score"):
        scores = index_scores(q_index, k_index, w_index)
    with jax.named_scope("select"):
        mask = select_topk(scores, topk)
        pairs = selected_pairs(mask)
    with jax.named_scope("attn_core"):
        out = sparse_attention(q, k, v, mask, scale=scale)
    return out, pairs, mask
