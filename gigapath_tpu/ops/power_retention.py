"""Power retention of degree 2: a gated, normalised linear attention whose
state is a degree-2 feature expansion of the keys (Manifest AI, "Scaling
Context Requires Rethinking Attention"; Brumby's token mixer).

Per query head ``h`` over the KV head ``g`` its group reads, with ``log
gamma_s <= 0`` one gate a KV head and position and ``G_t`` its running sum::

    y_t = sum_{s<=t} e^{G_t - G_s} (q_t . k_s)^2 v_s
          / (sum_{s<=t} e^{G_t - G_s} (q_t . k_s)^2 + eps)

The chunked form: with ``phi(x) = [x_i^2 ; sqrt(2) x_i x_j (i < j)]``,
``phi(q) . phi(k) = (q . k)^2`` (``D = d (d + 1) / 2`` features). The
sequence is cut into chunks of ``chunk`` positions whose running gate sum
``a_t`` restarts at the chunk's first position (no float32 sum is ever taken
over the whole sequence). What a chunk hands the next is the state ``S [D,
d]`` and the normaliser's state ``z [D]`` of its KV head, float32::

    S_c = e^{a_end} S_{c-1} + sum_{s in c} e^{a_end - a_s} phi(k_s) v_s^T
    z_c = e^{a_end} z_{c-1} + sum_{s in c} e^{a_end - a_s} phi(k_s)
    num_t = e^{a_t} phi(q_t)^T S_{c-1} + sum_{s in c, s <= t} e^{a_t - a_s} (q_t . k_s)^2 v_s
    den_t = e^{a_t} phi(q_t) . z_{c-1} + sum_{s in c, s <= t} e^{a_t - a_s} (q_t . k_s)^2

Two tiers, chosen by the library's one device gate and the shapes: the
``jnp`` tier here (any backend; blocks of chunks inside one ``lax.scan`` that
carries the state, as :mod:`gigapath_tpu.ops.ssd` has it) and the Pallas
kernel of :mod:`gigapath_tpu.ops.pallas_retention` (a TPU, heads of 128,
chunks of a multiple of 128), which keeps the expansion and the state in
VMEM. Both return, beside ``y``, the share of each query's denominator that
came through the state handed between chunks.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gigapath_tpu.ops import flash_attention as _gate
from gigapath_tpu.ops.common import round_up

EPS = 1e-6
# chunks whose [heads, chunk, chunk] weights and expanded queries are alive together
CHUNKS_PER_BLOCK = 4


@functools.lru_cache(maxsize=None)
def _pairs(d: int):
    """``(i, j, coefficient)`` of the ``d (d + 1) / 2`` features, ``i <= j``."""
    i, j = np.triu_indices(d)
    return i, j, np.where(i == j, 1.0, np.sqrt(2.0)).astype(np.float32)


def feature_map(x: jnp.ndarray) -> jnp.ndarray:
    """``phi(x)`` over the last axis, float32: ``[..., d] -> [..., d (d + 1) /
    2]``, ``x_i^2`` and ``sqrt(2) x_i x_j`` for ``i < j``."""
    i, j, coef = _pairs(x.shape[-1])
    x = x.astype(jnp.float32)
    return x[..., i] * x[..., j] * coef


def chunk_gate_sums(log_gate: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """``log_gate [B, Lp, G]`` (``Lp`` a multiple of ``chunk``) -> the running
    sum ``a`` restarted at each chunk's first position, float32, same shape."""
    B, Lp, G = log_gate.shape
    blocks = log_gate.astype(jnp.float32).reshape(B, Lp // chunk, chunk, G)
    return jnp.cumsum(blocks, axis=2).reshape(B, Lp, G)


def _advance(S, z, decay, chunk_S, chunk_z):
    """One chunk of the recurrence: the state a chunk hands on is the state it
    was handed, decayed over the chunk (``decay [B, G]``), plus what the
    chunk's keys left."""
    return decay[..., None, None] * S + chunk_S, decay[..., None] * z + chunk_z


def _chunk_block(eps, carry, block):
    """``cb`` chunks at once; ``carry = (S [B, G, D, d], z [B, G, D])`` float32
    enters the first. ``q [B, cb, C, G, r, d]``, ``k``, ``v`` ``[B, cb, C, G,
    d]`` float32, ``a [B, cb, C, G]``. Returns the carry the block hands on
    and ``(y [B, cb, C, G, r, d], carried share [B, cb, C, G, r])``."""
    S, z = carry
    q, k, v, a = block
    C = q.shape[2]
    # inside a chunk: (q_t . k_s)^2 e^{a_t - a_s}, s <= t; the exponent is masked,
    # not the exponential: above the diagonal the difference is positive
    scores = jnp.einsum("bctgrd,bcsgd->bcgrts", q, k)
    lower = jnp.tril(jnp.ones((C, C), bool))
    a_g = jnp.moveaxis(a, 3, 2)                                        # [B, cb, G, C]
    decay = jnp.exp(jnp.where(lower, a_g[..., :, None] - a_g[..., None, :], -jnp.inf))
    w = scores * scores * decay[:, :, :, None]                         # [B, cb, G, r, C, C]
    num = jnp.einsum("bcgrts,bcsgd->bctgrd", w, v)
    den = jnp.moveaxis(w.sum(-1), 4, 2)                                # [B, cb, C, G, r]
    # what each chunk leaves: sum_s e^{a_end - a_s} phi(k_s) [v_s | 1]
    left = jnp.exp(a[:, :, -1:] - a)                                   # [B, cb, C, G]
    phik = feature_map(k) * left[..., None]
    chunk_S = jnp.einsum("bcsgD,bcsgd->bcgDd", phik, v)
    chunk_z = phik.sum(2)                                              # [B, cb, G, D]
    # the states that enter each chunk, by the recurrence over the block's chunks
    entering_S, entering_z = [], []
    for c in range(q.shape[1]):
        entering_S.append(S)
        entering_z.append(z)
        S, z = _advance(S, z, jnp.exp(a[:, c, -1]), chunk_S[:, c], chunk_z[:, c])
    entering_S, entering_z = jnp.stack(entering_S, 1), jnp.stack(entering_z, 1)
    phiq = feature_map(q) * jnp.exp(a)[..., None, None]                # [B, cb, C, G, r, D]
    num = num + jnp.einsum("bctgrD,bcgDd->bctgrd", phiq, entering_S)
    carried = jnp.einsum("bctgrD,bcgD->bctgr", phiq, entering_z)
    whole = den + carried + eps
    return (S, z), (num / whole[..., None], carried / whole)


def power_retention_jnp(q, k, v, log_gate, *, chunk: int, eps: float = EPS):
    """The ``jnp`` tier of :func:`power_retention`, float32 inside."""
    B, L, H, d = q.shape
    G = k.shape[2]
    r = H // G
    Lp = round_up(L, chunk)
    nc = Lp // chunk
    cb = max(n for n in range(1, min(CHUNKS_PER_BLOCK, nc) + 1) if nc % n == 0)

    def blocks(x):  # [B, L, ...] -> [blocks, B, cb, chunk, ...], zeros in the tail
        x = jnp.pad(x, ((0, 0), (0, Lp - L)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(B, nc // cb, cb, chunk, *x.shape[2:]), 1, 0)

    f32 = jnp.float32
    # a padded position has a gate of 1 and a zero key: it neither decays nor adds
    a = chunk_gate_sums(jnp.pad(log_gate.astype(f32), ((0, 0), (0, Lp - L), (0, 0))), chunk)
    a = jnp.moveaxis(a.reshape(B, nc // cb, cb, chunk, G), 1, 0)
    D = d * (d + 1) // 2
    carry = (jnp.zeros((B, G, D, d), f32), jnp.zeros((B, G, D), f32))
    _, (y, carried) = jax.lax.scan(
        functools.partial(_chunk_block, eps), carry,
        (blocks(q.astype(f32).reshape(B, L, G, r, d)), blocks(k.astype(f32)),
         blocks(v.astype(f32)), a))
    y = jnp.moveaxis(y, 0, 1).reshape(B, Lp, H, d)[:, :L]
    carried = jnp.moveaxis(carried, 0, 1).reshape(B, Lp, H)[:, :L]
    return y.astype(v.dtype), carried


def power_retention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, log_gate: jnp.ndarray, *,
                    chunk: int = 128, eps: float = EPS) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``q [B, L, H, d]``, ``k``, ``v`` ``[B, L, G, d]`` (query head ``h`` reads
    KV head ``h // (H / G)``), ``log_gate [B, L, G]`` (``<= 0``) -> ``(y [B, L,
    H, d]`` in ``v``'s type, ``carried [B, L, H]`` float32``)``: ``carried`` is
    ``e^{a_t} phi(q_t) . z_{c-1}``, the part of each query's denominator that
    came through the state handed between chunks, over the whole denominator
    (``eps`` in it). ``L`` need be no multiple of ``chunk``. The kernel where
    the device gate says TPU and the head is 128 wide and the chunk a multiple
    of 128, the ``jnp`` tier elsewhere; either opens the scope ``kernel_fwd``."""
    if _gate._on_tpu() and q.shape[-1] == 128 and chunk % 128 == 0:
        from gigapath_tpu.ops.pallas_retention import power_retention_fwd

        return power_retention_fwd(q, k, v, log_gate, chunk=chunk, eps=eps)
    with jax.named_scope("kernel_fwd"):
        return power_retention_jnp(q, k, v, log_gate, chunk=chunk, eps=eps)
