"""Pallas TPU kernel of power retention's forward
(:mod:`gigapath_tpu.ops.power_retention` has the mathematics and the
dispatch): ``power_retention_fwd``.

One grid step is one chunk of one KV head; the chunk axis runs in order and
the KV head's state stays in VMEM scratch from one chunk to the next, so
neither the expanded features nor the state ever reach HBM. The layout puts
positions on the lanes and features on the sublanes (``q^T [d, r C]``, the
``r`` query heads of the group side by side), and the state transposed:

- ``S^T [d + 8, P d]`` float32: rows ``0..d-1`` the values' state, row ``d``
  the normaliser's, seven rows of zeros. The normaliser rides as an extra
  *row* of every product, which costs 8 sublanes, where a ``[D, d + 1]``
  state would cost 128 more lanes.
- The ``d (d + 1) / 2`` features are ``P = d / 2 + 1`` slices of ``d`` rows
  each (``P d = 8,320`` for heads of 128): slice ``p`` holds the pairs ``(p,
  p + l)`` in rows ``l < d - p`` and the pairs ``(d - p, l)`` in the rows
  after, so a slice is whole rows, built from ``x^T`` and ``x^T`` shifted by
  ``p`` rows times one row broadcast, never a lane shuffle. Slice ``d / 2``
  uses only its first half; its second half repeats the first and is zeroed
  on the keys' side.
- The keys' side carries the coefficients (1 on ``i == j``, 2 off it), the
  queries' side none: ``phi_q . phi_k = (q . k)^2`` all the same, and the
  five query heads skip a multiply a feature.

A step: the chunk's own part (``k q^T``, squared, decayed, against ``[v^T ;
1]``), then for each slice the carried read ``S^T_p phi_p(q^T)`` and the
update ``S^T_p <- e^{a_end} S^T_p + [v^T ; 1] e^{a_end - a} phi_p(k^T)^T``,
matrix operands in bfloat16 with float32 accumulation, the state float32.
Forward only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gigapath_tpu.ops.common import round_up
from gigapath_tpu.ops.power_retention import EPS, chunk_gate_sums

_AUG = 8  # the normaliser's row and seven of padding under the values' d rows


def _slice(x_ref, x, p: int, d: int):
    """Slice ``p`` of the features of ``x [d, n]`` (float32, copied twice
    down the rows of ``x_ref [2 d, n]``), without coefficients: ``[d, n]``."""
    first = jax.lax.broadcasted_iota(jnp.int32, (d, 1), 0) < d - p
    return jnp.where(first, x_ref[p:p + d, :] * x_ref[p:p + 1, :],
                     x * x_ref[d - p:d - p + 1, :])


def _key_coefficients(p: int, d: int):
    """``[d, 1]``: 1 on the pair ``(i, i)``, 2 off it, 0 on the repeated half
    of slice ``d / 2``."""
    row = jax.lax.broadcasted_iota(jnp.int32, (d, 1), 0)
    coef = jnp.where((row == 0) | (row == d - p), 1.0, 2.0)
    return jnp.where(row < d - p, coef, 0.0) if 2 * p == d else coef


def _retention_kernel(q_ref, k_ref, kt_ref, vt_ref, arow_ref, acol_ref,
                      y_ref, share_ref, st_ref, qq_ref, kk_ref, *, r, d, chunk, eps):
    C, bf16, f32 = chunk, jnp.bfloat16, jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _start():
        st_ref[...] = jnp.zeros(st_ref.shape, f32)

    a_row = arow_ref[0, 0]                                          # [1, C]
    a_col = acol_ref[0, 0]                                          # [C, 1]
    a_end = jnp.broadcast_to(a_col[C - 1:C, :], (1, max(C, d)))     # a lane broadcast
    q_t = jnp.concatenate([q_ref[0, 0, h] for h in range(r)], axis=1)   # [d, r C] bf16
    qq_ref[0:d, :] = q_t.astype(f32)
    qq_ref[d:2 * d, :] = qq_ref[0:d, :]
    k_t = kt_ref[0, 0].astype(f32)                                  # [d, C]
    kk_ref[0:d, :] = k_t
    kk_ref[d:2 * d, :] = k_t
    v_aug = jnp.concatenate(
        [vt_ref[0, 0].astype(f32), jnp.ones((1, C), f32), jnp.zeros((_AUG - 1, C), f32)],
        axis=0)                                                     # [d + 8, C]

    # the chunk's own part: rows s, columns (head, t); the exponent is masked
    scores = jnp.dot(k_ref[0, 0], q_t, preferred_element_type=f32)  # [C, r C]
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    decay = jnp.exp(jnp.where(s_idx <= t_idx, a_row - a_col, -jnp.inf))
    weights = scores * scores * jnp.concatenate([decay] * r, axis=1)
    out = jnp.dot(v_aug.astype(bf16), weights.astype(bf16), preferred_element_type=f32)

    # the state handed in: read by the group's queries, then advanced by the chunk
    left = (v_aug * jnp.exp(a_end[:, :C] - a_row)).astype(bf16)     # [d + 8, C]
    end = jnp.exp(a_end[:, :d])                                     # [1, d]
    q_f = qq_ref[0:d, :]
    carried = jnp.zeros(out.shape, f32)
    for p in range(d // 2 + 1):
        cols = slice(p * d, (p + 1) * d)
        state = st_ref[:, cols]                                     # [d + 8, d]
        carried = carried + jnp.dot(state.astype(bf16), _slice(qq_ref, q_f, p, d).astype(bf16),
                                    preferred_element_type=f32)
        phi_k = (_slice(kk_ref, k_t, p, d) * _key_coefficients(p, d)).astype(bf16)   # [d, C]
        st_ref[:, cols] = end * state + jax.lax.dot_general(
            left, phi_k, (((1,), (1,)), ((), ())), preferred_element_type=f32)

    carried = carried * jnp.exp(jnp.concatenate([a_row] * r, axis=1))
    whole = out[d:d + 1] + carried[d:d + 1] + eps                   # [1, r C]
    y = (out[0:d] + carried[0:d]) / whole
    share = carried[d:d + 1] / whole
    for h in range(r):
        y_ref[0, 0, h] = y[:, h * C:(h + 1) * C].astype(y_ref.dtype)
        share_ref[0, 0, h:h + 1, :] = share[:, h * C:(h + 1) * C]


def power_retention_fwd(q, k, v, log_gate, *, chunk=128, eps=EPS, interpret=False):
    """``q [B, L, H, d]``, ``k``, ``v`` ``[B, L, G, d]``, ``log_gate [B, L,
    G]`` -> ``(y [B, L, H, d]`` in ``v``'s type, ``carried [B, L, H]``
    float32``)``, as :func:`gigapath_tpu.ops.power_retention.power_retention`
    returns them. ``d`` even; on a TPU ``d`` is 128 and ``chunk`` a multiple
    of 128."""
    B, L, H, d = q.shape
    G = k.shape[2]
    r = H // G
    Lp = round_up(L, chunk)

    def pad(x):
        return jnp.pad(x, ((0, 0), (0, Lp - L)) + ((0, 0),) * (x.ndim - 2))

    # a padded position has a gate of 1 and a zero key: it neither decays nor adds
    a = chunk_gate_sums(pad(log_gate.astype(jnp.float32)), chunk).transpose(0, 2, 1)   # [B, G, Lp]
    q_t = pad(q).reshape(B, Lp, G, r, d).transpose(0, 2, 3, 4, 1)   # [B, G, r, d, Lp]
    k_rows = pad(k).transpose(0, 2, 1, 3)                           # [B, G, Lp, d]
    k_t, v_t = (pad(x).transpose(0, 2, 3, 1) for x in (k, v.astype(k.dtype)))   # [B, G, d, Lp]
    grid = (B, G, Lp // chunk)
    kernel = functools.partial(_retention_kernel, r=r, d=d, chunk=chunk, eps=eps)

    def spec(*block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    with jax.named_scope("kernel_fwd"):
        y, share = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                spec(1, 1, r, d, chunk, index=lambda b, g, c: (b, g, 0, 0, c)),
                spec(1, 1, chunk, d, index=lambda b, g, c: (b, g, c, 0)),
                spec(1, 1, d, chunk, index=lambda b, g, c: (b, g, 0, c)),
                spec(1, 1, d, chunk, index=lambda b, g, c: (b, g, 0, c)),
                spec(1, 1, 1, chunk, index=lambda b, g, c: (b, g, 0, c)),
                spec(1, 1, chunk, 1, index=lambda b, g, c: (b, g, c, 0)),
            ],
            out_specs=[
                spec(1, 1, r, d, chunk, index=lambda b, g, c: (b, g, 0, 0, c)),
                spec(1, 1, r, chunk, index=lambda b, g, c: (b, g, 0, c)),
            ],
            out_shape=[jax.ShapeDtypeStruct((B, G, r, d, Lp), v.dtype),
                       jax.ShapeDtypeStruct((B, G, r, Lp), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((d + _AUG, (d // 2 + 1) * d), jnp.float32),
                            pltpu.VMEM((2 * d, r * chunk), jnp.float32),
                            pltpu.VMEM((2 * d, chunk), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="power_retention_fwd",
        )(q_t, k_rows, k_t, v_t, a[:, :, None, :], a[:, :, :, None])
    y = y.transpose(0, 4, 1, 2, 3).reshape(B, Lp, H, d)[:, :L]
    return y, share.transpose(0, 3, 1, 2).reshape(B, Lp, H)[:, :L]
