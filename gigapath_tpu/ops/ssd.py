"""Mamba-2 state-space mixer: fused input projection, causal depthwise
convolution, the chunked state-space-duality (SSD) scan, gated RMSNorm and
the output projection.

The recurrence, per head with state ``S [P, N]`` (``P`` the head size, ``N``
the state size; ``B`` and ``C`` are shared by the heads of a group: Mamba-2
has one group, and decayed linear attention (:func:`linear_scan`) a group a
head)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

The chunked form (Dao & Gu 2024, "Transformers are SSMs", section 6) cuts
the sequence into chunks of ``Q`` positions. Inside a chunk the output is a
masked matrix product, ``(C B^T * decay * dt) x``; what a chunk leaves
behind is one ``[P, N]`` state per head, carried to the next chunk by the
recurrence above taken ``Q`` steps at a time.

Two tiers, chosen by the library's one device gate and the shapes: the
``jnp`` tier here (any backend; plain XLA einsums, the decay matrix ``[heads,
Q, Q]`` in float32 built for a block of chunks at a time inside one
``lax.scan`` that carries the state, so a 16,384-token sequence never holds
all 64 chunks' matrices (2.1 GB) at once) and the Pallas kernel of
:mod:`gigapath_tpu.ops.pallas_ssd` (a TPU, widths its ``fits`` takes), which
reads ``x``, ``B`` and ``C`` where the convolution leaves them and keeps the
state in VMEM.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from gigapath_tpu.ops import flash_attention as _gate
from gigapath_tpu.ops.common import round_up
from gigapath_tpu.ops.norms import RMSNorm

# Chunks whose decay matrices are alive together: 8 x 128 heads x 256 x 256
# float32 is 268 MB at the published sizes.
CHUNKS_PER_BLOCK = 8


def causal_conv1d(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray) -> jnp.ndarray:
    """Causal depthwise convolution over ``x [B, L, C]`` with ``weight [K, C]``
    (tap ``K - 1`` multiplies the current position) and ``bias [C]``, in
    float32: ``y_t = sum_j w_j x_{t - (K - 1) + j} + b``."""
    K, L = weight.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    taps = sum(xp[:, j:j + L].astype(jnp.float32) * w[j] for j in range(K))
    return taps + bias.astype(jnp.float32)


def _advance(state, decay, chunk_state):
    """One chunk of the recurrence: the state a chunk hands on is the state it
    was handed, decayed over the chunk, plus what the chunk's inputs left."""
    return decay[..., None, None] * state + chunk_state


def _chunk_block(D, state, block):
    """``cb`` chunks at once: ``state [b, H, P, N]`` float32 enters the first.
    ``x [b, cb, Q, H, P]``, ``dt`` and ``acs`` (the running sum of ``dt A``
    inside each chunk) ``[b, cb, H, Q]`` float32, ``B``, ``C`` ``[b, cb, Q, N]``
    (one group) or ``[b, cb, Q, H, N]`` (a group a head). The block's ``y``
    leaves in ``x``'s type, summed in float32 with the ``D x`` skip before
    that one rounding."""
    x, dt, acs, B, C = block
    Q = x.shape[2]
    per_head = B.ndim == 5
    # inside a chunk: y_i = sum_{j <= i} (C_i . B_j) exp(acs_i - acs_j) dt_j x_j
    if per_head:
        scores = jnp.einsum("bcqhn,bckhn->bchqk", C, B, preferred_element_type=jnp.float32)
    else:
        scores = jnp.einsum("bcqn,bckn->bcqk", C, B, preferred_element_type=jnp.float32)
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    # the exponent is masked, not the exponential: above the diagonal the
    # difference is positive and would overflow
    decay = jnp.exp(jnp.where(lower, acs[..., :, None] - acs[..., None, :], -jnp.inf))
    mixed = ((scores if per_head else scores[:, :, None]) * decay
             * dt[..., None, :]).astype(x.dtype)
    y = jnp.einsum("bchqk,bckhp->bcqhp", mixed, x, preferred_element_type=jnp.float32)
    # what each chunk leaves: sum_j exp(acs_last - acs_j) dt_j x_j B_j^T
    left = (jnp.exp(acs[..., -1:] - acs) * dt).transpose(0, 1, 3, 2)[..., None]
    chunk_states = jnp.einsum(
        "bcqhn,bcqhp->bchpn" if per_head else "bcqn,bcqhp->bchpn", B,
        (x.astype(jnp.float32) * left).astype(x.dtype), preferred_element_type=jnp.float32)
    # the state that enters each chunk, by the recurrence over the block's chunks
    entering = []
    for c in range(x.shape[1]):
        entering.append(state)
        state = _advance(state, jnp.exp(acs[:, c, :, -1]), chunk_states[:, c])
    entering = jnp.stack(entering, axis=1).astype(x.dtype)
    carried = jnp.einsum("bcqhn,bchpn->bcqhp" if per_head else "bcqn,bchpn->bcqhp", C, entering,
                         preferred_element_type=jnp.float32)
    y = y + carried * jnp.exp(acs).transpose(0, 1, 3, 2)[..., None]
    return state, (y + D[:, None] * x.astype(jnp.float32)).astype(x.dtype)


def ssd_scan_jnp(x, dt, A, B, C, D, *, chunk: int = 256,
                 chunks_per_block: int = CHUNKS_PER_BLOCK):
    """The ``jnp`` tier of :func:`ssd_scan`, ``y_t = S_t C_t + D x_t``.

    ``x [b, L, H, P]``; ``dt [b, L, H]`` float32, after the softplus; ``A [H]``
    float32, negative; ``B``, ``C`` ``[b, L, N]`` (one group) or ``[b, L, H,
    N]`` (a group a head); ``D [H]``. Returns ``y [b,
    L, H, P]`` in ``x``'s type (a [L, heads x head size] float32 array is
    0.5 GB at 16,384 tokens). ``L`` need be no multiple of ``chunk``: the tail
    is padded with ``dt = 0``, under which a position neither decays the
    state nor adds to it."""
    b, L, H, P = x.shape
    Lp = round_up(L, chunk)
    nc = Lp // chunk
    cb = max(d for d in range(1, min(chunks_per_block, nc) + 1) if nc % d == 0)

    def blocks(a):  # [b, L, ...] -> [blocks, b, cb, chunk, ...]
        a = jnp.pad(a, ((0, 0), (0, Lp - L)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(b, nc // cb, cb, chunk, *a.shape[2:])
        return jnp.moveaxis(a, 1, 0)

    dt = blocks(dt.astype(jnp.float32)).transpose(0, 1, 2, 4, 3)  # [.., H, chunk]
    acs = jnp.cumsum(dt * A.astype(jnp.float32)[:, None], axis=-1)
    state = jnp.zeros((b, H, P, B.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(functools.partial(_chunk_block, D.astype(jnp.float32)), state,
                        (blocks(x), dt, acs, blocks(B), blocks(C)))
    return jnp.moveaxis(y, 0, 1).reshape(b, Lp, H, P)[:, :L]


def _steps(dt, A_log, dt_bias):
    """``(softplus(dt + dt_bias), A = -exp(A_log))``, float32."""
    return (jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32)),
            -jnp.exp(A_log.astype(jnp.float32)))


def ssd_scan(xBC, dt, A_log, dt_bias, D, *, state_size: int, chunk: int = 256):
    """The state-space scan over the convolution's output ``xBC [b, L, H P +
    2 N]`` (``x``, then ``B``, then ``C``), with ``dt [b, L, H]`` the input
    projection's columns: the steps are ``softplus(dt + dt_bias)`` and ``A =
    -exp(A_log)``. Returns ``y [b, L, H P]`` in ``xBC``'s type, the gate
    norm's layout. The kernel where the device gate says TPU and
    :func:`gigapath_tpu.ops.pallas_ssd.fits` takes the widths and the chunk,
    the ``jnp`` tier elsewhere."""
    b, L, H = dt.shape
    N = state_size
    inner = xBC.shape[-1] - 2 * N
    P = inner // H
    if _gate._on_tpu():
        from gigapath_tpu.ops import pallas_ssd

        if pallas_ssd.fits(H, P, N, chunk):
            return pallas_ssd.ssd_scan_fwd(xBC, *_steps(dt, A_log, dt_bias), D,
                                           state_size=N, chunk=chunk)
    x, B, C = jnp.split(xBC, [inner, inner + N], axis=-1)
    x = x.reshape(b, L, H, P)
    dt, A = _steps(dt, A_log, dt_bias)
    return ssd_scan_jnp(x, dt, A, B, C, D, chunk=chunk).reshape(b, L, inner)


def linear_scan(x, B, C, A, *, chunk: int = 128):
    """Decayed linear attention as the scan with a ``B`` / ``C`` group a head,
    ``dt = 1`` and ``D = 0``: ``y_t,h = sum_{s<=t} exp(A_h (t - s)) (C_t,h .
    B_s,h) x_s,h``, so ``S_t = exp(A_h) S_{t-1} + x_t B_t^T``. ``x [b, L, H,
    P]`` (the values), ``B``, ``C`` ``[b, L, H, N]`` (keys and queries, any
    scale already on them), ``A [H]`` float32, the log of each head's decay.
    Returns ``y [b, L, H P]`` in ``x``'s type. The kernel where the device gate
    says TPU and :func:`gigapath_tpu.ops.pallas_ssd.fits` takes the widths,
    the ``jnp`` tier elsewhere."""
    b, L, H, P = x.shape
    A = A.astype(jnp.float32)
    if _gate._on_tpu():
        from gigapath_tpu.ops import pallas_ssd

        if pallas_ssd.fits(H, P, B.shape[-1], chunk, per_head=True):
            return pallas_ssd.linear_scan_fwd(x.reshape(b, L, -1), B.reshape(b, L, -1),
                                              C.reshape(b, L, -1), A, chunk=chunk)
    dt = jnp.ones((b, L, H), jnp.float32)
    return ssd_scan_jnp(x, dt, A, B, C, jnp.zeros((H,), jnp.float32),
                        chunk=chunk).reshape(b, L, H * P)


class Mamba2Mixer(nn.Module):
    """``u [b, L, hidden] -> [b, L, hidden]``: ``[z | xBC | dt] = u W_in``;
    ``xBC = silu(conv(xBC))``; the scan over ``x``, ``B``, ``C`` with
    ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``y = RMSNorm(y *
    silu(z))`` (the gate goes in before the norm); ``y W_out``."""

    hidden_size: int
    num_heads: int
    head_dim: int
    state_size: int
    conv_kernel: int = 4
    chunk_size: int = 256
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u: jnp.ndarray) -> jnp.ndarray:
        H, P, N = self.num_heads, self.head_dim, self.state_size
        inner, conv_dim = H * P, H * P + 2 * N
        dense = dict(use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype)
        zxbcdt = nn.Dense(inner + conv_dim + H, name="in_proj", **dense)(u)
        z, xBC, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
        with jax.named_scope("conv"):
            weight = self.param("conv_weight", nn.initializers.lecun_normal(),
                                (self.conv_kernel, conv_dim), self.param_dtype)
            bias = self.param("conv_bias", nn.initializers.zeros, (conv_dim,), self.param_dtype)
            xBC = jax.nn.silu(causal_conv1d(xBC, weight, bias)).astype(self.dtype)
        A_log = self.param("A_log", nn.initializers.zeros, (H,), self.param_dtype)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (H,), self.param_dtype)
        D = self.param("D", nn.initializers.ones, (H,), self.param_dtype)
        with jax.named_scope("ssd_scan"):
            y = ssd_scan(xBC, dt, A_log, dt_bias, D, state_size=N, chunk=self.chunk_size)
        with jax.named_scope("gate_norm"):
            gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
            y = RMSNorm(inner, eps=self.norm_eps, param_dtype=self.param_dtype,
                        name="norm")(gated).astype(self.dtype)
        return nn.Dense(self.hidden_size, name="out_proj", **dense)(y)
