"""Plain reference for the `brumby` model: the forward pass of a Brumby-14B
stack (``model_type: brumby``; Qwen3's block with power retention of degree 2
as its token mixer) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, one sequence at a time. No
kernels, no chunks, no feature expansion, no carried state: the retention is
taken in its quadratic form straight from the equation, so it shares nothing
with either of the program's tiers. Nothing here imports the program; the
weights are the benchmark's own, read from the program's bfloat16 tree by
its names. ``mode`` is ``lib/reference.py``'s: the precision of every matrix
product (the query-key products and the weighted sum of values among them),
and how the control is made.

The equations (all norms RMSNorm, eps ``rms_norm_eps``, with a gain)::

    x = RMSNorm(u);  q = RMSNorm_q(x W_q) per head;  k = RMSNorm_k(x W_k);  v = x W_v
    q, k <- rotate-half RoPE at theta = rope_theta, positions 0 .. L - 1
    log gamma = logsigmoid(x W_gate + gate_bias)          one a KV head
    y_t = sum_{s<=t} e^{G_t - G_s} (q_t . k_s)^2 v_s / (sum_{s<=t} e^{G_t - G_s} (q_t . k_s)^2 + eps)
    a = u + y W_o;  out = a + W_down(silu(a') * b'),  [a' | b'] = RMSNorm(a) W_in
    logits = RMSNorm(h)[rows] @ W_head

Departures from the published description, each a matter of form:

- the gate sums are never differences of one running sum over the whole
  sequence (it reaches about -10^4 at 32,768 positions for a head that
  forgets fast, where float32 keeps three decimals): for a block of query
  rows starting at ``t0``, ``G_t - G_s`` is the sum from ``t0`` to ``t``
  plus, for ``s < t0``, the sum from ``s + 1`` to ``t0 - 1``, each summed
  outward from ``t0``;
- the retention is taken a block of query rows at a time against every key
  (those after ``t`` masked), and the SwiGLU a block of rows at a time, so
  that the reference fits beside the program at 32,768 tokens;
- logits are produced for the rows ``positions`` names; only the first
  ``depth`` layers run; ``W_gate`` and ``W_up`` are one ``[a | b]`` matrix
  (``input_linear``), as the program stores them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import mm
from benchmarks.lib.reference_lm import gated_mlp, rms_norm

_F32 = jnp.float32
_QUERY_ROWS = 64      # query rows a block of the retention: [heads, 64, L] float32
_MLP_ROWS = 4096      # rows a block of the SwiGLU: [4096, 2 x intermediate] float32


def rope_halfsplit(x, theta):
    """Rotate the pairs ``(x[i], x[i + d/2])`` of ``x [L, heads, d]`` by
    ``position * theta ** (-2 i / d)``."""
    L, _, d = x.shape
    inv_freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angles = jnp.arange(L, dtype=_F32)[:, None] * jnp.asarray(inv_freq, _F32)
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    lo, hi = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos], axis=-1)


def retention_quadratic(q, k, v, log_gate, eps, mode, block_rows=_QUERY_ROWS):
    """``q [L, H, d]``, ``k``, ``v`` ``[L, G, d]``, ``log_gate [L, G]`` ->
    ``y [L, H, d]``, the equation above, a block of query rows at a time."""
    L, H, d = q.shape
    G = k.shape[1]
    r = H // G
    pad = -L % block_rows
    keys = jnp.arange(L)
    kT = k.transpose(1, 2, 0)                                      # [G, d, L]
    vg = v.transpose(1, 0, 2)                                      # [G, L, d]
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block_rows, G, r, d)

    def block(args):
        i, q_blk = args                                            # [rows, G, r, d]
        t0 = i * block_rows
        rows = t0 + jnp.arange(block_rows)
        # outward from t0: forward over the block, backward over the keys before it
        inside = jnp.cumsum(jnp.where((keys >= t0)[:, None], log_gate, 0.0), axis=0)   # [L, G]
        before = jnp.where((keys < t0)[:, None], log_gate, 0.0)
        behind = jnp.cumsum(before[::-1], axis=0)[::-1] - before  # sum over (s, t0)
        r_t = jnp.take(inside, jnp.minimum(rows, L - 1), axis=0)   # [rows, G]
        e_s = jnp.where((keys < t0)[:, None], behind, -inside)     # [L, G]
        exponent = r_t.T[:, :, None] + e_s.T[:, None, :]           # [G, rows, L]
        causal = keys[None, None, :] <= rows[None, :, None]
        decay = jnp.exp(jnp.where(causal, exponent, -jnp.inf))
        q_g = q_blk.transpose(1, 2, 0, 3).reshape(G, r * block_rows, d)
        s = mm(q_g, kT, mode).reshape(G, r, block_rows, L)
        w = (s * s * decay[:, None]).reshape(G, r * block_rows, L)
        num = mm(w, vg, mode)                                      # [G, r rows, d]
        den = w.sum(-1, keepdims=True)
        y = (num / (den + eps)).reshape(G, r, block_rows, d)
        return y.transpose(2, 0, 1, 3).reshape(block_rows, H, d)

    out = jax.lax.map(block, (jnp.arange(qb.shape[0]), qb))
    return out.reshape(-1, H, d)[:L]


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _mixer(lp, h, *, dims, mode):
    """``h + W_o retention(RMSNorm(h))``."""
    with jax.default_matmul_precision("highest"):
        p, eps = lp["self_attn"], dims["eps"]
        L, H, G, d = h.shape[0], dims["heads"], dims["kv_heads"], dims["head_dim"]
        x = rms_norm(lp["input_layernorm"]["weight"], h, eps)
        q = rms_norm(p["q_norm"]["weight"], mm(x, p["q_proj"]["kernel"], mode).reshape(L, H, d), eps)
        k = rms_norm(p["k_norm"]["weight"], mm(x, p["k_proj"]["kernel"], mode).reshape(L, G, d), eps)
        v = mm(x, p["v_proj"]["kernel"], mode).reshape(L, G, d)
        q, k = rope_halfsplit(q, dims["theta"]), rope_halfsplit(k, dims["theta"])
        log_gate = jax.nn.log_sigmoid(mm(x, p["gate"]["kernel"], mode)
                                      + p["gate_bias"].astype(_F32))
        y = retention_quadratic(q, k, v, log_gate, dims["retention_eps"], mode)
        return h + mm(y.reshape(L, H * d), p["o_proj"]["kernel"], mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _mlp(lp, h, *, dims, mode):
    """``h + SwiGLU(RMSNorm(h))``, a block of rows at a time."""
    with jax.default_matmul_precision("highest"):
        L = h.shape[0]
        rows = min(_MLP_ROWS, L)
        pad = -L % rows
        mlp = lp["mlp"]

        def block(h_blk):
            u = rms_norm(lp["post_attention_layernorm"]["weight"], h_blk, dims["eps"])
            return h_blk + gated_mlp(mlp["input_linear"]["kernel"], mlp["output_linear"]["kernel"],
                                     u, mode)

        out = jax.lax.map(block, jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, rows, h.shape[1]))
        return out.reshape(-1, h.shape[1])[:L]


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(kernel, norm_weight, rows, *, eps, mode):
    with jax.default_matmul_precision("highest"):
        return mm(rms_norm(norm_weight, rows, eps), kernel, mode)


class _Dims(dict):
    """The sizes a layer needs, hashable so that ``jit`` can hold them static."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def lm_forward(params, ids, positions, sizes, mode="f32"):
    """One sequence: ``ids [L]`` int, ``positions [P]`` int -> logits ``[P,
    vocab_size]`` float32 on the host. ``sizes`` is the configuration file
    (or its tiny preset)."""
    dims = _Dims(eps=float(sizes["rms_norm_eps"]), heads=int(sizes["num_attention_heads"]),
                 kv_heads=int(sizes["num_key_value_heads"]), head_dim=int(sizes["head_dim"]),
                 theta=float(sizes["rope_theta"]), retention_eps=float(sizes["retention_eps"]))
    h = params["embed_tokens"]["embedding"][jnp.asarray(ids, jnp.int32)].astype(_F32)
    for i in range(int(sizes["depth"])):
        lp = params[f"layers_{i}"]
        h = _mlp(lp, _mixer(lp, h, dims=dims, mode=mode), dims=dims, mode=mode)
    rows = h[jnp.asarray(positions, jnp.int32)]
    return np.asarray(_head(params["lm_head"]["kernel"], params["norm"]["weight"], rows,
                            eps=dims["eps"], mode=mode))
