"""Plain reference for the `granite_hybrid` model: the forward pass of a Granite 4.0-H
hybrid stack (``model_type: granitemoehybrid``) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, one sequence at a time. No
kernels, no chunks, no sorting, no batching; nothing here imports the
program. The weights are the benchmark's own (``lib/weights_lm.py``), read
layer by layer from the program's bfloat16 tree by its names, the one
interface the two share. ``mode`` is ``lib/reference.py``'s: the precision of
every matrix product, and how the control is made.

The equations (all norms RMSNorm, eps ``rms_norm_eps``, with a gain)::

    h = E[ids] * embedding_multiplier
    h = h + residual_multiplier * Mixer(RMSNorm(h))
    u = RMSNorm(h);  h = h + residual_multiplier * (MoE(u) + Shared(u))
    logits = RMSNorm(h) @ E^T / logits_scaling

    Mamba-2:   [z | xBC | dt] = u W_in;  xBC = silu(conv1d_causal(xBC) + b)
               dt = softplus(dt + dt_bias);  A = -exp(A_log)
               S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
               out = RMSNorm(y * silu(z)) W_out
    attention: softmax(causal(q k^T * attention_multiplier)) v, then W_o;
               query head h reads KV head h // (heads / kv_heads); no positions
    experts:   g = u W_r;  the k largest;  w = softmax over those k values
               MoE(u) = sum_i w_i W2_e(silu(a) * b),  [a | b] = W1_e u
               Shared(u) the same gated form, always on

Departures from the published implementation (transformers'
``modeling_granitemoehybrid.py``), each a matter of form and none of value:

- the state-space layer is the recurrence above, a ``lax.scan`` over
  positions; the published code runs the chunked dual form (chunk 256), which
  computes the same numbers;
- logits are produced for the rows ``positions`` names, where the published
  ``logits_to_keep`` keeps a suffix of rows;
- the chip's share: only experts ``[expert_offset, expert_offset +
  num_local_experts)`` add to ``MoE(u)`` (the router still ranks all of
  them), only the first ``vocab_size`` rows of ``E`` exist, only the first
  ``depth`` layers run. What the absent experts would add is left out here as
  in the program, and that partial sum goes on to the next layer;
- each held expert is applied to the tokens gathered for it (the published
  code sorts tokens by expert and splits one buffer);
- no dropout, no cache, no padding mask; attention is taken a block of query
  rows at a time so that the scores fit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import mm

_F32 = jnp.float32


def rms_norm(weight, x, eps):
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(_F32)


def gated_mlp(w_in, w_out, u, mode):
    a, b = jnp.split(mm(u, w_in, mode), 2, axis=-1)
    return mm(jax.nn.silu(a) * b, w_out, mode)


def causal_conv(x, weight, bias):
    """``x [L, C]``, ``weight [K, C]`` with the last tap on the current
    position, ``bias [C]``."""
    K, L = weight.shape[0], x.shape[0]
    out = jnp.zeros_like(x) + bias.astype(_F32)
    for back in range(K):  # tap K-1-back reads the position `back` steps ago
        shifted = jnp.concatenate([jnp.zeros((back, x.shape[1]), _F32), x[: L - back]])
        out = out + shifted * weight[K - 1 - back].astype(_F32)
    return out


def state_space_recurrence(x, dt, A, B, C):
    """``x [L, H, P]``, ``dt [L, H]``, ``A [H]``, ``B``, ``C`` ``[L, N]`` ->
    ``y [L, H, P]``: one position at a time, the state ``[H, P, N]`` carried."""

    def step(S, inputs):
        x_t, dt_t, B_t, C_t = inputs
        S = jnp.exp(dt_t * A)[:, None, None] * S + (dt_t[:, None] * x_t)[..., None] * B_t
        return S, (S * C_t).sum(-1)

    S0 = jnp.zeros((x.shape[1], x.shape[2], B.shape[1]), _F32)
    return jax.lax.scan(step, S0, (x, dt, B, C))[1]


def mamba_mixer(p, u, heads, head_dim, state, eps, mode):
    inner = heads * head_dim
    z, xBC, dt = jnp.split(mm(u, p["in_proj"]["kernel"], mode),
                           [inner, 2 * inner + 2 * state], axis=-1)
    xBC = jax.nn.silu(causal_conv(xBC, p["conv_weight"], p["conv_bias"]))
    x, B, C = jnp.split(xBC, [inner, inner + state], axis=-1)
    x = x.reshape(-1, heads, head_dim)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(_F32))
    y = state_space_recurrence(x, dt, -jnp.exp(p["A_log"].astype(_F32)), B, C)
    y = y + p["D"].astype(_F32)[:, None] * x
    y = rms_norm(p["norm"]["weight"], y.reshape(-1, inner) * jax.nn.silu(z), eps)
    return mm(y, p["out_proj"]["kernel"], mode)


def causal_attention(p, u, heads, kv_heads, scale, mode, block_rows=256):
    L = u.shape[0]
    q = mm(u, p["q_proj"]["kernel"], mode).reshape(L, heads, -1).transpose(1, 0, 2)
    k, v = (jnp.repeat(mm(u, p[name]["kernel"], mode).reshape(L, kv_heads, -1),
                       heads // kv_heads, axis=1).transpose(1, 0, 2)
            for name in ("k_proj", "v_proj"))
    keys = jnp.arange(L)
    out = []
    for start in range(0, L, block_rows):
        rows = keys[start:start + block_rows]
        s = mm(q[:, start:start + block_rows], k.transpose(0, 2, 1), mode) * scale
        s = jnp.where(keys[None, None, :] > rows[None, :, None], -jnp.inf, s)
        out.append(mm(jax.nn.softmax(s, axis=-1), v, mode))
    out = jnp.concatenate(out, axis=1).transpose(1, 0, 2).reshape(L, -1)
    return mm(out, p["o_proj"]["kernel"], mode)


def route(router_kernel, u, k, mode):
    """``(weights [L, k], experts [L, k])``: the ``k`` largest router logits
    of each token and the softmax over them."""
    values, experts = jax.lax.top_k(mm(u, router_kernel, mode), k)
    return jax.nn.softmax(values, axis=-1), experts


def held_experts(p, u, weights, experts, offset, rows_max, mode):
    """The held experts' part of ``MoE(u)``: expert ``offset + e`` is applied
    to the tokens that chose it, gathered (``rows_max`` bounds their number),
    and its answers are added to those tokens' rows, weighted by their gates."""

    def one(out, e_w1_w2):
        e, w1, w2 = e_w1_w2
        hit = experts == offset + e
        gate = jnp.where(hit, weights, 0.0).sum(-1)
        chosen = hit.any(-1)
        idx = jnp.nonzero(chosen, size=rows_max, fill_value=0)[0]
        real = (jnp.arange(rows_max) < chosen.sum())[:, None]
        answers = gated_mlp(w1, w2, u[idx], mode) * gate[idx][:, None]
        return out.at[idx].add(jnp.where(real, answers, 0.0)), None

    held = p["w1"].shape[0]
    return jax.lax.scan(one, jnp.zeros_like(u), (jnp.arange(held), p["w1"], p["w2"]))[0]


@functools.partial(jax.jit, static_argnames=("kind", "dims", "mode"))
def _mix_and_route(lp, h, *, kind, dims, mode):
    """The mixer half of a layer and the router: ``(h, u, weights, experts)``."""
    with jax.default_matmul_precision("highest"):
        u = rms_norm(lp["input_layernorm"]["weight"], h, dims["eps"])
        if kind == "mamba":
            mixed = mamba_mixer(lp["ssm_mixer"], u, dims["mamba_heads"], dims["mamba_head_dim"],
                                dims["mamba_state"], dims["eps"], mode)
        else:
            mixed = causal_attention(lp["self_attn"], u, dims["heads"], dims["kv_heads"],
                                     dims["attention_multiplier"], mode)
        h = h + dims["residual_multiplier"] * mixed
        u = rms_norm(lp["post_attention_layernorm"]["weight"], h, dims["eps"])
        return (h, u) + route(lp["moe"]["router"]["kernel"], u, dims["top_k"], mode)


@functools.partial(jax.jit, static_argnames=("dims", "rows_max", "mode"))
def _experts_and_shared(lp, h, u, weights, experts, *, dims, rows_max, mode):
    with jax.default_matmul_precision("highest"):
        routed = held_experts(lp["moe"], u, weights, experts, dims["expert_offset"],
                              rows_max, mode)
        shared = gated_mlp(lp["shared_mlp"]["input_linear"]["kernel"],
                           lp["shared_mlp"]["output_linear"]["kernel"], u, mode)
        return h + dims["residual_multiplier"] * (routed + shared)


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "mode"))
def _head(embedding, norm_weight, rows, *, eps, scaling, mode):
    with jax.default_matmul_precision("highest"):
        return mm(rms_norm(norm_weight, rows, eps), embedding.T, mode) / scaling


class _Dims(dict):
    """The sizes a layer needs, hashable so that ``jit`` can hold them static."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def lm_forward(params, ids, positions, sizes, mode="f32", routing=None):
    """One sequence: ``ids [L]`` int, ``positions [P]`` int -> logits ``[P,
    vocab_size]`` float32 on the host. ``sizes`` is the configuration file
    (or its tiny preset). ``routing``, where a list is given, receives each
    layer's ``experts [L, k]`` choices."""
    dims = _Dims(
        eps=float(sizes["rms_norm_eps"]), heads=int(sizes["num_attention_heads"]),
        kv_heads=int(sizes["num_key_value_heads"]),
        attention_multiplier=float(sizes["attention_multiplier"]),
        residual_multiplier=float(sizes["residual_multiplier"]),
        mamba_heads=int(sizes["mamba_n_heads"]), mamba_head_dim=int(sizes["mamba_d_head"]),
        mamba_state=int(sizes["mamba_d_state"]), top_k=int(sizes["num_experts_per_tok"]),
        expert_offset=int(sizes["expert_offset"]),
    )
    ids = jnp.asarray(ids, jnp.int32)
    h = params["embed_tokens"]["embedding"][ids].astype(_F32) * float(sizes["embedding_multiplier"])
    for i, kind in enumerate(sizes["layer_types"][: int(sizes["depth"])]):
        lp = params[f"layers_{i}"]
        h, u, weights, experts = _mix_and_route(lp, h, kind=kind, dims=dims, mode=mode)
        if routing is not None:
            routing.append(np.asarray(experts))
        # the fullest expert's tokens, to the next 512: how many rows a gather holds
        counts = np.bincount(np.asarray(experts).ravel(), minlength=1)
        rows_max = min(-(-int(counts.max()) // 512) * 512, int(ids.shape[0]))
        h = _experts_and_shared(lp, h, u, weights, experts, dims=dims, rows_max=rows_max,
                                mode=mode)
    rows = h[jnp.asarray(positions, jnp.int32)]
    return np.asarray(_head(params["embed_tokens"]["embedding"], params["norm"]["weight"], rows,
                            eps=dims["eps"], scaling=float(sizes["logits_scaling"]), mode=mode))
