"""Plain reference for the `axk1` model: the forward pass of A.X-K1
(``model_type: axk1``, the DeepSeek-V3 family's layer: multi-head latent
attention, a leading dense layer, sigmoid group-limited routing beside a
shared expert) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, one sequence at a time. No
kernels, no sorting of tokens, no batching; nothing here imports the program.
The weights are the benchmark's own (``lib/weights_lm.py``), read layer by
layer from the program's bfloat16 tree by its names, the one interface the two
share, and upcast a matrix at a time. ``mode`` is ``lib/reference.py``'s: the
precision of every matrix product, and how the control is made.

The equations (all norms RMSNorm, eps ``rms_norm_eps``, with a gain; ``u`` a
layer's normed input)::

    h = E[ids]
    h = h + MLA(RMSNorm(h));  u = RMSNorm(h);  h = h + FFN_l(u)
    FFN_l = W_down(silu(W_gate u) * W_up u)            l <  first_k_dense_replace
    FFN_l = Routed(u) + Shared(u)                      l >= first_k_dense_replace
    logits = RMSNorm(h)[rows] @ W_head

    MLA(u):  c_q = RMSNorm(u W_qa);  q = c_q W_qb -> heads x [q_n | q_r]
             [c_kv | k_r] = u W_kva;  c_kv = RMSNorm(c_kv);  c_kv W_kvb -> heads x [k_n | v]
             q_r, k_r = RoPE(.) on interleaved pairs (x[2i], x[2i+1]); k_r one head, used by all
             s = ([q_n | q_r] . [k_n | k_r]) * scale, causal;  out = softmax(s) v, then W_o
             scale = (nope + rope) ** -0.5 * m ** 2,  m = 0.1 mscale_all_dim ln(factor) + 1
    RoPE:    f_i = theta ** (-2 i / rope),  i = 0 .. rope / 2 - 1
             corr(b) = rope ln(original / (2 pi b)) / (2 ln theta)
             low = floor(corr(beta_fast)), high = ceil(corr(beta_slow)), in [0, rope - 1]
             ramp_i = clip((i - low) / (high - low), 0, 1)
             f_i <- f_i / factor * ramp_i + f_i * (1 - ramp_i);  cos / sin carry mscale / mscale_all_dim
    Routed:  s = sigmoid(u W_r) [n_routed_experts];  n_group groups;  a group scores its largest s
             the topk_group best groups stay;  the k largest s among their experts
             w = s[chosen] / sum(s[chosen]) * routed_scaling_factor
             sum over the chosen experts of  w_e W2_e(silu(a) * b),  [a | b] = W1_e u
    Shared:  the same gated MLP at n_shared_experts x moe_intermediate_size, always on

Departures from the published description (the DeepSeek-V3 reference
implementation's ``MLA`` / ``Gate`` / ``MoE``, whose layer this is; A.X-K1's own
modelling file is not to be had here), each a matter of form and none of value
unless it says so:

- ``topk_method`` is ``"none"`` in the published file, a value that code does
  not have. It is read as "no selection bias": no ``e_score_correction_bias``,
  and a group is ranked by its largest score, which is what ``Gate`` does
  wherever it has no bias. An assumption (the configuration file's first);
- attention is the un-absorbed form (``kv_b_proj`` on every token), which is
  what that implementation's ``naive`` path and its prefill compute; there is
  no latent cache because nothing decodes;
- YaRN's blend is applied at every length (the published code of the family
  applies it whenever the context exceeds ``original_max_position_embeddings``,
  which 131,072 does);
- the gated MLP's ``W_gate`` and ``W_up`` are one matrix ``[a | b]``
  (``input_linear``), as the program stores them;
- logits are produced for the rows ``positions`` names;
- the chip's share: only experts ``[expert_offset, expert_offset +
  n_routed_experts)`` add to ``Routed(u)`` (the router still scores all of the
  published ones, in their groups), only the first ``vocab_size`` rows of ``E``
  and of ``W_head`` exist, only the first ``depth`` layers run. What the absent
  experts would add is left out here as in the program, and that partial sum
  goes on to the next layer;
- each held expert is applied to the tokens gathered for it; attention and the
  dense layer's MLP are taken a block of rows at a time so that they fit beside
  the program's weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import mm
from benchmarks.lib.reference_lm import _Dims, held_experts, rms_norm  # noqa: F401  (the same pieces)

_F32 = jnp.float32


def gated_mlp(w_in, w_out, u, mode, block_rows=4096):
    out = []
    for start in range(0, u.shape[0], block_rows):
        a, b = jnp.split(mm(u[start:start + block_rows], w_in, mode), 2, axis=-1)
        out.append(mm(jax.nn.silu(a) * b, w_out, mode))
    return jnp.concatenate(out) if len(out) > 1 else out[0]


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_correction_range(dim, theta, original, beta_fast, beta_slow):
    def corr(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    return max(math.floor(corr(beta_fast)), 0), min(math.ceil(corr(beta_slow)), dim - 1)


def yarn_frequencies(dim, theta, scaling) -> np.ndarray:
    """The ``dim / 2`` blended frequencies; ``scaling`` is the published
    ``rope_scaling`` group."""
    i = np.arange(dim // 2)
    f = theta ** (-2.0 * i / dim)
    low, high = yarn_correction_range(
        dim, theta, scaling["original_max_position_embeddings"], scaling["beta_fast"],
        scaling["beta_slow"])
    ramp = np.clip((i - low) / (high - low if high > low else 0.001), 0.0, 1.0)
    return f / scaling["factor"] * ramp + f * (1.0 - ramp)


def softmax_scale(sizes) -> float:
    scaling = sizes["rope_scaling"]
    m = yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
    return (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, cos, sin):
    """``x [L, H, d]`` rotated on its interleaved pairs by ``cos`` / ``sin``
    ``[L, d / 2]``, pairs left where they were."""
    x0, x1 = x[..., 0::2], x[..., 1::2]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1).reshape(x.shape)


def latent_attention(p, u, dims, mode, block_rows=256):
    L = u.shape[0]
    H, nope, rot, dv = dims["heads"], dims["nope"], dims["rope"], dims["v"]
    eps = dims["eps"]
    c_q = rms_norm(p["q_a_layernorm"]["weight"], mm(u, p["q_a_proj"]["kernel"], mode), eps)
    q = mm(c_q, p["q_b_proj"]["kernel"], mode).reshape(L, H, nope + rot)
    kv_a = mm(u, p["kv_a_proj_with_mqa"]["kernel"], mode)
    c_kv = rms_norm(p["kv_a_layernorm"]["weight"], kv_a[:, :dims["kv_rank"]], eps)
    kv = mm(c_kv, p["kv_b_proj"]["kernel"], mode).reshape(L, H, nope + dv)
    angles = jnp.arange(L, dtype=_F32)[:, None] * jnp.asarray(dims["freqs"], _F32)
    cos, sin = jnp.cos(angles) * dims["rope_carry"], jnp.sin(angles) * dims["rope_carry"]
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cos, sin)], axis=-1)
    k_r = rope(kv_a[:, None, dims["kv_rank"]:], cos, sin)            # [L, 1, rope]: one head
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (L, H, rot))], axis=-1)
    q, k, v = (x.transpose(1, 0, 2) for x in (q, k, kv[..., nope:]))  # [H, L, .]
    out = []
    for start in range(0, L, block_rows):
        end = min(start + block_rows, L)                             # no key after the block's last row
        s = mm(q[:, start:end], k[:, :end].transpose(0, 2, 1), mode) * dims["scale"]
        rows = jnp.arange(start, end)
        s = jnp.where(jnp.arange(end)[None, None, :] > rows[None, :, None], -jnp.inf, s)
        out.append(mm(jax.nn.softmax(s, axis=-1), v[:, :end], mode))
    out = jnp.concatenate(out, axis=1).transpose(1, 0, 2).reshape(L, H * dv)
    return mm(out, p["o_proj"]["kernel"], mode)


def route(router_kernel, u, k, n_group, topk_group, scaling_factor, mode):
    """``(weights [L, k], experts [L, k])``: sigmoid scores, the ``topk_group``
    groups whose best expert scores highest, the ``k`` largest scores inside
    them, renormalised and scaled."""
    scores = jax.nn.sigmoid(mm(u, router_kernel, mode))
    L, E = scores.shape
    best = scores.reshape(L, n_group, E // n_group).max(-1)
    kept = jax.lax.top_k(best, topk_group)[1]
    eligible = jnp.zeros((L, n_group), bool).at[jnp.arange(L)[:, None], kept].set(True)
    eligible = jnp.repeat(eligible, E // n_group, axis=1)
    values, experts = jax.lax.top_k(jnp.where(eligible, scores, -jnp.inf), k)
    return values / values.sum(-1, keepdims=True) * scaling_factor, experts


def layer_dims(sizes) -> _Dims:
    scaling = sizes["rope_scaling"]
    return _Dims(
        eps=float(sizes["rms_norm_eps"]), heads=int(sizes["num_attention_heads"]),
        kv_rank=int(sizes["kv_lora_rank"]), nope=int(sizes["qk_nope_head_dim"]),
        rope=int(sizes["qk_rope_head_dim"]), v=int(sizes["v_head_dim"]),
        scale=softmax_scale(sizes),
        freqs=tuple(float(f) for f in yarn_frequencies(
            int(sizes["qk_rope_head_dim"]), float(sizes["rope_theta"]), scaling)),
        rope_carry=yarn_mscale(scaling["factor"], scaling["mscale"])
        / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]),
        top_k=int(sizes["num_experts_per_tok"]), n_group=int(sizes["n_group"]),
        topk_group=int(sizes["topk_group"]),
        scaling_factor=float(sizes["routed_scaling_factor"]),
        expert_offset=int(sizes["expert_offset"]),
    )


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _attend(lp, h, *, dims, mode):
    """The attention half of a layer: ``(h, u)``, ``u`` the FFN's normed input."""
    with jax.default_matmul_precision("highest"):
        u = rms_norm(lp["input_layernorm"]["weight"], h, dims["eps"])
        h = h + latent_attention(lp["self_attn"], u, dims, mode)
        return h, rms_norm(lp["post_attention_layernorm"]["weight"], h, dims["eps"])


@functools.partial(jax.jit, static_argnames=("mode",))
def _dense_ffn(mlp, h, u, *, mode):
    with jax.default_matmul_precision("highest"):
        return h + gated_mlp(mlp["input_linear"]["kernel"], mlp["output_linear"]["kernel"], u, mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _route(router_kernel, u, *, dims, mode):
    with jax.default_matmul_precision("highest"):
        return route(router_kernel, u, dims["top_k"], dims["n_group"], dims["topk_group"],
                     dims["scaling_factor"], mode)


@functools.partial(jax.jit, static_argnames=("dims", "rows_max", "mode"))
def _experts_and_shared(lp, h, u, weights, experts, *, dims, rows_max, mode):
    with jax.default_matmul_precision("highest"):
        routed = held_experts(lp["moe"], u, weights, experts, dims["expert_offset"],
                              rows_max, mode)
        shared = gated_mlp(lp["shared_experts"]["input_linear"]["kernel"],
                           lp["shared_experts"]["output_linear"]["kernel"], u, mode)
        return h + routed + shared


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(kernel, norm_weight, rows, *, eps, mode):
    with jax.default_matmul_precision("highest"):
        return mm(rms_norm(norm_weight, rows, eps), kernel, mode)


def lm_forward(params, ids, positions, sizes, mode="f32", routing=None):
    """One sequence: ``ids [L]`` int, ``positions [P]`` int -> logits ``[P,
    vocab_size]`` float32 on the host. ``sizes`` is the configuration file
    (or its tiny preset). ``routing``, where a list is given, receives each
    expert layer's ``experts [L, k]`` choices."""
    dims = layer_dims(sizes)
    ids = jnp.asarray(ids, jnp.int32)
    h = params["embed_tokens"]["embedding"][ids].astype(_F32)
    for i in range(int(sizes["depth"])):
        lp = params[f"layers_{i}"]
        h, u = _attend(lp, h, dims=dims, mode=mode)
        if i < int(sizes["first_k_dense_replace"]):
            h = _dense_ffn(lp["mlp"], h, u, mode=mode)
            continue
        weights, experts = _route(lp["moe"]["router"]["kernel"], u, dims=dims, mode=mode)
        if routing is not None:
            routing.append(np.asarray(experts))
        # the fullest expert's tokens, to the next 512: how many rows a gather holds
        counts = np.bincount(np.asarray(experts).ravel(), minlength=1)
        rows_max = min(-(-int(counts.max()) // 512) * 512, int(ids.shape[0]))
        h = _experts_and_shared(lp, h, u, weights, experts, dims=dims, rows_max=rows_max,
                                mode=mode)
    rows = h[jnp.asarray(positions, jnp.int32)]
    return np.asarray(_head(params["lm_head"]["kernel"], params["norm"]["weight"], rows,
                            eps=dims["eps"], mode=mode))
