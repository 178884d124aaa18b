"""Plain reference for the `deepseek_v32` model: the forward pass of
DeepSeek-V3.2 (``model_type: deepseek_v32``: the DeepSeek-V3 layer with a
lightning indexer and a top-k in front of latent attention's core, a biased
group-limited gate, leading dense layers, one multi-token-prediction module)
in float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
one sequence at a time. No kernels, no batching; nothing here imports the
program. The indexer and the top-k stand in the open (``index_select``): the
scores a block of query rows at a time, ``jax.lax.top_k`` over each row (it
puts the lower index first among equals), the selection as a boolean ``[L,
L]``. The weights are the benchmark's own (``lib/weights_lm.py``), read from
the program's bfloat16 tree by its names, the one interface the two share.
``mode`` is ``lib/reference.py``'s: the precision of every matrix product, the
indexer's among them, and how the control is made.

The equations (RMSNorm with a gain, eps ``rms_norm_eps``, unless said)::

    h = E[ids];  h = h + Attn_l(RMSNorm(h));  u = RMSNorm(h);  h = h + FFN_l(u)
    FFN_l = W_down(silu(W_gate u) * W_up u)                   l <  first_k_dense_replace
    FFN_l = Routed(u) + Shared(u)                             l >= first_k_dense_replace
    logits = RMSNorm(h)[rows] @ W_head

    Attn(x):  c_q = RMSNorm(x W_qa);  q = c_q W_qb -> heads x [q_n | q_r]
              [c_kv | k_r] = x W_kva;  c_kv = RMSNorm(c_kv);  c_kv W_kvb -> heads x [k_n | v]
              q_r, k_r = RoPE on interleaved pairs (x[2i], x[2i+1]); k_r one head for all; YaRN as
              ``lib/reference_axk1.py`` writes it out
      Indexer: qI = c_q W_Iq -> index_n_heads x index_head_dim;  the first qk_rope_head_dim features of
               each rotated on the pairs (x[i], x[i + rope / 2]) by the same tables
               kI = LayerNorm(x W_Ik) (gain and bias), its first rope features rotated alike; one key
               w  = (x W_Iw) * index_n_heads ** -0.5 * index_head_dim ** -0.5
               I[t, s] = sum_h w[t, h] * relu(qI[t, h] . kI[s]),  s <= t
               S_t = the min(t + 1, index_topk) keys s <= t of largest I[t, s]; ties to the lower s
      core:    softmax over s in S_t of ([q_n | q_r] . [k_n | k_r] * scale) v, then W_o
               scale = (nope + rope) ** -0.5 * (0.1 mscale_all_dim ln(factor) + 1) ** 2

    Routed:  s = sigmoid(u W_r) [n_routed_experts];  pick = s + e_score_correction_bias
             n_group groups; a group scores the sum of its two largest pick; the topk_group best stay
             the k largest pick among their experts;  w = s[chosen] / sum(s[chosen]) * routed_scaling_factor
    MTP:     x'_i = [RMSNorm_e(E[ids[i + 1]]) ; RMSNorm_h(h_i)] W_eh, h the last layer's output before
             the final norm, ids[L] taken as 0;  x' -> one expert layer of the kind above, its own weights
             mtp_logits = RMSNorm_s(x')[min(rows, L - 2)] @ W_head

Departures from the published implementation (``inference/model.py`` of the
release), the program's and this file's alike: (a) the indexer's scores are
computed at ``mode``'s precision with float32 accumulation, not in FP8 with
per-block scales; (b) the Hadamard rotation of ``qI`` and ``kI`` is left out
(orthogonal: ``qI . kI`` is the same number; it serves the FP8 quantiser); (c)
``W_eh`` takes ``[embedding ; hidden]`` in that order, as the released weights
have it; (d) the indexer's LayerNorm takes ``rms_norm_eps``; (e) forward only,
un-absorbed (``kv_b_proj`` on every token), no cache. The chip's share is
``lib/reference_axk1.py``'s: experts ``[expert_offset, expert_offset +
n_routed_experts)`` of the published ones, the first ``vocab_size`` rows, the
first ``depth`` layers, and ``num_nextn_predict_layers`` prediction modules
(0 or 1) of the published one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import mm
from benchmarks.lib.reference_axk1 import (_dense_ffn, _experts_and_shared, _head, gated_mlp,  # noqa: F401
                                           layer_dims as _axk1_dims, rope)
from benchmarks.lib.reference_lm import _Dims, rms_norm

_F32 = jnp.float32


def layer_norm(weight, bias, x, eps):
    x = x.astype(_F32)
    x = x - x.mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(_F32) \
        + bias.astype(_F32)


def rope_halfsplit(x, cos, sin):
    """``x [L, H, d]`` rotated on the pairs ``(x[i], x[i + d / 2])``."""
    lo, hi = x[..., : x.shape[-1] // 2], x[..., x.shape[-1] // 2:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos], axis=-1)


def index_select(q_index, k_index, w, topk, mode, block_rows=128):
    """``q_index [L, H, D]``, ``k_index [L, D]``, ``w [L, H]`` -> the selection,
    boolean ``[L, L]``: row ``t`` names the ``min(t + 1, topk)`` keys ``s <= t``
    of largest ``I[t, s]``, the lower ``s`` first among equals."""
    L = k_index.shape[0]
    rows_n = min(block_rows, L)
    pad = -L % rows_n
    q_index = jnp.pad(q_index, ((0, pad), (0, 0), (0, 0)))
    w = jnp.pad(w, ((0, pad), (0, 0)))
    k = min(topk, L)
    cols = jnp.arange(L)

    def block(start):
        rows = start + jnp.arange(rows_n)
        qb = jax.lax.dynamic_slice_in_dim(q_index, start, rows_n).transpose(1, 0, 2)   # [H, rows, D]
        wb = jax.lax.dynamic_slice_in_dim(w, start, rows_n)
        s = mm(qb, k_index.T, mode)                                                    # [H, rows, L]
        scores = jnp.einsum("hts,th->ts", jax.nn.relu(s), wb, precision=jax.lax.Precision.HIGHEST)
        scores = jnp.where(scores == 0, 0.0, scores)            # -0.0 ties with 0.0, as floats compare
        scores = jnp.where(cols[None, :] <= rows[:, None], scores, -jnp.inf)
        chosen = jax.lax.top_k(scores, k)[1]                                           # [rows, k]
        real = jnp.arange(k)[None, :] <= rows[:, None]          # a row's first t + 1 are keys
        return jnp.zeros((rows_n, L), bool).at[jnp.arange(rows_n)[:, None], chosen].max(real)

    starts = jnp.arange(0, L + pad, rows_n)
    return jax.lax.map(block, starts).reshape(L + pad, L)[:L]


def selected_core(q, k, v, selection, scale, mode, block_rows=128):
    """``q, k [H, L, d]``, ``v [H, L, dv]``, ``selection [L, L]`` bool -> ``[H, L,
    dv]``: each query's softmax over the keys its row names."""
    H, L, _ = q.shape
    rows_n = min(block_rows, L)
    pad = -L % rows_n
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    sp = jnp.pad(selection, ((0, pad), (0, 0)))
    sp = sp.at[L:, 0].set(True)                                  # a padded row keeps a key: no 0 / 0

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, rows_n, axis=1)
        keep = jax.lax.dynamic_slice_in_dim(sp, start, rows_n)
        s = jnp.where(keep[None], mm(qb, k.transpose(0, 2, 1), mode) * scale, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), v, mode)           # [H, rows, dv]

    out = jax.lax.map(block, jnp.arange(0, L + pad, rows_n))     # [blocks, H, rows, dv]
    return out.transpose(1, 0, 2, 3).reshape(H, L + pad, -1)[:, :L]


def layer_dims(sizes) -> _Dims:
    return _Dims(
        _axk1_dims(sizes),
        index_heads=int(sizes["index_n_heads"]), index_dim=int(sizes["index_head_dim"]),
        index_topk=int(sizes["index_topk"]))


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _latents_and_selection(lp, h, *, dims, mode):
    """``(c_q, c_kv, k_r rotated [L, rope], selection [L, L], cos, sin)``."""
    with jax.default_matmul_precision("highest"):
        p, eps, rot = lp["self_attn"], dims["eps"], dims["rope"]
        u = rms_norm(lp["input_layernorm"]["weight"], h, eps)
        L = u.shape[0]
        c_q = rms_norm(p["q_a_layernorm"]["weight"], mm(u, p["q_a_proj"]["kernel"], mode), eps)
        kv_a = mm(u, p["kv_a_proj_with_mqa"]["kernel"], mode)
        c_kv = rms_norm(p["kv_a_layernorm"]["weight"], kv_a[:, :dims["kv_rank"]], eps)
        angles = jnp.arange(L, dtype=_F32)[:, None] * jnp.asarray(dims["freqs"], _F32)
        cos, sin = jnp.cos(angles) * dims["rope_carry"], jnp.sin(angles) * dims["rope_carry"]
        k_r = rope(kv_a[:, None, dims["kv_rank"]:], cos, sin)[:, 0]
        ix = p["indexer"]
        Hi, Di = dims["index_heads"], dims["index_dim"]
        q_index = mm(c_q, ix["wq_b"]["kernel"], mode).reshape(L, Hi, Di)
        q_index = jnp.concatenate(
            [rope_halfsplit(q_index[..., :rot], cos, sin), q_index[..., rot:]], axis=-1)
        k_index = layer_norm(ix["k_norm"]["weight"], ix["k_norm"]["bias"],
                             mm(u, ix["wk"]["kernel"], mode), eps)
        k_index = jnp.concatenate(
            [rope_halfsplit(k_index[:, None, :rot], cos, sin)[:, 0], k_index[:, rot:]], axis=-1)
        w = mm(u, ix["weights_proj"]["kernel"], mode) * (Hi ** -0.5 * Di ** -0.5)
        selection = index_select(q_index, k_index, w, dims["index_topk"], mode)
        return c_q, c_kv, k_r, selection, cos, sin


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _core_of_heads(w_qb, w_kvb, c_q, c_kv, k_r, selection, cos, sin, *, dims, mode):
    """A group of heads, their columns of ``W_qb`` / ``W_kvb`` given: ``[L,
    heads x v]``."""
    with jax.default_matmul_precision("highest"):
        L = c_q.shape[0]
        nope, rot, dv = dims["nope"], dims["rope"], dims["v"]
        q = mm(c_q, w_qb, mode).reshape(L, -1, nope + rot)
        kv = mm(c_kv, w_kvb, mode).reshape(L, -1, nope + dv)
        heads = q.shape[1]
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cos, sin)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (L, heads, rot))], axis=-1)
        out = selected_core(*(x.transpose(1, 0, 2) for x in (q, k, kv[..., nope:])),
                            selection, dims["scale"], mode)
        return out.transpose(1, 0, 2).reshape(L, heads * dv)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _attention_out(lp, h, heads_out, *, eps, mode):
    with jax.default_matmul_precision("highest"):
        h = h + mm(heads_out, lp["self_attn"]["o_proj"]["kernel"], mode)
        return h, rms_norm(lp["post_attention_layernorm"]["weight"], h, eps)


def attend(lp, h, dims, mode, head_group=32):
    """The attention half of a layer: ``(h, u, selection)``, ``u`` the FFN's
    normed input. The core a group of heads at a time, so that 128 heads at
    16,384 tokens fit beside the program's weights."""
    p = lp["self_attn"]
    c_q, c_kv, k_r, selection, cos, sin = _latents_and_selection(lp, h, dims=dims, mode=mode)
    H, qk, nv = dims["heads"], dims["nope"] + dims["rope"], dims["nope"] + dims["v"]
    outs = []
    for g in range(0, H, head_group):
        n = min(head_group, H - g)
        outs.append(_core_of_heads(
            p["q_b_proj"]["kernel"][:, g * qk:(g + n) * qk],
            p["kv_b_proj"]["kernel"][:, g * nv:(g + n) * nv],
            c_q, c_kv, k_r, selection, cos, sin, dims=dims, mode=mode))
    heads_out = jnp.concatenate(outs, axis=-1) if len(outs) > 1 else outs[0]
    h, u = _attention_out(lp, h, heads_out, eps=dims["eps"], mode=mode)
    return h, u, selection


def route(router_kernel, bias, u, k, n_group, topk_group, scaling_factor, mode):
    """``(weights [L, k], experts [L, k])``: sigmoid scores; the choice is made
    on ``scores + bias`` (a group by the sum of its two best, then the ``k``
    best inside the kept groups), the weights from the scores themselves."""
    scores = jax.nn.sigmoid(mm(u, router_kernel, mode))
    pick = scores + bias.astype(_F32)
    L, E = scores.shape
    group_scores = jax.lax.top_k(pick.reshape(L, n_group, E // n_group), 2)[0].sum(-1)
    kept = jax.lax.top_k(group_scores, topk_group)[1]
    eligible = jnp.zeros((L, n_group), bool).at[jnp.arange(L)[:, None], kept].set(True)
    eligible = jnp.repeat(eligible, E // n_group, axis=1)
    experts = jax.lax.top_k(jnp.where(eligible, pick, -jnp.inf), k)[1]
    values = jnp.take_along_axis(scores, experts, axis=1)
    return values / values.sum(-1, keepdims=True) * scaling_factor, experts


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _route(moe, u, *, dims, mode):
    with jax.default_matmul_precision("highest"):
        return route(moe["router"]["kernel"], moe["e_score_correction_bias"], u, dims["top_k"],
                     dims["n_group"], dims["topk_group"], dims["scaling_factor"], mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _mtp_input(params, following, h, *, eps, mode):
    with jax.default_matmul_precision("highest"):
        e = params["embed_tokens"]["embedding"][following].astype(_F32)
        joined = jnp.concatenate([rms_norm(params["mtp_enorm"]["weight"], e, eps),
                                  rms_norm(params["mtp_hnorm"]["weight"], h, eps)], axis=-1)
        return mm(joined, params["mtp_eh_proj"]["kernel"], mode)


def _layer(lp, h, is_dense, dims, mode, seen):
    h, u, selection = attend(lp, h, dims, mode)
    if seen.get("selection") is not None:
        rows = seen.get("selection_rows")
        seen["selection"].append(np.asarray(selection if rows is None else selection[rows]))
    seen.setdefault("selected_pairs", []).append(int(selection.sum()))
    if is_dense:
        return _dense_ffn(lp["mlp"], h, u, mode=mode)
    weights, experts = _route(lp["moe"], u, dims=dims, mode=mode)
    if seen.get("routing") is not None:
        seen["routing"].append(np.asarray(experts))
    # the fullest expert's tokens, to the next 512: how many rows a gather holds
    counts = np.bincount(np.asarray(experts).ravel(), minlength=1)
    rows_max = min(-(-int(counts.max()) // 512) * 512, int(h.shape[0]))
    return _experts_and_shared(lp, h, u, weights, experts, dims=dims, rows_max=rows_max, mode=mode)


def lm_forward(params, ids, positions, sizes, mode="f32", seen=None):
    """One sequence: ``ids [L]`` int, ``positions [P]`` int -> logits ``[P,
    vocab_size]`` float32 on the host. ``sizes`` is the configuration file (or
    its tiny preset). ``seen``, where a dict is given, receives
    ``selected_pairs`` (a count a layer), ``mtp_logits`` where the share runs
    the prediction module, and, under the keys ``routing`` / ``selection`` if
    it holds a list there, each expert layer's ``experts [L, k]`` choices and
    each layer's selection ``[L, L]`` (the rows ``selection_rows`` names, where
    it names some)."""
    seen = {} if seen is None else seen
    dims = layer_dims(sizes)
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    L = int(ids.shape[0])
    h = params["embed_tokens"]["embedding"][ids].astype(_F32)
    for i in range(int(sizes["depth"])):
        h = _layer(params[f"layers_{i}"], h, i < int(sizes["first_k_dense_replace"]), dims, mode,
                   seen)
    head = functools.partial(_head, params["lm_head"]["kernel"], eps=dims["eps"], mode=mode)
    logits = np.asarray(head(params["norm"]["weight"], h[positions]))
    if int(sizes["num_nextn_predict_layers"]):
        following = jnp.concatenate([ids[1:], jnp.zeros((1,), jnp.int32)])
        x = _mtp_input(params, following, h, eps=dims["eps"], mode=mode)
        x = _layer(params["mtp_layer"], x, False, dims, mode, seen)
        seen["mtp_logits"] = np.asarray(head(params["mtp_shared_head_norm"]["weight"],
                                             x[jnp.minimum(positions, L - 2)]))
    return logits
