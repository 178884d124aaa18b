"""DeepSeek-V3.2: latent attention over the keys a learned indexer selects.

``https://huggingface.co/deepseek-ai/DeepSeek-V3.2/blob/main/config.json``
(``model_type: deepseek_v32``). The DeepSeek-V3 layer of
:mod:`gigapath_tpu.models.axk1` (low-rank q / kv projections, 64 decoupled
rotary features with YaRN, 192-wide keys beside 128-wide values; its
projections are that module's, called from here) with a second, small
attention in front of every layer's core, the *lightning indexer*: 64 heads
of 128 score every earlier token for every query against one shared key, and
the core attends to the 2,048 best and to no other. The router has a learned
selection bias and ranks a group by its two best experts (``topk_method:
noaux_tc``); three leading layers are dense; one multi-token-prediction
module follows the stack. RMSNorm with a gain everywhere unless said;
bfloat16 parameters and activations, float32 accumulation, softmax, index
scores and router::

    h = E[ids];  h = h + Attn_l(RMSNorm(h));  u = RMSNorm(h);  h = h + FFN_l(u)
    FFN_l = W_down(silu(W_gate u) * W_up u)                   l <  first_k_dense_replace
    FFN_l = Routed(u) + Shared(u)                             l >= first_k_dense_replace
    logits = RMSNorm(h)[rows] @ W_head                        float32

    Attn(x):  c_q = RMSNorm(x W_qa);  q = c_q W_qb -> heads x [q_n | q_r]
              [c_kv | k_r] = x W_kva;  c_kv = RMSNorm(c_kv);  c_kv W_kvb -> heads x [k_n | v]
              q_r, k_r = RoPE_interleaved(.), k_r one head for all;  YaRN as A.X-K1 has it
      Indexer: qI = c_q W_Iq -> index heads x index dim,  first rope features of each: RoPE_halfsplit
               kI = LayerNorm(x W_Ik) (gain and bias), first rope features: RoPE_halfsplit; one key
               w  = (x W_Iw) [index heads] * index_heads ** -0.5 * index_dim ** -0.5       float32
               I[t, s] = sum_h w[t, h] * relu(qI[t, h] . kI[s])                            float32, s <= t
               S_t = the min(t + 1, index_topk) keys s <= t of largest I[t, s]; ties to the lower s
      core:    softmax over s in S_t of ([q_n | q_r] . [k_n | k_r] * scale) v, then W_o
               scale = (nope + rope) ** -0.5 * yarn_mscale(factor, mscale_all_dim) ** 2

    Routed(u): scores = sigmoid(u W_r) float32;  pick = scores + e_score_correction_bias
               a group scores the sum of its two largest pick; the topk_group best groups stay;
               the k largest pick among them; weight = score / sum(scores of the k) * scale
    MTP (one module, after the last layer run here; h that layer's output before the final norm):
               x'_i = [RMSNorm_e(E[ids[i + 1]]) ; RMSNorm_h(h_i)] W_eh        (2 hidden -> hidden)
               x'   -> one expert layer of the kind above (its own weights, indexer included)
               mtp_logits = RMSNorm_s(x')[rows'] @ W_head      (embedding and head are the main model's)
               run at length L with ids shifted left and slot L - 1 fed id 0: causality keeps rows
               <= L - 2 exact; rows' = min(rows, L - 2)

Departures from the published implementation (``inference/model.py`` of the
release): (a) the indexer's scores are bfloat16 operands with float32
accumulation, not FP8 with per-block scales; (b) the Hadamard rotation of
``qI`` and ``kI`` is left out: it is orthogonal, ``qI . kI`` is the same
number, and it exists for the FP8 quantiser; (c) ``W_eh`` takes ``[embedding ;
hidden]`` in that order, as the released weights have it (the paper writes the
other order; a row permutation under random weights); (d) the indexer's
LayerNorm takes ``rms_norm_eps`` as its eps; (e) forward only: every key's
``kv_b_proj`` is computed (the un-absorbed form) and nothing is cached. The
selection is exact, and the core attends to nothing outside it, at any length
(:mod:`gigapath_tpu.ops.sparse_index`).

A chip may hold a share of a layer exactly as :mod:`gigapath_tpu.models.axk1`
has it (``depth``, ``vocab_size``, ``experts_held``, ``expert_offset``), plus
``mtp``: how many prediction modules run here (0 or 1).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from gigapath_tpu.models.axk1 import AXK1Config, _Head, mla_projections, mla_rope_join
from gigapath_tpu.models.granite_hybrid import GatedMLP
from gigapath_tpu.ops import rope
from gigapath_tpu.ops.moe import DroplessMoE, GroupLimitedSigmoidGate
from gigapath_tpu.ops.sparse_index import sparse_index_attention
from gigapath_tpu.utils.registry import register_model


@dataclasses.dataclass(frozen=True)
class DeepseekV32Config(AXK1Config):
    """A.X-K1's keys (the DeepSeek-V3 layer) and the four V3.2 adds."""

    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    num_nextn_predict_layers: int = 1    # as published
    mtp: int = 0                         # prediction modules run here


class _LayerNorm(nn.Module):
    """LayerNorm with a gain and a bias under the published names (``weight``,
    ``bias``), float32 inside."""

    dim: int
    eps: float
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x32 = x.astype(jnp.float32)
        x32 = x32 - x32.mean(-1, keepdims=True)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        weight = self.param("weight", nn.initializers.ones, (self.dim,), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (self.dim,), self.param_dtype)
        return (x32 * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


class Indexer(nn.Module):
    """The lightning indexer's inputs: ``(x [B, L, hidden], c_q [B, L,
    q_lora_rank], tables) -> (qI [B, L, index heads, index dim], kI [B, L,
    index dim], w [B, L, index heads] float32)``."""

    cfg: DeepseekV32Config

    @nn.compact
    def __call__(self, x, c_q, cos, sin):
        c = self.cfg
        B, L, _ = x.shape
        H, D, rot = c.index_n_heads, c.index_head_dim, c.qk_rope_head_dim
        dense = dict(use_bias=False, dtype=c.dtype, param_dtype=c.param_dtype)
        q = nn.Dense(H * D, name="wq_b", **dense)(c_q).reshape(B, L, H, D)
        k = _LayerNorm(D, c.rms_norm_eps, c.param_dtype, name="k_norm")(
            nn.Dense(D, name="wk", **dense)(x))
        w = nn.Dense(H, use_bias=False, dtype=jnp.float32, param_dtype=c.param_dtype,
                     precision=jax.lax.Precision.HIGHEST, name="weights_proj")(x)
        with jax.named_scope("rope"):
            q = jnp.concatenate(
                [rope.apply_rope_halfsplit(q[..., :rot], cos, sin), q[..., rot:]], axis=-1)
            k = jnp.concatenate(
                [rope.apply_rope_halfsplit(k[:, :, None, :rot], cos, sin)[:, :, 0], k[..., rot:]],
                axis=-1)
        return q, k, w * (H ** -0.5 * D ** -0.5)


class SparseMLAttention(nn.Module):
    """Latent attention, un-absorbed, over the keys the indexer selects: ``u
    [B, L, hidden]`` and the rotary tables -> ``([B, L, hidden], pairs
    selected [B] int32)``. The selection itself (``[B, L, L]`` int8) is sowed
    as ``selection``, as :class:`~gigapath_tpu.ops.moe.DroplessMoE` sows its
    choices."""

    cfg: DeepseekV32Config

    @nn.compact
    def __call__(self, u, cos, sin):
        c = self.cfg
        B, L, _ = u.shape
        c_q, q, k_r, kv = mla_projections(c, u)
        q_index, k_index, w_index = Indexer(c, name="indexer")(u, c_q, cos, sin)
        q, k = mla_rope_join(c, q, k_r, kv, cos, sin)
        out, pairs, selection = sparse_index_attention(
            q_index, k_index, w_index, q, k, kv[..., c.qk_nope_head_dim:],
            topk=c.index_topk, scale=c.softmax_scale)
        # for whoever asks (mutable=["intermediates"]); nothing is kept otherwise
        self.sow("intermediates", "selection", selection)
        out = nn.Dense(c.hidden_size, use_bias=False, dtype=c.dtype, param_dtype=c.param_dtype,
                       name="o_proj")(out.reshape(B, L, c.num_attention_heads * c.v_head_dim))
        return out, pairs


class DeepseekV32Layer(nn.Module):
    """One layer: ``h [B, L, hidden] -> (h, tokens each held expert received
    [experts_held] or None for a dense layer, pairs selected)``."""

    cfg: DeepseekV32Config
    is_dense: bool

    @nn.compact
    def __call__(self, h, cos, sin):
        c = self.cfg
        B, L, _ = h.shape
        common = dict(dtype=c.dtype, param_dtype=c.param_dtype)
        attn, pairs = SparseMLAttention(c, name="self_attn")(c.norm("input_layernorm")(h), cos, sin)
        h = h + attn
        u = c.norm("post_attention_layernorm")(h)
        if self.is_dense:
            return h + GatedMLP(c.hidden_size, c.intermediate_size, name="mlp", **common)(u), \
                None, pairs
        routed, received = DroplessMoE(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts, c.num_experts_per_tok,
            expert_offset=c.expert_offset, experts_held=c.experts_held,
            gate=GroupLimitedSigmoidGate(c.n_group, c.topk_group, c.routed_scaling_factor,
                                         group_top=2, selection_bias=True),
            name="moe", **common,
        )(u.reshape(B * L, c.hidden_size))
        shared = GatedMLP(c.hidden_size, c.n_shared_experts * c.moe_intermediate_size,
                          name="shared_experts", **common)(u)
        return h + routed.reshape(B, L, -1) + shared, received, pairs


class DeepseekV32LM(nn.Module):
    """``(ids [B, L] int32, positions [B, P] int32) -> (logits [B, P,
    vocab_size] float32, tokens each held expert received [expert layers,
    experts_held] int32, extras)``: the first two are the contract
    ``pipeline.lm_forward_fn`` serves for every LM. ``extras`` is a dict of
    what this model counts and predicts besides: ``selected_pairs [layers, B]
    int32``, the (query, key) pairs each layer's selection handed its core for
    each sequence, counted on the device from the selection itself; and, only where a
    prediction module runs here (``mtp == 1``), ``mtp_logits [B, P,
    vocab_size]`` float32 for the token after next at rows ``min(positions, L
    - 2)``. The module's expert layer adds its row to the second output and its
    entry to ``selected_pairs``, last."""

    cfg: DeepseekV32Config

    @nn.compact
    def __call__(self, ids: jnp.ndarray, positions: jnp.ndarray):
        c = self.cfg
        if c.mtp not in (0, 1):
            raise ValueError(f"mtp is {c.mtp}: 0 or 1 prediction modules run here")
        depth = c.num_hidden_layers if c.depth is None else c.depth
        L = ids.shape[1]
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype, param_dtype=c.param_dtype,
                         name="embed_tokens")
        head = _Head(c.hidden_size, c.vocab_size, c.param_dtype, name="lm_head")
        positions = positions.astype(jnp.int32)
        h = embed(ids)
        with jax.named_scope("rope"):
            cos, sin = c.rope_tables(L)
        counts, selected = [], []
        for i in range(depth):
            h, received, pairs = DeepseekV32Layer(
                c, i < c.first_k_dense_replace, name=f"layers_{i}")(h, cos, sin)
            # one layer's temporaries at a time, as AXK1LM has it
            h = jax.lax.optimization_barrier(h)
            selected.append(pairs)
            if received is not None:
                counts.append(received)
        with jax.named_scope("lm_head"):
            rows = jnp.take_along_axis(h, positions[..., None], axis=1)
            logits = head(c.norm("norm")(rows))
        extras = {}
        if c.mtp:
            with jax.named_scope("mtp"):
                # the token after each row's own; slot L - 1 has none and is fed id 0
                following = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
                joined = jnp.concatenate(
                    [c.norm("mtp_enorm")(embed(following)), c.norm("mtp_hnorm")(h)], axis=-1)
                x = nn.Dense(c.hidden_size, use_bias=False, dtype=c.dtype,
                             param_dtype=c.param_dtype, name="mtp_eh_proj")(joined)
            x, received, pairs = DeepseekV32Layer(c, False, name="mtp_layer")(x, cos, sin)
            counts.append(received)
            selected.append(pairs)
            with jax.named_scope("lm_head"):
                rows = jnp.take_along_axis(
                    x, jnp.minimum(positions, L - 2)[..., None], axis=1)
                extras["mtp_logits"] = head(c.norm("mtp_shared_head_norm")(rows))
        extras["selected_pairs"] = jnp.stack(selected)
        held = c.n_routed_experts - c.expert_offset if c.experts_held is None else c.experts_held
        return (logits, jnp.stack(counts) if counts else jnp.zeros((0, held), jnp.int32), extras)


# https://huggingface.co/deepseek-ai/DeepSeek-V3.2/blob/main/config.json
@register_model
def deepseek_v32(**kwargs):
    """DeepSeek-V3.2 (671B-A37B): 61 layers, three dense and 58 with 256
    routed experts (top-8 within the 4 best of 8 groups, biased choice) and a
    shared one; 128 heads of latent attention over the 2,048 keys a 64-head
    indexer selects; one multi-token-prediction module."""
    return DeepseekV32LM(DeepseekV32Config(**{**dict(
        hidden_size=7168, vocab_size=129280, num_hidden_layers=61, num_attention_heads=128,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, intermediate_size=18432, moe_intermediate_size=2048,
        n_routed_experts=256, num_experts_per_tok=8, n_group=8, topk_group=4,
        routed_scaling_factor=2.5, n_shared_experts=1, first_k_dense_replace=3,
        rope_theta=10000.0, rope_factor=40.0, rope_original_max_position_embeddings=4096,
        rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0,
        rms_norm_eps=1e-6, index_n_heads=64, index_head_dim=128, index_topk=2048,
        num_nextn_predict_layers=1,
    ), **kwargs}))


@register_model
def deepseek_v32_tiny(**kwargs):
    """Hidden 64, three layers (one dense, two with 16 experts in 4 groups, 2
    groups kept, top-4), 4 heads of 16 + 8 / 16, an indexer of 4 heads of 16
    that keeps 16 keys a query, YaRN factor 4 over 32 positions, vocabulary
    256: the CPU tests' size."""
    return DeepseekV32LM(DeepseekV32Config(**{**dict(
        hidden_size=64, vocab_size=256, num_hidden_layers=3, num_attention_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        n_routed_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
        routed_scaling_factor=2.5, n_shared_experts=1, first_k_dense_replace=1,
        rope_theta=10000.0, rope_factor=4.0, rope_original_max_position_embeddings=32,
        rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0,
        rms_norm_eps=1e-6, index_n_heads=4, index_head_dim=16, index_topk=16,
        num_nextn_predict_layers=1,
    ), **kwargs}))
