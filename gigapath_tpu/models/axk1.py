"""A.X-K1: a causal decoder with multi-head latent attention on the forward path.

``https://huggingface.co/skt/A.X-K1/blob/main/config.json`` (``model_type:
axk1``, the DeepSeek-V3 family's layer): queries and keys / values go through
low-rank projections with an RMSNorm on the latent; 64 features of every query
head and of one key head that all heads share carry rotary positions with
YaRN's blended frequencies (:mod:`gigapath_tpu.ops.rope`), beside 128 features
without positions, so a key is 192 wide and a value 128. The first
``first_k_dense_replace`` layers have a dense gated MLP; every later one a
dropless expert layer (:class:`~gigapath_tpu.ops.moe.DroplessMoE`) whose gate
scores with a sigmoid and ranks groups of experts before it picks
(:class:`~gigapath_tpu.ops.moe.GroupLimitedSigmoidGate`), beside an always-on
shared expert. All norms are RMSNorm with a gain; the head is untied::

    h = E[ids]
    h = h + MLA(RMSNorm(h));  u = RMSNorm(h);  h = h + FFN_l(u)
    FFN_l = W_down(silu(W_gate u) * W_up u)        l <  first_k_dense_replace
    FFN_l = Routed(u) + Shared(u)                  l >= first_k_dense_replace
    logits = RMSNorm(h)[rows] @ W_head             float32

    MLA(u):  c_q = RMSNorm(u W_qa);  q = c_q W_qb -> heads x [q_n | q_r]
             [c_kv | k_r] = u W_kva;  c_kv = RMSNorm(c_kv);  c_kv W_kvb -> heads x [k_n | v]
             q_r, k_r = RoPE(.), k_r one head used by all
             softmax(causal([q_n | q_r] . [k_n | k_r] * scale)) v, then W_o
             scale = (nope + rope) ** -0.5 * yarn_mscale(factor, mscale_all_dim) ** 2

``topk_method`` is ``"none"`` in the published file, a value the family's code
does not have; it is read as "no selection bias": the router has no
``e_score_correction_bias`` and a group is ranked by its largest score.

A chip may hold a share of a layer, as :mod:`gigapath_tpu.models.granite_hybrid`
has it: ``experts_held`` routed experts from ``expert_offset`` (the router keeps
all ``n_routed_experts`` outputs, its groups and its top-k), the first
``vocab_size`` rows of the vocabulary (embedding and head alike), the first
``depth`` layers. Parameters are bfloat16. Forward only: no latent cache, no
decode phase, and ``kv_b_proj`` runs on every token (the un-absorbed form).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from gigapath_tpu.models.granite_hybrid import GatedMLP
from gigapath_tpu.ops import rope
from gigapath_tpu.ops.flash_attention import flash_attention
from gigapath_tpu.ops.moe import DroplessMoE, GroupLimitedSigmoidGate
from gigapath_tpu.ops.norms import RMSNorm
from gigapath_tpu.utils.registry import register_model


@dataclasses.dataclass(frozen=True)
class AXK1Config:
    """The published ``config.json`` keys the forward pass reads (``rope_*``
    are the keys of its ``rope_scaling`` group), and the share of the model
    this chip holds."""

    hidden_size: int
    vocab_size: int                      # rows of the vocabulary held here, from row 0
    num_hidden_layers: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int               # the dense layers' MLP
    moe_intermediate_size: int           # one expert's width, routed or shared
    n_routed_experts: int                # the router's outputs, whatever is held here
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    rope_theta: float = 10000.0
    rope_factor: float = 1.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    rms_norm_eps: float = 1e-6
    depth: Optional[int] = None          # layers run here: the first of the stack
    experts_held: Optional[int] = None   # routed experts held here, from expert_offset
    expert_offset: int = 0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def norm(self, name: str, dim: Optional[int] = None) -> RMSNorm:
        return RMSNorm(dim or self.hidden_size, eps=self.rms_norm_eps,
                       param_dtype=self.param_dtype, name=name)

    @property
    def softmax_scale(self) -> float:
        m = rope.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    def rope_tables(self, length: int):
        """``(cos, sin) [length, qk_rope_head_dim / 2]`` float32 for positions
        ``0 .. length - 1``, carrying ``mscale / mscale_all_dim``."""
        inv_freq = rope.yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_original_max_position_embeddings, self.rope_beta_fast, self.rope_beta_slow)
        cos, sin = rope.rope_tables(jnp.arange(length), inv_freq)
        carry = rope.yarn_mscale(self.rope_factor, self.rope_mscale) \
            / rope.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (cos, sin) if carry == 1.0 else (cos * carry, sin * carry)


@functools.partial(jax.jit, static_argnames=("scale",))
def _causal_core(q, k, v, *, scale):
    # a jitted function of its own: every layer's core is one trace and one lowering
    return flash_attention(q, k, v, is_causal=True, scale=scale)[0]


def mla_projections(c: AXK1Config, u):
    """Latent attention's four input projections and two latent norms, as
    submodules of the module whose ``__call__`` is running (its parameters
    keep the published names): ``u [B, L, hidden] -> (c_q [B, L, q_lora_rank],
    q [B, L, H, nope + rope], k_r [B, L, rope] not yet rotated, kv [B, L, H,
    nope + v])``."""
    B, L, _ = u.shape
    H, nope, rot, dv = (c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
                        c.v_head_dim)
    dense = dict(use_bias=False, dtype=c.dtype, param_dtype=c.param_dtype)
    c_q = c.norm("q_a_layernorm", c.q_lora_rank)(
        nn.Dense(c.q_lora_rank, name="q_a_proj", **dense)(u))
    q = nn.Dense(H * (nope + rot), name="q_b_proj", **dense)(c_q).reshape(B, L, H, nope + rot)
    c_kv, k_r = jnp.split(
        nn.Dense(c.kv_lora_rank + rot, name="kv_a_proj_with_mqa", **dense)(u),
        [c.kv_lora_rank], axis=-1)
    kv = nn.Dense(H * (nope + dv), name="kv_b_proj", **dense)(
        c.norm("kv_a_layernorm", c.kv_lora_rank)(c_kv)).reshape(B, L, H, nope + dv)
    return c_q, q, k_r, kv


def mla_rope_join(c: AXK1Config, q, k_r, kv, cos, sin):
    """Scope ``rope``: the rotary features of every query head and of the one
    shared key rotated, the shared key broadcast to the heads and joined to
    their own part: ``(q, k) [B, L, H, nope + rope]`` for the core, whose
    values are ``kv[..., nope:]``."""
    B, L, H, _ = q.shape
    nope, rot = c.qk_nope_head_dim, c.qk_rope_head_dim
    with jax.named_scope("rope"):
        q_r = rope.apply_rope_interleaved(q[..., nope:], cos, sin)
        k_r = rope.apply_rope_interleaved(k_r[:, :, None, :], cos, sin)
        q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (B, L, H, rot))], axis=-1)
    return q, k


class MLAttention(nn.Module):
    """Multi-head latent attention, un-absorbed: ``u [B, L, hidden]`` and the
    rotary tables ``[L, rope / 2]`` -> ``[B, L, hidden]``. The shared rotary
    key is broadcast to the heads and joined to their own part, so the core
    sees plain ``[B, L, heads, nope + rope]`` keys beside ``[B, L, heads,
    v_head_dim]`` values."""

    cfg: AXK1Config

    @nn.compact
    def __call__(self, u, cos, sin):
        c = self.cfg
        B, L, _ = u.shape
        _, q, k_r, kv = mla_projections(c, u)
        q, k = mla_rope_join(c, q, k_r, kv, cos, sin)
        with jax.named_scope("attn_core"):
            out = _causal_core(q, k, kv[..., c.qk_nope_head_dim:], scale=c.softmax_scale)
        return nn.Dense(c.hidden_size, use_bias=False, dtype=c.dtype, param_dtype=c.param_dtype,
                        name="o_proj")(out.reshape(B, L, c.num_attention_heads * c.v_head_dim))


class AXK1Layer(nn.Module):
    """One layer: ``h [B, L, hidden] -> (h, tokens each held expert received
    [experts_held], or None for a dense layer)``."""

    cfg: AXK1Config
    is_dense: bool

    @nn.compact
    def __call__(self, h, cos, sin):
        c = self.cfg
        B, L, _ = h.shape
        common = dict(dtype=c.dtype, param_dtype=c.param_dtype)
        h = h + MLAttention(c, name="self_attn")(c.norm("input_layernorm")(h), cos, sin)
        u = c.norm("post_attention_layernorm")(h)
        if self.is_dense:
            return h + GatedMLP(c.hidden_size, c.intermediate_size, name="mlp", **common)(u), None
        routed, received = DroplessMoE(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts, c.num_experts_per_tok,
            expert_offset=c.expert_offset, experts_held=c.experts_held,
            gate=GroupLimitedSigmoidGate(c.n_group, c.topk_group, c.routed_scaling_factor),
            name="moe", **common,
        )(u.reshape(B * L, c.hidden_size))
        shared = GatedMLP(c.hidden_size, c.n_shared_experts * c.moe_intermediate_size,
                          name="shared_experts", **common)(u)
        return h + routed.reshape(B, L, -1) + shared, received


class _Head(nn.Module):
    """The untied head's rows held here: ``[.., hidden] -> [.., vocab]`` float32."""

    hidden_size: int
    vocab_size: int
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, rows):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (self.hidden_size, self.vocab_size), self.param_dtype)
        return jnp.einsum("bpd,dv->bpv", rows, kernel, preferred_element_type=jnp.float32)


class AXK1LM(nn.Module):
    """``(ids [B, L] int32, positions [B, P] int32) -> (logits [B, P,
    vocab_size] float32, tokens each held expert received [expert layers,
    experts_held] int32)``: the contract of
    :class:`~gigapath_tpu.models.granite_hybrid.GraniteHybridLM`, so
    ``pipeline.lm_forward_fn`` serves both. ``positions`` names the rows whose
    logits are wanted; a token's own position is its index in ``ids``."""

    cfg: AXK1Config

    @nn.compact
    def __call__(self, ids: jnp.ndarray, positions: jnp.ndarray):
        c = self.cfg
        depth = c.num_hidden_layers if c.depth is None else c.depth
        h = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype, param_dtype=c.param_dtype,
                     name="embed_tokens")(ids)
        with jax.named_scope("rope"):
            cos, sin = c.rope_tables(ids.shape[1])
        counts = []
        for i in range(depth):
            h, received = AXK1Layer(c, i < c.first_k_dense_replace, name=f"layers_{i}")(h, cos, sin)
            # one layer's temporaries at a time, as GraniteHybridLM has it
            h = jax.lax.optimization_barrier(h)
            if received is not None:
                counts.append(received)
        with jax.named_scope("lm_head"):
            rows = jnp.take_along_axis(h, positions[..., None].astype(jnp.int32), axis=1)
            logits = _Head(c.hidden_size, c.vocab_size, c.param_dtype, name="lm_head")(
                c.norm("norm")(rows))
        held = c.n_routed_experts - c.expert_offset if c.experts_held is None else c.experts_held
        return logits, jnp.stack(counts) if counts else jnp.zeros((0, held), jnp.int32)


# https://huggingface.co/skt/A.X-K1/blob/main/config.json
@register_model
def axk1(**kwargs):
    """A.X K1 (519B): 61 layers, one dense and 60 with 192 routed experts
    (top-8 within the 4 best of 8 groups) and a shared one; 64 heads of
    latent attention."""
    return AXK1LM(AXK1Config(**{**dict(
        hidden_size=7168, vocab_size=163840, num_hidden_layers=61, num_attention_heads=64,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, intermediate_size=18432, moe_intermediate_size=2048,
        n_routed_experts=192, num_experts_per_tok=8, n_group=8, topk_group=4,
        routed_scaling_factor=2.5, n_shared_experts=1, first_k_dense_replace=1,
        rope_theta=10000.0, rope_factor=32.0, rope_original_max_position_embeddings=4096,
        rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0,
        rms_norm_eps=1e-6,
    ), **kwargs}))


@register_model
def axk1_tiny(**kwargs):
    """Hidden 64, three layers (one dense, two with 16 experts in 4 groups,
    2 groups kept, top-4), 4 heads of 16 + 8 / 16, YaRN factor 4 over 32
    positions, vocabulary 256: the CPU tests' size."""
    return AXK1LM(AXK1Config(**{**dict(
        hidden_size=64, vocab_size=256, num_hidden_layers=3, num_attention_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        n_routed_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
        routed_scaling_factor=2.5, n_shared_experts=1, first_k_dense_replace=1,
        rope_theta=10000.0, rope_factor=4.0, rope_original_max_position_embeddings=32,
        rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0,
        rms_norm_eps=1e-6,
    ), **kwargs}))
