"""Granite 4.0-H: a causal hybrid decoder stack on the forward path.

``https://huggingface.co/ibm-granite/granite-4.0-h-small`` (``model_type:
granitemoehybrid``): by ``layer_types`` a layer's mixer is a Mamba-2
state-space mixer (:mod:`gigapath_tpu.ops.ssd`) or causal attention with
grouped KV heads and no positional encoding of any kind; every layer then
has a dropless top-k expert layer (:class:`~gigapath_tpu.ops.moe.DroplessMoE`)
beside an always-on shared gated MLP. All norms are RMSNorm with a gain::

    h = E[ids] * embedding_multiplier
    h = h + residual_multiplier * Mixer(RMSNorm(h))
    u = RMSNorm(h);  h = h + residual_multiplier * (MoE(u) + Shared(u))
    logits = RMSNorm(h) @ E^T / logits_scaling          (tied head)

A chip may hold a share of a layer: ``experts_held`` experts from
``expert_offset`` (the router keeps all ``num_local_experts`` outputs and
its top-k), the first ``vocab_size`` rows of the vocabulary, the first
``depth`` layers of the pattern. Parameters are bfloat16, the published
``torch_dtype``. Forward only: the system has no decode phase.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from gigapath_tpu.ops.flash_attention import flash_attention
from gigapath_tpu.ops.moe import DroplessMoE
from gigapath_tpu.ops.norms import RMSNorm
from gigapath_tpu.ops.ssd import Mamba2Mixer
from gigapath_tpu.utils.registry import register_model


class GatedMLP(nn.Module):
    """``W2(silu(a) * b)`` with ``[a | b] = W1 u``."""

    hidden_size: int
    intermediate_size: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        dense = dict(use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype)
        a, b = jnp.split(
            nn.Dense(2 * self.intermediate_size, name="input_linear", **dense)(u), 2, axis=-1)
        return nn.Dense(self.hidden_size, name="output_linear", **dense)(jax.nn.silu(a) * b)


class CausalGQAttention(nn.Module):
    """``softmax(causal(q k^T * scale)) v`` with ``num_heads`` query heads over
    ``num_kv_heads`` KV heads, no bias, no positions, then ``W_o``."""

    hidden_size: int
    num_heads: int
    num_kv_heads: int
    scale: float
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        b, L, _ = u.shape
        hd = self.hidden_size // self.num_heads
        dense = dict(use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype)
        q = nn.Dense(self.num_heads * hd, name="q_proj", **dense)(u)
        k = nn.Dense(self.num_kv_heads * hd, name="k_proj", **dense)(u)
        v = nn.Dense(self.num_kv_heads * hd, name="v_proj", **dense)(u)
        with jax.named_scope("attn_core"):
            out, _ = flash_attention(
                q.reshape(b, L, self.num_heads, hd),
                k.reshape(b, L, self.num_kv_heads, hd),
                v.reshape(b, L, self.num_kv_heads, hd),
                is_causal=True, scale=self.scale,
            )
        return nn.Dense(self.hidden_size, name="o_proj", **dense)(out.reshape(b, L, -1))


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The published ``config.json`` keys the forward pass reads, and the
    share of the model this chip holds."""

    layer_types: Tuple[str, ...]
    hidden_size: int
    vocab_size: int                      # rows of the vocabulary held here, from row 0
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int               # one expert's width
    shared_intermediate_size: int
    num_local_experts: int               # the router's outputs, whatever is held here
    num_experts_per_tok: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    attention_multiplier: float = 0.0078125
    embedding_multiplier: float = 12.0
    logits_scaling: float = 16.0
    residual_multiplier: float = 0.22
    rms_norm_eps: float = 1e-5
    depth: Optional[int] = None          # layers run here: the first of the pattern
    experts_held: Optional[int] = None   # experts held here, from expert_offset
    expert_offset: int = 0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def norm(self, name: str) -> RMSNorm:
        return RMSNorm(self.hidden_size, eps=self.rms_norm_eps,
                       param_dtype=self.param_dtype, name=name)


class HybridLayer(nn.Module):
    """One layer of the stack: ``h [B, L, hidden] -> (h, tokens each held
    expert received [experts_held])``; ``kind`` is the layer's entry of
    ``layer_types``."""

    kind: str
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        B, L, _ = h.shape
        common = dict(dtype=c.dtype, param_dtype=c.param_dtype)
        u = c.norm("input_layernorm")(h)
        if self.kind == "mamba":
            mixed = Mamba2Mixer(
                c.hidden_size, c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                conv_kernel=c.mamba_d_conv, chunk_size=c.mamba_chunk_size, norm_eps=c.rms_norm_eps,
                name="ssm_mixer", **common)(u)
        elif self.kind == "attention":
            mixed = CausalGQAttention(
                c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                c.attention_multiplier, name="self_attn", **common)(u)
        else:
            raise ValueError(f"unknown layer type {self.kind!r}")
        h = h + c.residual_multiplier * mixed
        u = c.norm("post_attention_layernorm")(h)
        routed, received = DroplessMoE(
            c.hidden_size, c.intermediate_size, c.num_local_experts, c.num_experts_per_tok,
            expert_offset=c.expert_offset, experts_held=c.experts_held, name="moe", **common,
        )(u.reshape(B * L, c.hidden_size))
        shared = GatedMLP(c.hidden_size, c.shared_intermediate_size,
                          name="shared_mlp", **common)(u)
        return h + c.residual_multiplier * (routed.reshape(B, L, -1) + shared), received


class GraniteHybridLM(nn.Module):
    """``(ids [B, L] int32, positions [B, P] int32) -> (logits [B, P,
    vocab_size] float32, tokens each held expert received [depth,
    experts_held] int32)``. ``positions`` names where logits are wanted (what
    ``logits_to_keep`` is to the published implementation): the head runs on
    those rows only."""

    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, ids: jnp.ndarray, positions: jnp.ndarray):
        c = self.cfg
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                         param_dtype=c.param_dtype, name="embed_tokens")
        h = embed(ids) * jnp.asarray(c.embedding_multiplier, c.dtype)
        counts = []
        for i, kind in enumerate(c.layer_types[: c.depth]):
            h, received = HybridLayer(kind, c, name=f"layers_{i}")(h)
            # one layer's temporaries at a time: without the barrier the
            # compiler overlaps neighbours and the 10-layer cut needs 6.3 GB
            # of them beside 9.5 GB of weights, with it 4.2 GB
            h = jax.lax.optimization_barrier(h)
            counts.append(received)
        with jax.named_scope("lm_head"):
            rows = jnp.take_along_axis(h, positions[..., None].astype(jnp.int32), axis=1)
            logits = jnp.einsum("bpd,vd->bpv", c.norm("norm")(rows), embed.embedding,
                                preferred_element_type=jnp.float32)
        return logits / c.logits_scaling, jnp.stack(counts)


def create_lm(model_arch: str = "granite_4_0_h_small", *, rng=None, **share):
    """Build a causal LM through the registry (the hybrids of this module,
    ``axk1`` / ``axk1_tiny`` of :mod:`gigapath_tpu.models.axk1`, ``deepseek_v32`` /
    ``deepseek_v32_tiny`` of :mod:`gigapath_tpu.models.deepseek_v32`, ``brumby`` /
    ``brumby_tiny`` of :mod:`gigapath_tpu.models.brumby`) and initialise
    its share's parameters at random on the device, under ``jit``. Returns
    ``(module, params)``. ``share`` cuts the model to what this chip holds
    (``depth``, ``experts_held``, ``expert_offset``, ``vocab_size``). No
    converter for a published checkpoint is here: the zero-egress build cannot
    fetch one."""
    import gigapath_tpu.models.axk1  # noqa: F401  (registers its archs)
    import gigapath_tpu.models.deepseek_v32  # noqa: F401
    import gigapath_tpu.models.brumby  # noqa: F401
    from gigapath_tpu.utils.registry import create_model_from_registry

    model = create_model_from_registry(model_arch, **share)
    ids = jnp.zeros((1, 4), jnp.int32)
    variables = jax.jit(model.init)(rng if rng is not None else jax.random.PRNGKey(0), ids, ids)
    return model, variables["params"]


# https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json
_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@register_model
def granite_4_0_h_small(**kwargs):
    """Granite 4.0-H Small (32B-A9B): 40 layers, 36 Mamba-2 and 4 attention,
    72 experts with top-10 and a shared MLP in every one."""
    return GraniteHybridLM(GraniteHybridConfig(**{**dict(
        layer_types=_PERIOD * 4, hidden_size=4096, vocab_size=100352,
        num_attention_heads=32, num_key_value_heads=8, intermediate_size=768,
        shared_intermediate_size=1536, num_local_experts=72, num_experts_per_tok=10,
        mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128,
        mamba_d_conv=4, mamba_chunk_size=256, attention_multiplier=0.0078125,
        embedding_multiplier=12.0, logits_scaling=16.0, residual_multiplier=0.22,
        rms_norm_eps=1e-5,
    ), **kwargs}))


@register_model
def granite_hybrid_tiny(**kwargs):
    """Hidden 64, four layers ``m a m m``, 8 experts with top-4, vocabulary
    256: the CPU tests' size. Top-4 and not top-2: the choice that a rounding
    tie moves then carries at most a quarter of a token's routed weight, as
    the tenth of ten carries little at the published size."""
    return GraniteHybridLM(GraniteHybridConfig(**{**dict(
        layer_types=("mamba", "attention", "mamba", "mamba"), hidden_size=64, vocab_size=256,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=32,
        shared_intermediate_size=48, num_local_experts=8, num_experts_per_tok=4,
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=16,
        attention_multiplier=0.0625, embedding_multiplier=12.0, logits_scaling=16.0,
        residual_multiplier=0.22, rms_norm_eps=1e-5,
    ), **kwargs}))
