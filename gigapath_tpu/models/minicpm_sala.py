"""MiniCPM-SALA: a dense decoder whose token mixers are InfLLM-v2 block-sparse
attention and decayed lightning linear attention, one to three.

``https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json``
(``model_type: minicpm_sala``): ``mixer_types`` gives each layer's mixer,
``minicpm4`` (sparse) or ``lightning-attn``. All norms are RMSNorm with a
gain; MiniCPM's muP scales; the head is untied::

    h_0 = scale_emb * E[ids]
    h  <- h + scale_depth / sqrt(num_hidden_layers) * Mixer(RMSNorm(h))
    h  <- h + scale_depth / sqrt(num_hidden_layers) * W_down(silu(W_gate u) * W_up u),  u = RMSNorm(h)
    logits = (RMSNorm(h)[rows] / (hidden_size / dim_model_base)) @ W_head              float32

    sparse (minicpm4), x the mixer's input, no positions:
      q = RMSNorm_q(x W_q) (heads of head_dim);  k = RMSNorm_k(x W_k);  v = x W_v      num_key_value_heads
      a = InfLLM-v2 attention (ops/block_sparse.py; causal GQA up to dense_len tokens)
      out = (a * sigmoid(x W_gate)) W_o
    lightning (lightning-attn):
      q = RoPE(RMSNorm_q(x W_q));  k = RoPE(RMSNorm_k(x W_k));  v = x W_v              rotate-half, rope_theta
      S_t,h = lambda_l,h S_t-1,h + k_t,h v_t,h^T;  o_t,h = q_t,h^T S_t,h / sqrt(d)      ops/ssd.linear_scan
      out = (RMSNorm_out(o) * sigmoid(x W_gate)) W_o                                    the norm over all heads

Set by the family's convention where the published file says nothing: the
sparse layers' ``sparse`` sizes (MiniCPM4's ``sparse_config``) and the
decay ``lambda_l,h`` (MiniMax-01's Lightning Attention schedule,
:func:`lightning_log_decay`, one function of the layer and the head). A chip
may hold the first ``depth`` layers. Parameters are bfloat16. Forward only: no
state is kept between calls and nothing decodes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from gigapath_tpu.models.axk1 import _Head
from gigapath_tpu.models.granite_hybrid import GatedMLP
from gigapath_tpu.ops import rope
from gigapath_tpu.ops.block_sparse import SparseSpec, infllm_attention
from gigapath_tpu.ops.norms import RMSNorm
from gigapath_tpu.ops.ssd import linear_scan
from gigapath_tpu.utils.registry import register_model

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
COUNTERS = ("selected_pairs", "kv_blocks_fetched", "kv_blocks_selected")


def lightning_log_decay(layer: int, heads: int, num_layers: int):
    """``log lambda [heads]`` float32 of one lightning layer: MiniMax-01's
    schedule, ``-2^(-8 (h + 1) / heads) (1 - layer / (num_layers - 1) +
    1e-5)`` with ``layer`` the index among all ``num_layers``."""
    slopes = 2.0 ** (-8.0 * (jnp.arange(heads, dtype=jnp.float32) + 1) / heads)
    return -slopes * (1.0 - layer / (num_layers - 1) + 1e-5)


@dataclasses.dataclass(frozen=True)
class MiniCPMSALAConfig:
    """The published ``config.json`` keys the forward pass reads, the sparse
    layers' sizes (``sparse_<key>`` for MiniCPM4's ``sparse_config[key]``),
    and the share of the model this chip holds. The lightning scan's chunk is
    128, the kernel's; the tiny preset's positions span several of its chunks
    of 16."""

    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    lightning_nh: int
    lightning_nkv: int
    lightning_head_dim: int
    vocab_size: int
    num_hidden_layers: int
    mixer_types: Tuple[str, ...]
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    lightning_chunk: int = 128
    depth: Optional[int] = None          # layers run here: the first of the stack
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def norm(self, name: str, dim: Optional[int] = None) -> RMSNorm:
        return RMSNorm(dim or self.hidden_size, eps=self.rms_norm_eps,
                       param_dtype=self.param_dtype, name=name)

    @property
    def sparse(self) -> SparseSpec:
        return SparseSpec(self.sparse_kernel_size, self.sparse_kernel_stride, self.sparse_block_size,
                          self.sparse_topk, self.sparse_init_blocks, self.sparse_window_size,
                          self.sparse_dense_len)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.num_hidden_layers)

    def dense(self, features: int, name: str) -> nn.Dense:
        return nn.Dense(features, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype,
                        name=name)


def _out_gate(c: MiniCPMSALAConfig, x, o):
    """``o * sigmoid(x W_gate)``, in float32 before the one rounding; ``W_gate``
    is as wide as ``o``, the heads side by side."""
    with jax.named_scope("out_gate"):
        gate = c.dense(o.shape[-1], "gate_proj")(x)
        return (o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(c.dtype)


class SparseAttention(nn.Module):
    """The ``minicpm4`` mixer: ``x [B, L, hidden]`` (normed) and the rows
    ``positions [B, P]`` -> ``([B, L, hidden], counters {name: [B] int32},
    the core's output at those rows [B, P, heads x head_dim] float32)``."""

    cfg: MiniCPMSALAConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        B, L, _ = x.shape
        H, G, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        q = c.dense(H * hd, "q_proj")(x).reshape(B, L, H, hd)
        k = c.dense(G * hd, "k_proj")(x).reshape(B, L, G, hd)
        v = c.dense(G * hd, "v_proj")(x).reshape(B, L, G, hd)
        q, k = c.norm("q_norm", hd)(q), c.norm("k_norm", hd)(k)
        out, counters = infllm_attention(q, k, v, c.sparse, scale=hd ** -0.5)
        out = out.reshape(B, L, H * hd)
        rows = jnp.take_along_axis(out, positions[..., None].astype(jnp.int32), axis=1)
        o = _out_gate(c, x, out)
        return c.dense(c.hidden_size, "o_proj")(o), counters, rows.astype(jnp.float32)


class LightningAttention(nn.Module):
    """The ``lightning-attn`` mixer of layer ``layer``: ``x [B, L, hidden]``
    (normed) and the rotary tables -> ``[B, L, hidden]``."""

    cfg: MiniCPMSALAConfig
    layer: int

    @nn.compact
    def __call__(self, x, cos, sin):
        c = self.cfg
        B, L, _ = x.shape
        H, hd = c.lightning_nh, c.lightning_head_dim
        q = c.dense(H * hd, "q_proj")(x).reshape(B, L, H, hd)
        k = c.dense(H * hd, "k_proj")(x).reshape(B, L, H, hd)
        v = c.dense(H * hd, "v_proj")(x).reshape(B, L, H, hd)
        q, k = c.norm("q_norm", hd)(q), c.norm("k_norm", hd)(k)
        with jax.named_scope("rope"):
            q = rope.apply_rope_halfsplit(q, cos, sin) * jnp.asarray(hd ** -0.5, q.dtype)
            k = rope.apply_rope_halfsplit(k, cos, sin)
        with jax.named_scope("lightning"):
            o = linear_scan(v, k, q, lightning_log_decay(self.layer, H, c.num_hidden_layers),
                            chunk=c.lightning_chunk)
        with jax.named_scope("out_norm"):
            o = c.norm("o_norm", H * hd)(o)
        return c.dense(c.hidden_size, "o_proj")(_out_gate(c, x, o))


class SALALayer(nn.Module):
    """One layer: ``h [B, L, hidden] -> (h, counters or None)``; a sparse
    layer's counters hold its core's rows too, under ``core_rows``."""

    cfg: MiniCPMSALAConfig
    layer: int

    @nn.compact
    def __call__(self, h, cos, sin, positions):
        c = self.cfg
        x = c.norm("input_layernorm")(h)
        counters = None
        if c.mixer_types[self.layer] == SPARSE:
            mixed, counters, rows = SparseAttention(c, name="self_attn")(x, positions)
            counters = {**counters, "core_rows": rows}
        else:
            mixed = LightningAttention(c, self.layer, name="self_attn")(x, cos, sin)
        scale = jnp.asarray(c.residual_scale, c.dtype)
        h = h + scale * mixed
        mlp = GatedMLP(c.hidden_size, c.intermediate_size, dtype=c.dtype,
                       param_dtype=c.param_dtype, name="mlp")
        return h + scale * mlp(c.norm("post_attention_layernorm")(h)), counters


class MiniCPMSALALM(nn.Module):
    """``(ids [B, L] int32, positions [B, P] int32) -> (logits [B, P,
    vocab_size] float32, received, counters)``: the contract
    ``pipeline.lm_forward_fn`` serves for every LM. No expert layer, so
    ``received`` is ``()``. ``counters`` holds, each ``[sparse layers, B]``
    int32, what each sparse layer's selection handed its core:
    ``selected_pairs`` (the (query, key) pairs ``s <= t``, summed over the KV
    groups), ``kv_blocks_selected`` (the blocks the selection named) and
    ``kv_blocks_fetched`` (the blocks the core visits, each once for every
    query position of its tile); and ``core_rows [sparse layers, B, P, heads
    x head_dim]`` float32, each sparse layer's core output (before its gate)
    at the rows ``positions`` names, what the selection, the tiles' lists and
    the core made of them."""

    cfg: MiniCPMSALAConfig

    @nn.compact
    def __call__(self, ids: jnp.ndarray, positions: jnp.ndarray):
        c = self.cfg
        depth = c.num_hidden_layers if c.depth is None else c.depth
        h = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype, param_dtype=c.param_dtype,
                     name="embed_tokens")(ids) * jnp.asarray(c.scale_emb, c.dtype)
        with jax.named_scope("rope"):
            inv_freq = rope.yarn_inv_freq(c.lightning_head_dim, c.rope_theta, 1.0, 0, 0.0, 0.0)
            cos, sin = rope.rope_tables(jnp.arange(ids.shape[1]), inv_freq)
        counted = []
        for i in range(depth):
            h, counters = SALALayer(c, i, name=f"layers_{i}")(h, cos, sin, positions)
            # one layer's temporaries at a time, as the other LMs have it
            h = jax.lax.optimization_barrier(h)
            if counters is not None:
                counted.append(counters)
        with jax.named_scope("lm_head"):
            rows = jnp.take_along_axis(h, positions[..., None].astype(jnp.int32), axis=1)
            rows = c.norm("norm")(rows) / jnp.asarray(c.hidden_size / c.dim_model_base, c.dtype)
            logits = _Head(c.hidden_size, c.vocab_size, c.param_dtype, name="lm_head")(rows)
        B = ids.shape[0]
        empty = {name: jnp.zeros((0, B), jnp.int32) for name in COUNTERS}
        empty["core_rows"] = jnp.zeros((0, B, positions.shape[1],
                                        c.num_attention_heads * c.head_dim), jnp.float32)
        return logits, (), {name: jnp.stack([n[name] for n in counted]) if counted else zeros
                            for name, zeros in empty.items()}


_PUBLISHED_MIXERS = (SPARSE,) + (LIGHTNING,) * 8 + (SPARSE,) + (LIGHTNING,) * 6 + (SPARSE,) * 2 + (
    LIGHTNING,) * 4 + (SPARSE,) + (LIGHTNING,) * 6 + (SPARSE,) * 3


# https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json
@register_model
def minicpm_sala(**kwargs):
    """MiniCPM-SALA: 32 layers, 8 sparse (32 query heads over 2 KV heads of
    128) and 24 lightning (32 heads of 128), SwiGLU 16,384, hidden 4,096,
    vocabulary 73,448, untied head."""
    return MiniCPMSALALM(MiniCPMSALAConfig(**{**dict(
        hidden_size=4096, intermediate_size=16384, num_attention_heads=32, num_key_value_heads=2,
        head_dim=128, lightning_nh=32, lightning_nkv=32, lightning_head_dim=128,
        vocab_size=73448, num_hidden_layers=32, mixer_types=_PUBLISHED_MIXERS,
        rope_theta=10000.0, rms_norm_eps=1e-6, scale_emb=12.0, scale_depth=1.4,
        dim_model_base=256,
    ), **kwargs}))


@register_model
def minicpm_sala_tiny(**kwargs):
    """Hidden 64, four layers (sparse, then three lightning), 4 query heads
    over 2 KV heads of 16, lightning 4 heads of 16, SwiGLU 128, vocabulary
    256; the selection at windows of 8 positions a step of 4, blocks of 8,
    the top 12 with the first block and the last 32 positions forced, and no
    dense length, so that a few hundred positions exercise every part of it; the
    lightning chunk 16."""
    return MiniCPMSALALM(MiniCPMSALAConfig(**{**dict(
        hidden_size=64, intermediate_size=128, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, lightning_nh=4, lightning_nkv=4, lightning_head_dim=16, vocab_size=256,
        num_hidden_layers=4, mixer_types=(SPARSE,) + (LIGHTNING,) * 3,
        sparse_kernel_size=8, sparse_kernel_stride=4, sparse_block_size=8, sparse_topk=12,
        sparse_init_blocks=1, sparse_window_size=32, sparse_dense_len=0,
        rope_theta=10000.0, rms_norm_eps=1e-6, scale_emb=12.0, scale_depth=1.4,
        dim_model_base=16, lightning_chunk=16,
    ), **kwargs}))
