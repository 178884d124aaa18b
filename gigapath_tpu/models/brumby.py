"""Brumby-14B-Base: a dense decoder whose every token mixer is power retention.

``https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json``
(``model_type: brumby``): Qwen3's block with attention replaced by power
retention of degree 2 (:mod:`gigapath_tpu.ops.power_retention`), a gated,
normalised linear attention whose state is a degree-2 feature expansion of the
keys. All norms are RMSNorm with a gain; the head is untied::

    x   = RMSNorm(u)
    q_h = RMSNorm_q(x W_q,h);  k_g = RMSNorm_k(x W_k,g);  v_g = x W_v,g     heads of head_dim
    q, k <- RoPE(rope_theta, rotate-half) at each position
    log gamma_t,g = logsigmoid(x_t . w_g + b_g);  G_t,g = sum_{s<=t} log gamma_s,g
    y_t,h = sum_{s<=t} e^{G_t - G_s} (q_t,h . k_s,g)^2 v_s,g
            / (sum_{s<=t} e^{G_t - G_s} (q_t,h . k_s,g)^2 + eps),   g = g(h) the head's group
    a   = u + [y_1 ... y_H] W_o
    out = a + W_down(silu(W_gate RMSNorm(a)) * W_up RMSNorm(a))
    logits = RMSNorm(h)[rows] @ W_head                                    float32

The published file gives the widths and not the mixer's form; what is set
here by the family's convention: degree 2; one gate a KV head (the query
heads of a group read one state), a log-sigmoid of a linear map with a bias;
Qwen3's per-head q / k norms and rotate-half rotation at ``rope_theta``; no
norm or gate after the mixer; ``eps`` ``1e-6``. A chip may hold the first
``depth`` layers. Parameters are bfloat16. Forward only: no state is kept
between calls and nothing decodes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from gigapath_tpu.models.axk1 import _Head
from gigapath_tpu.models.granite_hybrid import GatedMLP
from gigapath_tpu.ops import rope
from gigapath_tpu.ops.norms import RMSNorm
from gigapath_tpu.ops.power_retention import EPS, power_retention
from gigapath_tpu.utils.registry import register_model


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    """The published ``config.json`` keys the forward pass reads (the head is
    untied, as published: no key chooses otherwise), the share of the model
    this chip holds, and the retention's chunk: 128, the kernel's; the tiny
    preset sets 16 so that its few dozen positions span several chunks."""

    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    vocab_size: int
    num_hidden_layers: int
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    retention_eps: float = EPS
    retention_chunk: int = 128
    depth: Optional[int] = None          # layers run here: the first of the stack
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def norm(self, name: str, dim: Optional[int] = None) -> RMSNorm:
        return RMSNorm(dim or self.hidden_size, eps=self.rms_norm_eps,
                       param_dtype=self.param_dtype, name=name)

    def rope_tables(self, length: int):
        """``(cos, sin) [length, head_dim / 2]`` float32, positions ``0 ..
        length - 1``."""
        inv_freq = rope.yarn_inv_freq(self.head_dim, self.rope_theta, 1.0, 0, 0.0, 0.0)
        return rope.rope_tables(jnp.arange(length), inv_freq)


class PowerRetention(nn.Module):
    """The token mixer: ``x [B, L, hidden]`` (normed) and the rotary tables ->
    ``([B, L, hidden], carried share [B] float32)``, the second the mean over
    positions and query heads of the share of each query's denominator that
    came through the state handed between chunks."""

    cfg: BrumbyConfig

    @nn.compact
    def __call__(self, x, cos, sin):
        c = self.cfg
        B, L, _ = x.shape
        H, G, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        dense = dict(use_bias=False, dtype=c.dtype, param_dtype=c.param_dtype)
        q = nn.Dense(H * hd, name="q_proj", **dense)(x).reshape(B, L, H, hd)
        k = nn.Dense(G * hd, name="k_proj", **dense)(x).reshape(B, L, G, hd)
        v = nn.Dense(G * hd, name="v_proj", **dense)(x).reshape(B, L, G, hd)
        q, k = c.norm("q_norm", hd)(q), c.norm("k_norm", hd)(k)
        with jax.named_scope("rope"):
            q = rope.apply_rope_halfsplit(q, cos, sin)
            k = rope.apply_rope_halfsplit(k, cos, sin)
        with jax.named_scope("retention"):
            with jax.named_scope("gate"):
                bias = self.param("gate_bias", nn.initializers.zeros, (G,), c.param_dtype)
                logits = nn.Dense(G, use_bias=False, dtype=jnp.float32, param_dtype=c.param_dtype,
                                  precision=jax.lax.Precision.HIGHEST, name="gate")(x)
                log_gate = jax.nn.log_sigmoid(logits + bias.astype(jnp.float32))
            y, carried = power_retention(q, k, v, log_gate, chunk=c.retention_chunk,
                                         eps=c.retention_eps)
        out = nn.Dense(c.hidden_size, name="o_proj", **dense)(y.reshape(B, L, H * hd))
        return out, carried.mean(axis=(1, 2))


class BrumbyLayer(nn.Module):
    """One layer: ``h [B, L, hidden] -> (h, carried share [B])``."""

    cfg: BrumbyConfig

    @nn.compact
    def __call__(self, h, cos, sin):
        c = self.cfg
        mixed, carried = PowerRetention(c, name="self_attn")(c.norm("input_layernorm")(h), cos, sin)
        h = h + mixed
        mlp = GatedMLP(c.hidden_size, c.intermediate_size, dtype=c.dtype,
                       param_dtype=c.param_dtype, name="mlp")
        return h + mlp(c.norm("post_attention_layernorm")(h)), carried


class BrumbyLM(nn.Module):
    """``(ids [B, L] int32, positions [B, P] int32) -> (logits [B, P,
    vocab_size] float32, received, {"carried_share": [depth, B] float32})``:
    the contract ``pipeline.lm_forward_fn`` serves for every LM. The model has
    no expert layer, so ``received`` holds no counts: ``()``, zero layers of
    them. ``carried_share`` is each layer's counter of what the state handed
    between chunks carried (:class:`PowerRetention`)."""

    cfg: BrumbyConfig

    @nn.compact
    def __call__(self, ids: jnp.ndarray, positions: jnp.ndarray):
        c = self.cfg
        depth = c.num_hidden_layers if c.depth is None else c.depth
        h = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype, param_dtype=c.param_dtype,
                     name="embed_tokens")(ids)
        with jax.named_scope("rope"):
            cos, sin = c.rope_tables(ids.shape[1])
        shares = []
        for i in range(depth):
            h, carried = BrumbyLayer(c, name=f"layers_{i}")(h, cos, sin)
            # one layer's temporaries at a time, as the other LMs have it
            h = jax.lax.optimization_barrier(h)
            shares.append(carried)
        with jax.named_scope("lm_head"):
            rows = jnp.take_along_axis(h, positions[..., None].astype(jnp.int32), axis=1)
            logits = _Head(c.hidden_size, c.vocab_size, c.param_dtype, name="lm_head")(
                c.norm("norm")(rows))
        return logits, (), {"carried_share": jnp.stack(shares)}


# https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json
@register_model
def brumby(**kwargs):
    """Brumby-14B-Base: 40 layers of power retention (40 query heads over 8
    KV heads of 128) and a SwiGLU of 17,408, hidden 5,120, vocabulary
    151,936, untied head."""
    return BrumbyLM(BrumbyConfig(**{**dict(
        hidden_size=5120, intermediate_size=17408, num_attention_heads=40, num_key_value_heads=8,
        head_dim=128, vocab_size=151936, num_hidden_layers=40, rope_theta=1000000.0,
        rms_norm_eps=1e-6,
    ), **kwargs}))


@register_model
def brumby_tiny(**kwargs):
    """Hidden 64, two layers, 4 query heads over 2 KV heads of 16, a SwiGLU
    of 128, vocabulary 256, chunks of 16: the CPU tests' size."""
    return BrumbyLM(BrumbyConfig(**{**dict(
        hidden_size=64, intermediate_size=128, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, vocab_size=256, num_hidden_layers=2, rope_theta=1000000.0,
        rms_norm_eps=1e-6, retention_chunk=16,
    ), **kwargs}))
