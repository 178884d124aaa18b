"""Multi-head attention with log-sum-exp output.

TPU-native counterpart of reference ``torchscale/component/multihead_attention.py``
and ``torchscale/component/flash_attention.py``. The reference needs two CUDA
kernel stacks (flash-attn, xformers CUTLASS) because its LSE output is required
by dilated attention's branch recombination (``dilated_attention.py:119-128``).
Here the op is a single function: a pure-jnp softmax attention that always
returns ``(out, lse)``, which XLA fuses well at the segment sizes dilated
attention produces, plus an opt-in Pallas flash kernel
(:mod:`gigapath_tpu.ops.flash_attention`) for long dense segments.

Shapes follow the flash-attn convention the reference uses at the kernel
boundary: q/k/v are ``[B, L, H, D]``, lse is ``[B, H, L]``.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

# Large-but-finite mask value: keeps fully-masked rows NaN-free (exp(-1e8)=0,
# lse=-1e8 instead of -inf) which the dilated-branch recombination relies on.
NEG_INF = -1e8


def attention_with_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    scale: Optional[float] = None,
    bias: Optional[jnp.ndarray] = None,
    key_padding_mask: Optional[jnp.ndarray] = None,
    kv_valid_len=None,
    is_causal: bool = False,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Softmax attention returning ``(out [B,Lq,H,Dv], lse [B,H,Lq])``;
    ``Dv`` is ``v``'s last width, which need not be ``q`` and ``k``'s ``D``.

    Softmax statistics are accumulated in fp32 regardless of input dtype
    (bf16-safe); the output is cast back to the input dtype.

    - ``bias``: additive logits bias broadcastable to ``[B, H, Lq, Lk]``
      (T5 relative-position bias or a pre-built attn_mask).
    - ``key_padding_mask``: ``[B, Lk]`` bool, True = padding.
    - ``kv_valid_len``: static [B, H] per-(batch, head) valid key counts
      (keys at index >= count are masked) — same contract as the Pallas
      kernel's ragged masking.
    - ``is_causal``: lower-triangular mask (query i attends keys <= i).
    - grouped KV heads: ``k`` / ``v`` may carry ``H / g`` heads; query head
      ``h`` then attends KV head ``h // g``.
    """
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    if scale is None:
        scale = D**-0.5
    if k.shape[2] != H:
        k = jnp.repeat(k, H // k.shape[2], axis=2)
        v = jnp.repeat(v, H // v.shape[2], axis=2)

    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ).astype(jnp.float32) * scale

    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    kv_mask = None
    if kv_valid_len is not None:
        # accepts trace-time constants (numpy/tuple) or traced int arrays
        # (dynamic suffix-pad masking)
        lens = jnp.asarray(kv_valid_len, jnp.int32).reshape(B, H)[:, :, None, None]
        kv_mask = jnp.arange(Lk)[None, None, None, :] >= lens
        logits = jnp.where(kv_mask, NEG_INF, logits)
    pad_mask = None
    if key_padding_mask is not None:
        pad_mask = key_padding_mask[:, None, None, :]
        logits = jnp.where(pad_mask, NEG_INF, logits)
    if is_causal:
        qi = jnp.arange(Lq)[:, None] + (Lk - Lq)  # align ends when Lq != Lk
        ki = jnp.arange(Lk)[None, :]
        logits = jnp.where(ki > qi, NEG_INF, logits)

    lse = jax.scipy.special.logsumexp(logits, axis=-1)  # [B, H, Lq]
    probs = jnp.exp(logits - lse[..., None])
    if kv_mask is not None:
        # rows with zero valid keys yield out=0, not a mean over masked slots
        # (matches the Pallas kernel's explicit zeroing)
        probs = jnp.where(kv_mask, 0.0, probs)
    if pad_mask is not None:
        # same zeroing for key_padding_mask: a fully-padded row otherwise
        # degenerates to uniform probs (mean of V) instead of zeros
        probs = jnp.where(pad_mask, 0.0, probs)

    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = probs * keep / (1.0 - dropout_rate)

    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype), lse


class MultiheadAttention(nn.Module):
    """Self/cross attention block with q/k/v/out projections.

    Parity with reference ``multihead_attention.py:20-171``: optional xPos
    rotary position, optional sub-LayerNorm on the attention output
    (``subln``), an inner attention op returning ``(out, lse)``, and
    Multiway (BEiT-3) two-branch projections/inner-LN when ``multiway`` is
    set — the split index is passed per call as ``multiway_split_position``,
    mirroring the reference's ``MultiwayWrapper``-wrapped projections.
    """

    embed_dim: int
    num_heads: int
    dropout: float = 0.0
    self_attention: bool = True
    encoder_decoder_attention: bool = False
    subln: bool = False
    layernorm_eps: float = 1e-5
    xpos_rel_pos: bool = False
    xpos_scale_base: int = 512
    multiway: bool = False
    dtype: Any = None

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    def _attend(
        self,
        q: jnp.ndarray,
        k: jnp.ndarray,
        v: jnp.ndarray,
        *,
        key_padding_mask=None,
        attn_mask=None,
        rel_pos=None,
        is_causal: bool = False,
        deterministic: bool = True,
        offset: int = 0,
    ) -> jnp.ndarray:
        """Inner attention on [B, L, H, D] tensors -> [B, Lq, H*D].

        Subclasses (DilatedAttention) override this to restructure the
        sequence around the core op. ``offset`` is the decode position of
        the first query row — only produced by subclasses that opt into
        positional cache handling (see ``_cached_attend_inputs``).
        """
        assert offset == 0, "base attention consumes the cache via its bias"
        bias = None
        if attn_mask is not None:
            bias = attn_mask
        if rel_pos is not None:
            rel = rel_pos.reshape(q.shape[0], self.num_heads, q.shape[1], k.shape[1])
            bias = rel if bias is None else bias + rel
        rng = None
        if self.dropout > 0.0 and not deterministic:
            rng = self.make_rng("dropout")
        out, _ = attention_with_lse(
            q,
            k,
            v,
            bias=bias,
            key_padding_mask=key_padding_mask,
            is_causal=is_causal,
            dropout_rate=0.0 if deterministic else self.dropout,
            dropout_rng=rng,
        )
        return out.reshape(out.shape[0], out.shape[1], self.embed_dim)

    def _cached_attend_inputs(self, k, v, cur, Lq, attn_mask, is_causal):
        """Turn the updated KV cache into inputs for ``_attend``.

        Returns ``(k, v, attn_mask, is_causal, offset)``. The base class
        attends the whole static cache buffer with future rows masked by a
        per-query bias: query row i (absolute position cur+i) may attend
        keys <= cur+i — correct for single-token steps AND multi-token
        chunked prefill. DilatedAttention overrides this with positional
        (offset-based) handling, because its segment structure needs real
        positions rather than a dense mask.
        """
        max_len = k.shape[1]
        qi = jnp.arange(Lq)[:, None]
        ki = jnp.arange(max_len)[None, :]
        cache_bias = jnp.where(ki <= (cur + qi), 0.0, NEG_INF)[None, None]
        attn_mask = cache_bias if attn_mask is None else attn_mask + cache_bias
        return k, v, attn_mask, False, 0  # the cache bias supersedes the triangle

    @nn.compact
    def __call__(
        self,
        query: jnp.ndarray,
        key: jnp.ndarray,
        value: jnp.ndarray,
        *,
        key_padding_mask: Optional[jnp.ndarray] = None,
        attn_mask: Optional[jnp.ndarray] = None,
        rel_pos: Optional[jnp.ndarray] = None,
        is_causal: bool = False,
        decode: bool = False,
        multiway_split_position: int = -1,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        assert self.self_attention ^ self.encoder_decoder_attention
        B, Lq, _ = query.shape
        H, Dh = self.num_heads, self.head_dim

        from gigapath_tpu.ops.multiway import maybe_multiway

        def proj(name: str, x: jnp.ndarray) -> jnp.ndarray:
            make = lambda name: nn.Dense(  # noqa: E731
                self.embed_dim,
                use_bias=True,
                dtype=self.dtype,
                kernel_init=nn.initializers.xavier_uniform(),
                name=name,
            )
            return maybe_multiway(self.multiway, make, name)(
                x, split_position=multiway_split_position
            )

        q = proj("q_proj", query).reshape(B, Lq, H, Dh)
        k = proj("k_proj", key).reshape(B, key.shape[1], H, Dh)
        v = proj("v_proj", value).reshape(B, value.shape[1], H, Dh)

        if self.xpos_rel_pos and self.self_attention:
            from gigapath_tpu.ops.xpos import apply_xpos

            assert not decode, "xPos + incremental decode not supported"
            k = apply_xpos(k, scale_base=self.xpos_scale_base, downscale=True)
            q = apply_xpos(q, scale_base=self.xpos_scale_base, downscale=False)

        decode_offset = 0
        if decode and self.self_attention:
            # flax-style KV cache: the incremental-state counterpart of the
            # reference (multihead_attention.py:129-144 stores prev_key/
            # prev_value dicts). Cache shape is fixed by the first (init)
            # call; subsequent calls write the new rows at cache_index and
            # attend the buffer through the subclass-selected mechanism.
            is_initialized = self.has_variable("cache", "cached_key")
            cached_key = self.variable("cache", "cached_key", jnp.zeros, k.shape, k.dtype)
            cached_value = self.variable("cache", "cached_value", jnp.zeros, v.shape, v.dtype)
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.array(0, jnp.int32)
            )
            if is_initialized:
                cur = cache_index.value
                k = jax.lax.dynamic_update_slice(cached_key.value, k, (0, cur, 0, 0))
                v = jax.lax.dynamic_update_slice(cached_value.value, v, (0, cur, 0, 0))
                cached_key.value, cached_value.value = k, v
                cache_index.value = cur + Lq
                k, v, attn_mask, is_causal, decode_offset = (
                    self._cached_attend_inputs(k, v, cur, Lq, attn_mask, is_causal)
                )

        attn = self._attend(
            q,
            k,
            v,
            key_padding_mask=key_padding_mask,
            attn_mask=attn_mask,
            rel_pos=rel_pos,
            is_causal=is_causal,
            deterministic=deterministic,
            offset=decode_offset,
        )

        if self.subln and self.self_attention:
            from gigapath_tpu.ops.multiway import multiway_layernorm

            attn = multiway_layernorm(
                self.multiway,
                "inner_attn_ln",
                epsilon=self.layernorm_eps,
                dtype=self.dtype,
            )(attn, split_position=multiway_split_position)

        return proj("out_proj", attn)
