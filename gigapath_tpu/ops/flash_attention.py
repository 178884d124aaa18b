"""Fused attention entry point: (out, lse) with backend dispatch.

Role parity with reference ``torchscale/component/flash_attention.py``, which
tiers flash-attn CUDA -> xformers CUTLASS -> None by GPU capability. On TPU
the tiers are: Pallas flash kernel (long segments, memory-bound) or the
XLA-fused jnp op (short segments, default) — both emit the LSE that dilated
attention's branch fusion requires.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from gigapath_tpu.ops.attention import attention_with_lse

# Segments at least this long route to the Pallas kernel on TPU by default:
# below it, XLA's fused dense attention is faster than paying kernel overhead.
PALLAS_MIN_SEQ = 512


def _on_tpu() -> bool:
    """THE device gate: every Pallas dispatch in the library (here,
    ``ops/dilated_attention.py``, ``quant/``) asks this one function. True
    exactly when the default backend is ``tpu``; a backend that fails to
    initialise raises — it is never read as "no TPU"."""
    return jax.default_backend() == "tpu"


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    is_causal: bool = False,
    bias: Optional[jnp.ndarray] = None,
    kv_valid_len=None,
    use_pallas: Optional[bool] = None,
    scale: Optional[float] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Attention on [B, L, H, D] returning ``(out [B,L,H,Dv], lse [B,H,L])``.

    ``k`` / ``v`` may carry fewer heads than ``q`` (grouped KV heads: ``H_kv``
    divides ``H``, query head ``h`` reads KV head ``h // (H / H_kv)``). ``v``
    may have a width ``Dv`` other than ``q`` and ``k``'s ``D`` (latent
    attention's 192-wide keys beside 128-wide values); ``out`` takes ``v``'s,
    on both tiers, and the Pallas tier is then forward only.
    ``scale`` multiplies the logits; ``None`` is ``D ** -0.5``.

    ``kv_valid_len``: [B, H] valid-key counts (ragged tail masking). Static
    (numpy/tuple) counts ride both backends; *traced* counts (dynamic
    per-batch padding) are only supported by the jnp path — the Pallas
    wrapper bakes them into the compiled grid.
    """
    kvlen_is_dynamic = isinstance(kv_valid_len, jax.Array) or isinstance(
        kv_valid_len, jax.core.Tracer
    )
    if use_pallas is None:
        use_pallas = (
            _on_tpu()
            and bias is None
            and not kvlen_is_dynamic
            and q.shape[1] >= PALLAS_MIN_SEQ
        )
    elif use_pallas and kvlen_is_dynamic:
        raise ValueError(
            "use_pallas=True requires static kv_valid_len; traced counts "
            "(dynamic padding masks) need the jnp path"
        )
    elif use_pallas and bias is not None:
        # the Pallas kernel takes no bias; silently dropping it would produce
        # wrong attention output for an explicit override
        raise ValueError(
            "use_pallas=True is incompatible with a non-None bias; "
            "use the jnp path (use_pallas=False) for biased attention"
        )
    if use_pallas:
        from gigapath_tpu.ops.pallas_flash import pallas_flash_attention

        return pallas_flash_attention(
            q, k, v, is_causal=is_causal, kv_len=kv_valid_len, scale=scale
        )
    with jax.named_scope("kernel_fwd"):
        return attention_with_lse(
            q, k, v, is_causal=is_causal, bias=bias, kv_valid_len=kv_valid_len,
            scale=scale,
        )


def partial_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    kv_valid_len=None,
    use_pallas: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Partial-softmax entry point for blockwise/ring schedules.

    Attention of ``q`` against ONE resident K/V chunk, returning the
    chunk-normalized ``(out [B,Lq,H,D], lse [B,H,Lq])`` pair — exactly
    the state :func:`combine_partials` folds across chunks: because the
    output is normalized by its own softmax sum and the sum's log rides
    in the lse, partials over disjoint key sets merge into the full
    softmax without ever materializing the concatenated key axis. This
    is :func:`flash_attention` restricted to the non-causal self-shape
    case (a ring step has no global causal structure — callers mask
    before/at the chunk level via ``kv_valid_len``); it exists as a
    named entry so ring-step call sites read as partial-softmax by
    contract, not by accident of the default path.
    """
    return flash_attention(
        q, k, v, is_causal=False, kv_valid_len=kv_valid_len,
        use_pallas=use_pallas,
    )


def combine_partials(
    out_a: jnp.ndarray,
    lse_a: jnp.ndarray,
    out_b: jnp.ndarray,
    lse_b: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Merge two partial-softmax results by their stored log-sum-exps.

    ``out_*`` are ``[B, L, H, D]`` attention outputs each normalized
    over its OWN key set, ``lse_*`` the matching ``[B, H, L]``
    log-sum-exps; returns the pair normalized over the UNION of the key
    sets — the same online-softmax identity flash attention applies
    across key blocks inside one kernel and the stream-fusion epilogue
    applies across branches (pallas_dilated.py), here applied across
    ring steps. Fully-masked partials carry ``lse ~ NEG_INF`` and fold
    in with weight ``exp(NEG_INF - lse) == 0``, so no special-casing.

    Accumulates in fp32 and returns ``out`` in ``out_a``'s dtype — ring
    loops keep the accumulator fp32 end to end by seeding with an fp32
    first partial.
    """
    lse = jnp.logaddexp(lse_a, lse_b)  # [B, H, L]

    def w4(w):  # [B, H, L] -> broadcastable [B, L, H, 1]
        return w.transpose(0, 2, 1)[..., None]

    out = (
        out_a.astype(jnp.float32) * w4(jnp.exp(lse_a - lse))
        + out_b.astype(jnp.float32) * w4(jnp.exp(lse_b - lse))
    )
    return out.astype(out_a.dtype), lse
