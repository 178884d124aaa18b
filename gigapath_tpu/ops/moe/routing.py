"""GShard-style MoE gating (top-1 / top-2) as pure, static-shape jnp.

Parity with reference ``torchscale/component/xmoe/routing.py``: softmax gates,
capacity = ``cf * ceil(S/E)`` (top-1) or ``2 * ceil(S/E)`` (top-2) with the
eval-mode token-fraction override (``routing.py:58-62,278-282``), location
assignment by cumsum-minus-one over the token axis, the balance loss
``l_aux = mean(me * ce) * E^2`` (``routing.py:94-99,345-349``), the xmoe
cosine router (16-dim reduction + L2-normalized expert embeddings,
``routing.py:187-193,220-225``), and the gating telemetry (entropy, unused
experts, balance top/bottom fractions, ``routing.py:53,72-87``).

TPU-first notes: capacity is a Python int derived from static shapes, so the
dispatch/combine tensors have static ``[S, E, C]`` shapes under ``jit``; the
scatter-based ``one_hot`` becomes ``jax.nn.one_hot`` (einsum-friendly); the
custom Gumbel sampler is ``jax.random.gumbel``; there is no fused-cumsum
special case — XLA fuses ``cumsum`` fine. The torch in-place renorm of the
xmoe expert embeddings (``routing.py:190-191``) is redundant with the
cosine's own normalization and becomes a plain normalized matmul here.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

# fixed constants, parity with reference routing.py:25-33
EVAL_CAPACITY_TOKEN_FRACTION = 0.25
SAMPLE_FRACTION = 0.2

GatingResult = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]


def _entropy(probs: jnp.ndarray) -> jnp.ndarray:
    logp = jnp.log(jnp.clip(probs, 1e-9))
    return -(probs * logp).sum(-1)


def _balance_metadata(
    indices_s: jnp.ndarray, num_experts: int, num_tokens: int, prefix: str
) -> Dict[str, jnp.ndarray]:
    """Percent-of-tokens-per-expert histogram stats (routing.py:72-87)."""
    hist = 100.0 * jnp.bincount(indices_s, length=num_experts) / num_tokens
    sample_count = max(math.ceil(num_experts * SAMPLE_FRACTION), 1)
    hist_sorted = jnp.sort(hist)[::-1] + jnp.finfo(jnp.float32).tiny
    return {
        f"unused_{prefix}_count": (hist == 0).sum(),
        f"{prefix}_balance_top": hist_sorted[:sample_count].sum(),
        f"{prefix}_balance_bottom": hist_sorted[-sample_count:].sum(),
    }


def _capacity(
    num_tokens: int,
    num_experts: int,
    *,
    capacity_factor: float,
    eval_mode: bool,
    eval_capacity_token_fraction: float,
) -> int:
    if eval_capacity_token_fraction > 0.0 and eval_mode:
        return math.ceil(eval_capacity_token_fraction * num_tokens)
    return int(capacity_factor * math.ceil(num_tokens / num_experts))


def top1_gating(
    logits: jnp.ndarray,
    input_mask: Optional[jnp.ndarray] = None,
    *,
    use_fp32: bool = True,
    capacity_factor: float = 1.0,
    eval_mode: bool = False,
    eval_capacity_token_fraction: float = EVAL_CAPACITY_TOKEN_FRACTION,
) -> GatingResult:
    """Top-1 gating on ``logits [S, E]``.

    Returns ``(l_aux, combine_weights [S,E,C], dispatch_mask [S,E,C],
    metadata)``; semantics of reference ``top1gating`` (routing.py:36-137).
    """
    orig_dtype = logits.dtype
    if use_fp32:
        logits = logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    num_tokens, num_experts = gates.shape
    capacity = _capacity(
        num_tokens,
        num_experts,
        capacity_factor=capacity_factor,
        eval_mode=eval_mode,
        eval_capacity_token_fraction=eval_capacity_token_fraction,
    )

    indices1_s = jnp.argmax(gates, axis=-1)
    mask1 = jax.nn.one_hot(indices1_s, num_experts, dtype=gates.dtype)
    if input_mask is not None:
        mask1 = mask1 * (~input_mask)[:, None].astype(mask1.dtype)

    metadata = {"entropy_gating": _entropy(gates).mean()}
    metadata.update(_balance_metadata(indices1_s, num_experts, num_tokens, "expert1"))

    gates1_s = (gates * mask1).sum(axis=-1)
    locations1 = jnp.cumsum(mask1, axis=0) - 1

    # balance loss (fraction-routed x mean-gate, scaled E^2)
    me = gates.mean(axis=0)
    ce = mask1.mean(axis=0)
    l_aux = (me * ce).mean() * num_experts * num_experts

    mask1 = mask1 * (locations1 < capacity)
    locations1_s = (locations1 * mask1).sum(axis=-1).astype(jnp.int32)

    gates1 = gates1_s[:, None] * mask1  # [S, E]
    locations1_sc = jax.nn.one_hot(locations1_s, capacity, dtype=gates.dtype)
    combine_sec = jnp.einsum("se,sc->sec", gates1, locations1_sc)
    dispatch_mask = combine_sec > 0
    if use_fp32:
        combine_sec = combine_sec.astype(orig_dtype)
    return l_aux, combine_sec, dispatch_mask, metadata


def top2_gating(
    logits: jnp.ndarray,
    input_mask: Optional[jnp.ndarray] = None,
    *,
    rng: Optional[jax.Array] = None,
    use_fp32: bool = True,
    second_expert_policy: str = "sampling",
    normalize_gate_prob_before_dropping: bool = False,
    eval_mode: bool = False,
    eval_capacity_token_fraction: float = EVAL_CAPACITY_TOKEN_FRACTION,
    batch_prioritized_routing: bool = False,
) -> GatingResult:
    """Top-2 gating on ``logits [S, E]`` (reference ``top2gating``,
    routing.py:258-445).

    ``rng`` drives the stochastic second-expert policies (``sampling`` adds
    Gumbel noise to the second-expert argmax; ``random`` keeps the second
    expert with probability ``min(1, 2*gate2)``); with ``rng=None`` both
    policies fall back to their noise-free deterministic core — the
    functional-API equivalent of inference without sampling.
    """
    orig_dtype = logits.dtype
    if use_fp32:
        logits = logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    num_tokens, num_experts = gates.shape
    if eval_capacity_token_fraction > 0.0 and eval_mode:
        capacity = math.ceil(eval_capacity_token_fraction * num_tokens)
    else:
        capacity = 2 * math.ceil(num_tokens / num_experts)

    indices1_s = jnp.argmax(gates, axis=-1)
    mask1 = jax.nn.one_hot(indices1_s, num_experts, dtype=gates.dtype)

    if second_expert_policy == "sampling" and rng is not None:
        logits_w_noise = logits + jax.random.gumbel(rng, logits.shape, logits.dtype)
    else:
        logits_w_noise = logits
    logits_except1 = jnp.where(mask1 > 0, -jnp.inf, logits_w_noise)
    indices2_s = jnp.argmax(logits_except1, axis=-1)
    mask2 = jax.nn.one_hot(indices2_s, num_experts, dtype=gates.dtype)

    gates1_s = (gates * mask1).sum(axis=-1)
    gates2_s = (gates * mask2).sum(axis=-1)

    if normalize_gate_prob_before_dropping:
        denom_s = jnp.clip(gates1_s + gates2_s, jnp.finfo(gates.dtype).eps)
        gates1_s = gates1_s / denom_s
        gates2_s = gates2_s / denom_s

    if second_expert_policy == "random" and rng is not None:
        sampled = (2 * gates2_s) > jax.random.uniform(rng, gates2_s.shape, gates2_s.dtype)
        mask2 = mask2 * sampled[:, None].astype(mask2.dtype)

    if input_mask is not None:
        nonpad = (~input_mask)[:, None].astype(mask1.dtype)
        mask1 = mask1 * nonpad
        mask2 = mask2 * nonpad

    if batch_prioritized_routing:
        # sort tokens by gate confidence; assign capacity in that order
        # (routing.py:318-338) — argsort/inverse-argsort, all static shapes
        importance = -gates.max(axis=-1)
        order = jnp.argsort(importance, axis=0)
        inverse = jnp.argsort(order, axis=0)
        sorted_mask1 = mask1[order]
        locations1 = ((jnp.cumsum(sorted_mask1, axis=0) - 1) * sorted_mask1)[inverse]
        sorted_mask2 = mask2[order]
        locations2 = ((jnp.cumsum(sorted_mask2, axis=0) - 1) * sorted_mask2)[inverse]
        locations2 = locations2 + mask1.sum(axis=0, keepdims=True)
    else:
        locations1 = jnp.cumsum(mask1, axis=0) - 1
        locations2 = jnp.cumsum(mask2, axis=0) - 1
        locations2 = locations2 + mask1.sum(axis=0, keepdims=True)

    me = gates.mean(axis=0)
    ce = mask1.mean(axis=0)
    l_aux = (me * ce).mean() * num_experts * num_experts

    metadata = {
        "entropy_gating": _entropy(gates).mean(),
        "overflow_expert1": 100.0
        * (mask1 * (locations1 >= capacity)).sum()
        / jnp.clip(mask1.sum(), 1.0),
        "overflow_expert2": 100.0
        * (mask2 * (locations2 >= capacity)).sum()
        / jnp.clip(mask2.sum(), 1.0),
    }
    metadata.update(_balance_metadata(indices1_s, num_experts, num_tokens, "expert1"))
    metadata.update(_balance_metadata(indices2_s, num_experts, num_tokens, "expert2"))

    mask1 = mask1 * (locations1 < capacity)
    mask2 = mask2 * (locations2 < capacity)
    locations1_s = (locations1 * mask1).sum(axis=-1).astype(jnp.int32)
    locations2_s = (locations2 * mask2).sum(axis=-1).astype(jnp.int32)

    if not normalize_gate_prob_before_dropping:
        gates1_s = (gates * mask1).sum(axis=-1)
        gates2_s = (gates * mask2).sum(axis=-1)
        denom_s = jnp.clip(gates1_s + gates2_s, jnp.finfo(gates.dtype).eps)
        gates1_s = gates1_s / denom_s
        gates2_s = gates2_s / denom_s

    gates1 = gates1_s[:, None] * mask1
    gates2 = gates2_s[:, None] * mask2
    locations1_sc = jax.nn.one_hot(locations1_s, capacity, dtype=gates.dtype)
    locations2_sc = jax.nn.one_hot(locations2_s, capacity, dtype=gates.dtype)
    combine_sec = jnp.einsum("se,sc->sec", gates1, locations1_sc) + jnp.einsum(
        "se,sc->sec", gates2, locations2_sc
    )
    dispatch_mask = combine_sec > 0
    if use_fp32:
        combine_sec = combine_sec.astype(orig_dtype)
    return l_aux, combine_sec, dispatch_mask, metadata


class _GateBase(nn.Module):
    """Shared router projection: plain linear or xmoe cosine router."""

    model_dim: int = 768
    num_experts: int = 8
    use_xmoe: bool = False
    dtype: Any = None

    def _logits(self, x: jnp.ndarray) -> jnp.ndarray:
        if not self.use_xmoe:
            return nn.Dense(
                self.num_experts, use_bias=False, dtype=self.dtype, name="wg"
            )(x)
        # xmoe cosine router: reduce to 16-d, cosine vs orthogonal-init
        # expert embeddings (routing.py:175-178,220-225)
        reduced = nn.Dense(16, use_bias=False, dtype=self.dtype, name="wg_reduction")(x)
        wg = self.param(
            "wg", nn.initializers.orthogonal(scale=0.32), (self.num_experts, 16)
        )
        wg = wg / jnp.clip(jnp.linalg.norm(wg, axis=-1, keepdims=True), 1e-4)
        logits = reduced.astype(jnp.float32) @ wg.astype(jnp.float32).T
        logits = jnp.where(jnp.isfinite(logits), logits, jnp.finfo(jnp.float32).min)
        return logits.astype(reduced.dtype)


class Top1Gate(_GateBase):
    """Flax Top-1 gate (reference ``Top1Gate``, routing.py:140-225)."""

    use_fp32: bool = True
    capacity_factor: float = 1.0
    eval_capacity_token_fraction: float = EVAL_CAPACITY_TOKEN_FRACTION

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, mask: Optional[jnp.ndarray] = None, *, eval_mode: bool = True
    ) -> GatingResult:
        return top1_gating(
            self._logits(x),
            mask,
            use_fp32=self.use_fp32,
            capacity_factor=self.capacity_factor,
            eval_mode=eval_mode,
            eval_capacity_token_fraction=self.eval_capacity_token_fraction,
        )


class Top2Gate(_GateBase):
    """Flax Top-2 gate (reference ``Top2Gate``, routing.py:448-525)."""

    use_fp32: bool = True
    second_expert_policy: str = "sampling"
    normalize_gate_prob_before_dropping: bool = False
    eval_capacity_token_fraction: float = EVAL_CAPACITY_TOKEN_FRACTION
    batch_prioritized_routing: bool = False

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        *,
        rng: Optional[jax.Array] = None,
        eval_mode: bool = True,
    ) -> GatingResult:
        return top2_gating(
            self._logits(x),
            mask,
            rng=rng,
            use_fp32=self.use_fp32,
            second_expert_policy=self.second_expert_policy,
            normalize_gate_prob_before_dropping=self.normalize_gate_prob_before_dropping,
            eval_mode=eval_mode,
            eval_capacity_token_fraction=self.eval_capacity_token_fraction,
            batch_prioritized_routing=self.batch_prioritized_routing,
        )


def topk_softmax_gating(logits: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless top-k routing: the ``k`` largest of each token's router
    logits ``[S, E]``, then a softmax over those ``k`` values alone. No
    capacity, no dropped token, no ``[S, E, C]`` tensor. Returns ``(weights
    [S, k] float32, experts [S, k] int32)``."""
    values, experts = jax.lax.top_k(logits.astype(jnp.float32), k)
    return jax.nn.softmax(values, axis=-1), experts.astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class GroupLimitedSigmoidGate:
    """Dropless routing as DeepSeek-V3's ``Gate`` does it: sigmoid scores
    ``[S, E]``; the experts lie in ``n_group`` equal groups, a group scores the
    sum of its ``group_top`` best experts (1: what its best expert scores), and
    only the ``topk_group`` best groups stay eligible; the ``k`` largest among
    those; weights are the chosen *scores* over their sum, times ``scale``.
    Called as :func:`topk_softmax_gating` is: ``gate(logits, k) -> (weights
    [S, k] float32, experts [S, k] int32)``. Ties go to the lower index, in
    the groups and in the experts. A value, so that two modules built alike
    compare equal.

    ``selection_bias`` (``topk_method: noaux_tc``) asks the layer that owns the
    gate for a learned ``e_score_correction_bias [E]`` and hands it on as
    ``gate(logits, k, bias)``: it is added to the scores for *choosing* only,
    the groups and the experts alike; the weights stay the unbiased scores.
    Without it (``bias`` None) the choice is made on the scores themselves."""

    n_group: int
    topk_group: int
    scale: float = 1.0
    group_top: int = 1
    selection_bias: bool = False

    def __call__(self, logits: jnp.ndarray, k: int,
                 bias: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        S, E = logits.shape
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        pick = scores if bias is None else scores + bias.astype(jnp.float32)
        grouped = pick.reshape(S, self.n_group, E // self.n_group)
        group_scores = grouped.max(-1) if self.group_top == 1 \
            else jax.lax.top_k(grouped, self.group_top)[0].sum(-1)
        _, kept = jax.lax.top_k(group_scores, self.topk_group)
        eligible = (kept[:, :, None] == jnp.arange(self.n_group)).any(1)        # [S, n_group]
        eligible = jnp.repeat(eligible, E // self.n_group, axis=1)
        if bias is None:
            # a sigmoid is positive, so -1 ranks below every eligible expert
            values, experts = jax.lax.top_k(jnp.where(eligible, scores, -1.0), k)
        else:
            _, experts = jax.lax.top_k(jnp.where(eligible, pick, -jnp.inf), k)
            values = jnp.take_along_axis(scores, experts, axis=1)
        weights = values / values.sum(-1, keepdims=True) * self.scale
        return weights, experts.astype(jnp.int32)
