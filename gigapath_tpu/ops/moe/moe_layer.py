"""GShard MoE layer: gate -> dispatch einsum -> vmapped experts -> combine.

Parity with reference ``torchscale/component/xmoe/moe_layer.py``: the same
Algorithm-2 einsum choreography (``sec,sm->ecm`` dispatch, ``sec,ecm->sm``
combine, ``moe_layer.py:229-262``) and the same (output, l_aux) contract
(``moe_layer.py:271``). The distributed pieces map to TPU idioms:

- per-rank expert construction with per-rank seeds
  (``feedforward_network.py:43-91``) -> one vmapped parameter axis of size E
  with split init RNGs (each expert gets distinct init, all experts live in
  one array tree, shardable over the mesh ``expert`` axis);
- ``_AllToAll`` autograd function + NCCL all2all groups
  (``moe_layer.py:48-63``, ``global_groups.py``) -> GSPMD: a sharding
  constraint on the ``[E, C, M]`` dispatch tensor makes XLA insert the
  all-to-all over ICI, differentiable by construction. The explicit
  shard_map choreography lives in
  :mod:`gigapath_tpu.ops.moe.expert_parallel` for when manual control or
  per-shard gating is wanted;
- a2a CUDA-event timing (``moe_layer.py:276-307``) -> ``jax.profiler`` traces
  cover collectives natively; gating telemetry is sowed under
  ``intermediates/moe_metadata``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from gigapath_tpu.ops.feedforward import FeedForwardNetwork
from gigapath_tpu.ops.moe.expert_parallel import dispatch_to_held, grouped_matmul
from gigapath_tpu.ops.moe.pallas_rows import combine_rows, dispatch_rows
from gigapath_tpu.ops.moe.routing import Top1Gate, Top2Gate, topk_softmax_gating


def _maybe_expert_constraint(x: jnp.ndarray, axis: str = "expert") -> jnp.ndarray:
    """Constrain the leading (expert) dim over the mesh ``expert`` axis when a
    physical mesh with that axis is active; no-op otherwise."""
    try:
        from jax.sharding import PartitionSpec as P

        mesh = jax.sharding.get_abstract_mesh()
        if (
            mesh is not None
            and not mesh.empty
            and axis in mesh.axis_names
            and mesh.shape[axis] > 1
        ):
            spec = P(axis, *([None] * (x.ndim - 1)))
            return jax.lax.with_sharding_constraint(x, spec)
    except Exception:  # pragma: no cover - constraint is best-effort
        pass
    return x


class MOELayer(nn.Module):
    """Mixture-of-experts block over ``[B, L, M]`` tokens.

    Returns ``(output [B, L, M], l_aux scalar)``. Gating metadata is sowed to
    ``intermediates`` as ``moe_metadata`` (collect with
    ``model.apply(..., mutable=["intermediates"])``).
    """

    embed_dim: int
    ffn_dim: int
    num_experts: int
    top1: bool = False
    activation_fn: str = "gelu"
    dropout: float = 0.0
    activation_dropout: float = 0.0
    layernorm_eps: float = 1e-5
    subln: bool = False
    gating_use_fp32: bool = True
    eval_capacity_token_fraction: float = 0.25
    second_expert_policy: str = "random"
    normalize_gate_prob_before_dropping: bool = False
    use_xmoe: bool = False
    capacity_factor: float = 1.0
    dtype: Any = None

    @classmethod
    def from_config(
        cls,
        args,
        *,
        prefix: Optional[str] = None,
        dtype=None,
        name: Optional[str] = None,
    ) -> "MOELayer":
        """Build from an Encoder/Decoder config (the EncoderLayer MoE hook).

        ``prefix`` ("encoder" / "decoder") selects which dim fields to read —
        required for EncoderDecoderConfig, which defines both; when omitted
        it is inferred from whichever single prefix the config carries."""
        if prefix is None:
            has_enc = hasattr(args, "encoder_embed_dim")
            has_dec = hasattr(args, "decoder_embed_dim")
            assert has_enc ^ has_dec, (
                "config defines both encoder_* and decoder_* dims; pass "
                "prefix='encoder' or 'decoder'"
            )
            prefix = "encoder" if has_enc else "decoder"
        embed = getattr(args, f"{prefix}_embed_dim")
        ffn = getattr(args, f"{prefix}_ffn_embed_dim")
        return cls(
            embed_dim=embed,
            ffn_dim=ffn,
            num_experts=args.moe_expert_count,
            top1=args.moe_top1_expert,
            activation_fn=args.activation_fn,
            dropout=args.dropout,
            activation_dropout=args.activation_dropout,
            layernorm_eps=args.layernorm_eps,
            subln=args.subln,
            gating_use_fp32=args.moe_gating_use_fp32,
            eval_capacity_token_fraction=args.moe_eval_capacity_token_fraction,
            second_expert_policy=args.moe_second_expert_policy,
            normalize_gate_prob_before_dropping=args.moe_normalize_gate_prob_before_dropping,
            use_xmoe=args.use_xmoe,
            dtype=dtype,
            name=name,
        )

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        input_padding_mask: Optional[jnp.ndarray] = None,
        deterministic: bool = True,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        B, L, M = x.shape
        assert M == self.embed_dim, (M, self.embed_dim)
        tokens = x.reshape(B * L, M)
        pad = (
            input_padding_mask.reshape(B * L)
            if input_padding_mask is not None
            else None
        )

        if self.top1:
            gate = Top1Gate(
                model_dim=self.embed_dim,
                num_experts=self.num_experts,
                use_xmoe=self.use_xmoe,
                use_fp32=self.gating_use_fp32,
                capacity_factor=self.capacity_factor,
                eval_capacity_token_fraction=self.eval_capacity_token_fraction,
                dtype=self.dtype,
                name="gate",
            )
            l_aux, combine, dispatch, metadata = gate(
                tokens, pad, eval_mode=deterministic
            )
        else:
            gate = Top2Gate(
                model_dim=self.embed_dim,
                num_experts=self.num_experts,
                use_xmoe=self.use_xmoe,
                use_fp32=self.gating_use_fp32,
                second_expert_policy=self.second_expert_policy,
                normalize_gate_prob_before_dropping=self.normalize_gate_prob_before_dropping,
                eval_capacity_token_fraction=self.eval_capacity_token_fraction,
                dtype=self.dtype,
                name="gate",
            )
            needs_rng = not deterministic and self.second_expert_policy in (
                "sampling",
                "random",
            )
            rng = self.make_rng("dropout") if needs_rng else None
            l_aux, combine, dispatch, metadata = gate(
                tokens, pad, rng=rng, eval_mode=deterministic
            )
        self.sow("intermediates", "moe_metadata", metadata)

        # dispatch: [S,E,C] x [S,M] -> [E,C,M]; the expert axis is the mesh
        # collective boundary (GSPMD inserts the all-to-all here)
        dispatched = jnp.einsum(
            "sec,sm->ecm", dispatch.astype(tokens.dtype), tokens
        )
        dispatched = _maybe_expert_constraint(dispatched)

        experts = nn.vmap(
            FeedForwardNetwork,
            in_axes=(0, None),
            out_axes=0,
            variable_axes={"params": 0},
            split_rngs={"params": True, "dropout": True},
        )(
            embed_dim=self.embed_dim,
            ffn_dim=self.ffn_dim,
            activation_fn=self.activation_fn,
            dropout=self.dropout,
            activation_dropout=self.activation_dropout,
            layernorm_eps=self.layernorm_eps,
            subln=self.subln,
            dtype=self.dtype,
            name="experts",
        )
        expert_output = experts(dispatched, deterministic)
        expert_output = _maybe_expert_constraint(expert_output)

        combined = jnp.einsum(
            "sec,ecm->sm", combine.astype(tokens.dtype), expert_output
        )
        return combined.reshape(B, L, M), l_aux.astype(jnp.float32)


class DroplessMoE(nn.Module):
    """Dropless top-k expert layer over ``[S, M]`` tokens, for the experts
    held here: ``g = u W_r`` in float32 over all ``num_experts``; the gate
    picks ``top_k`` experts a token and weighs them; ``sum_i w_i W2_e(silu(a)
    * b)`` with ``[a | b] = W1_e u``, summed over the choices whose expert is
    one of ``[expert_offset, expert_offset + experts_held)``. The other
    choices are another chip's part of the sum and are left out.

    ``gate`` is the layer's to be given: ``gate(logits [S, E], top_k) ->
    (weights [S, top_k] float32, experts [S, top_k] int32)``, hashable (a
    function, or a value such as :class:`~gigapath_tpu.ops.moe.routing.
    GroupLimitedSigmoidGate`). ``None`` is the ``top_k`` largest logits and a
    softmax over them (:func:`~gigapath_tpu.ops.moe.routing.topk_softmax_gating`).
    A gate whose ``selection_bias`` is true gets a learned float32
    ``e_score_correction_bias [num_experts]`` of this layer as its third
    argument (the bias a router is balanced by without an auxiliary loss).

    Shapes are static, so the sorted buffer is sized for every choice landing
    here (``S * top_k`` rows), but only the rows a held expert owns are moved:
    ``total = group_sizes.sum()`` of them, sorted to the front. The dispatch
    fills the tiles of the buffer that start before ``total`` and leaves the
    rest unwritten, the grouped products skip the rows past it, and the
    combine brings back only the choices that stand before it, taking 0 for
    the others by a select (what lies past ``total`` is nobody's, NaN
    included) and summing a token's choices in float32 in their order
    (:mod:`gigapath_tpu.ops.moe.pallas_rows`: two kernels on a TPU, the
    ``jnp`` gathers elsewhere). With every expert held ``total`` is the whole
    buffer and nothing is skipped: a value in the input, not a switch.

    Returns ``(output [S, M], tokens each held expert received [experts_held]
    int32)``; the choices, the counts and ``held_rows_share`` (``total`` over
    ``S * top_k``: the share of the buffer that is touched) are sowed as
    ``moe_metadata`` as :class:`MOELayer` sows its gating telemetry. Forward
    only on the kernel tier."""

    embed_dim: int
    ffn_dim: int
    num_experts: int
    top_k: int
    expert_offset: int = 0
    experts_held: Optional[int] = None
    gate: Optional[Callable] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        S, M = x.shape
        held = self.num_experts - self.expert_offset if self.experts_held is None \
            else self.experts_held
        logits = nn.Dense(
            self.num_experts, use_bias=False, dtype=jnp.float32,
            param_dtype=self.param_dtype, precision=jax.lax.Precision.HIGHEST,
            name="router",
        )(x)
        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=-2,
                                                out_axis=-1, batch_axis=(0,))
        w1 = self.param("w1", init, (held, M, 2 * self.ffn_dim), self.param_dtype)
        w2 = self.param("w2", init, (held, self.ffn_dim, M), self.param_dtype)

        gate_args = (logits, self.top_k)
        if getattr(self.gate, "selection_bias", False):
            gate_args += (self.param("e_score_correction_bias", nn.initializers.zeros,
                                     (self.num_experts,), jnp.float32),)
        with jax.named_scope("router"):
            weights, experts = (self.gate or topk_softmax_gating)(*gate_args)
        with jax.named_scope("dispatch"):
            order, position, group_sizes = dispatch_to_held(
                experts, expert_offset=self.expert_offset, experts_held=held)
            total = group_sizes.sum()
            rows = dispatch_rows(x.astype(self.dtype), order, total, self.top_k)
        self.sow("intermediates", "moe_metadata",
                 {"experts": experts, "held_counts": group_sizes,
                  "held_rows_share": total / (S * self.top_k)})
        with jax.named_scope("experts"):
            a, b = jnp.split(grouped_matmul(rows, w1, group_sizes), 2, axis=-1)
            out_rows = grouped_matmul(jax.nn.silu(a) * b, w2, group_sizes)
        with jax.named_scope("combine"):
            out = combine_rows(out_rows, position, weights, total)
        return out, group_sizes
