"""Parameter/activation sharding rules (GSPMD annotations).

Tensor-parallel layout for the transformer stack: attention and FFN kernels
split over the ``model`` axis (column-parallel fc1/q/k/v, row-parallel
fc2/out_proj), everything else replicated; optional ZeRO-style sharding of
the largest replicated kernels over ``data``. XLA inserts the matching
collectives — this file contains *only* layout decisions, no communication
code. (The reference has no TP at all, SURVEY §2.6; FSDP maps to the ZeRO
rule here.)
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# leaf module name -> (spec for `kernel`); biases/scales stay replicated.
# Coverage of these lists against every Dense construction site in the
# model stack is enforced mechanically by gigalint GL003
# (tools/gigalint/sharding_coverage.py) — a name in neither list falls
# through to replicated P() below, silently.
_COLUMN_PARALLEL = (
    "q_proj", "k_proj", "v_proj", "fc1", "gate",
    # retention gate projection: [E, value_dim], split like q/k/v
    "g_proj",
    # ViT packed qkv: [D, 3D], output-dim split (megatron fused-qkv rule)
    "qkv",
    # vocab head: [E, V], vocab-dim split (softmax gathers under GSPMD)
    "output_projection",
)
_ROW_PARALLEL = (
    "out_proj", "fc2",
    # ViT attention output projection (models/tile_encoder.py); the
    # PatchEmbed Dense shares the name — its [in_chans, E] kernel also
    # input-dim splits correctly (GSPMD inserts the gather)
    "proj",
)

# Sequence-parallel collective registry: the explicit communication the
# library is ALLOWED to perform over the ``seq`` mesh axis, by module.
# Unlike the GSPMD parameter rules above (layout only, XLA inserts the
# collectives), the seq-parallel attention paths issue collectives BY
# HAND inside shard_map — each one is a deliberate sharding decision
# (what crosses the axis, and in which schedule) and must be recorded
# here so the layout story stays auditable in one file. Coverage is
# enforced mechanically by gigalint GL009
# (tools/gigalint/sharding_coverage.py): a ``ppermute``/``all_gather``
# call in library code whose module has no matching entry flags.
#
# Keys are module-path suffixes; values the sanctioned collective names.
_SEQ_COLLECTIVES: Dict[str, tuple] = {
    # gathered dilated branches: the hoisted per-call all_gather of
    # rank-local valid counts ([W, B] ints, shared by every gathered
    # branch), the legacy full-segment K/V all_gather (fallback + parity
    # oracle), and the ring schedule's sub-ring ppermute rotation of
    # local sparse K/V chunks (GIGAPATH_RING_ATTN, fwd + reverse ring in
    # the custom VJP)
    "gigapath_tpu/ops/dilated_attention.py": ("all_gather", "ppermute"),
}


def param_spec(
    path_names,
    leaf,
    *,
    model_axis: str | None = "model",
    expert_axis: str | None = None,
) -> P:
    """PartitionSpec for one parameter, by its module path. Either axis may
    be None, disabling that rule."""
    if expert_axis and "experts" in path_names and hasattr(leaf, "ndim") and leaf.ndim >= 1:
        # vmapped MoE expert params carry a leading E axis
        # (ops/moe/moe_layer.py) — shard it over the mesh ``expert`` axis
        return P(expert_axis, *([None] * (leaf.ndim - 1)))
    if (
        model_axis
        and path_names
        and path_names[-1] == "kernel"
        and hasattr(leaf, "ndim")
        and leaf.ndim == 2
    ):
        owner = path_names[-2] if len(path_names) >= 2 else ""
        if owner in _COLUMN_PARALLEL:
            return P(None, model_axis)
        if owner in _ROW_PARALLEL:
            return P(model_axis, None)
    return P()


def param_shardings(params: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """NamedSharding tree for a param tree under ``mesh``.

    If the mesh has no ``model`` axis (or size 1), everything is replicated —
    the rules degrade gracefully to pure DP/SP meshes.
    """
    has_model = "model" in mesh.axis_names and mesh.shape["model"] > 1
    expert_axis = (
        "expert"
        if "expert" in mesh.axis_names and mesh.shape["expert"] > 1
        else None
    )

    def one(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        spec = param_spec(
            names,
            leaf,
            model_axis="model" if has_model else None,
            expert_axis=expert_axis,
        )
        return NamedSharding(mesh, spec)

    flat = jax.tree_util.tree_flatten_with_path(params)
    leaves = [one(path, leaf) for path, leaf in flat[0]]
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(params), leaves)


def apply_shardings(params: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """device_put the param tree with its sharding rules."""
    return jax.device_put(params, param_shardings(params, mesh))
