"""Explicit expert-parallel MoE choreography (shard_map + all_to_all).

The reference's distributed MoE path: each rank gates its *local* tokens,
dispatches into an ``[E, C_local, M]`` buffer, exchanges it with
``dist.all_to_all_single`` so every rank ends up holding all shards' tokens
for its *local* experts, runs them, and all-to-alls back before the local
combine (``xmoe/moe_layer.py:229-262``; the ``_AllToAll`` autograd function
at ``moe_layer.py:48-63``; group construction at ``global_groups.py:36-61``).

TPU-native version: the same choreography inside one ``shard_map`` region
over the mesh ``expert`` axis, with ``jax.lax.all_to_all`` — which is
differentiable by construction, so both custom autograd functions of the
reference disappear. ``tiled=True`` splits the expert dim and concatenates
along capacity, exactly the ``ecm -> gecm`` reshape dance of
``moe_layer.py:236-251``.

Prefer the GSPMD path in :class:`~gigapath_tpu.ops.moe.moe_layer.MOELayer`
(annotation-only) for training; this module is the manual-control variant
and doubles as the executable spec of the collective pattern.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def moe_shard_fn(
    gate_fn: Callable,
    expert_fn: Callable,
    axis_name: str = "expert",
) -> Callable:
    """Per-shard MoE body for use inside ``shard_map``.

    ``gate_fn(tokens [S_loc, M]) -> (l_aux, combine, dispatch, metadata)``;
    ``expert_fn(local_expert_params, dispatched [E_loc, D*C_loc, M]) ->
    same shape``. The returned function maps
    ``(local_expert_params, tokens [S_loc, M]) -> ([S_loc, M], l_aux)``.
    """

    def fn(local_expert_params, tokens: jnp.ndarray):
        l_aux, combine, dispatch, _ = gate_fn(tokens)
        # local dispatch: [S_loc, E, C_loc] x [S_loc, M] -> [E, C_loc, M]
        dispatched = jnp.einsum("sec,sm->ecm", dispatch.astype(tokens.dtype), tokens)
        n_shards = jax.lax.psum(1, axis_name)
        if n_shards > 1:
            # exchange: every shard keeps its E/D local experts and receives
            # the other shards' capacity slots -> [E/D, D*C_loc, M]
            dispatched = jax.lax.all_to_all(
                dispatched, axis_name, split_axis=0, concat_axis=1, tiled=True
            )
        expert_output = expert_fn(local_expert_params, dispatched)
        if n_shards > 1:
            # inverse exchange back to [E, C_loc, M]
            expert_output = jax.lax.all_to_all(
                expert_output, axis_name, split_axis=1, concat_axis=0, tiled=True
            )
        combined = jnp.einsum(
            "sec,ecm->sm", combine.astype(tokens.dtype), expert_output
        )
        # average the balance loss across shards (each gated locally)
        l_aux = jax.lax.pmean(l_aux, axis_name)
        return combined, l_aux

    return fn


def moe_expert_parallel(
    mesh: Mesh,
    gate_fn: Callable,
    expert_fn: Callable,
    expert_params,
    tokens: jnp.ndarray,
    axis_name: str = "expert",
):
    """Run the expert-parallel MoE over ``tokens [S, M]`` sharded on
    ``axis_name``; ``expert_params`` leaves carry a leading E axis sharded the
    same way. Returns ``(output [S, M], l_aux)``."""
    body = moe_shard_fn(gate_fn, expert_fn, axis_name)
    param_specs = jax.tree.map(
        lambda p: P(axis_name, *([None] * (p.ndim - 1))), expert_params
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, P(axis_name, None)),
        out_specs=(P(axis_name, None), P()),
    )(expert_params, tokens)
