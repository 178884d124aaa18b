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

The dropless layer's share of that pattern is here too: a chip is told which
experts it holds (``expert_offset``, ``experts_held``), sorts the
(token, expert) choices that name one of them to the front, expert by expert
(:func:`dispatch_to_held`), and runs one grouped matrix product per projection
over those rows (:func:`grouped_matmul`). What the other experts would add is
another chip's part; nothing here stands in for it.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from gigapath_tpu.ops.common import round_up

# Rows of a grouped product's tile on the chip: a tile that straddles two
# experts is visited once for each, so smaller wastes less and re-reads the
# weights more often (36 experts of ~2,300 rows each: 160 tiles, ~36 twice).
GMM_TILE_ROWS = 512


def dispatch_to_held(experts: jnp.ndarray, *, expert_offset: int, experts_held: int):
    """Sort the ``[S, k]`` choices of a top-k router by held expert.

    Returns ``(order, position, group_sizes)``: ``order [S * k]`` lists the
    flat choices (token ``i // k``) with those of held expert 0 first, then 1,
    ..., and every choice of an expert that lives elsewhere last;
    ``position [S, k]`` is where each choice stands in that order;
    ``group_sizes [experts_held]`` int32 counts the rows of each held expert,
    so rows from ``group_sizes.sum()`` on belong to no expert here."""
    local = experts.reshape(-1) - expert_offset
    key = jnp.where((local >= 0) & (local < experts_held), local, experts_held)
    order = jnp.argsort(key, stable=True)
    position = jnp.argsort(order).reshape(experts.shape)
    group_sizes = (key[:, None] == jnp.arange(experts_held)).sum(0, dtype=jnp.int32)
    return order, position, group_sizes


def _gmm_tiling(k: int, n: int):
    """(rows, contraction, columns) of a grouped product's tile: whole-width
    divisors, the weight tile under 2 MB so that two of each operand, the
    output and the float32 accumulator stay inside the 16 MB of fast memory."""
    tn = next((t for t in (1536, 1024, 768, 512, 256, 128) if n % t == 0), None)
    tk = next((t for t in (1024, 768, 512, 256, 128)
               if k % t == 0 and tn and t * tn <= 1 << 20), None)
    if not (tk and tn):
        raise ValueError(f"grouped product [{k}, {n}]: both widths must be multiples of 128")
    return GMM_TILE_ROWS, tk, tn


def grouped_matmul(rows: jnp.ndarray, weights: jnp.ndarray, group_sizes: jnp.ndarray):
    """``rows [M, K]`` sorted by group, ``weights [G, K, N]``, ``group_sizes
    [G]``: each group's rows times its own matrix, ``[M, N]``. Rows past the
    last group are not visited and hold whatever was there (the Pallas
    ``megablox.gmm`` on a TPU) or zeros (``lax.ragged_dot`` elsewhere): the
    caller masks them."""
    from gigapath_tpu.ops.flash_attention import _on_tpu

    with jax.named_scope("kernel_fwd"):
        if not _on_tpu():
            return jax.lax.ragged_dot(rows, weights, group_sizes)
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        M = rows.shape[0]
        padded = jnp.pad(rows, ((0, round_up(M, GMM_TILE_ROWS) - M), (0, 0)))
        out = gmm(padded, weights, group_sizes, preferred_element_type=rows.dtype,
                  tiling=_gmm_tiling(rows.shape[1], weights.shape[2]))
        return out[:M]


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
