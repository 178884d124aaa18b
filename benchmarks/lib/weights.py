"""The benchmark's own weights: every leaf of the program's parameter tree
filled on the device from ``--seed``.

The program's initialisers are not used: LayerScale at its published 1e-5
would make every ViT block a no-op under random weights, and the comparison
with the reference would then cover the patch embedding and little else. The
rules below keep activations of order one through the whole depth, so that an
error made in any layer reaches the output.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _fill(key, i, kind, shape, dtype):
    """Leaf ``i`` of the tree, drawn from ``fold_in(key, i)``."""
    key = jax.random.fold_in(key, i)
    if kind == "kernel":  # fan-in scaled, whatever the rank (conv kernels too)
        fan_in = math.prod(shape[:-1])
        x = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
    elif kind == "bias":
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif kind == "scale":  # LayerNorm gain
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif kind == "gamma":  # LayerScale
        x = jax.random.uniform(key, shape, jnp.float32, 0.1, 0.3)
    else:  # class token, learned positions
        x = 0.5 * jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


_KINDS = ("kernel", "bias", "scale", "gamma")


def make_weights(shapes, seed: int):
    """``shapes``: a pytree of ``jax.ShapeDtypeStruct`` (``jax.eval_shape`` of
    the program's ``init``). Returns the same tree of arrays, made on the
    device from ``seed``; leaf ``i`` in flattening order draws from
    ``fold_in(key(seed), i)``, so a tree keeps its values when leaves are
    added after it.

    One small jitted program per kind and shape of leaf (15 for the ViT-G's
    566 leaves), run once per leaf with the leaf's number as an argument. One
    program for the whole tree gave the same values but took 167 s to compile
    for the ViT-G and 11 s to load from the cache in every later run (my chip
    run, PR 24): a threefry subgraph per leaf."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    # seeds run a little past 2**31: fold the high bits in, not truncate them
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    built = []
    for i, (path, leaf) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        kind = name if name in _KINDS else "other"
        built.append(_fill(key, i, kind, tuple(leaf.shape), jnp.dtype(leaf.dtype)))
    return jax.tree_util.tree_unflatten(treedef, built)
