"""The `lm` system's weights: every leaf of the program's parameter tree
filled on the device from ``--seed``, by the leaf's name.

Its own maker beside ``lib/weights.py``, whose ``kernel`` rule takes the
fan-in as ``prod(shape[:-1])``: for a stacked ``[experts, in, out]`` leaf
that is too large by the number of experts, every expert's output would come
out that much too small, and an error in the routed experts would not reach
the comparison. Here a matrix's fan-in is ``shape[-2]`` whatever is stacked
in front of it. The rules keep activations of order one through the depth:

- matrices (``kernel``; the experts' stacked ``w1`` / ``w2``): normal over
  ``sqrt(shape[-2])``; the convolution's ``conv_weight [taps, channels]``:
  normal over ``sqrt(taps)``;
- ``embedding``: normal x 0.08, so that ``E[ids] * 12`` (the published
  ``embedding_multiplier``) is of order one beside the layers' updates;
- a norm's gain (``weight``): 1 + 0.1 normal; ``conv_bias``: 0.02 normal;
- ``dt_bias``: uniform over [-7, -1], a head each. The step is ``softplus(dt
  + dt_bias)`` with ``dt`` of order one, so the heads' steps span about 0.001
  to 0.3 (the Mamba-2 initialiser's range is 0.001 to 0.1) and with ``A`` near
  -1 a head remembers between 3 and 1,000 positions. Drawn 0.5 normal, as an
  unnamed leaf, every head would forget within 3 positions: the state a chunk
  hands to the next would be nothing, and a fault in it (the planted one of
  ``tests/benchmarks/test_benchmark_lm.py``) would not reach the comparison;
- a router's selection bias (``e_score_correction_bias``): drawn as an
  unnamed leaf, then multiplied by ``BIAS_SCALE`` (0.04) in its own dtype, a
  product of its own after the draw: 0.02 beside scores that spread by 0.2
  moves choices at the edge and leaves the load near even, as a router that
  the bias has balanced is; at 0.5 it alone would pick every token's experts;
- ``A_log``, ``D`` and anything else: 0.5 normal, as ``lib/weights.py`` draws
  an unnamed leaf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_MATRICES = ("kernel", "w1", "w2")
_NAMED = _MATRICES + ("conv_weight", "embedding", "weight", "conv_bias", "dt_bias")
BIAS_SCALE = 0.04


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _fill(key, i, name, shape, dtype):
    """Leaf ``i`` of the tree, drawn from ``fold_in(key, i)``."""
    key = jax.random.fold_in(key, i)
    if name == "dt_bias":
        return jax.random.uniform(key, shape, jnp.float32, -7.0, -1.0).astype(dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    if name in _MATRICES:
        x = x * shape[-2] ** -0.5
    elif name == "conv_weight":
        x = x * shape[0] ** -0.5
    elif name == "embedding":
        x = 0.08 * x
    elif name == "weight":
        x = 1.0 + 0.1 * x
    elif name == "conv_bias":
        x = 0.02 * x
    else:
        x = 0.5 * x
    return x.astype(dtype)


def make_weights(shapes, seed: int):
    """``shapes``: a pytree of ``jax.ShapeDtypeStruct``. Returns the same tree
    of arrays made on the device; leaf ``i`` in flattening order draws from
    ``fold_in(key(seed), i)``. One small jitted program per name and shape of
    leaf, as ``lib/weights.py`` has it."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    # seeds run a little past 2**31: fold the high bits in, not truncate them
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    built = []
    for i, (path, leaf) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        made = _fill(key, i, name if name in _NAMED else "other", tuple(leaf.shape),
                     jnp.dtype(leaf.dtype))
        built.append(made * BIAS_SCALE if name == "e_score_correction_bias" else made)
    return jax.tree_util.tree_unflatten(treedef, built)
