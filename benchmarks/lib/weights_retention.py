"""The weights of a model whose token mixer is a gated linear attention:
``lib/weights_lm.py``'s, then every leaf named ``gate_bias`` drawn again.

The gate is ``gamma = sigmoid(x . w + gate_bias)``, one a KV head, and a head
remembers about ``1 / (1 - gamma) = 1 + e^{gate_bias}`` positions at zero
input. Drawn 0.5 normal, as ``weights_lm`` draws an unnamed leaf, ``gamma``
would be about 0.5 and every head would forget within two positions: the
state a chunk hands the next would carry nothing, and a fault in it would not
reach the comparison (the trap ``dt_bias`` set for Granite, and
``weights_lm``'s rule for it). Here each KV head's memory is drawn
log-uniform over 32 to 32,768 positions (the published context) and the bias
set to ``log(memory - 1)``, from ``--seed`` and the leaf's place in the tree.
``x . w`` is of order one under ``weights_lm``'s matrices, so it moves a
head's memory by a factor of about ``e`` either way from token to token.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.lib import weights_lm

MEMORY = (32.0, 32768.0)   # positions, at zero input


@functools.partial(jax.jit, static_argnums=(2, 3))
def _gate_bias(key, i, shape, dtype):
    """Leaf ``i``'s bias, drawn from a stream of its own."""
    key = jax.random.fold_in(jax.random.fold_in(key, i), 1)
    memory = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(MEMORY[0]), math.log(MEMORY[1])))
    return jnp.log(memory - 1.0).astype(dtype)


def make_weights(shapes, seed: int):
    """``weights_lm.make_weights(shapes, seed)`` with each ``gate_bias`` leaf
    drawn again as above."""
    made = weights_lm.make_weights(shapes, seed)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(made)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.tree_util.tree_unflatten(treedef, [
        _gate_bias(key, i, tuple(leaf.shape), jnp.dtype(leaf.dtype))
        if str(getattr(path[-1], "key", path[-1])) == "gate_bias" else leaf
        for i, (path, leaf) in enumerate(leaves)])
