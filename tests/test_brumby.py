"""Brumby-14B-Base on the CPU: the degree-2 feature map, both tiers of power
retention against the reference's quadratic form, the tiny model through the
scoring entry against ``benchmarks/lib/reference_brumby.py``, the published
parameter counts, and the counter of what the carried state carries."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.closed_loop import row_gaps
from benchmarks.lib import reference_brumby as reference
from benchmarks.lib import tables, weights_retention
from gigapath_tpu import pipeline
from gigapath_tpu.models import brumby  # noqa: F401  (registers the archs)
from gigapath_tpu.ops import power_retention as pr
from gigapath_tpu.ops.pallas_retention import power_retention_fwd
from gigapath_tpu.utils.registry import create_model_from_registry

CONFIG = tables.load("configs", "brumby14b_pp5")
TINY = CONFIG["tiny"]


def _inputs(seed, B, L, H, G, d, log_gate):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.standard_normal((B, L, n, d)).astype(np.float32) for n in (H, G, G))
    return q, k, v, np.broadcast_to(np.asarray(log_gate, np.float32), (B, L, G)).copy()


def _quadratic(q, k, v, log_gate, block_rows=8):
    """The reference's quadratic form, one sequence at a time, float32."""
    with jax.default_matmul_precision("highest"):
        return np.stack([np.asarray(reference.retention_quadratic(
            jnp.asarray(q[b]), jnp.asarray(k[b]), jnp.asarray(v[b]), jnp.asarray(log_gate[b]),
            pr.EPS, "f32", block_rows=block_rows)) for b in range(q.shape[0])])


def test_the_feature_map_squares_the_dot_product():
    rng = np.random.default_rng(0)
    q, k = rng.standard_normal((2, 5, 16)), rng.standard_normal((2, 5, 16))
    phi_q, phi_k = pr.feature_map(jnp.asarray(q)), pr.feature_map(jnp.asarray(k))
    assert phi_q.shape == (2, 5, 16 * 17 // 2)
    np.testing.assert_allclose(np.sum(np.asarray(phi_q) * np.asarray(phi_k), -1),
                               np.sum(q * k, -1) ** 2, rtol=1e-5)


# gates: log gamma near 0 (a head that remembers ~10^4 positions), a mix
# drawn per position, and near -inf (a head that forgets at once)
_GATES = {"remembers": -1e-4, "mixed": None, "forgets": -30.0}


def _gate(which, B, L, G, seed=3):
    if _GATES[which] is not None:
        return np.full((B, L, G), _GATES[which], np.float32)
    logits = np.random.default_rng(seed).normal(3.0, 2.0, (B, L, G))
    return np.asarray(jax.nn.log_sigmoid(logits), np.float32)


@pytest.mark.parametrize("gate", list(_GATES))
@pytest.mark.parametrize("L,chunk", [(77, 8), (77, 16), (50, 32), (64, 16)])
def test_the_jnp_tier_is_the_quadratic_form(L, chunk, gate):
    """Float32 throughout: the two forms differ by summation order alone."""
    B, H, G, d = 2, 4, 2, 8
    q, k, v, _ = _inputs(1, B, L, H, G, d, 0.0)
    log_gate = _gate(gate, B, L, G)
    y, carried = pr.power_retention(q, k, v, log_gate, chunk=chunk)
    want = _quadratic(q, k, v, log_gate)
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)
    carried = np.asarray(carried)
    assert carried.shape == (B, L, H) and (carried >= 0).all() and (carried <= 1).all()
    assert (carried[:, :chunk] == 0).all()  # the first chunk is handed nothing
    if gate == "forgets":
        assert carried.max() < 1e-6
    if gate == "remembers":
        assert carried[:, -1].min() > 0.3


@pytest.mark.parametrize("gate", list(_GATES))
@pytest.mark.parametrize("L,chunk", [(77, 8), (50, 16), (70, 32)])
def test_the_kernel_in_interpret_mode_is_the_quadratic_form(L, chunk, gate):
    """The kernel multiplies bfloat16 operands with float32 accumulation (the
    values, the expanded features, the state it reads): each rounding is 2^-9
    relative and the sums are of positive weights, so a row's gap stays near
    that; 1e-2 of the largest value, and the carried share to 1e-2."""
    B, H, G, d = 1, 6, 2, 16
    q, k, v, _ = _inputs(2, B, L, H, G, d, 0.0)
    log_gate = _gate(gate, B, L, G)
    bf = lambda x: np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32)  # noqa: E731
    q, k = bf(q), bf(k)
    y, carried = power_retention_fwd(jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
                                     jnp.asarray(v), log_gate, chunk=chunk, interpret=True)
    want = _quadratic(q, k, v, log_gate)
    assert y.dtype == jnp.float32 and y.shape == (B, L, H, d)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-2 * np.abs(want).max())
    _, carried_jnp = pr.power_retention(q, k, v, log_gate, chunk=chunk)
    np.testing.assert_allclose(np.asarray(carried), np.asarray(carried_jnp), atol=1e-2)


def _tiny_model(**fields):
    return create_model_from_registry(TINY["arch"], depth=TINY["depth"], **fields)


def _weights(model, seed, dtype=None):
    ids = jax.ShapeDtypeStruct((1, 4), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids, ids)["params"]
    if dtype is not None:
        shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype), shapes)
    return weights_retention.make_weights(shapes, seed)


def test_the_tiny_model_through_the_entry_is_the_reference():
    """Float32 parameters and activations: the program's jnp tier and the
    reference's quadratic form agree to summation order, 1e-4 of a row."""
    model = _tiny_model(dtype=jnp.float32, param_dtype=jnp.float32)
    params = _weights(model, 3000000019, jnp.float32)
    ids = np.random.default_rng(4).integers(0, TINY["vocab_size"], (2, 77)).astype(np.int32)
    positions = np.array([[3, 40, 63, 76], [0, 17, 70, 76]], np.int32)
    logits, received, extras = pipeline.lm_forward_fn(model)(params, ids, positions)
    assert received == () and extras["carried_share"].shape == (TINY["depth"], 2)
    for b in range(2):
        with jax.default_matmul_precision("highest"):
            want = reference.lm_forward(params, ids[b], positions[b], TINY)
        assert row_gaps(np.asarray(logits[b]), want).max() < 1e-4


def test_the_entry_serves_the_model_with_no_branch_on_it():
    model = _tiny_model()
    params = _weights(model, 11)
    out = pipeline.run_inference_with_lm(np.arange(40) % 256, [[5, 39]], lm=model, lm_params=params)
    assert out["logits"].shape == (1, 2, TINY["vocab_size"]) and np.isfinite(out["logits"]).all()
    # no expert layer: no counts, in the counts' type
    assert out["expert_tokens"].shape == (0,) and out["expert_tokens"].dtype == np.int32
    assert out["carried_share"].shape == (TINY["depth"], 1)


def test_the_carried_state_carries_under_the_benchmarks_weights():
    """``weights_retention``'s gate biases keep a head's memory log-uniform
    over 32 to 32,768 positions: at the tiny size (chunks of 16) most of a
    query's weight comes through the state handed between chunks."""
    model = _tiny_model()
    params = _weights(model, 5)
    biases = np.concatenate([np.asarray(params[f"layers_{i}"]["self_attn"]["gate_bias"],
                                        np.float32) for i in range(TINY["depth"])])
    memory = 1.0 + np.exp(biases)
    assert ((memory > 31) & (memory < 33500)).all() and len(set(biases)) == len(biases)
    ids = np.random.default_rng(6).integers(0, TINY["vocab_size"], (2, 77)).astype(np.int32)
    _, _, extras = pipeline.lm_forward_fn(model)(params, ids, np.full((2, 1), 76, np.int32))
    share = np.asarray(extras["carried_share"])
    assert ((share >= 0) & (share <= 1)).all()
    assert share.mean() > 0.2


def test_the_published_parameter_counts():
    """330.35 M a layer (q and o 26.2 M, k and v 5.24 M, the gate 40,968, the
    norms 10,496, the SwiGLU 267.4 M); at the cut's depth of 8 with the whole
    vocabulary in the embedding and the untied head, 4,198.65 M, 8.40 GB of
    bfloat16."""
    from benchmarks.systems.lm import System

    shapes = System(CONFIG, tiny=False).param_shapes()
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    layer = shapes["layers_0"]
    assert count(layer) == 330_352_904
    attn = layer["self_attn"]
    assert attn["q_proj"]["kernel"].shape == (5120, 5120)
    assert attn["k_proj"]["kernel"].shape == attn["v_proj"]["kernel"].shape == (5120, 1024)
    assert attn["gate"]["kernel"].shape == (5120, 8) and attn["gate_bias"].shape == (8,)
    assert attn["q_norm"]["weight"].shape == attn["k_norm"]["weight"].shape == (128,)
    assert count(layer["mlp"]) == 3 * 5120 * 17408
    assert count(shapes) == 4_198_652_992
    assert count(shapes["embed_tokens"]) == count(shapes["lm_head"]) == 151936 * 5120
    assert sorted(k for k in shapes if k.startswith("layers_")) == [f"layers_{i}" for i in range(8)]
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(shapes))
