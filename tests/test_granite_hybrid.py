"""Granite 4.0-H on the forward path, at the tiny preset on the CPU (hidden
64, four layers ``m a m m``, 8 experts with top-4, vocabulary 256): the
program against the benchmark's plain reference (one source for both:
``benchmarks/lib/reference_lm.py``), the chunked state-space scan against the
recurrence, the dropless expert layer against a per-token loop, the shares of
a layer adding up to the uncut layer, and the entry points."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.closed_loop import row_gaps
from benchmarks.lib import reference_lm, tables, weights_lm
from gigapath_tpu import pipeline
from gigapath_tpu.models import granite_hybrid
from gigapath_tpu.ops import ssd
from gigapath_tpu.ops.moe import DroplessMoE, topk_softmax_gating
from gigapath_tpu.ops.moe.expert_parallel import dispatch_to_held
from gigapath_tpu.utils.registry import create_model_from_registry

CONFIG = tables.load("configs", "granite4h_small_ep2")
TINY = CONFIG["tiny"]


def _tiny_model(**share):
    share = {"depth": TINY["depth"], "vocab_size": TINY["vocab_size"],
             "experts_held": TINY["num_local_experts"], "expert_offset": 0, **share}
    return create_model_from_registry(TINY["arch"], **share)


def _weights(model, seed):
    ids = jax.ShapeDtypeStruct((1, 4), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids, ids)["params"]
    return weights_lm.make_weights(shapes, seed)


@pytest.mark.parametrize("length", [77, 512])
@pytest.mark.parametrize("seed", [11, 3000000019])
def test_program_matches_the_reference_at_the_rows_asked_for(seed, length):
    model = _tiny_model()
    params = _weights(model, seed)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TINY["vocab_size"], (2, length), dtype=np.int32)
    positions = np.sort(rng.permutation(length)[:4]).astype(np.int32)
    out = pipeline.run_inference_with_lm(ids, positions, lm=(model, params))
    assert out["logits"].shape == (2, 4, TINY["vocab_size"]) and out["logits"].dtype == np.float32
    assert out["expert_tokens"].shape == (TINY["depth"], TINY["num_local_experts"])
    for b in range(2):
        ref = reference_lm.lm_forward(params, ids[b], positions, TINY)
        # bfloat16 activations through four layers; a routing tie that rounds
        # the other way moves one row most (the widest gap)
        gaps = row_gaps(out["logits"][b], ref)
        assert gaps.max() < 0.03 and gaps.mean() < 0.015, gaps


@pytest.mark.parametrize("length,chunk,per_block", [
    (77, 16, 8), (50, 16, 2), (256, 64, 8), (33, 64, 8),
    # the kernel (in interpret mode) at widths it takes: three chunks, and a
    # length it pads to three
    pytest.param(384, 128, "kernel", id="kernel-384-128"),
    pytest.param(300, 128, "kernel", id="kernel-300-128")])
def test_chunked_scan_matches_the_recurrence(length, chunk, per_block):
    """Both tiers against the recurrence one position at a time. ``dt_bias``
    is drawn as ``lib/weights_lm.py`` draws it (a head remembers 3 to 1,000
    positions), so the state a chunk hands on matters: the kernel called a
    chunk at a time, which drops it, misses the same tolerance."""
    from gigapath_tpu.ops import pallas_ssd

    H, P, N = (16, 64, 128) if per_block == "kernel" else (4, 8, 16)
    rng = np.random.default_rng(length)
    xBC = jnp.asarray(rng.standard_normal((2, length, H * P + 2 * N)), jnp.float32)
    dt_bias = jnp.asarray(rng.uniform(-7.0, -1.0, H), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((2, length, H)), jnp.float32) + dt_bias)
    A = -jnp.exp(jnp.asarray(0.5 * rng.standard_normal(H), jnp.float32))
    D = jnp.asarray(rng.standard_normal(H), jnp.float32)
    x, B, C = jnp.split(xBC, [H * P, H * P + N], axis=-1)
    x = x.reshape(2, length, H, P)
    want = jnp.stack([reference_lm.state_space_recurrence(x[b], dt[b], A, B[b], C[b])
                      + D[:, None] * x[b] for b in range(2)]).reshape(2, length, H * P)
    if per_block != "kernel":
        got = ssd.ssd_scan_jnp(x, dt, A, B, C, D, chunk=chunk, chunks_per_block=per_block)
        np.testing.assert_allclose(got.reshape(2, length, H * P), want, rtol=2e-4, atol=2e-4)
        return
    assert pallas_ssd.fits(H, P, N, chunk)

    def kernel(xBC, dt):
        return pallas_ssd.ssd_scan_fwd(xBC, dt, A, D, state_size=N, chunk=chunk, interpret=True)

    got = kernel(xBC, dt)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    tier = ssd.ssd_scan_jnp(x, dt, A, B, C, D, chunk=chunk).reshape(2, length, H * P)
    np.testing.assert_allclose(got, tier, rtol=1e-5, atol=1e-5)
    # in bfloat16, as the cell runs it: the kernel rounds the update's weighted
    # B where the jnp tier rounds the weighted x, and is as close as the tier to
    # the recurrence over the same rounded inputs (both ~3e-3 of the largest |y|)
    x16, B16, C16 = (a.astype(jnp.bfloat16) for a in (x, B, C))
    exact = jnp.stack([reference_lm.state_space_recurrence(
        *(a.astype(jnp.float32) for a in (x16[b], dt[b], A, B16[b], C16[b])))
        + D[:, None] * x16[b].astype(jnp.float32) for b in range(2)]).reshape(2, length, H * P)
    half = kernel(xBC.astype(jnp.bfloat16), dt).astype(jnp.float32)
    tier16 = ssd.ssd_scan_jnp(x16, dt, A, B16, C16, D, chunk=chunk).astype(jnp.float32)
    gap, tier_gap = (np.abs(y.reshape(2, length, H * P) - exact).max() for y in (half, tier16))
    assert gap <= 1.5 * tier_gap < 1e-2 * np.abs(exact).max(), (gap, tier_gap)
    dropped = jnp.concatenate([kernel(xBC[:, s:s + chunk], dt[:, s:s + chunk])
                               for s in range(0, length, chunk)], axis=1)
    assert not np.allclose(dropped, want, rtol=2e-4, atol=2e-4)


def test_causal_conv_reads_no_later_position():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 20, 6)), jnp.float32)
    w, b = jnp.asarray(rng.standard_normal((4, 6)), jnp.float32), jnp.zeros(6)
    base = ssd.causal_conv1d(x, w, b)
    moved = ssd.causal_conv1d(x.at[0, 12].add(1.0), w, b)
    assert np.array_equal(np.asarray(base[0, :12]), np.asarray(moved[0, :12]))
    assert not np.allclose(base[0, 12:16], moved[0, 12:16])
    np.testing.assert_allclose(base[0], reference_lm.causal_conv(x[0], w, b), rtol=1e-5, atol=1e-5)


def _moe(held, offset, E=8, k=2, M=16, F=8):
    return DroplessMoE(M, F, E, k, expert_offset=offset, experts_held=held,
                       dtype=jnp.float32, param_dtype=jnp.float32)


def _per_token_loop(params, x, k, offset):
    """Each token alone: its router logits, its k largest, the softmax over
    them, each chosen expert that is held applied to it."""
    router, w1, w2 = (np.asarray(params[n], np.float64) for n in ("router_kernel", "w1", "w2"))
    out = np.zeros_like(x, dtype=np.float64)
    received = np.zeros(w1.shape[0], int)
    for t, u in enumerate(np.asarray(x, np.float64)):
        logits = u @ router
        top = np.argsort(-logits, kind="stable")[:k]
        gates = np.exp(logits[top] - logits[top].max())
        gates /= gates.sum()
        for e, g in zip(top, gates):
            if offset <= e < offset + w1.shape[0]:
                a, b = np.split(u @ w1[e - offset], 2)
                out[t] += g * ((a / (1 + np.exp(-a)) * b) @ w2[e - offset])
                received[e - offset] += 1
    return out, received


@pytest.mark.parametrize("case", ["spread", "one_expert_gets_none", "one_expert_gets_all"])
def test_dropless_topk_matches_a_per_token_loop(case):
    rng = np.random.default_rng(5)
    layer = _moe(held=8, offset=0)
    x = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    params = jax.tree.map(np.array, layer.init(jax.random.PRNGKey(1), x)["params"])
    if case == "one_expert_gets_none":
        params["router"]["kernel"][:, 3] = 0.0
        params["router"]["kernel"][0, 3] = -1e4  # below every other logit: x[:, 0] made positive
        x = x.at[:, 0].set(jnp.abs(x[:, 0]) + 0.1)
    if case == "one_expert_gets_all":
        params["router"]["kernel"][:, 5] = 0.0
        params["router"]["kernel"][0, 5] = 1e4
        x = x.at[:, 0].set(jnp.abs(x[:, 0]) + 0.1)
    got, received = layer.apply({"params": params}, x)
    flat = {"router_kernel": params["router"]["kernel"], "w1": params["w1"], "w2": params["w2"]}
    want, want_received = _per_token_loop(flat, x, 2, 0)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert received.tolist() == want_received.tolist() and int(received.sum()) == 40 * 2
    if case == "one_expert_gets_none":
        assert received[3] == 0
    if case == "one_expert_gets_all":
        assert received[5] == 40


def test_dispatch_sorts_the_held_choices_first_and_counts_them():
    experts = jnp.asarray([[0, 5], [4, 7], [5, 6], [1, 4]], jnp.int32)
    order, position, sizes = dispatch_to_held(experts, expert_offset=4, experts_held=3)
    assert sizes.tolist() == [2, 2, 1]  # experts 4, 5, 6; 0, 1 and 7 live elsewhere
    flat = np.asarray(experts).ravel()
    assert flat[np.asarray(order)][:5].tolist() == [4, 4, 5, 5, 6]
    assert np.array_equal(np.asarray(order)[np.asarray(position).ravel()], np.arange(8))
    weights, picked = topk_softmax_gating(jnp.asarray([[0.0, 2.0, 1.0, -1.0]]), 2)
    assert picked.tolist() == [[1, 2]]
    np.testing.assert_allclose(weights, [[1 / (1 + np.exp(-1.0)), 1 / (1 + np.exp(1.0))]], rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """Layer 0 of the tiny model under the two chips' shares, experts [0, 4)
    and [4, 8): the routed parts plus the shared MLP counted once are the
    whole layer's MoE(u) + Shared(u), as the uncut reference computes it."""
    seed, E = 7, TINY["published"]["num_local_experts"]
    whole = _tiny_model(experts_held=E)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), _weights(whole, seed))
    lp = params["layers_0"]
    u = jnp.asarray(np.random.default_rng(seed).standard_normal((60, TINY["hidden_size"])),
                    jnp.float32)
    weights, experts = reference_lm.route(lp["moe"]["router"]["kernel"], u, 2, "f32")
    uncut = reference_lm.held_experts(lp["moe"], u, weights, experts, 0, 64, "f32")
    shared = reference_lm.gated_mlp(lp["shared_mlp"]["input_linear"]["kernel"],
                                    lp["shared_mlp"]["output_linear"]["kernel"], u, "f32")
    parts, received = [], []
    for offset in (0, E // 2):
        share = {"router": lp["moe"]["router"],
                 "w1": lp["moe"]["w1"][offset:offset + E // 2],
                 "w2": lp["moe"]["w2"][offset:offset + E // 2]}
        layer = _moe(held=E // 2, offset=offset, E=E, M=TINY["hidden_size"],
                     F=TINY["intermediate_size"])
        part, got = layer.apply({"params": share}, u)
        parts.append(part)
        received.append(got)
        ref_part = reference_lm.held_experts(share, u, weights, experts, offset, 64, "f32")
        np.testing.assert_allclose(part, ref_part, rtol=2e-4, atol=2e-5)
    assert int(sum(r.sum() for r in received)) == 60 * 2  # every choice lands on one chip
    np.testing.assert_allclose(parts[0] + parts[1] + shared, uncut + shared, rtol=2e-4, atol=2e-5)


def test_registry_builds_the_published_model_and_the_file_states_it():
    model = create_model_from_registry("granite_4_0_h_small")
    built, published = model.cfg, {**CONFIG, **CONFIG["published"]}
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads", "intermediate_size",
                "shared_intermediate_size", "num_local_experts", "num_experts_per_tok",
                "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_d_conv", "mamba_chunk_size", "attention_multiplier",
                "embedding_multiplier", "logits_scaling", "residual_multiplier", "rms_norm_eps",
                "vocab_size"):
        assert getattr(built, key) == published[key], key
    assert published["mamba_n_groups"] == 1  # B and C shared by all heads: the one form the mixer has
    assert list(built.layer_types) == CONFIG["layer_types"]
    assert len(built.layer_types) == CONFIG["num_hidden_layers"] == published["depth"]
    assert built.layer_types[:10].count("attention") == 1 and built.layer_types[5] == "attention"
    assert built.param_dtype == jnp.bfloat16 == jnp.dtype(CONFIG["param_dtype"])


def test_the_cut_holds_the_parameters_the_issue_counted():
    """4,757 M parameters at bfloat16 for the chip's share (9.51 GB)."""
    from benchmarks.systems.lm import System

    shapes = System(CONFIG, tiny=False).param_shapes()
    leaves = jax.tree.leaves(shapes)
    assert round(sum(x.size for x in leaves) / 1e6) == 4757
    assert all(x.dtype == jnp.bfloat16 for x in leaves)


def test_lm_entry_scores_the_last_row_by_default():
    model = _tiny_model()
    params = granite_hybrid.create_lm(TINY["arch"], depth=4, vocab_size=128, experts_held=4)[1]
    ids = np.arange(30) % 128
    last = pipeline.run_inference_with_lm(ids, lm=(model, params))
    both = pipeline.run_inference_with_lm(ids, [3, 29], lm=(model, params))
    assert last["positions"].tolist() == [[29]] and last["logits"].shape == (1, 1, 128)
    np.testing.assert_allclose(last["logits"][0, 0], both["logits"][0, 1], rtol=1e-4, atol=1e-6)
    # causal: a later token does not move an earlier row's logits
    changed = pipeline.run_inference_with_lm(np.where(np.arange(30) > 10, 5, ids), [3, 29],
                                             lm=(model, params))
    np.testing.assert_allclose(changed["logits"][0, 0], both["logits"][0, 0], rtol=1e-4, atol=1e-6)
    assert not np.allclose(changed["logits"][0, 1], both["logits"][0, 1], rtol=1e-2)


def test_lm_entry_traces_a_model_once():
    """`run_inference_with_lm` builds its jitted function once a model: a
    second call with the same shapes traces nothing."""
    model = _tiny_model()
    params = granite_hybrid.create_lm(TINY["arch"], depth=4, vocab_size=128, experts_held=4)[1]
    fn = pipeline.lm_forward_fn(model)
    assert pipeline.lm_forward_fn(_tiny_model()) is fn  # equal fields, one function
    ids = np.arange(20) % 128
    pipeline.run_inference_with_lm(ids, [3, 19], lm=(model, params))
    traced = fn._cache_size()
    pipeline.run_inference_with_lm(ids[::-1], [5, 19], lm=(model, params))
    assert fn._cache_size() == traced >= 1


@pytest.mark.parametrize("k, n, tile", [(4096, 1536, (512, 512, 1536)), (768, 4096, (512, 768, 1024)),
                                        (64, 96, None)])
def test_grouped_product_tiles_divide_the_widths_or_it_refuses(k, n, tile):
    from gigapath_tpu.ops.moe.expert_parallel import GMM_TILE_ROWS, _gmm_tiling

    if tile is None:
        with pytest.raises(ValueError, match="multiples of 128"):
            _gmm_tiling(k, n)
        return
    rows, tk, tn = _gmm_tiling(k, n)
    assert (rows, tk, tn) == (GMM_TILE_ROWS, *tile[1:]) and k % tk == 0 and n % tn == 0
    assert tk * tn * 2 <= 2 << 20  # the bfloat16 weight tile stays under 2 MB
