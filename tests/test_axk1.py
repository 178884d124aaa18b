"""A.X-K1 on the forward path, at the tiny preset on the CPU (hidden 64, one
dense and two expert layers, 4 heads of 16 + 8 / 16, 16 experts in 4 groups
with 2 kept and top-4, vocabulary 256): the program against the benchmark's
plain reference (``benchmarks/lib/reference_axk1.py``), latent attention
against an explicit per-head loop, YaRN's frequencies and the softmax scale
against the numbers ISSUE 32 wrote down, the gate against a per-token loop
with ties, the shares of a layer adding up to the uncut layer, and the entry
points."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.closed_loop import row_gaps
from benchmarks.lib import reference_axk1, tables, weights_lm
from gigapath_tpu import pipeline
from gigapath_tpu.models import axk1, granite_hybrid
from gigapath_tpu.ops import rope
from gigapath_tpu.ops.moe import DroplessMoE, GroupLimitedSigmoidGate
from gigapath_tpu.utils.registry import create_model_from_registry

CONFIG = tables.load("configs", "axk1_ep16")
TINY = CONFIG["tiny"]


def _tiny_model(**share):
    share = {"depth": TINY["depth"], "vocab_size": TINY["vocab_size"],
             "experts_held": TINY["n_routed_experts"], "expert_offset": 0, **share}
    return create_model_from_registry(TINY["arch"], **share)


def _weights(model, seed, dtype=None):
    ids = jax.ShapeDtypeStruct((1, 4), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids, ids)["params"]
    params = weights_lm.make_weights(shapes, seed)
    return params if dtype is None else jax.tree.map(lambda a: a.astype(dtype), params)


@pytest.mark.parametrize("length", [77, 300])
@pytest.mark.parametrize("seed", [11, 3000000019])
def test_float32_program_is_the_reference_to_rounding(seed, length):
    """The same bfloat16-valued weights, the program computing in float32: no
    routing tie can round the other way, so every row agrees closely."""
    model = _tiny_model(dtype=jnp.float32, param_dtype=jnp.float32)
    params = _weights(model, seed, jnp.float32)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TINY["vocab_size"], (2, length), dtype=np.int32)
    positions = np.sort(rng.permutation(length)[:4]).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        out = pipeline.run_inference_with_lm(ids, positions, lm=(model, params))
    assert out["logits"].shape == (2, 4, TINY["vocab_size"]) and out["logits"].dtype == np.float32
    assert out["expert_tokens"].shape == (TINY["depth"] - 1, TINY["n_routed_experts"])
    counted = np.zeros_like(out["expert_tokens"])
    for b in range(2):
        routing = []
        ref = reference_axk1.lm_forward(params, ids[b], positions, TINY, routing=routing)
        assert row_gaps(out["logits"][b], ref).max() < 2e-4
        assert len(routing) == TINY["depth"] - 1
        for layer, experts in enumerate(routing):
            counted[layer] += np.bincount(experts.ravel(), minlength=16)[: TINY["n_routed_experts"]]
    # the counter: the choices that name a held expert, both sequences' together
    assert out["expert_tokens"].tolist() == counted.tolist() and counted.sum() > 0


@pytest.mark.parametrize("seed", [11, 3000000019, 5])
def test_bfloat16_program_matches_the_reference_but_for_routing_ties(seed):
    """bfloat16 activations through three layers. Where a score's rounding
    moves a token's fourth choice, that row moves by up to a third of its norm
    (one of four choices carries ~2.5 / 4 of an expert's output): the median
    row is close, and the mean stays under the limit the cell's rehearsal
    holds it to."""
    model = _tiny_model()
    params = _weights(model, seed)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TINY["vocab_size"], (2, 77), dtype=np.int32)
    positions = np.sort(rng.permutation(77)[:8]).astype(np.int32)
    out = pipeline.run_inference_with_lm(ids, positions, lm=(model, params))
    gaps = np.concatenate([
        row_gaps(out["logits"][b], reference_axk1.lm_forward(params, ids[b], positions, TINY))
        for b in range(2)])
    limits = tables.load("workloads", "axk1_prefill_b1_16k")["correct"]["tiny_limits"]
    assert np.median(gaps) < 0.03 and gaps.mean() < limits["embed_gap_mean"], gaps
    assert gaps.max() < 0.6, gaps  # a row whose choice flipped; a wrong row reads 1 and more
    assert list(limits) == ["embed_gap_mean"]  # PERF.md §6 says why the widest gap is no limit here


def test_yarn_frequencies_and_scale_are_the_issues_numbers():
    """ISSUE 32: ``low`` 10, ``high`` 23 by hand; scale 0.13086; m 1.3466."""
    assert rope.yarn_correction_range(64, 10000.0, 4096, 32, 1) == (10, 23)
    corr_fast = 64 * math.log(4096 / (2 * math.pi * 32)) / (2 * math.log(10000))
    corr_slow = 64 * math.log(4096 / (2 * math.pi * 1)) / (2 * math.log(10000))
    assert (math.floor(corr_fast), math.ceil(corr_slow)) == (10, 23)
    f = rope.yarn_inv_freq(64, 10000.0, 32, 4096, 32, 1)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)           # fast pairs as published
    np.testing.assert_allclose(f[23:], plain[23:] / 32, rtol=1e-6)      # slow pairs over the factor
    ramp = (16 - 10) / 13
    np.testing.assert_allclose(f[16], plain[16] / 32 * ramp + plain[16] * (1 - ramp), rtol=1e-6)
    assert rope.yarn_mscale(32, 1) == pytest.approx(1.3466, abs=5e-5)
    cfg = create_model_from_registry("axk1").cfg
    assert cfg.softmax_scale == pytest.approx(0.13086, abs=5e-6)
    assert cfg.softmax_scale == pytest.approx(reference_axk1.softmax_scale(CONFIG))
    np.testing.assert_allclose(
        f, reference_axk1.yarn_frequencies(64, 10000.0, CONFIG["rope_scaling"]), rtol=1e-6)
    cos, sin = cfg.rope_tables(5)   # mscale / mscale_all_dim = 1: the tables carry nothing
    np.testing.assert_allclose(cos[3], np.cos(3 * f), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(sin[3], np.sin(3 * f), rtol=1e-5, atol=1e-6)
    assert rope.yarn_inv_freq(8, 10000.0, 1, 32, 32, 1) == pytest.approx(10000.0 ** (-np.arange(4) / 4))


def test_rotation_turns_the_interleaved_pairs_where_they_lie():
    rng = np.random.default_rng(0)
    freqs = rope.yarn_inv_freq(8, 10000.0, 4, 32, 32, 1)
    cos, sin = rope.rope_tables(jnp.arange(6), freqs)
    for dtype, tol in ((jnp.float32, 1e-6), (jnp.bfloat16, 1e-2)):
        x = jnp.asarray(rng.standard_normal((1, 6, 2, 8)), dtype)
        got = rope.apply_rope_interleaved(x, cos, sin)
        assert got.dtype == dtype and got.shape == x.shape
        want = reference_axk1.rope(x[0].astype(jnp.float32), cos, sin)
        np.testing.assert_allclose(np.asarray(got[0], np.float32), want, rtol=tol, atol=tol)
    # by hand: position p turns pair i by p * f_i (x is the bfloat16 draw: its partner is exact)
    p, i = 5, 1
    angle = p * float(freqs[i])
    a, b = float(x[0, p, 0, 2 * i]), float(x[0, p, 0, 2 * i + 1])
    assert float(got[0, p, 0, 2 * i]) == pytest.approx(a * math.cos(angle) - b * math.sin(angle), abs=1e-2)
    assert float(got[0, p, 0, 2 * i + 1]) == pytest.approx(a * math.sin(angle) + b * math.cos(angle), abs=1e-2)
    swapped = np.asarray(jnp.asarray([[1.0, 2.0, 3.0, 4.0]]) @ rope._pair_swap(4))
    assert swapped.tolist() == [[-2.0, 1.0, -4.0, 3.0]]


def _attention_by_heads(p, u, cfg):
    """Latent attention one head and one query row at a time, float64."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    u = np.asarray(u, np.float64)
    L = u.shape[0]
    H, nope, rot, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim)

    def norm(w, x):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + cfg.rms_norm_eps) * w

    def turn(x, pos):  # interleaved pairs of one vector
        f = reference_axk1.yarn_frequencies(
            rot, cfg.rope_theta, {"factor": cfg.rope_factor, "beta_fast": cfg.rope_beta_fast,
                                  "beta_slow": cfg.rope_beta_slow,
                                  "original_max_position_embeddings":
                                      cfg.rope_original_max_position_embeddings})
        out = np.empty_like(x)
        for i in range(rot // 2):
            c, s = math.cos(pos * f[i]), math.sin(pos * f[i])
            out[2 * i], out[2 * i + 1] = x[2 * i] * c - x[2 * i + 1] * s, x[2 * i] * s + x[2 * i + 1] * c
        return out

    q = (norm(p["q_a_layernorm"]["weight"], u @ p["q_a_proj"]["kernel"])
         @ p["q_b_proj"]["kernel"]).reshape(L, H, nope + rot)
    kv_a = u @ p["kv_a_proj_with_mqa"]["kernel"]
    kv = (norm(p["kv_a_layernorm"]["weight"], kv_a[:, :cfg.kv_lora_rank])
          @ p["kv_b_proj"]["kernel"]).reshape(L, H, nope + dv)
    k_r = np.stack([turn(kv_a[t, cfg.kv_lora_rank:], t) for t in range(L)])   # one head, all use it
    out = np.zeros((L, H, dv))
    for h in range(H):
        for t in range(L):
            q_t = np.concatenate([q[t, h, :nope], turn(q[t, h, nope:], t)])
            s = np.array([q_t @ np.concatenate([kv[j, h, :nope], k_r[j]]) for j in range(t + 1)])
            w = np.exp((s - s.max()) * cfg.softmax_scale)
            out[t, h] = (w / w.sum()) @ kv[: t + 1, h, nope:]
    return out.reshape(L, H * dv) @ p["o_proj"]["kernel"]


def test_latent_attention_matches_a_loop_over_heads_and_rows():
    cfg = dataclasses.replace(_tiny_model().cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    layer = axk1.MLAttention(cfg)
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.standard_normal((2, 19, cfg.hidden_size)), jnp.float32)
    cos, sin = cfg.rope_tables(19)
    params = layer.init(jax.random.PRNGKey(2), u, cos, sin)["params"]
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, u, cos, sin)
    for b in range(2):
        np.testing.assert_allclose(got[b], _attention_by_heads(params, u[b], cfg),
                                   rtol=2e-4, atol=2e-5)
    # the plain reference's attention is the same function of the same names
    dims = reference_axk1.layer_dims(TINY)
    with jax.default_matmul_precision("highest"):
        ref = reference_axk1.latent_attention(params, u[0], dims, "f32", block_rows=8)
    np.testing.assert_allclose(got[0], ref, rtol=2e-4, atol=2e-5)


def _gate_by_token(logits, k, n_group, topk_group, scale):
    """Each token alone, float64: its sigmoid scores, the groups ranked by
    their best score (the lower group first among equals), the k best scores
    inside the groups kept (the lower expert first among equals)."""
    weights, experts = [], []
    for row in np.asarray(logits, np.float64):
        s = 1.0 / (1.0 + np.exp(-row))
        groups = s.reshape(n_group, -1)
        kept = sorted(np.argsort(-groups.max(-1), kind="stable")[:topk_group])
        size = groups.shape[1]
        eligible = [g * size + j for g in kept for j in range(size)]
        chosen = sorted(eligible, key=lambda e: (-s[e], e))[:k]
        experts.append(chosen)
        weights.append(s[chosen] / s[chosen].sum() * scale)
    return np.asarray(weights), np.asarray(experts)


@pytest.mark.parametrize("case", ["random", "ties"])
def test_gate_matches_a_per_token_loop(case):
    rng = np.random.default_rng(9)
    logits = rng.standard_normal((50, 16)).astype(np.float32)
    if case == "ties":  # whole groups and single experts that score alike
        logits = np.round(logits)            # many equal scores inside and across groups
        logits[0] = 0.0                      # everything ties: groups 0, 1 and experts 0..3
        logits[1, 4:8] = logits[1, 0:4]      # two groups alike
    gate = GroupLimitedSigmoidGate(4, 2, 2.5)
    weights, experts = gate(jnp.asarray(logits), 4)
    want_w, want_e = _gate_by_token(logits, 4, 4, 2, 2.5)
    assert np.asarray(experts).tolist() == want_e.tolist()
    np.testing.assert_allclose(weights, want_w, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-5)
    assert experts.dtype == jnp.int32 and weights.dtype == jnp.float32
    if case == "ties":
        assert np.asarray(experts)[0].tolist() == [0, 1, 2, 3]
    # the plain reference picks alike
    ref_w, ref_e = reference_axk1.route(jnp.eye(16), jnp.asarray(logits), 4, 4, 2, 2.5, "f32")
    assert np.asarray(ref_e).tolist() == want_e.tolist()
    np.testing.assert_allclose(ref_w, want_w, rtol=1e-5)
    assert gate == GroupLimitedSigmoidGate(4, 2, 2.5) and hash(gate) == hash(
        GroupLimitedSigmoidGate(4, 2, 2.5))  # a value: two models built alike share one jitted function


def test_group_ranking_keeps_a_better_expert_of_a_worse_group_out():
    """Expert 8 (group 2) scores third of all, but groups 0 and 1 hold the two
    best experts: with 2 groups kept it is not eligible."""
    logits = np.full((1, 16), -3.0, np.float32)
    logits[0, [0, 4, 8, 1, 5]] = [3.0, 2.5, 2.0, 0.5, 0.4]
    _, experts = GroupLimitedSigmoidGate(4, 2, 1.0)(jnp.asarray(logits), 4)
    assert np.asarray(experts)[0].tolist() == [0, 4, 1, 5]


def test_dense_layer_and_expert_layer_follow_the_reference():
    """Layer 0 (dense MLP) and layer 1 (routed + shared), each alone on a
    random stream, float32."""
    model = _tiny_model(dtype=jnp.float32, param_dtype=jnp.float32)
    params = _weights(model, 21, jnp.float32)
    cfg = model.cfg
    rng = np.random.default_rng(21)
    h = jnp.asarray(rng.standard_normal((1, 33, cfg.hidden_size)), jnp.float32)
    cos, sin = cfg.rope_tables(33)
    dims = reference_axk1.layer_dims(TINY)
    for i, is_dense in ((0, True), (1, False)):
        lp = params[f"layers_{i}"]
        with jax.default_matmul_precision("highest"):
            got, received = axk1.AXK1Layer(cfg, is_dense).apply({"params": lp}, h, cos, sin)
            ref_h, u = reference_axk1._attend(lp, h[0], dims=dims, mode="f32")
            if is_dense:
                assert received is None and "mlp" in lp and "moe" not in lp
                want = reference_axk1._dense_ffn(lp["mlp"], ref_h, u, mode="f32")
            else:
                assert received.shape == (TINY["n_routed_experts"],) and "mlp" not in lp
                weights, experts = reference_axk1._route(lp["moe"]["router"]["kernel"], u,
                                                         dims=dims, mode="f32")
                want = reference_axk1._experts_and_shared(lp, ref_h, u, weights, experts,
                                                          dims=dims, rows_max=64, mode="f32")
                assert received.tolist() == [int((np.asarray(experts) == e).sum()) for e in range(4)]
        np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """Layer 1 of the tiny model under four chips' shares, experts [0, 4),
    [4, 8), [8, 12), [12, 16): the routed parts plus the shared expert counted
    once are the whole layer's Routed(u) + Shared(u), as the uncut reference
    computes it; every choice lands on exactly one chip."""
    seed, E = 7, TINY["published"]["n_routed_experts"]
    whole = _tiny_model(experts_held=E, dtype=jnp.float32, param_dtype=jnp.float32)
    lp = _weights(whole, seed, jnp.float32)["layers_1"]
    u = jnp.asarray(np.random.default_rng(seed).standard_normal((60, TINY["hidden_size"])),
                    jnp.float32)
    k, groups, kept, scale = (TINY["num_experts_per_tok"], TINY["n_group"], TINY["topk_group"],
                              TINY["routed_scaling_factor"])
    weights, experts = reference_axk1.route(lp["moe"]["router"]["kernel"], u, k, groups, kept,
                                            scale, "f32")
    uncut = reference_axk1.held_experts(lp["moe"], u, weights, experts, 0, 64, "f32")
    shared = reference_axk1.gated_mlp(lp["shared_experts"]["input_linear"]["kernel"],
                                      lp["shared_experts"]["output_linear"]["kernel"], u, "f32")
    parts, received = [], []
    for offset in range(0, E, E // 4):
        share = {"router": lp["moe"]["router"],
                 "w1": lp["moe"]["w1"][offset:offset + E // 4],
                 "w2": lp["moe"]["w2"][offset:offset + E // 4]}
        layer = DroplessMoE(TINY["hidden_size"], TINY["moe_intermediate_size"], E, k,
                            expert_offset=offset, experts_held=E // 4,
                            gate=GroupLimitedSigmoidGate(groups, kept, scale),
                            dtype=jnp.float32, param_dtype=jnp.float32)
        part, got = layer.apply({"params": share}, u)
        parts.append(part)
        received.append(got)
        ref_part = reference_axk1.held_experts(share, u, weights, experts, offset, 64, "f32")
        np.testing.assert_allclose(part, ref_part, rtol=2e-4, atol=2e-5)
    assert int(sum(r.sum() for r in received)) == 60 * k
    np.testing.assert_allclose(sum(parts) + shared, uncut + shared, rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(uncut).mean()) > 0.1 and float(jnp.abs(shared).mean()) > 0.1


_PUBLISHED = {  # the catalog row's `config` (https://huggingface.co/skt/A.X-K1/blob/main/config.json)
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 7168, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "axk1", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none", "v_head_dim": 128,
    "vocab_size": 163840,
}


def test_registry_builds_the_published_model_and_the_file_states_it():
    for key, value in _PUBLISHED.items():  # every key as published, or listed as reduced
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value and CONFIG[key] < value
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["depth", "n_routed_experts", "vocab_size"]
    assert CONFIG["published"]["depth"] == _PUBLISHED["num_hidden_layers"]
    assert "topk_method" in CONFIG["assumed"][0] and "16 chips" in CONFIG["deployment"]
    built = create_model_from_registry("axk1").cfg
    for key in ("hidden_size", "num_hidden_layers", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "intermediate_size", "moe_intermediate_size", "n_routed_experts",
                "num_experts_per_tok", "n_group", "topk_group", "routed_scaling_factor",
                "n_shared_experts", "first_k_dense_replace", "rope_theta", "rms_norm_eps",
                "vocab_size"):
        assert getattr(built, key) == _PUBLISHED[key], key
    for key in ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale",
                "mscale_all_dim"):
        assert getattr(built, "rope_" + key) == _PUBLISHED["rope_scaling"][key], key
    assert built.param_dtype == jnp.bfloat16 == jnp.dtype(CONFIG["param_dtype"])


def test_the_cut_holds_the_parameters_the_issue_counted():
    """4,166 M parameters at bfloat16 for the chip's share (8.33 GB): an
    expert layer here 675.0 M, the dense layer 497.5 M, embedding + head
    293.6 M."""
    from benchmarks.systems.lm import System

    shapes = System(CONFIG, tiny=False).param_shapes()
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    assert round(count(shapes) / 1e6) == 4166
    assert count(shapes["layers_0"]) / 1e6 == pytest.approx(497.5, abs=0.05)
    assert count(shapes["layers_3"]) / 1e6 == pytest.approx(675.0, abs=0.05)
    assert count(shapes["layers_3"]["self_attn"]) / 1e6 == pytest.approx(101.12, abs=0.01)
    assert (count(shapes["embed_tokens"]) + count(shapes["lm_head"])) / 1e6 == pytest.approx(293.6, abs=0.05)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(shapes))
    assert shapes["layers_3"]["moe"]["w1"].shape == (12, 7168, 4096)
    assert shapes["layers_3"]["moe"]["router"]["kernel"].shape == (7168, 192)


def test_lm_entry_serves_the_model_with_no_branch_on_it():
    model = _tiny_model()
    params = granite_hybrid.create_lm(TINY["arch"], depth=3, vocab_size=128, experts_held=4)[1]
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
    # positions matter: the same token at another place scores otherwise (Granite's attention has none)
    moved = pipeline.run_inference_with_lm(np.concatenate([[7], ids]), [4, 30], lm=(model, params))
    assert not np.allclose(moved["logits"][0, 1], both["logits"][0, 1], rtol=1e-2)
    assert pipeline.lm_forward_fn(_tiny_model()) is pipeline.lm_forward_fn(model)
