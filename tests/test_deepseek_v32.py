"""DeepSeek-V3.2 on the forward path, at the tiny preset on the CPU (hidden 64,
one dense and two expert layers, 4 heads of 16 + 8 / 16, an indexer of 4 heads
of 16 that keeps 16 keys a query, 16 experts in 4 groups with 2 kept and
top-4 under a selection bias, one prediction module, vocabulary 256): the
program against the benchmark's plain reference
(``benchmarks/lib/reference_deepseek_v32.py``) with the same selected sets, the
indexer and the top-k against three lines of ``einsum`` and a per-row sort with
ties, the Pallas tier in interpret mode against the ``jnp`` tier, the layer
below ``index_topk`` against dense causal latent attention, the gate against
A.X-K1's and against its own bias, the prediction module's rows, the shares of
a layer adding up to the uncut layer, and the entry points."""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.closed_loop import row_gaps
from benchmarks.lib import reference_deepseek_v32 as reference
from benchmarks.lib import tables, weights_lm
from gigapath_tpu import pipeline
from gigapath_tpu.models import axk1, deepseek_v32, granite_hybrid
from gigapath_tpu.ops import rope, sparse_index
from gigapath_tpu.ops.moe import DroplessMoE, GroupLimitedSigmoidGate
from gigapath_tpu.utils.registry import create_model_from_registry

CONFIG = tables.load("configs", "deepseek_v32_ep32")
TINY = CONFIG["tiny"]
TOPK = TINY["index_topk"]


def _tiny_model(**share):
    share = {"depth": TINY["depth"], "vocab_size": TINY["vocab_size"],
             "experts_held": TINY["n_routed_experts"], "expert_offset": 0,
             "mtp": TINY["num_nextn_predict_layers"], **share}
    return create_model_from_registry(TINY["arch"], **share)


def _weights(model, seed, dtype=None):
    ids = jax.ShapeDtypeStruct((1, 4), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids, ids)["params"]
    params = weights_lm.make_weights(shapes, seed)
    return params if dtype is None else jax.tree.map(lambda a: a.astype(dtype), params)


def _spy_on_selection(monkeypatch):
    """Every mask the program's selection hands its core, in call order."""
    masks, select = [], sparse_index.select_topk

    def spy(scores, topk, **kw):
        masks.append(select(scores, topk, **kw))
        return masks[-1]

    monkeypatch.setattr(sparse_index, "select_topk", spy)
    return masks


def _selected(length, topk=TOPK):
    return sum(min(t + 1, topk) for t in range(length))


@pytest.mark.parametrize("length", [77, 300])
@pytest.mark.parametrize("seed", [11, 3000000019])
def test_float32_program_selects_the_references_sets_and_gives_its_logits(seed, length, monkeypatch):
    """The same bfloat16-valued weights, the program computing in float32: no
    index score and no routing score can round the other way, so the selected
    sets are the reference's key for key, in every layer and in the prediction
    module's, and every row of both logits agrees to 1e-4."""
    model = _tiny_model(dtype=jnp.float32, param_dtype=jnp.float32)
    params = _weights(model, seed, jnp.float32)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TINY["vocab_size"], (2, length), dtype=np.int32)
    positions = np.sort(rng.permutation(length)[:4]).astype(np.int32)
    masks = _spy_on_selection(monkeypatch)
    with jax.default_matmul_precision("highest"), jax.disable_jit():
        out = pipeline.run_inference_with_lm(ids, positions, lm=(model, params))
    layers = TINY["depth"] + 1
    assert len(masks) == layers
    assert out["logits"].shape == out["mtp_logits"].shape == (2, 4, TINY["vocab_size"])
    assert out["expert_tokens"].shape == (layers - 1, TINY["n_routed_experts"])
    assert out["selected_pairs"].tolist() == [[_selected(length)] * 2] * layers
    assert length > TOPK and _selected(length) < length * (length + 1) // 2   # the selection bites
    counted = np.zeros_like(out["expert_tokens"])
    for b in range(2):
        seen = {"selection": [], "routing": []}
        ref = reference.lm_forward(params, ids[b], positions, TINY, seen=seen)
        assert row_gaps(out["logits"][b], ref).max() < 1e-4
        assert row_gaps(out["mtp_logits"][b], seen["mtp_logits"]).max() < 1e-4
        assert seen["selected_pairs"] == [_selected(length)] * layers
        for layer in range(layers):
            assert (np.asarray(masks[layer][b]).astype(bool) == seen["selection"][layer]).all()
        for layer, experts in enumerate(seen["routing"]):
            counted[layer] += np.bincount(experts.ravel(), minlength=16)[: TINY["n_routed_experts"]]
    assert out["expert_tokens"].tolist() == counted.tolist() and counted.sum() > 0


@pytest.mark.parametrize("seed", [11, 3000000019, 5])
def test_bfloat16_program_stays_near_the_reference(seed):
    """bfloat16 activations through three layers at hidden 64. Rows below
    ``index_topk`` attend densely and stay within a few hundredths (but for a
    routing flip, as in A.X-K1); past it a
    score's rounding flips keys at the 16th place (a sixteenth of a row's
    attention at this size, a 2,048th at the cell's) and routing choices as in
    A.X-K1, and the flips compound over the layers: the mean stays under the
    limit the cell's rehearsal holds it to, which the fp8 control passes by
    half again (0.49-0.65 over eight seeds; PERF.md §6, PR 34)."""
    model = _tiny_model(mtp=0)
    params = _weights(model, seed)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TINY["vocab_size"], (2, 77), dtype=np.int32)
    positions = np.concatenate([np.arange(8), np.sort(16 + rng.permutation(61)[:8])]).astype(np.int32)
    out = pipeline.run_inference_with_lm(ids, positions, lm=(model, params))
    sizes = dict(TINY, num_nextn_predict_layers=0)
    gaps = np.stack([
        row_gaps(out["logits"][b], reference.lm_forward(params, ids[b], positions, sizes))
        for b in range(2)])
    limits = tables.load("workloads", "dsv32_prefill_b1_16k")["correct"]["tiny_limits"]
    # dense rows have no key to flip: close, but for a row whose routing choice flipped
    assert np.median(gaps[:, :8]) < 0.03 and gaps[:, :8].max() < 0.6, gaps
    assert gaps.mean() < limits["embed_gap_mean"], gaps
    assert list(limits) == ["embed_gap_mean"]


def test_halfsplit_rotation_pairs_a_feature_with_the_one_half_a_width_on():
    rng = np.random.default_rng(0)
    freqs = rope.yarn_inv_freq(8, 10000.0, 4, 32, 32, 1)
    cos, sin = rope.rope_tables(jnp.arange(6), freqs)
    for dtype, tol in ((jnp.float32, 1e-6), (jnp.bfloat16, 1e-2)):
        x = jnp.asarray(rng.standard_normal((1, 6, 2, 8)), dtype)
        got = rope.apply_rope_halfsplit(x, cos, sin)
        assert got.dtype == dtype and got.shape == x.shape
        want = reference.rope_halfsplit(x[0].astype(jnp.float32), cos, sin)
        np.testing.assert_allclose(np.asarray(got[0], np.float32), want, rtol=tol, atol=tol)
    p, i = 5, 1   # by hand: position p turns the pair (x[i], x[i + 4]) by p * f_i
    angle = p * float(freqs[i])
    a, b = float(x[0, p, 0, i]), float(x[0, p, 0, i + 4])
    assert float(got[0, p, 0, i]) == pytest.approx(a * np.cos(angle) - b * np.sin(angle), abs=1e-2)
    assert float(got[0, p, 0, i + 4]) == pytest.approx(a * np.sin(angle) + b * np.cos(angle), abs=1e-2)
    # the interleaved rotation turns other pairs
    assert not np.allclose(np.asarray(got, np.float32),
                           np.asarray(rope.apply_rope_interleaved(x, cos, sin), np.float32), atol=0.05)


def _index_inputs(rng, B=2, L=300, H=8, D=128):
    q = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, L, D)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((B, L, H)), jnp.float32)
    return q, k, w


def _topk_by_sort(scores, topk):
    """Row by row on the host: the ``min(t + 1, topk)`` largest among ``s <=
    t``, the lower index first among equals (a stable sort of the negated row)."""
    scores = np.asarray(scores)
    mask = np.zeros(scores.shape, np.int8)
    for b in range(scores.shape[0]):
        for t in range(scores.shape[1]):
            order = np.argsort(-scores[b, t, : t + 1], kind="stable")[:topk]
            mask[b, t, order] = 1
    return mask


@pytest.mark.parametrize("tier", ["jnp", "pallas"])
def test_index_scores_are_a_three_line_einsum(tier):
    q, k, w = _index_inputs(np.random.default_rng(0))
    got = sparse_index.index_scores(q, k, w, use_pallas=tier == "pallas", interpret=True)
    s = jnp.einsum("bthd,bsd->bths", q.astype(jnp.float32), k.astype(jnp.float32),
                   precision="highest")
    want = jnp.einsum("bths,bth->bts", jax.nn.relu(s), w, precision="highest")
    causal = np.tril(np.ones((300, 300), bool))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got)[:, causal], np.asarray(want)[:, causal],
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("tier", ["jnp", "pallas"])
@pytest.mark.parametrize("topk", [16, 64, 299, 400])
def test_selection_is_the_exact_top_k_with_ties_to_the_lower_index(tier, topk):
    q, k, w = _index_inputs(np.random.default_rng(1))
    scores = sparse_index.index_scores(q, k, w, use_pallas=False)
    for case in (scores, jnp.round(scores * 0.5), jnp.zeros_like(scores)):   # few, many, all ties
        got = np.asarray(sparse_index.select_topk(case, topk, use_pallas=tier == "pallas",
                                                  interpret=True))
        assert got.dtype == np.int8
        assert (got == _topk_by_sort(case, topk)).all()
        assert (got.sum(-1) == np.minimum(np.arange(300) + 1, topk)).all()
        assert not got[:, ~np.tril(np.ones((300, 300), bool))].any()
        assert sparse_index.selected_pairs(jnp.asarray(got)).tolist() == [_selected(300, topk)] * 2
    # all scores equal: the first min(t + 1, topk) keys
    assert (got[0, 250, :topk] == 1).all() if topk < 250 else (got[0, 250, :251] == 1).all()


def test_core_attends_to_the_selected_keys_and_to_no_other():
    rng = np.random.default_rng(2)
    B, L, H = 2, 300, 4
    q, k, w = _index_inputs(rng)
    mask = sparse_index.select_topk(sparse_index.index_scores(q, k, w, use_pallas=False), 16,
                                    use_pallas=False)
    qq, kk = (jnp.asarray(rng.standard_normal((B, L, H, 24)), jnp.float32) for _ in range(2))
    vv = jnp.asarray(rng.standard_normal((B, L, H, 16)), jnp.float32)
    s = np.einsum("bthd,bshd->bhts", qq, kk) * 0.2
    s = np.where(np.asarray(mask)[:, None] != 0, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhts,bshd->bthd", p / p.sum(-1, keepdims=True), vv)
    got = sparse_index.sparse_attention(qq, kk, vv, mask, scale=0.2, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)
    kernel = sparse_index.sparse_attention(
        qq.astype(jnp.bfloat16), kk.astype(jnp.bfloat16), vv.astype(jnp.bfloat16), mask,
        scale=0.2, use_pallas=True, interpret=True)
    assert kernel.dtype == jnp.bfloat16 and kernel.shape == (B, L, H, 16)
    np.testing.assert_allclose(np.asarray(kernel, np.float32), want, atol=0.06)
    # a key outside the selection changes nothing; one inside does
    t = 200
    out_key = int(np.flatnonzero(np.asarray(mask)[0, t, : t + 1] == 0)[0])
    in_key = int(np.flatnonzero(np.asarray(mask)[0, t])[0])
    for key, moves in ((out_key, False), (in_key, True)):
        moved = sparse_index.sparse_attention(qq, kk, vv.at[0, key].add(5.0), mask, scale=0.2,
                                              use_pallas=False)
        assert bool(jnp.abs(moved[0, t] - got[0, t]).max() > 1e-3) is moves


@pytest.mark.parametrize("window", [3, 40])
def test_core_passes_a_key_block_that_holds_none_of_a_rows_keys(window):
    """A selection of each query's last ``window`` keys: for the second and
    third query blocks of 128 the earlier key blocks are visited (other heads
    of other selections would need them) and hold no selected key of any of
    their rows. There the running max stays at ``M_FLOOR``, every ``p`` is
    exactly 0 and nothing reaches the sum or the accumulator: the result is
    the softmax over the window alone, at the kernel's bfloat16 operands to
    the jnp tier's rounding and, rows in two chains or four, the same bits."""
    from gigapath_tpu.ops import pallas_flash as pf
    from gigapath_tpu.ops.pallas_sparse import sparse_attn_fwd

    rng = np.random.default_rng(4)
    B, L, H = 1, 300, 2
    t = np.arange(L)
    mask = jnp.asarray(((t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - window))[None],
                       jnp.int8)
    q, k = (jnp.asarray(rng.standard_normal((B, L, H, 24)) * 3, jnp.bfloat16) for _ in range(2))
    v = jnp.asarray(rng.standard_normal((B, L, H, 16)), jnp.bfloat16)
    # the empty blocks' keys would win every row's max by far if a p leaked
    k = k.at[:, :128].multiply(8.0)
    v = v.at[:, :128].add(100.0)
    want = sparse_index.sparse_attention(*(x.astype(jnp.float32) for x in (q, k, v)), mask,
                                         scale=0.2, use_pallas=False)
    got = sparse_attn_fwd(q, k, v, mask, scale=0.2, block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32)[:, 128 + window:],
                               np.asarray(want)[:, 128 + window:], atol=0.06)
    assert float(jnp.abs(got[:, 128 + window:].astype(jnp.float32)).max()) < 10  # no v + 100 in it
    four = sparse_attn_fwd(q, k, v, mask, scale=0.2, block_q=128, block_k=128, interpret=True,
                           body=pf.FwdPlan("overlap", 32))
    assert int((np.asarray(got, np.float32) != np.asarray(four, np.float32)).sum()) == 0


def test_below_index_topk_the_layer_is_dense_causal_latent_attention():
    """At ``L <= index_topk`` every earlier key is selected: the same weights
    under A.X-K1's module (which has no indexer and ignores its parameters)
    give the same output; one token more and they part."""
    cfg = _tiny_model().cfg
    sparse, dense = deepseek_v32.SparseMLAttention(cfg), axk1.MLAttention(cfg)
    rng = np.random.default_rng(3)
    for length, same in ((TOPK, True), (4 * TOPK, False)):
        u = jnp.asarray(rng.standard_normal((2, length, cfg.hidden_size)), jnp.bfloat16)
        cos, sin = cfg.rope_tables(length)
        params = jax.tree.map(
            lambda a: jnp.asarray(rng.standard_normal(a.shape) * 0.3, a.dtype),
            jax.eval_shape(sparse.init, jax.random.PRNGKey(0), u, cos, sin)["params"])
        got, pairs = sparse.apply({"params": params}, u, cos, sin)
        want = dense.apply({"params": {k: v for k, v in params.items() if k != "indexer"}},
                           u, cos, sin)
        assert pairs.tolist() == [_selected(length)] * 2
        close = np.allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=0.03)
        assert close is same
        if not same:   # the rows that still see every earlier key agree
            np.testing.assert_allclose(np.asarray(got[:, :TOPK], np.float32),
                                       np.asarray(want[:, :TOPK], np.float32), atol=0.03)


def test_gate_without_bias_and_max_ranking_is_axk1s_bit_for_bit():
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.standard_normal((500, 16)), jnp.float32)
    logits = logits.at[:50].set(jnp.round(logits[:50]))            # ties among scores and groups
    plain = GroupLimitedSigmoidGate(4, 2, 2.5)
    assert plain == GroupLimitedSigmoidGate(4, 2, 2.5, group_top=1, selection_bias=False)
    w0, e0 = plain(logits, 4)
    # what PR 32's gate computed, written out
    scores = jax.nn.sigmoid(logits)
    kept = jax.lax.top_k(scores.reshape(500, 4, 4).max(-1), 2)[1]
    eligible = jnp.repeat((kept[:, :, None] == jnp.arange(4)).any(1), 4, axis=1)
    values, experts = jax.lax.top_k(jnp.where(eligible, scores, -1.0), 4)
    assert (np.asarray(e0) == np.asarray(experts)).all()
    assert (np.asarray(w0) == np.asarray(values / values.sum(-1, keepdims=True) * 2.5)).all()
    # a zero bias is no bias; A.X-K1's layer asks for none
    w1, e1 = plain(logits, 4, jnp.zeros((16,)))
    assert (np.asarray(e1) == np.asarray(e0)).all()
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w0), rtol=1e-6)
    moe = DroplessMoE(8, 4, 16, 4, gate=plain)
    assert "e_score_correction_bias" not in moe.init(
        jax.random.PRNGKey(0), jnp.zeros((3, 8), jnp.bfloat16))["params"]


def test_bias_moves_the_choice_and_leaves_the_weights_the_unbiased_scores():
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.standard_normal((400, 16)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(16) * 0.3, jnp.float32)
    gate = GroupLimitedSigmoidGate(4, 2, 2.5, group_top=2, selection_bias=True)
    weights, experts = (np.asarray(a) for a in gate(logits, 4, bias))
    scores = np.asarray(jax.nn.sigmoid(logits))
    pick = scores + np.asarray(bias)
    moved = 0
    for t in range(400):   # a token at a time, by hand
        groups = pick[t].reshape(4, 4)
        group_scores = np.sort(groups, -1)[:, -2:].sum(-1)         # the two best of each group
        kept = np.argsort(-group_scores, kind="stable")[:2]
        eligible = np.zeros(16, bool)
        for g in kept:
            eligible[4 * g: 4 * g + 4] = True
        chosen = np.argsort(-np.where(eligible, pick[t], -np.inf), kind="stable")[:4]
        assert experts[t].tolist() == chosen.tolist()
        np.testing.assert_allclose(weights[t], scores[t, chosen] / scores[t, chosen].sum() * 2.5,
                                   rtol=1e-6)
        moved += set(chosen) != set(np.asarray(gate(logits[t:t + 1], 4)[1])[0])
    assert moved > 40                                              # the bias changes who is chosen
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    # a group with one outstanding expert loses to one with two good ones
    two = jnp.asarray([[4.0, -9, -9, -9, 1.5, 1.5, -9, -9, 1.4, 1.4, -9, -9, -9, -9, -9, -9]])
    assert set(np.asarray(GroupLimitedSigmoidGate(4, 2, group_top=1)(two, 2)[1])[0]) == {0, 4}
    assert set(np.asarray(GroupLimitedSigmoidGate(4, 2, group_top=2)(two, 2)[1])[0]) == {4, 5}
    # the layer owns the bias, float32, one an expert of the published count
    moe = DroplessMoE(8, 4, 16, 4, experts_held=4, gate=gate)
    bias_leaf = moe.init(jax.random.PRNGKey(0), jnp.zeros((3, 8), jnp.bfloat16))["params"][
        "e_score_correction_bias"]
    assert bias_leaf.shape == (16,) and bias_leaf.dtype == jnp.float32


def test_prediction_rows_follow_the_reference_and_ignore_what_the_last_slot_is_fed(monkeypatch):
    seed, length = 7, 60
    model = _tiny_model(dtype=jnp.float32, param_dtype=jnp.float32)
    params = _weights(model, seed, jnp.float32)
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, TINY["vocab_size"], (1, length), dtype=np.int32)
    positions = np.asarray([0, 17, length - 3, length - 2, length - 1], np.int32)
    with jax.default_matmul_precision("highest"):
        out = pipeline.run_inference_with_lm(ids, positions, lm=(model, params))
        seen = {}
        reference.lm_forward(params, ids[0], positions, TINY, seen=seen)
        np.testing.assert_allclose(out["mtp_logits"][0], seen["mtp_logits"], rtol=2e-4, atol=2e-5)
        # rows' = min(rows, L - 2): the last position reads the row before it
        assert (out["mtp_logits"][0, -1] == out["mtp_logits"][0, -2]).all()
        # a row's prediction sees ids[: row + 2] and nothing later: feed other tokens after it
        other = ids.copy()
        other[0, 19:] = rng.integers(1, TINY["vocab_size"], length - 19)
        moved = pipeline.run_inference_with_lm(other, positions, lm=(model, params))
        np.testing.assert_allclose(moved["mtp_logits"][0, :2], out["mtp_logits"][0, :2],
                                   rtol=1e-5, atol=1e-6)
        assert np.abs(moved["mtp_logits"][0, 2:] - out["mtp_logits"][0, 2:]).max() > 1e-3
        # slot L - 1 is fed id 0 by the program; whatever stands there reaches no row <= L - 2
        fed = []
        embed = deepseek_v32.nn.Embed.__call__

        def spy(self, inputs):
            fed.append(np.asarray(inputs))
            return embed(self, inputs)

        monkeypatch.setattr(deepseek_v32.nn.Embed, "__call__", spy)
        with jax.disable_jit():
            model.apply({"params": params}, jnp.asarray(ids), jnp.asarray(positions[None]))
        assert fed[1][0, :-1].tolist() == ids[0, 1:].tolist() and fed[1][0, -1] == 0


def test_the_module_is_built_only_where_it_runs():
    ids = jnp.zeros((1, 4), jnp.int32)
    without = jax.eval_shape(_tiny_model(mtp=0).init, jax.random.PRNGKey(0), ids, ids)["params"]
    with_it = jax.eval_shape(_tiny_model(mtp=1).init, jax.random.PRNGKey(0), ids, ids)["params"]
    assert sorted(set(with_it) - set(without)) == [
        "mtp_eh_proj", "mtp_enorm", "mtp_hnorm", "mtp_layer", "mtp_shared_head_norm"]
    assert with_it["mtp_eh_proj"]["kernel"].shape == (2 * 64, 64)
    assert set(with_it["mtp_layer"]) == set(with_it["layers_1"])      # an expert layer, indexer and all
    with pytest.raises(ValueError, match="0 or 1"):
        _tiny_model(mtp=2).init(jax.random.PRNGKey(0), ids, ids)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold four experts each of the tiny layer's 16: the routed
    parts of all four shares and the shared expert once are the uncut
    reference's ``Routed(u) + Shared(u)``, bias and two-best group ranking
    included; and each share's program agrees with the reference given that
    share."""
    seed = 13
    rng = np.random.default_rng(seed)
    whole = _tiny_model(experts_held=16, depth=2, mtp=0, dtype=jnp.float32, param_dtype=jnp.float32)
    params = _weights(whole, seed, jnp.float32)
    lp = params["layers_1"]
    u = jnp.asarray(rng.standard_normal((90, 64)), jnp.float32)
    sizes = dict(TINY, n_routed_experts=16, depth=2, num_nextn_predict_layers=0)
    dims = reference.layer_dims(sizes)
    with jax.default_matmul_precision("highest"):
        weights, experts = reference._route(lp["moe"], u, dims=dims, mode="f32")
        uncut = reference._experts_and_shared(lp, jnp.zeros_like(u), u, weights, experts, dims=dims,
                                              rows_max=90, mode="f32")
        shared = reference.gated_mlp(lp["shared_experts"]["input_linear"]["kernel"],
                                     lp["shared_experts"]["output_linear"]["kernel"], u, "f32")
        cfg = whole.cfg
        total = jnp.zeros_like(u)
        for offset in range(0, 16, 4):
            gate = GroupLimitedSigmoidGate(cfg.n_group, cfg.topk_group, cfg.routed_scaling_factor,
                                           group_top=2, selection_bias=True)
            layer = DroplessMoE(64, 32, 16, 4, expert_offset=offset, experts_held=4, gate=gate,
                                dtype=jnp.float32, param_dtype=jnp.float32)
            part = dict(lp["moe"], w1=lp["moe"]["w1"][offset:offset + 4],
                        w2=lp["moe"]["w2"][offset:offset + 4])
            routed, received = layer.apply({"params": part}, u)
            assert received.tolist() == [int((np.asarray(experts) == offset + e).sum())
                                         for e in range(4)]
            total = total + routed
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(uncut), rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(lp["moe"]["e_score_correction_bias"]).max()) > 0


def test_registry_builds_the_published_model_and_the_file_states_it():
    cfg = create_model_from_registry("deepseek_v32").cfg
    for key in ("hidden_size", "num_hidden_layers", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "intermediate_size", "moe_intermediate_size", "num_experts_per_tok", "n_group",
                "topk_group", "routed_scaling_factor", "n_shared_experts", "rope_theta",
                "rms_norm_eps", "index_n_heads", "index_head_dim", "index_topk"):
        assert getattr(cfg, key) == CONFIG[key], key
    for key in ("first_k_dense_replace", "n_routed_experts", "vocab_size",
                "num_nextn_predict_layers"):
        assert getattr(cfg, key) == CONFIG["published"][key], key
    assert (cfg.num_attention_heads, cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        128, 64, 128, 2048)
    assert cfg.rope_factor == CONFIG["rope_scaling"]["factor"] == 40
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)
    assert cfg.softmax_scale == pytest.approx(reference.layer_dims(CONFIG)["scale"])
    assert CONFIG["reduced"] == ["depth", "first_k_dense_replace", "n_routed_experts", "vocab_size",
                                 "num_nextn_predict_layers"]
    assert CONFIG["topk_method"] == "noaux_tc" and CONFIG["model_type"] == "deepseek_v32"
    doc = inspect.getdoc(deepseek_v32)
    for departure in ("(a)", "(b)", "(c)", "(d)", "(e)", "Hadamard", "FP8", "un-absorbed"):
        assert departure in doc and departure in inspect.getdoc(reference)
    assert sum(d.startswith(("the indexer's scores", "the Hadamard", "the multi-token",
                             "the indexer's LayerNorm", "forward only"))
               for d in CONFIG["assumed"]) == 5


def test_the_cut_holds_the_parameters_the_issue_counted():
    """ISSUE 34: 187.1 M of latent attention a layer, 13.96 M of indexer, 44.0 M
    an expert, 599.3 M an expert layer with 8 held, 597.4 M the dense layer,
    231.7 M of embedding and head: 3,226 M, 6.45 GB in bfloat16."""
    model = create_model_from_registry(
        "deepseek_v32", depth=CONFIG["depth"], vocab_size=CONFIG["vocab_size"],
        experts_held=CONFIG["n_routed_experts"], first_k_dense_replace=1, mtp=0)
    ids = jax.ShapeDtypeStruct((1, 4), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids, ids)["params"]
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
    attn = shapes["layers_1"]["self_attn"]
    assert count(attn["indexer"]) == 13_959_424
    assert count(attn) - count(attn["indexer"]) == 187_107_328
    assert count(shapes["layers_1"]["shared_experts"]) == 44_040_192
    assert count(shapes["layers_1"]["moe"]["router"]) + 256 == 1_835_264
    assert count(shapes["layers_1"]) == 599_278_080 and count(shapes["layers_0"]) == 597_442_816
    assert count(shapes["embed_tokens"]) + count(shapes["lm_head"]) == 231_669_760
    assert count(shapes) == 3_226_232_064
    assert all(a.dtype == jnp.bfloat16 for path, a in
               jax.tree_util.tree_flatten_with_path(shapes)[0]
               if "e_score_correction_bias" not in str(path))
    assert "3,226,232,064" in CONFIG["assumed"][-1] and "6.45 GB" in CONFIG["assumed"][-1]


def test_lm_entry_serves_the_model_with_no_branch_on_it():
    model, params = granite_hybrid.create_lm(
        "deepseek_v32_tiny", depth=3, experts_held=4, vocab_size=128)
    assert isinstance(model, deepseek_v32.DeepseekV32LM) and model.cfg.mtp == 0
    out = pipeline.run_inference_with_lm(np.arange(40) % 128, lm=(model, params))
    assert out["logits"].shape == (1, 1, 128) and out["positions"].tolist() == [[39]]
    assert out["expert_tokens"].shape == (2, 4) and out["selected_pairs"].shape == (3, 1)
    assert "mtp_logits" not in out
    assert pipeline.lm_forward_fn(model) is pipeline.lm_forward_fn(
        dataclasses.replace(model))                                  # one function a model
    source = inspect.getsource(pipeline.run_inference_with_lm) + inspect.getsource(
        pipeline.lm_forward_fn)
    code = "\n".join(line for line in source.split('"""')[::2])       # docstrings off
    assert "deepseek" not in code and "axk1" not in code and "mtp" not in code
    # the other models of the entry still give two outputs and no third
    other, other_params = granite_hybrid.create_lm("axk1_tiny", depth=2, experts_held=4,
                                                   vocab_size=128)
    assert set(pipeline.run_inference_with_lm(np.arange(9), lm=(other, other_params))) == {
        "logits", "positions", "expert_tokens"}
