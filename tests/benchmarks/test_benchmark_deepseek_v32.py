"""The DeepSeek-V3.2 cell's side of the yardstick at the tiny size: the
rehearsal is correct and its fp8 control is not; a fault planted inside each
new mechanism (a dense core in the selected one's place, a selection one key
short, the indexer's rotation paired as latent attention's, its scores ranked
upside down, its head weights left out) is caught, by ``correct`` or by
``index_selected_share`` (the gate's bias and its two-best group ranking move
too few choices at the bias the benchmark draws to pass a limit at this size:
``tests/test_deepseek_v32.py`` holds them to a per-token loop); the
counters ride on the adapter and the weight maker scales the selection
bias as PR 34's adapter did; the scope table puts each path in its group and
the cell lists a share for every group; the operation counts are a hand count;
the cell sends the traffic ISSUE 34 names; and the new readers find nothing
(None, never 0) in a program that has no such counter or kernel."""

import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as harness
from benchmarks.layer_metrics import (index_score_roofline, index_selected_share,
                                      sparse_attn_roofline)
from benchmarks.lib import flops_deepseek_v32 as flops
from benchmarks.lib import tables

CELL = "dsv32_prefill_b1_16k"
CONFIG = tables.load("configs", "deepseek_v32_ep32")
TINY = CONFIG["tiny"]
SEED = 3000000019
MANIFEST = tables.manifest()


def _run(capsys, monkeypatch, seed=SEED):
    from gigapath_tpu import pipeline

    # the entry keeps one jitted function a model: a trace made before a fault
    # was planted must not serve this run, nor this run's trace a later test
    monkeypatch.setattr(pipeline, "lm_forward_fn", pipeline.lm_forward_fn.__wrapped__)
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.2",
                       "--trace", "0", "--tiny"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["failed"] == 0
    return line


def _window(monkeypatch, seed=SEED):
    from gigapath_tpu import pipeline

    monkeypatch.setattr(pipeline, "lm_forward_fn", pipeline.lm_forward_fn.__wrapped__)
    ctx, driver = harness.prepare(types.SimpleNamespace(
        workload=CELL, seed=seed, seconds=0.2, trace=0, tiny=True))
    return ctx, driver, driver.run(ctx)


@pytest.mark.parametrize("seed", [SEED, 7])
def test_rehearsal_is_correct_and_reports_the_cells_rate(capsys, monkeypatch, seed):
    line = _run(capsys, monkeypatch, seed)
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["metrics"]) == {"slide_tokens_per_s", "setup_s"}
    assert list(line["checks"]) == ["embed_gap_mean"]
    assert line["checks"]["embed_gap_mean"]["limit"] == tables.load("workloads", CELL)[
        "correct"]["tiny_limits"]["embed_gap_mean"]


def test_fp8_control_in_the_programs_place_is_not_correct(monkeypatch):
    """The reference one precision down (every matrix product, the indexer's
    among them, with operands rounded to float8) reads past the limit the
    program stays under; bfloat16, what the configuration states, does not."""
    ctx, driver, window = _window(monkeypatch)
    limit = ctx.cell["correct"]["tiny_limits"]["embed_gap_mean"]
    assert ctx.cell["correct"]["control"] == "fp8"
    assert driver.check(ctx, window)["embed_gap_mean"] < limit
    assert driver.check(ctx, window, stand_in="bf16")["embed_gap_mean"] < limit
    assert driver.check(ctx, window, stand_in="fp8")["embed_gap_mean"] > 1.15 * limit


def _attends_densely(monkeypatch):
    """Every earlier key in every query's set: a dense causal core in the
    selected one's place."""
    from gigapath_tpu.ops import sparse_index

    def everything(scores, topk, **kw):
        L = scores.shape[1]
        return jnp.broadcast_to(jnp.tril(jnp.ones((L, L), jnp.int8)), scores.shape)

    monkeypatch.setattr(sparse_index, "select_topk", everything)


def _selects_one_key_fewer(monkeypatch):
    from gigapath_tpu.ops import sparse_index

    select = sparse_index.select_topk
    monkeypatch.setattr(sparse_index, "select_topk",
                        lambda scores, topk, **kw: select(scores, topk - 1, **kw))


def _rotates_the_indexer_as_latent_attention_does(monkeypatch):
    from gigapath_tpu.ops import rope

    monkeypatch.setattr(rope, "apply_rope_halfsplit", rope.apply_rope_interleaved)


def _keeps_the_worst_keys(monkeypatch):
    """The selection ranks the index scores upside down."""
    from gigapath_tpu.ops import sparse_index

    scores = sparse_index.index_scores
    monkeypatch.setattr(sparse_index, "index_scores", lambda q, k, w, **kw: -scores(q, k, w, **kw))


def _leaves_the_head_weights_out(monkeypatch):
    """Every index head counts alike: ``sum_h relu(q_h . k)``."""
    from gigapath_tpu.ops import sparse_index

    scores = sparse_index.index_scores
    monkeypatch.setattr(sparse_index, "index_scores",
                        lambda q, k, w, **kw: scores(q, k, jnp.ones_like(w), **kw))


_FAULTS = {
    "dense_core": (_attends_densely, "count"),
    "one_key_fewer": (_selects_one_key_fewer, "count"),
    "indexer_rotation": (_rotates_the_indexer_as_latent_attention_does, "correct"),
    "worst_keys": (_keeps_the_worst_keys, "correct"),
    "head_weights": (_leaves_the_head_weights_out, "correct"),
}


@pytest.mark.parametrize("fault", list(_FAULTS))
def test_fault_inside_a_new_mechanism_is_caught(monkeypatch, fault):
    """``correct`` catches what moves the logits far enough; what it cannot see
    at this size (16 of at most 77 keys dropped or kept changes late rows only)
    the program's own counter does: ``index_selected_share`` is off its exact
    value."""
    plant, by = _FAULTS[fault]
    plant(monkeypatch)
    ctx, driver, window = _window(monkeypatch)
    gap = driver.check(ctx, window)["embed_gap_mean"]
    share = index_selected_share.read("index_selected_share.dsv32", None, window, ctx)
    n = ctx.traffic["tokens"]
    exact = flops.selected_pairs(TINY, n) / flops.causal_pairs(n)
    if by == "count":
        assert abs(share - exact) > 0.01
        assert share == pytest.approx(
            1.0 if fault == "dense_core" else
            flops.selected_pairs(dict(TINY, index_topk=TINY["index_topk"] - 1), n)
            / flops.causal_pairs(n))
    else:
        assert share == pytest.approx(exact, abs=1e-12)
        assert gap > ctx.cell["correct"]["tiny_limits"]["embed_gap_mean"]


def test_the_counters_ride_on_the_adapter(monkeypatch):
    ctx, driver, window = _window(monkeypatch)
    kept = ctx.system.kept
    layers = TINY["depth"] + TINY["num_nextn_predict_layers"]          # the module's layer last
    tokens = ctx.traffic["batch"] * ctx.traffic["tokens"]
    assert len(kept["received"]) == len(kept["selected_pairs"]) == len(kept["mtp_logits"]) \
        == window["attempted"] + 2                                       # the two warm-up requests first
    for counts, pairs, mtp in zip(kept["received"], kept["selected_pairs"], kept["mtp_logits"]):
        assert counts.shape == (layers - TINY["first_k_dense_replace"], TINY["n_routed_experts"])
        assert pairs.shape == (layers, ctx.traffic["batch"]) and pairs.dtype == np.int32
        assert (pairs == flops.selected_pairs(TINY, ctx.traffic["tokens"])).all()
        assert mtp.shape == (ctx.traffic["batch"], ctx.traffic["positions"], TINY["vocab_size"])
        assert (counts.sum(-1) <= tokens * TINY["num_experts_per_tok"]).all() and counts.sum() > 0
    share = index_selected_share.read("index_selected_share.dsv32", None, window, ctx)
    assert share == pytest.approx(1112 / 3003, abs=1e-12)               # 16 of 77: by hand


def test_the_weight_maker_scales_the_selection_bias_as_the_adapter_did():
    """``lib/weights_lm.py``'s rule for ``e_score_correction_bias`` gives, bit
    for bit, what PR 34's adapter handed program and reference: the leaf drawn
    as an unnamed one, then times 0.04 in its own dtype; every other leaf is
    its rule's draw, untouched (ISSUE 38)."""
    from benchmarks.lib import weights_lm
    from benchmarks.systems.lm import System

    shapes = System(CONFIG, tiny=True).param_shapes()
    made = jax.tree.leaves(weights_lm.make_weights(shapes, SEED))
    key = jax.random.fold_in(jax.random.PRNGKey(SEED & 0x7FFFFFFF), SEED >> 31)
    scaled = 0
    for i, (path, leaf) in enumerate(jax.tree_util.tree_flatten_with_path(shapes)[0]):
        name = str(path[-1].key)
        drawn = weights_lm._fill(key, i, name if name in weights_lm._NAMED else "other",
                                 tuple(leaf.shape), jnp.dtype(leaf.dtype))
        if name == "e_score_correction_bias":
            assert made[i].dtype == jnp.float32 and 0.2 < float(jnp.std(drawn)) < 1.0
            drawn, scaled = drawn * 0.04, scaled + 1
        assert np.array_equal(np.asarray(made[i], np.float32), np.asarray(drawn, np.float32))
    # the expert layers of the stack, and the prediction module's
    assert scaled == TINY["depth"] - TINY["first_k_dense_replace"] + TINY["num_nextn_predict_layers"]
    assert weights_lm.BIAS_SCALE == 0.04


def test_the_adapter_holds_the_program_to_the_file():
    from benchmarks.systems.lm import System

    system = System(CONFIG, tiny=True)
    assert system.model.cfg.index_topk == TINY["index_topk"] and system.model.cfg.mtp == 1
    for key, value in (("index_topk", 8), ("index_n_heads", 2), ("num_nextn_predict_layers", 0),
                       ("first_k_dense_replace", 2)):
        wrong = dict(CONFIG, tiny=dict(TINY, **{key: value}))
        if key in ("num_nextn_predict_layers", "first_k_dense_replace"):
            assert System(wrong, tiny=True).model.cfg.mtp in (0, 1)      # a share, built as stated
            continue
        with pytest.raises(ValueError, match=key):
            System(wrong, tiny=True)


@pytest.mark.parametrize("reader", [index_score_roofline, sparse_attn_roofline,
                                    index_selected_share],
                         ids=lambda m: m.__name__.split(".")[-1])
def test_reader_finds_nothing_in_a_program_without_the_counter_or_the_kernel(reader):
    """The parent commit's side of a traced run (no ``selected`` on the system,
    no such kernel in the trace), and another system's cell."""
    trace = types.SimpleNamespace(kernel_seconds=lambda table: 0.0, n_devices=1)
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    window = {"attempted": 3, "items": [16384] * 3, "work": 3 * 16384}
    name = reader.__name__.split(".")[-1] + ".dsv32"
    for sizes in (CONFIG, tables.load("configs", "axk1_ep16")):
        ctx = types.SimpleNamespace(system=object(), sizes=sizes, notes=[], peaks=peaks)
        assert reader.read(name, trace, window, ctx) is None
        assert reader.read(name, None, window, ctx) is None


def test_rooflines_read_selected_and_causal_pairs_and_cannot_pass_100():
    """A kernel that took exactly the least time reads 100; the core's count is
    of selected pairs, so a dense causal core at the chip's peak reads 23.4."""
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    window = {"attempted": 2, "items": [16384] * 2, "work": 2 * 16384}
    for reader, per_layer, table in (
            (index_score_roofline, flops.index_score_flops_per_layer, "index_score_by_name"),
            (sparse_attn_roofline, flops.sparse_core_flops_per_layer, "sparse_attn_by_name")):
        least = 2 * 5 * per_layer(CONFIG, 16384) / peaks["flops_per_s"]
        seen = []
        trace = types.SimpleNamespace(
            kernel_seconds=lambda t: (seen.append(t), least)[1], n_devices=1)
        ctx = types.SimpleNamespace(system=object(), sizes=CONFIG, notes=[], peaks=peaks)
        assert reader.read("x.dsv32", trace, window, ctx) == pytest.approx(100.0)
        assert seen == [tables.kernel_table(table)] and "compute" in ctx.notes[0]
    dense_core_s = 2 * 5 * 2.0 * 128 * 320 * flops.causal_pairs(16384) / peaks["flops_per_s"]
    trace = types.SimpleNamespace(kernel_seconds=lambda t: dense_core_s, n_devices=1)
    ctx = types.SimpleNamespace(system=object(), sizes=CONFIG, notes=[], peaks=peaks)
    assert sparse_attn_roofline.read("x.dsv32", trace, window, ctx) == pytest.approx(23.437, abs=0.001)


def test_the_anchored_tables_take_a_kernel_and_not_its_reader():
    from benchmarks.lib.trace import TraceReduction

    ops = {"%index_score.3 = f32[1,16384,16384]{2,1,0} custom-call(%q, %k, %w)": 2.0,
           "  ROOT %index_select.1 = s8[1,16384,16384]{2,1,0} custom-call(%index_score.3)": 1.0,
           "%sparse_attn.2 = bf16[1,128,16384,128]{3,2,1,0} custom-call(%a, %b, %c, %index_select.1)": 4.0,
           "%fusion.9 = s32[] fusion(%index_select.1)": 8.0,
           "%flash_fwd.4 = (bf16[8]) custom-call(%a, %b, %c)": 16.0}
    reduction = TraceReduction(1.0, 1.0, 1, ops, ops, {}, [])
    assert reduction.kernel_seconds(tables.kernel_table("index_score_by_name")) == 2.0
    assert reduction.kernel_seconds(tables.kernel_table("index_select_by_name")) == 1.0
    assert reduction.kernel_seconds(tables.kernel_table("sparse_attn_by_name")) == 4.0


def test_operation_counts_are_a_hand_count():
    """77.3 TFLOP a 16,384-token request: five layers of latent projections
    (6.13), indexer projections (0.457), index scores over the causal pairs
    (2.199) and the core over the selected ones (2.577); a dense MLP (12.99);
    four expert layers at 8 x 8 / 256 = 0.25 choices a token beside a shared
    expert."""
    d, L, H = 7168, 16384, 128
    projections = 2 * (d * 1536 + 1536 * H * 192 + d * (512 + 64) + 512 * H * 256 + H * 128 * d)
    assert projections == 2 * 187_105_280
    indexer = 2 * (1536 * 64 * 128 + d * 128 + d * 64)
    assert flops.indexer_projection_flops_per_token(CONFIG) == indexer == 2 * 13_959_168
    causal = L * (L + 1) // 2
    selected = sum(min(t + 1, 2048) for t in range(L))
    assert flops.causal_pairs(L) == causal == 134_225_920
    assert flops.selected_pairs(CONFIG, L) == selected == 31_458_304
    assert selected / causal == pytest.approx(0.23437, abs=1e-5)
    assert flops.selected_pairs(CONFIG, 1000) == 1000 * 1001 // 2       # under index_topk: dense
    assert flops.index_score_flops_per_layer(CONFIG, L) == 2 * 64 * 128 * causal
    assert flops.sparse_core_flops_per_layer(CONFIG, L) == 2 * H * 320 * selected
    assert flops.index_score_bytes_per_layer(CONFIG, L) == L * 65 * 128 * 2 + L * 64 * 4 + causal * 4
    assert flops.sparse_core_bytes_per_layer(CONFIG, L) == L * H * (2 * 192 + 2 * 128) * 2 + causal
    dense = 2 * 3 * d * 18432
    expert = 2 * 3 * d * 2048
    moe = 2 * d * 256 + 0.25 * expert + expert
    pairs = 2 * 64 * 128 * causal + 2 * H * 320 * selected
    head = 2 * 16 * d * 16160
    per_request = flops.lm_forward_flops(CONFIG, L, 16)
    assert per_request == pytest.approx(
        L * (5 * (projections + indexer) + dense + 4 * moe) + 5 * pairs + head, rel=1e-12)
    assert per_request == pytest.approx(77.27e12, rel=0.001)
    assert L * projections == pytest.approx(6.131e12, rel=1e-3)
    assert 5 * pairs / per_request == pytest.approx(0.309, abs=0.002)
    # the prediction module: its projection, one more expert layer with its attention, the head again
    with_module = dict(CONFIG, num_nextn_predict_layers=1)
    assert flops.lm_forward_flops(with_module, L, 16) - per_request == pytest.approx(
        L * (projections + indexer + moe + 2 * 2 * d * d) + pairs + head, rel=1e-12)
    assert flops.attention_layers(with_module) == 6 and flops.expert_layers(with_module) == 5
    more = dict(CONFIG, depth=9)
    assert flops.lm_forward_flops(more, L, 16) - per_request == pytest.approx(
        4 * (L * (projections + indexer + moe) + pairs), rel=1e-12)


# paths as a compiled program names them (models/deepseek_v32.py's scopes under jit_lm_forward)
_STACK = "lm_forward/DeepseekV32LM"
_PATHS = [
    (f"{_STACK}/layers_3/self_attn/indexer/wq_b/dot_general", "indexer"),
    (f"{_STACK}/layers_3/self_attn/indexer/wk/dot_general", "indexer"),
    (f"{_STACK}/layers_3/self_attn/indexer/weights_proj/dot_general", "indexer"),
    (f"{_STACK}/layers_3/self_attn/indexer/k_norm/rsqrt", "indexer"),
    (f"{_STACK}/layers_3/self_attn/indexer/rope/concatenate", "indexer"),
    (f"{_STACK}/layers_3/self_attn/indexer/score/kernel_fwd/index_score/pallas_call", "indexer"),
    (f"{_STACK}/layers_3/self_attn/indexer/score/kernel_fwd/transpose", "indexer"),
    (f"{_STACK}/layers_3/self_attn/select/kernel_fwd/index_select/pallas_call", "select"),
    (f"{_STACK}/layers_3/self_attn/select/reduce_sum", "select"),
    (f"{_STACK}/layers_3/self_attn/attn_core/kernel_fwd/sparse_attn/pallas_call", "attn_core"),
    (f"{_STACK}/layers_3/self_attn/attn_core/kernel_fwd/transpose", "attn_core"),
    (f"{_STACK}/layers_3/self_attn/rope/concatenate", "rope"),
    (f"{_STACK}/layers_3/self_attn/q_a_proj/dot_general", "mla_proj"),
    (f"{_STACK}/layers_3/self_attn/q_b_proj/dot_general", "mla_proj"),
    (f"{_STACK}/layers_0/self_attn/kv_a_proj_with_mqa/dot_general", "mla_proj"),
    (f"{_STACK}/layers_3/self_attn/kv_b_proj/dot_general", "mla_proj"),
    (f"{_STACK}/layers_3/self_attn/o_proj/dot_general", "mla_proj"),
    (f"{_STACK}/mtp_layer/self_attn/indexer/score/kernel_fwd/index_score/pallas_call", "indexer"),
    (f"{_STACK}/mtp_layer/self_attn/o_proj/dot_general", "mla_proj"),
    (f"{_STACK}/layers_3/moe/experts/kernel_fwd/gmm/pallas_call", "moe_experts"),
    (f"{_STACK}/layers_3/moe/router/top_k", "moe_route"),
    (f"{_STACK}/layers_3/moe/router/add", "moe_route"),
    (f"{_STACK}/mtp_layer/moe/combine/kernel_fwd/_combine_call/moe_combine/pallas_call", "moe_route"),
    (f"{_STACK}/layers_3/shared_experts/input_linear/dot_general", "dense"),
    (f"{_STACK}/layers_0/mlp/output_linear/dot_general", "dense"),
    (f"{_STACK}/mtp_layer/post_attention_layernorm/mul", "dense"),
    (f"{_STACK}/mtp/mtp_eh_proj/dot_general", "dense"),
    (f"{_STACK}/lm_head/lm_head/bpd,dv->bpv/dot_general", "dense"),
    (f"{_STACK}/embed_tokens/_take/gather", "other"),
    (f"{_STACK}/rope/cos", "other"),
]


@pytest.mark.parametrize("path,group", _PATHS, ids=[p.split("DeepseekV32LM/")[1] for p, _ in _PATHS])
def test_scope_table_puts_each_path_in_its_group(path, group):
    from benchmarks.lib import scopes

    required = f"{_STACK}/layers_0/self_attn/indexer/wk/dot_general"   # what the table requires
    reduction = scopes.ScopeReduction(
        window_s=1.0, busy_s=1.0, n_devices=1, inherited_s=0.0, no_path_s=0.0, modules={},
        parse_s=0.0, op_self_s={(path, "fusion"): 0.25, (required, "custom-call"): 0.5})
    seconds, _ = reduction.groups(scopes.table("dsv32"))
    assert seconds[group] == (0.75 if group == "indexer" else 0.25)
    assert sum(seconds.values()) == 0.75
    bare = dataclasses.replace(
        reduction, op_self_s={(path.replace("/indexer/", "/ix/"), "fusion"): 1.0})
    if "/indexer/" in path:   # a program without the indexer (A.X-K1's, the parent's): nothing to read
        assert bare.groups(scopes.table("dsv32")) is None


def test_the_program_opens_the_scopes_the_table_reads(monkeypatch):
    """The tiny program's lowered text: each step of the new attention under the
    scope its group matches on, in the stack's layers and in the prediction
    module's; on the kernel tier each new kernel by its name (two dense layers
    there: the tiny experts are too narrow for the grouped product)."""
    import gigapath_tpu.ops.flash_attention as fa
    from gigapath_tpu.utils.registry import create_model_from_registry
    import gigapath_tpu.models.deepseek_v32  # noqa: F401

    def lowered(**share):
        model = create_model_from_registry("deepseek_v32_tiny", experts_held=4, vocab_size=128,
                                           **share)
        ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids, ids)["params"]
        return jax.jit(lambda p, i, q: model.apply({"params": p}, i, q)).lower(
            shapes, ids, jax.ShapeDtypeStruct((1, 4), jnp.int32)).as_text(debug_info=True)

    text = lowered(depth=2, mtp=1)
    for layer in ("layers_0", "layers_1", "mtp_layer"):
        for scope in ("indexer/score/kernel_fwd", "select/kernel_fwd", "attn_core/kernel_fwd",
                      "indexer/wq_b", "indexer/k_norm", "indexer/rope", "rope", "q_b_proj"):
            assert f"{layer}/self_attn/{scope}" in text, (layer, scope)
    assert "/mtp/mtp_eh_proj" in text and "mtp_layer/moe/router" in text
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "PALLAS_MIN_SEQ", 128)
    with pltpu.force_tpu_interpret_mode():
        text = lowered(depth=2, first_k_dense_replace=2, mtp=0)
    for layer in ("layers_0", "layers_1"):
        for scope in ("indexer/score/kernel_fwd/index_score", "select/kernel_fwd/index_select",
                      "attn_core/kernel_fwd/sparse_attn"):
            assert f"{layer}/self_attn/{scope}" in text, (layer, scope)
    assert "flash_fwd" not in text


def test_the_cell_lists_a_share_for_every_group_of_its_table():
    from benchmarks.lib import scopes

    cell = tables.load("workloads", CELL)
    groups = [g["name"] for g in scopes.table("dsv32")["groups"]]
    assert groups == ["indexer", "select", "attn_core", "rope", "mla_proj", "moe_experts",
                      "moe_route", "dense", "other"]
    assert [m for m in cell["per_layer"] if m.startswith("scope_time_share.")] == [
        f"scope_time_share.{g}.dsv32" for g in groups]
    assert scopes.table("dsv32")["module"] == "jit_lm_forward"
    assert all(m.endswith(".dsv32") for m in cell["per_layer"])
    for name in ("step_mfu.dsv32", "index_score_roofline.dsv32", "sparse_attn_roofline.dsv32",
                 "expert_gmm_roofline.dsv32", "index_selected_share.dsv32", "held_rows_share.dsv32",
                 "expert_load_max_over_mean.dsv32", "window_compiles.dsv32",
                 "device_idle_share.dsv32"):
        assert name in cell["per_layer"]
    listed = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in cell["per_layer"]:
        assert listed[name]["workloads"] == [CELL] and listed[name]["moves"] == "slide_tokens_per_s"
    names = [m["name"] for m in MANIFEST["per_layer"]]           # one run, in the file's order
    start = names.index(cell["per_layer"][0])
    assert names[start:start + len(cell["per_layer"])] == cell["per_layer"]


def test_the_cell_sends_the_traffic_the_issue_named():
    """ISSUE 34: the traffic file that is there, so that the three language-model
    cells differ by the model alone; the manifest lists the cell as its file
    has it (a later cell's entries come after, so no position is asserted)."""
    cell = tables.load("workloads", CELL)
    assert cell["traffic"] == "closed_ids_b1_16k" == tables.load(
        "workloads", "axk1_prefill_b1_16k")["traffic"]
    traffic = tables.load("traffic", cell["traffic"])
    assert {k: v for k, v in traffic.items() if k != "tiny"} == {
        "driver": "closed_loop_lm", "in_flight": 2, "batch": 1, "tokens": 16384,
        "positions": 16, "distinct_batches": 4}
    assert traffic["tiny"]["tokens"] > TINY["index_topk"]               # the selection bites in the rehearsal
    assert cell["chips"] == 1 and cell["config"] == "deepseek_v32_ep32"
    assert "2 in flight" in cell["why"] and "32x" in cell["why"] and "5 layers" in cell["why"]
    assert len(cell["why"]) <= 200 and "1/32" in cell["why_long"]
    assert cell["end_to_end"] == {"rate": "slide_tokens_per_s"}
    assert cell["correct"]["control"] == "fp8" and cell["correct"]["rows"] == 16
    assert cell["correct"]["requests"] == 2
    assert {k: cell[k] for k in ("name", "config", "traffic", "chips", "why")} in MANIFEST["workloads"]
    assert "deepseek_v32_ep32" in [c["name"] for c in MANIFEST["configs"]]
    rate = next(m for m in MANIFEST["end_to_end"] if m["name"] == "slide_tokens_per_s")
    assert CELL in rate["workloads"] and rate["bound"] == 0.02
    # the floors of a model_config cut: four expert layers after the dense one, 8 experts, an eighth
    assert CONFIG["depth"] - CONFIG["first_k_dense_replace"] == 4 and CONFIG["n_routed_experts"] == 8
    assert CONFIG["vocab_size"] == 16160 == CONFIG["published"]["vocab_size"] // 8
    # every number of the catalog's entry stands under its key, but for the five that are reduced
    published = {"first_k_dense_replace": 3, "n_routed_experts": 256, "vocab_size": 129280,
                 "num_nextn_predict_layers": 1, "depth": 61}
    assert CONFIG["published"] == published
    assert (CONFIG["num_attention_heads"], CONFIG["index_n_heads"], CONFIG["index_head_dim"],
            CONFIG["index_topk"], CONFIG["hidden_size"], CONFIG["num_hidden_layers"]) == (
        128, 64, 128, 2048, 7168, 61)
