"""The Granite cell's side of the yardstick at the tiny size, and the one
`lm` adapter's: faults planted inside the two new mechanisms come out not
correct, the operation counts are the ones ISSUE 27 derived from shapes, the
weights give every stacked expert its own fan-in, the new readers find
nothing (None, never 0) in a program that has no such counter or kernel, and
the adapter holds each language model's program to its configuration file key
for key, as the three adapters it replaced did."""

import copy
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as harness
from benchmarks.layer_metrics import (expert_load_max_over_mean, flash_attn_roofline,
                                      moe_gmm_roofline)
from benchmarks.lib import flops_lm, tables, weights_lm

CELL = "granite_prefill_b1_16k"
CONFIG = tables.load("configs", "granite4h_small_ep2")
SEED = 3000000019


def _forgets_the_carried_state(monkeypatch):
    """Every chunk of the scan starts from what its own inputs left."""
    from gigapath_tpu.ops import ssd

    monkeypatch.setattr(ssd, "_advance", lambda state, decay, chunk_state: chunk_state)


def _gates_all_equal(monkeypatch):
    """The chosen experts are the right ones, each weighted 1 / k."""
    from gigapath_tpu.ops.moe import moe_layer, routing

    def flat(logits, k):
        weights, experts = routing.topk_softmax_gating(logits, k)
        return jnp.full_like(weights, 1.0 / k), experts

    monkeypatch.setattr(moe_layer, "topk_softmax_gating", flat)


@pytest.mark.parametrize("fault", [_forgets_the_carried_state, _gates_all_equal],
                         ids=["scan_carried_state", "gate_weights"])
def test_fault_inside_a_new_mechanism_comes_out_not_correct(capsys, monkeypatch, fault):
    from gigapath_tpu import pipeline

    # the entry keeps one jitted function a model: a trace made before the fault
    # was planted must not serve this run, nor this run's trace a later test
    monkeypatch.setattr(pipeline, "lm_forward_fn", pipeline.lm_forward_fn.__wrapped__)
    fault(monkeypatch)
    rc = harness.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.2",
                       "--trace", "0", "--tiny"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["failed"] == 0
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_the_counter_rides_on_the_adapter_one_array_a_request(capsys):
    ctx, driver = harness.prepare(types.SimpleNamespace(
        workload=CELL, seed=SEED, seconds=0.2, trace=0, tiny=True))
    window = driver.run(ctx)
    tiny = CONFIG["tiny"]
    received = ctx.system.kept["received"]
    assert len(received) == window["attempted"] + 2  # the two warm-up requests first
    tokens = ctx.traffic["batch"] * ctx.traffic["tokens"]
    for counts in received:
        assert counts.shape == (tiny["depth"], tiny["num_local_experts"])
        assert (counts.sum(-1) <= tokens * tiny["num_experts_per_tok"]).all()
        assert counts.sum() > 0
    load = expert_load_max_over_mean.read("expert_load_max_over_mean.lm", None, window, ctx)
    assert 1.0 <= load <= tiny["num_local_experts"]


@pytest.mark.parametrize("reader", [expert_load_max_over_mean, flash_attn_roofline,
                                    moe_gmm_roofline], ids=lambda m: m.__name__.split(".")[-1])
def test_reader_finds_nothing_in_a_program_without_the_counter_or_the_kernel(reader):
    """The parent commit's side of a traced run: no ``received`` on the
    system, no ``flash_fwd`` / ``gmm`` kernel in the trace."""
    trace = types.SimpleNamespace(kernel_seconds=lambda table: 0.0, n_devices=1)
    ctx = types.SimpleNamespace(system=object(), sizes=CONFIG, notes=[],
                                peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    window = {"attempted": 3, "items": [16384] * 3}
    name = reader.__name__.split(".")[-1] + ".lm"
    assert reader.read(name, trace, window, ctx) is None
    assert reader.read(name, None, window, ctx) is None


def test_operation_counts_are_the_issues():
    """3.46 GFLOP a token, 56.7 TFLOP a 16,384-token request, the held experts
    at 10 x 36 / 72 = 5 choices a token; the causal core as the lower
    triangle."""
    per_request = flops_lm.lm_forward_flops(CONFIG, 16384, 16)
    assert per_request == pytest.approx(56.7e12, rel=0.005)
    assert per_request / 16384 == pytest.approx(3.46e9, rel=0.005)
    d, L = 4096, 16384
    assert flops_lm.attention_core_flops(CONFIG, L) == 4 * (L * L / 2) * d  # one attention layer in ten
    assert flops_lm.attention_core_bytes(CONFIG, L) == L * (2 * d + 2 * 8 * 128) * 2
    assert flops_lm.expert_flops_per_row(CONFIG) == 2 * 3 * 4096 * 768
    held_experts_share = 5 * flops_lm.expert_flops_per_row(CONFIG) * 10
    assert held_experts_share / (per_request / L) == pytest.approx(944 / 3462, rel=0.01)
    one_pass = flops_lm.grouped_matmul_bytes(CONFIG, 0, 1)
    assert one_pass == 36 * 3 * 4096 * 768 * 2  # every held expert's matrices once
    more = dict(CONFIG, depth=20)
    assert flops_lm.lm_forward_flops(more, L, 16) > 1.99 * per_request - 2 * 16 * d * 50176 * 2


def test_stacked_experts_get_their_own_fan_in_and_the_seed_decides():
    shapes = {"moe": {"w1": jax.ShapeDtypeStruct((36, 64, 32), jnp.bfloat16),
                      "router": {"kernel": jax.ShapeDtypeStruct((64, 72), jnp.bfloat16)}},
              "norm": {"weight": jax.ShapeDtypeStruct((64,), jnp.bfloat16)},
              "A_log": jax.ShapeDtypeStruct((8,), jnp.bfloat16),
              "embed": {"embedding": jax.ShapeDtypeStruct((128, 64), jnp.bfloat16)}}
    big = 2**31 + 12345
    one, two, other = (weights_lm.make_weights(shapes, s) for s in (big, big, big - 2**31))
    for x, y in zip(jax.tree.leaves(one), jax.tree.leaves(two)):
        assert x.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))
    assert not np.array_equal(np.asarray(one["moe"]["w1"], np.float32),
                              np.asarray(other["moe"]["w1"], np.float32))
    w1 = np.asarray(one["moe"]["w1"], np.float32)
    assert w1.std() == pytest.approx(64 ** -0.5, rel=0.05)  # not (36 * 64) ** -0.5
    assert np.asarray(one["embed"]["embedding"], np.float32).std() == pytest.approx(0.08, rel=0.1)
    assert abs(float(np.asarray(one["norm"]["weight"], np.float32).mean()) - 1.0) < 0.1


# paths as the traced window of PR 27's chip run names them (standard error's notes)
_STACK = "lm_forward/GraniteHybridLM"
_PATHS = [
    (f"{_STACK}/layers_3/ssm_mixer/in_proj/dot_general", "ssm_proj"),
    (f"{_STACK}/layers_3/ssm_mixer/out_proj/dot_general", "ssm_proj"),
    (f"{_STACK}/layers_3/ssm_mixer/conv/convert_element_type", "ssd_scan"),
    (f"{_STACK}/layers_3/ssm_mixer/ssd_scan/while/body/closed_call/bchqk,bckhp->bcqhp/dot_general",
     "ssd_scan"),
    (f"{_STACK}/layers_3/ssm_mixer/gate_norm/norm/reduce_sum", "ssd_scan"),
    (f"{_STACK}/layers_3/moe/experts/kernel_fwd/gmm/pallas_call", "moe_experts"),
    (f"{_STACK}/layers_3/moe/experts/mul", "moe_experts"),
    (f"{_STACK}/layers_3/moe/router/top_k", "moe_route"),
    (f"{_STACK}/layers_3/moe/dispatch/gather", "moe_route"),
    (f"{_STACK}/layers_3/moe/combine/reduce_sum", "moe_route"),
    (f"{_STACK}/layers_5/self_attn/attn_core/kernel_fwd/flash_fwd/pallas_call", "attn"),
    (f"{_STACK}/layers_5/self_attn/q_proj/dot_general", "attn"),
    (f"{_STACK}/layers_5/shared_mlp/input_linear/dot_general", "dense"),
    (f"{_STACK}/layers_5/post_attention_layernorm/mul", "dense"),
    (f"{_STACK}/lm_head/bpd,vd->bpv/dot_general", "dense"),
    (f"{_STACK}/embed_tokens/_take/gather", "other"),
]


@pytest.mark.parametrize("path,group", _PATHS, ids=[p.split("GraniteHybridLM/")[1] for p, _ in _PATHS])
def test_scope_table_puts_each_path_in_its_group(path, group):
    from benchmarks.lib import scopes

    mixer = f"{_STACK}/layers_0/ssm_mixer/in_proj/dot_general"  # what the table requires
    reduction = scopes.ScopeReduction(
        window_s=1.0, busy_s=1.0, n_devices=1, inherited_s=0.0, no_path_s=0.0, modules={},
        parse_s=0.0, op_self_s={(path, "fusion"): 0.25, (mixer, "fusion"): 0.5})
    seconds, _ = reduction.groups(scopes.table("lm"))
    assert seconds[group] == (0.75 if group == "ssm_proj" else 0.25)
    assert sum(seconds.values()) == 0.75
    bare = dataclasses.replace(reduction, op_self_s={(path.replace("ssm_mixer", "mixer"), "fusion"): 1.0})
    if "ssm_mixer" not in path:  # a program from before the names gives nothing to read
        assert bare.groups(scopes.table("lm")) is None


def test_the_cell_lists_a_share_for_every_group_of_its_table():
    from benchmarks.lib import scopes

    cell = tables.load("workloads", CELL)
    groups = [g["name"] for g in scopes.table("lm")["groups"]]
    assert [m for m in cell["per_layer"] if m.startswith("scope_time_share.")] == [
        f"scope_time_share.{g}.lm" for g in groups]
    assert scopes.table("lm")["module"] == "jit_lm_forward"


def test_the_cell_sends_the_traffic_the_issue_named():
    """ISSUE 27's client, parameter by parameter: a scoring client is not
    shown to keep more than two documents in flight, so the count is no knob
    for steadying the check."""
    cell = tables.load("workloads", CELL)
    traffic = tables.load("traffic", cell["traffic"])
    assert {k: v for k, v in traffic.items() if k != "tiny"} == {
        "driver": "closed_loop_lm", "in_flight": 2, "batch": 1, "tokens": 16384,
        "positions": 16, "distinct_batches": 4}
    assert traffic["tiny"] == {"batch": 2, "tokens": 77, "positions": 4, "distinct_batches": 2}
    assert cell["chips"] == 1 and "2 in flight" in cell["why"]


# What the three adapters before PR 38 checked between the program they built
# and the configuration file (their ``_BUILT``, the share's published counts,
# the YaRN block, Granite's one group and ``layer_types``, DeepSeek's bias
# leaf): a record for today's three configurations, which a new one does not
# extend. A key dropped from a file's ``share`` / ``built`` / ... fails here.
_ROPE = [f"rope_{k}" for k in ("factor", "original_max_position_embeddings", "beta_fast",
                               "beta_slow", "mscale", "mscale_all_dim")]
_MLA = ["hidden_size", "num_hidden_layers", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "intermediate_size",
        "moe_intermediate_size", "num_experts_per_tok", "n_group", "topk_group",
        "routed_scaling_factor", "n_shared_experts", "first_k_dense_replace", "rope_theta",
        "rms_norm_eps", "vocab_size", "depth", "expert_offset", "experts_held", "n_routed_experts"]
CHECKED = {
    "granite4h_small_ep2": [
        "hidden_size", "num_attention_heads", "num_key_value_heads", "intermediate_size",
        "shared_intermediate_size", "num_experts_per_tok", "mamba_n_heads", "mamba_d_head",
        "mamba_d_state", "mamba_d_conv", "mamba_chunk_size", "attention_multiplier",
        "embedding_multiplier", "logits_scaling", "residual_multiplier", "rms_norm_eps",
        "vocab_size", "depth", "expert_offset", "experts_held", "num_local_experts",
        "layer_types", "mamba_n_groups"],
    "axk1_ep16": _MLA + _ROPE,
    "deepseek_v32_ep32": _MLA + _ROPE + [
        "index_n_heads", "index_head_dim", "index_topk", "mtp", "num_nextn_predict_layers",
        "e_score_correction_bias"],
}


def _other(value):
    if isinstance(value, tuple):
        return tuple(reversed(value))
    return value + 1 if isinstance(value, int) else value * 2 + 1


@pytest.mark.parametrize("config,key", [(c, k) for c, keys in CHECKED.items() for k in keys])
def test_the_one_adapter_checks_every_key_the_three_checked(monkeypatch, config, key):
    """The program built with one field other than the file states (or the
    file stating a form the program lacks, or a layer without the leaf) is
    refused by name; as built, the tiny preset passes."""
    from benchmarks.systems.lm import System
    from gigapath_tpu.utils import registry

    stated = tables.load("configs", config)
    assert stated["system"] == "lm"
    System(stated, tiny=True)
    if key == "mamba_n_groups":
        stated = dict(stated, tiny=dict(stated["tiny"], mamba_n_groups=2))
    elif key == "e_score_correction_bias":
        shapes = System.param_shapes

        def without_bias(self):
            tree = shapes(self)
            layer = tree["layers_1"]
            return dict(tree, layers_1=dict(layer, moe={
                k: v for k, v in layer["moe"].items() if k != key}))

        monkeypatch.setattr(System, "param_shapes", without_bias)
    else:
        build = registry.create_model_from_registry

        def off_by_one_field(arch, **share):
            model = build(arch, **share)
            cfg = copy.copy(model.cfg)
            object.__setattr__(cfg, key, _other(getattr(cfg, key)))
            return model.clone(cfg=cfg)

        monkeypatch.setattr(registry, "create_model_from_registry", off_by_one_field)
    with pytest.raises(ValueError, match=key):
        System(stated, tiny=True)


LM_CELLS = [w["name"] for w in tables.manifest()["workloads"]
            if tables.load("configs", w["config"])["system"] == "lm"]


@pytest.mark.parametrize("cell", LM_CELLS)
def test_every_reader_of_the_adapters_outputs_finds_them(cell):
    """Each layer metric of the cell whose reader takes something from the
    system reads a number from the one adapter's tiny window, given a trace in
    which every kernel took a second and the v5e's peaks: a reader that looks
    for an output under another name would leave its metric out in silence."""
    import importlib
    import inspect

    ctx, driver = harness.prepare(types.SimpleNamespace(
        workload=cell, seed=SEED, seconds=0.2, trace=0, tiny=True))
    window = driver.run(ctx)
    ctx.peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = types.SimpleNamespace(kernel_seconds=lambda table: 1.0, n_devices=1)
    read = 0
    for name in ctx.cell["per_layer"]:
        module = importlib.import_module("benchmarks.layer_metrics." + name.split(".")[0])
        if "ctx.system" in inspect.getsource(module):
            assert module.read(name, trace, window, ctx) is not None, name
            read += 1
    assert read >= 1
