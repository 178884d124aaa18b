"""The A.X-K1 cell's side of the yardstick at the tiny size: a fault planted
inside each new mechanism (the rotary pairing, the group ranking, the shared
rotary key, YaRN's factor in the softmax scale) comes out not correct, the
scope table puts each path in its group and the cell lists a share for every
group, the operation counts are a hand count, the cell sends the traffic
ISSUE 32 names, and the new readers find nothing (None, never 0) in a program
that has no such counter or kernel."""

import dataclasses
import json
import types

import jax.numpy as jnp
import pytest

from benchmarks import run as harness
from benchmarks.layer_metrics import expert_gmm_roofline, held_rows_share, mla_attn_roofline
from benchmarks.lib import flops_axk1, tables

CELL = "axk1_prefill_b1_16k"
CONFIG = tables.load("configs", "axk1_ep16")
SEED = 3000000019


def _pairs_the_halves(monkeypatch):
    """Feature i turns with feature i + d/2 (the half-split pairing of other
    families), not with its neighbour."""
    from gigapath_tpu.ops import rope

    def halves(x, cos, sin):
        d = x.shape[-1] // 2
        first, second = x[..., :d].astype(jnp.float32), x[..., d:].astype(jnp.float32)
        cos, sin = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([first * cos - second * sin, first * sin + second * cos],
                               axis=-1).astype(x.dtype)

    monkeypatch.setattr(rope, "apply_rope_interleaved", halves)


def _ranks_no_groups(monkeypatch):
    """The k best scores of all experts, whatever their group."""
    import jax

    from gigapath_tpu.ops.moe import routing

    def ungrouped(self, logits, k):
        values, experts = jax.lax.top_k(jax.nn.sigmoid(logits.astype(jnp.float32)), k)
        return values / values.sum(-1, keepdims=True) * self.scale, experts.astype(jnp.int32)

    monkeypatch.setattr(routing.GroupLimitedSigmoidGate, "__call__", ungrouped)


def _gives_the_rotary_key_to_one_head(monkeypatch):
    """Head 0 reads the rotary key; the others read nothing in its place."""
    from gigapath_tpu.models import axk1

    core = axk1._causal_core

    def unshared(q, k, v, *, scale):
        nope = CONFIG["tiny"]["qk_nope_head_dim"]  # the run is the tiny preset's
        return core(q, k.at[:, :, 1:, nope:].set(0), v, scale=scale)

    monkeypatch.setattr(axk1, "_causal_core", unshared)


def _drops_mscale_from_the_scale(monkeypatch):
    """The softmax scale is (nope + rope) ** -0.5 and no more."""
    from gigapath_tpu.ops import rope

    monkeypatch.setattr(rope, "yarn_mscale", lambda factor, mscale: 1.0)


@pytest.mark.parametrize(
    "fault", [_pairs_the_halves, _ranks_no_groups, _gives_the_rotary_key_to_one_head,
              _drops_mscale_from_the_scale],
    ids=["rope_pairing", "group_ranking", "shared_rotary_key", "mscale_squared"])
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


def test_the_counters_ride_on_the_adapter(capsys):
    ctx, driver = harness.prepare(types.SimpleNamespace(
        workload=CELL, seed=SEED, seconds=0.2, trace=0, tiny=True))
    window = driver.run(ctx)
    tiny = CONFIG["tiny"]
    received = ctx.system.kept["received"]
    assert len(received) == window["attempted"] + 2  # the two warm-up requests first
    tokens = ctx.traffic["batch"] * ctx.traffic["tokens"]
    for counts in received:  # the expert layers only: the dense layer routes nothing
        assert counts.shape == (tiny["depth"] - tiny["first_k_dense_replace"], tiny["n_routed_experts"])
        assert (counts.sum(-1) <= tokens * tiny["num_experts_per_tok"]).all() and counts.sum() > 0
    share = held_rows_share.read("held_rows_share.axk1", None, window, ctx)
    served = received[-window["attempted"]:]
    assert share == pytest.approx(
        sum(int(r.sum()) for r in served) / (window["work"] * tiny["num_experts_per_tok"] * 2))
    assert 0.05 < share < 0.6  # 4 of 16 held: a quarter, if the router were even


@pytest.mark.parametrize("reader", [mla_attn_roofline, expert_gmm_roofline, held_rows_share],
                         ids=lambda m: m.__name__.split(".")[-1])
def test_reader_finds_nothing_in_a_program_without_the_counter_or_the_kernel(reader):
    """The parent commit's side of a traced run: no ``received`` on the
    system, no ``flash_fwd`` / ``gmm`` kernel in the trace."""
    trace = types.SimpleNamespace(kernel_seconds=lambda table: 0.0, n_devices=1)
    ctx = types.SimpleNamespace(system=object(), sizes=CONFIG, notes=[],
                                peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    window = {"attempted": 3, "items": [16384] * 3, "work": 3 * 16384}
    name = reader.__name__.split(".")[-1] + ".axk1"
    assert reader.read(name, trace, window, ctx) is None
    assert reader.read(name, None, window, ctx) is None


def test_the_anchored_table_takes_a_product_and_not_its_reader():
    """``kernels/expert_gmm_by_name.json`` (PERF.md §7 ix): the custom call named
    ``%gmm.<n>`` counts, one that reads such a result does not."""
    from benchmarks.lib.trace import TraceReduction

    ops = {"%gmm.7 = bf16[131072,4096]{1,0} custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"": 2.0,
           "  ROOT %gmm.9 = bf16[131072,7168]{1,0} custom-call(%c, %d)": 1.0,
           "%moe_combine.3 = bf16[16384,7168]{1,0} custom-call(%gmm.9, %p)": 4.0,
           "%fusion.2 = bf16[8,8]{1,0} fusion(%gmm.7)": 8.0}
    reduction = TraceReduction(1.0, 1.0, 1, ops, ops, {}, [])
    assert reduction.kernel_seconds(tables.kernel_table("expert_gmm_by_name")) == 3.0
    assert reduction.kernel_seconds(tables.kernel_table("moe_gmm_by_name")) == 7.0  # the unanchored one


def test_operation_counts_are_a_hand_count():
    """4.69 GFLOP a token, 76.9 TFLOP a 16,384-token request: latent attention
    69 % of it (its core alone 43 %), the held experts at 8 x 12 / 192 = 0.5
    choices a token."""
    d, L, H = 7168, 16384, 64
    projections = 2 * (d * 1536 + 1536 * H * 192 + d * (512 + 64) + 512 * H * 256 + H * 128 * d)
    assert projections == 2 * 101_122_048  # q_a 11.01 M, q_b 18.87, kv_a 4.13, kv_b 8.39, o 58.72
    assert flops_axk1.attention_projection_flops_per_token(CONFIG) == projections
    core = 2 * H * (192 + 128) * L / 2
    dense = 2 * 3 * d * 18432
    expert = 2 * 3 * d * 2048
    assert flops_axk1.expert_flops_per_row(CONFIG) == expert
    moe = 2 * d * 192 + 0.5 * expert + expert
    per_token = 6 * (projections + core) + dense + 5 * moe
    head = 2 * 16 * d * 20480
    per_request = flops_axk1.lm_forward_flops(CONFIG, L, 16)
    assert per_request == pytest.approx(L * per_token + head, rel=1e-12)
    assert per_request == pytest.approx(76.9e12, rel=0.002)
    assert 6 * (projections + core) / per_token == pytest.approx(0.69, abs=0.005)
    assert 6 * core / per_token == pytest.approx(0.43, abs=0.005)
    assert flops_axk1.attention_core_flops(CONFIG, L) == 6 * 2 * H * 320 * (L * L / 2)
    # padding v to the keys' width would be 384 for 320 a key: a fifth more
    assert 2 * 192 / (192 + 128) == 1.2
    assert flops_axk1.attention_core_bytes(CONFIG, L) == 6 * L * H * (2 * 192 + 2 * 128) * 2
    assert flops_axk1.grouped_matmul_flops(CONFIG, 1000) == 1000 * expert
    assert flops_axk1.grouped_matmul_bytes(CONFIG, 0, 1) == 12 * 3 * d * 2048 * 2
    assert flops_axk1.grouped_matmul_bytes(CONFIG, 10, 5) == (5 * 12 * 3 * d * 2048
                                                               + 10 * (2 * d + 3 * 2048)) * 2
    more = dict(CONFIG, depth=11)
    assert flops_axk1.lm_forward_flops(more, L, 16) - per_request == pytest.approx(
        5 * L * (projections + core + moe), rel=1e-12)


# paths as a compiled program names them (models/axk1.py's scopes under jit_lm_forward)
_STACK = "lm_forward/AXK1LM"
_PATHS = [
    (f"{_STACK}/layers_3/self_attn/attn_core/_causal_core/kernel_fwd/flash_fwd/pallas_call", "attn_core"),
    (f"{_STACK}/layers_3/self_attn/attn_core/_causal_core/transpose", "attn_core"),
    (f"{_STACK}/layers_3/self_attn/rope/concatenate", "rope"),
    (f"{_STACK}/layers_3/self_attn/rope/mul", "rope"),
    (f"{_STACK}/layers_3/self_attn/q_a_proj/dot_general", "mla_proj"),
    (f"{_STACK}/layers_3/self_attn/q_a_layernorm/rsqrt", "mla_proj"),
    (f"{_STACK}/layers_3/self_attn/q_b_proj/dot_general", "mla_proj"),
    (f"{_STACK}/layers_0/self_attn/kv_a_proj_with_mqa/dot_general", "mla_proj"),
    (f"{_STACK}/layers_3/self_attn/kv_a_layernorm/mul", "mla_proj"),
    (f"{_STACK}/layers_3/self_attn/kv_b_proj/dot_general", "mla_proj"),
    (f"{_STACK}/layers_3/self_attn/o_proj/dot_general", "mla_proj"),
    (f"{_STACK}/layers_3/moe/experts/kernel_fwd/gmm/pallas_call", "moe_experts"),
    (f"{_STACK}/layers_3/moe/experts/mul", "moe_experts"),
    (f"{_STACK}/layers_3/moe/router/top_k", "moe_route"),
    (f"{_STACK}/layers_3/moe/router/logistic", "moe_route"),
    (f"{_STACK}/layers_3/moe/dispatch/kernel_fwd/_dispatch_call/moe_dispatch/pallas_call", "moe_route"),
    (f"{_STACK}/layers_3/moe/combine/kernel_fwd/_combine_call/moe_combine/pallas_call", "moe_route"),
    (f"{_STACK}/layers_3/shared_experts/input_linear/dot_general", "dense"),
    (f"{_STACK}/layers_0/mlp/output_linear/dot_general", "dense"),
    (f"{_STACK}/layers_3/post_attention_layernorm/mul", "dense"),
    (f"{_STACK}/lm_head/lm_head/bpd,dv->bpv/dot_general", "dense"),
    (f"{_STACK}/embed_tokens/_take/gather", "other"),
    (f"{_STACK}/rope/cos", "other"),
]


@pytest.mark.parametrize("path,group", _PATHS, ids=[p.split("AXK1LM/")[1] for p, _ in _PATHS])
def test_scope_table_puts_each_path_in_its_group(path, group):
    from benchmarks.lib import scopes

    latent = f"{_STACK}/layers_0/self_attn/kv_a_proj_with_mqa/dot_general"  # what the table requires
    reduction = scopes.ScopeReduction(
        window_s=1.0, busy_s=1.0, n_devices=1, inherited_s=0.0, no_path_s=0.0, modules={},
        parse_s=0.0, op_self_s={(path, "fusion"): 0.25, (latent, "custom-call"): 0.5})
    seconds, _ = reduction.groups(scopes.table("axk1"))
    assert seconds[group] == (0.75 if group == "mla_proj" else 0.25)
    assert sum(seconds.values()) == 0.75
    bare = dataclasses.replace(
        reduction, op_self_s={(path.replace("kv_a_proj_with_mqa", "kv_a"), "fusion"): 1.0})
    # a program without latent attention (Granite's, the parent's) gives nothing to read
    assert bare.groups(scopes.table("axk1")) is None


def test_the_cell_lists_a_share_for_every_group_of_its_table():
    from benchmarks.lib import scopes

    cell = tables.load("workloads", CELL)
    groups = [g["name"] for g in scopes.table("axk1")["groups"]]
    assert groups == ["attn_core", "rope", "mla_proj", "moe_experts", "moe_route", "dense", "other"]
    assert [m for m in cell["per_layer"] if m.startswith("scope_time_share.")] == [
        f"scope_time_share.{g}.axk1" for g in groups]
    assert scopes.table("axk1")["module"] == "jit_lm_forward"
    assert all(m.endswith(".axk1") for m in cell["per_layer"])
    for name in ("step_mfu.axk1", "mla_attn_roofline.axk1", "expert_gmm_roofline.axk1",
                 "held_rows_share.axk1", "expert_load_max_over_mean.axk1",
                 "window_compiles.axk1", "device_idle_share.axk1"):
        assert name in cell["per_layer"]


def test_the_cell_sends_the_traffic_the_issue_named():
    """ISSUE 32: the Granite cell's traffic file as it stands, so that the two
    language-model cells differ by the model alone."""
    cell = tables.load("workloads", CELL)
    assert cell["traffic"] == "closed_ids_b1_16k" == tables.load(
        "workloads", "granite_prefill_b1_16k")["traffic"]
    traffic = tables.load("traffic", cell["traffic"])
    assert {k: v for k, v in traffic.items() if k != "tiny"} == {
        "driver": "closed_loop_lm", "in_flight": 2, "batch": 1, "tokens": 16384,
        "positions": 16, "distinct_batches": 4}
    assert cell["chips"] == 1 and "2 in flight" in cell["why"] and "1/16" in cell["why"]
    assert cell["end_to_end"] == {"rate": "slide_tokens_per_s"}
    assert cell["correct"]["control"] == "fp8" and cell["correct"]["rows"] == 16
    # ids are drawn from the held slice of the vocabulary
    assert CONFIG["vocab_size"] == 20480 == CONFIG["published"]["vocab_size"] // 8
