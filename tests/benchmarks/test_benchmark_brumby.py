"""The Brumby cell's side of the yardstick at the tiny size: the rehearsal is
correct and reports every layer metric it can read, its fp8 control is not
correct, and neither is a program that drops the state handed between chunks;
the two new readers on synthetic traces and counters; the adapter holds the
program to every key of the file's ``built``; the scope table puts each path
in its group and the cell lists a share for every group; the operation
counts are a hand count; the cell sends the traffic ISSUE 39 names; the
weights keep every gate's memory where the rule draws it."""

import copy
import dataclasses
import json
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as harness
from benchmarks.layer_metrics import carried_share, retention_roofline
from benchmarks.lib import flops_brumby as flops
from benchmarks.lib import tables, weights_lm, weights_retention

CELL = "brumby_prefill_b1_32k"
CONFIG = tables.load("configs", "brumby14b_pp5")
TINY = CONFIG["tiny"]
SEED = 3000000019
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _run(capsys, monkeypatch, trace=0, seed=SEED):
    from gigapath_tpu import pipeline

    # the entry keeps one jitted function a model: a trace made before a fault
    # was planted must not serve this run, nor this run's trace a later test
    monkeypatch.setattr(pipeline, "lm_forward_fn", pipeline.lm_forward_fn.__wrapped__)
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.2",
                       "--trace", str(trace), "--tiny"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["failed"] == 0
    return line


# what a CPU rehearsal cannot read, and why
_NOT_ON_A_CPU = {
    "step_mfu.brumby": "no peaks for a CPU: no share of a peak from one",
    "device_idle_share.brumby": "no device timeline in a CPU's trace",
    "retention_roofline.brumby": "no device trace, no peaks, and the jnp tier runs: no kernel",
}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_is_correct_and_reports_what_it_can_read(capsys, monkeypatch, trace):
    line = _run(capsys, monkeypatch, trace)
    cell = tables.load("workloads", CELL)
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["checks"]) == set(cell["correct"]["tiny_limits"])
    if trace == 0:
        assert set(line["metrics"]) == {"slide_tokens_per_s", "setup_s"}
        return
    scopes = {m for m in cell["per_layer"] if m.startswith("scope_time_share.")}
    absent = set(cell["per_layer"]) - set(line["metrics"])
    assert absent == set(_NOT_ON_A_CPU) | scopes  # scope shares: no device timeline either
    assert line["metrics"]["window_compiles.brumby"]["value"] == 0
    assert 0.2 < line["metrics"]["carried_share.brumby"]["value"] <= 1.0


def _window(monkeypatch, seed=SEED):
    from gigapath_tpu import pipeline

    monkeypatch.setattr(pipeline, "lm_forward_fn", pipeline.lm_forward_fn.__wrapped__)
    ctx, driver = harness.prepare(types.SimpleNamespace(
        workload=CELL, seed=seed, seconds=0.2, trace=0, tiny=True))
    return ctx, driver, driver.run(ctx)


def test_fp8_control_in_the_programs_place_is_not_correct(monkeypatch):
    """The reference with every matrix product's operands rounded to float8
    (the projections, q . k and the weighted sum of values among them) reads
    past the limits the program stays under; bfloat16, what the configuration
    states, does not."""
    ctx, driver, window = _window(monkeypatch)
    limits = ctx.cell["correct"]["tiny_limits"]
    assert ctx.cell["correct"]["control"] == "fp8"
    for stand_in in (None, "bf16"):
        got = driver.check(ctx, window, stand_in=stand_in)
        assert all(got[k] < limit for k, limit in limits.items()), stand_in
    control = driver.check(ctx, window, stand_in="fp8")
    assert all(control[k] > 1.2 * limit for k, limit in limits.items())


def test_a_program_that_drops_the_carried_state_is_not_correct(capsys, monkeypatch):
    """Every chunk starts from what its own keys left: the scan's recurrence
    hands on nothing."""
    from gigapath_tpu.ops import power_retention

    monkeypatch.setattr(power_retention, "_advance",
                        lambda S, z, decay, chunk_S, chunk_z: (chunk_S, chunk_z))
    line = _run(capsys, monkeypatch)
    assert line["correct"] is False
    assert line["checks"]["embed_gap_mean"]["value"] > 3 * line["checks"]["embed_gap_mean"]["limit"]


def test_the_counter_rides_on_the_adapter(monkeypatch):
    ctx, driver, window = _window(monkeypatch)
    kept = ctx.system.kept
    assert len(kept["carried_share"]) == len(kept["received"]) == window["attempted"] + 2
    for share, received in zip(kept["carried_share"], kept["received"]):
        assert share.shape == (TINY["depth"], ctx.traffic["batch"]) and share.dtype == np.float32
        assert ((share >= 0) & (share <= 1)).all()
        assert received.shape[0] == 0            # no expert layer: no counts
    value = carried_share.read("carried_share.brumby", None, window, ctx)
    served = kept["carried_share"][-window["attempted"]:]
    assert value == pytest.approx(float(np.mean(served)), rel=1e-6) and value > 0.2


def test_the_carried_share_reader_on_a_synthetic_counter():
    """The window's requests and not the warm-up's, every layer and sequence
    alike; None where the system keeps no such counter."""
    kept = {"carried_share": [np.full((8, 1), 0.9, np.float32)] * 2
            + [np.array([[0.2], [0.4]] * 4, np.float32)] * 3}
    ctx = types.SimpleNamespace(system=types.SimpleNamespace(kept=kept))
    assert carried_share.read("carried_share.brumby", None, {"attempted": 3}, ctx) \
        == pytest.approx(0.3)
    assert carried_share.read("carried_share.brumby", None, {"attempted": 0}, ctx) is None
    for system in (object(), types.SimpleNamespace(kept={"received": [np.zeros((3, 4))]})):
        ctx = types.SimpleNamespace(system=system)
        assert carried_share.read("carried_share.brumby", None, {"attempted": 3}, ctx) is None


def test_the_roofline_reads_the_chunked_form_and_cannot_pass_100():
    """A kernel that took exactly the least time reads 100; compute-bound at
    the cell's size (138 ms of operations against 8 ms of bytes a request);
    None without a trace, without peaks, or without the kernel (the parent's
    program, another system's cell)."""
    window = {"attempted": 2, "items": [32768] * 2, "work": 2 * 32768}
    least = 2 * 8 * flops.retention_flops(CONFIG, 32768) / PEAKS["flops_per_s"]
    assert least == pytest.approx(0.2763, abs=0.001)                  # two requests
    seen = []
    trace = types.SimpleNamespace(kernel_seconds=lambda t: (seen.append(t), least)[1], n_devices=1)
    ctx = types.SimpleNamespace(system=object(), sizes=CONFIG, notes=[], peaks=PEAKS)
    assert retention_roofline.read("retention_roofline.brumby", trace, window, ctx) \
        == pytest.approx(100.0)
    assert seen == [tables.kernel_table("power_retention_by_name")] and "compute" in ctx.notes[0]
    nothing = types.SimpleNamespace(kernel_seconds=lambda t: 0.0, n_devices=1)
    for sizes in (CONFIG, tables.load("configs", "granite4h_small_ep2")):
        ctx = types.SimpleNamespace(system=object(), sizes=sizes, notes=[], peaks=PEAKS)
        assert retention_roofline.read("retention_roofline.brumby", nothing, window, ctx) is None
        assert retention_roofline.read("retention_roofline.brumby", None, window, ctx) is None
    ctx = types.SimpleNamespace(system=object(), sizes=CONFIG, notes=[], peaks=None)
    assert retention_roofline.read("retention_roofline.brumby", trace, window, ctx) is None


def test_the_anchored_table_takes_the_kernel_and_not_its_readers():
    from benchmarks.lib.trace import TraceReduction

    ops = {"%power_retention_fwd.3 = (bf16[1,8,5,128,32768]{4,3,2,1,0}, f32[1,8,5,32768]{3,2,1,0}) "
           "custom-call(%a, %b, %c, %d, %e, %f)": 2.0,
           "%fusion.9 = bf16[1,32768,40,128]{3,2,1,0} fusion(%power_retention_fwd.3)": 8.0,
           "%flash_fwd.4 = (bf16[8]) custom-call(%a, %b, %c)": 16.0}
    reduction = TraceReduction(1.0, 1.0, 1, ops, ops, {}, [])
    assert reduction.kernel_seconds(tables.kernel_table("power_retention_by_name")) == 2.0


def test_operation_counts_are_the_issues():
    """ISSUE 39's count a token and a layer: projections 125.9 M, SwiGLU 534.8 M,
    the chunked retention 103.6 M (the state built over 8 KV heads 17.0 M,
    read by 40 query heads 85.2 M, the lower triangles of chunks of 128 1.3 M);
    200.4 TFLOP a 32,768-token request at depth 8."""
    L = 32768
    per_token = flops.retention_flops(CONFIG, L) / L
    D = 128 * 129 // 2
    assert D == 8256
    assert per_token == pytest.approx(2 * D * 129 * 48 + 64.5 * 514 * 40, rel=1e-9)
    assert per_token / 1e6 == pytest.approx(103.6, abs=0.05)
    request = flops.lm_forward_flops(CONFIG, L, 16)
    head = 2 * 16 * 5120 * 151936
    assert (request - head) / L / 8 / 1e6 == pytest.approx(764.3, abs=0.05)
    assert request / 1e12 == pytest.approx(200.4, abs=0.05)
    assert flops.retention_bytes(CONFIG, L) == L * ((2 * 5120 + 2 * 1024) * 2 + 8 * 4)
    # a tail shorter than a chunk counts its own triangle
    assert flops.retention_flops(CONFIG, 130) - flops.retention_flops(CONFIG, 128) == pytest.approx(
        2 * 2 * D * 129 * 48 + (1 + 2) * 514 * 40)


def test_gate_biases_are_drawn_again_and_nothing_else_is():
    """Each ``gate_bias`` leaf: a head's memory ``1 + e^bias`` log-uniform over
    32 to 32,768 positions, a KV head each, from the seed; every other leaf is
    ``weights_lm``'s draw, untouched."""
    from benchmarks.systems.lm import System

    shapes = System(CONFIG, tiny=True).param_shapes()
    made = weights_retention.make_weights(shapes, SEED)
    plain = weights_lm.make_weights(shapes, SEED)
    again = weights_retention.make_weights(shapes, SEED)
    redrawn = 0
    for (path, leaf), base, twin in zip(jax.tree_util.tree_flatten_with_path(made)[0],
                                       jax.tree.leaves(plain), jax.tree.leaves(again)):
        assert np.array_equal(np.asarray(leaf, np.float32), np.asarray(twin, np.float32))
        if str(path[-1].key) == "gate_bias":
            memory = 1.0 + np.exp(np.asarray(leaf, np.float64))
            assert leaf.shape == (TINY["num_key_value_heads"],)
            assert ((memory > 31.5) & (memory < 33000)).all()
            redrawn += 1
        else:
            assert np.array_equal(np.asarray(leaf, np.float32), np.asarray(base, np.float32))
    assert redrawn == TINY["depth"]
    many = weights_retention._gate_bias(jax.random.PRNGKey(1), 0, (4096,), jnp.float32)
    logs = np.log(1.0 + np.exp(np.asarray(many, np.float64)))
    assert logs.min() > math.log(31.9) and logs.max() < math.log(32800)
    assert np.mean(logs) == pytest.approx(math.log(32) + math.log(1024) / 2, abs=0.1)


def _other(value):
    return not value if isinstance(value, bool) else value + 1 if isinstance(value, int) \
        else value * 2 + 1


@pytest.mark.parametrize("key", CONFIG["built"])
def test_the_adapter_checks_every_key_the_file_says_is_built(monkeypatch, key):
    """The program built with one field other than the file states is refused
    by name; as built, the tiny preset passes."""
    from benchmarks.systems.lm import System
    from gigapath_tpu.utils import registry

    System(CONFIG, tiny=True)
    build = registry.create_model_from_registry

    def off_by_one_field(arch, **share):
        model = build(arch, **share)
        cfg = copy.copy(model.cfg)
        object.__setattr__(cfg, key, _other(getattr(cfg, key)))
        return model.clone(cfg=cfg)

    monkeypatch.setattr(registry, "create_model_from_registry", off_by_one_field)
    with pytest.raises(ValueError, match=key):
        System(CONFIG, tiny=True)


@pytest.mark.parametrize("key", sorted(CONFIG["supported"]))
def test_the_adapter_refuses_a_file_whose_supported_key_differs(key):
    """A file that states another value than the one the program supports
    (an untied head, no bias, SwiGLU, no window, plain RoPE) is refused by
    name."""
    from benchmarks.systems.lm import System

    config = copy.deepcopy(CONFIG)
    value = config["supported"][key][0]
    config["tiny"][key] = "gelu" if isinstance(value, str) else {"factor": 2.0} \
        if value is None else not value
    with pytest.raises(ValueError, match=key):
        System(config, tiny=True)


def test_the_file_keeps_every_published_number_and_cuts_only_the_depth():
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 17408, "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["reduced"] == ["depth"] and CONFIG["published"] == {"depth": 40}
    assert CONFIG["depth"] == 8 and CONFIG["share"] == {"depth": "depth"}
    entry = next(c for c in tables.manifest()["configs"] if c["name"] == "brumby14b_pp5")
    assert entry["reduced"] == ["depth"] and entry["source"] == CONFIG["source"]


# paths as the lowered program names them (tests/test_scope_names.py holds them)
_STACK = "lm_forward/BrumbyLM"
_PATHS = [
    (f"{_STACK}/layers_3/self_attn/retention/kernel_fwd/power_retention_fwd", "retention"),
    (f"{_STACK}/layers_3/self_attn/retention/gate/gate/dot_general", "retention"),
    (f"{_STACK}/layers_3/self_attn/retention/kernel_fwd/transpose", "retention"),
    (f"{_STACK}/layers_3/self_attn/rope/concatenate", "rope"),
    (f"{_STACK}/layers_3/self_attn/q_proj/dot_general", "attn_proj"),
    (f"{_STACK}/layers_3/self_attn/k_norm/mul", "attn_proj"),
    (f"{_STACK}/layers_3/self_attn/o_proj/dot_general", "attn_proj"),
    (f"{_STACK}/layers_3/mlp/input_linear/dot_general", "mlp"),
    (f"{_STACK}/layers_3/mlp/output_linear/dot_general", "mlp"),
    (f"{_STACK}/layers_3/post_attention_layernorm/mul", "dense"),
    (f"{_STACK}/lm_head/lm_head/bpd,dv->bpv/dot_general", "dense"),
    (f"{_STACK}/rope/cos", "other"),
    (f"{_STACK}/embed_tokens/_take/gather", "other"),
]


@pytest.mark.parametrize("path,group", _PATHS, ids=[p.split("BrumbyLM/")[1] for p, _ in _PATHS])
def test_scope_table_puts_each_path_in_its_group(path, group):
    from benchmarks.lib import scopes

    required = f"{_STACK}/layers_0/self_attn/retention/gate/gate/dot_general"
    reduction = scopes.ScopeReduction(
        window_s=1.0, busy_s=1.0, n_devices=1, inherited_s=0.0, no_path_s=0.0, modules={},
        parse_s=0.0, op_self_s={(path, "fusion"): 0.25, (required, "fusion"): 0.5})
    seconds, _ = reduction.groups(scopes.table("brumby"))
    assert seconds[group] == (0.75 if group == "retention" else 0.25)
    bare = dataclasses.replace(reduction, op_self_s={(path.replace("retention", "mixer"),
                                                      "fusion"): 1.0})
    if "retention" in path:  # a program without the names gives nothing to read
        assert bare.groups(scopes.table("brumby")) is None


def test_the_cell_lists_a_share_for_every_group_of_its_table():
    from benchmarks.lib import scopes

    cell = tables.load("workloads", CELL)
    groups = [g["name"] for g in scopes.table("brumby")["groups"]]
    assert [m for m in cell["per_layer"] if m.startswith("scope_time_share.")] == [
        f"scope_time_share.{g}.brumby" for g in groups]
    assert scopes.table("brumby")["module"] == "jit_lm_forward"
    assert tables.cell_kind(cell) == "brumby"


def test_the_cell_sends_the_traffic_the_issue_named():
    """ISSUE 39's client: the Granite cell's keys with 32,768 tokens, two
    documents in flight, the retention driver."""
    cell = tables.load("workloads", CELL)
    traffic = tables.load("traffic", cell["traffic"])
    twin = tables.load("traffic", "closed_ids_b1_16k")
    assert {k: v for k, v in traffic.items() if k not in ("tokens", "driver")} == {
        k: v for k, v in twin.items() if k not in ("tokens", "driver")}
    assert traffic["tokens"] == 32768 and traffic["driver"] == "closed_loop_retention"
    assert cell["chips"] == 1 and "2 in flight" in cell["why"]
    assert cell["correct"]["requests"] == 2 and cell["correct"]["rows"] == 16
    assert cell["end_to_end"]["rate"] == "slide_tokens_per_s"
