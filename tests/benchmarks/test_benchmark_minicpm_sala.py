"""The MiniCPM-SALA cell's side of the yardstick at the tiny size: the
rehearsal is correct and reports every layer metric it can read, its fp8
control is not correct, and neither is a program that drops the forced local
window, takes the lowest-scored blocks, lets every row of a core tile read the
tile's blocks or drops the lightning scan's carried state; the driver holds
the sparse core's rows to the reference's at the rows it samples; the new
readers on synthetic traces and counters; the adapter holds
the program to every key of the file's ``built``; the scope table puts each
path in its group and the cell lists a share for every group; the operation
counts are a hand count; the cell sends a 64k-token scoring client's traffic."""

import copy
import dataclasses
import json
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as harness
from benchmarks.layer_metrics import (block_score_roofline, kv_fetch_share, lightning_roofline,
                                      selected_pair_share, sparse_core_roofline)
from benchmarks.lib import flops_minicpm_sala as flops
from benchmarks.lib import tables

CELL = "sala_prefill_b1_64k"
CONFIG = tables.load("configs", "minicpm_sala_pp8")
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
    "step_mfu.sala": "no peaks for a CPU: no share of a peak from one",
    "device_idle_share.sala": "no device timeline in a CPU's trace",
    "sparse_core_roofline.sala": "no device trace, no peaks, and the jnp tier runs: no kernel",
    "lightning_roofline.sala": "the same",
    "block_score_roofline.sala": "the same",
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
    assert line["metrics"]["window_compiles.sala"]["value"] == 0
    # 259 tokens: the tiny selection's exact count (lib/flops_minicpm_sala.py)
    tokens = tables.load("traffic", cell["traffic"])["tiny"]["tokens"]
    exact = flops.selected_pairs(TINY, tokens) / (tokens * (tokens + 1) / 2)
    assert line["metrics"]["selected_pair_share.sala"]["value"] == pytest.approx(exact, rel=1e-9)
    assert line["metrics"]["kv_fetch_share.sala"]["value"] > 1.0


def _window(monkeypatch, seed=SEED):
    from gigapath_tpu import pipeline

    monkeypatch.setattr(pipeline, "lm_forward_fn", pipeline.lm_forward_fn.__wrapped__)
    ctx, driver = harness.prepare(types.SimpleNamespace(
        workload=CELL, seed=seed, seconds=0.2, trace=0, tiny=True))
    return ctx, driver, driver.run(ctx)


def test_fp8_control_in_the_programs_place_is_not_correct(monkeypatch):
    """The reference with every matrix product's operands rounded to float8
    (the projections, the compressed-key scores, q . k and the weighted sums
    of values among them) reads past the mean's limit, which the program
    stays under; bfloat16, what the configuration states, does not. The
    widest gap of one row swings with a block that a rounding moves into or
    out of a selection of 12 blocks of 8 (0.011-0.029 for the program,
    0.093-0.146 for the control over six seeds; the means 0.0085-0.0117
    against 0.074-0.086): its limit keeps room for that. The sparse core's
    rows: the program 0.0051-0.0056 on the mean over four seeds, the control
    0.096-0.127; one block in or out of a row's selection moves that row's
    core by ~0.3, so the widest has no limit."""
    ctx, driver, window = _window(monkeypatch)
    limits = ctx.cell["correct"]["tiny_limits"]
    assert ctx.cell["correct"]["control"] == "fp8"
    for stand_in in (None, "bf16"):
        got = driver.check(ctx, window, stand_in=stand_in)
        assert all(got[k] < limit for k, limit in limits.items()), stand_in
    control = driver.check(ctx, window, stand_in="fp8")
    assert control["embed_gap_mean"] > 1.5 * limits["embed_gap_mean"]
    assert control["embed_gap_max"] > limits["embed_gap_max"]
    assert control["core_gap_mean"] > 1.5 * limits["core_gap_mean"]


def _forced_window_dropped(select):
    return lambda scores, **kw: select(scores, **{**kw, "window": -10 ** 9})


def _lowest_taken(select):
    return lambda scores, **kw: select(jnp.where(jnp.isfinite(scores), -scores, scores), **kw)


def _every_bit_set(tile_lists):
    def planted(selected, nb, tile=8):
        lists, masks = tile_lists(selected, nb, tile)
        return lists, jnp.where(masks != 0, (1 << tile) - 1, 0)
    return planted


@pytest.mark.parametrize("fault", ["forced_window_dropped", "lowest_taken", "tile_bits_all_set",
                                   "carried_state_dropped"])
def test_a_planted_fault_is_not_correct(capsys, monkeypatch, fault):
    """(a) no block is forced but the first: the last window_size positions
    compete with the rest; (b) the top-k of the negated block scores, the
    lowest-scored blocks; the core's tile lists with every position's bit
    set, so that each row reads the union of its tile's choices (the
    selection and its counts as they were); (c) the lightning scan run a
    chunk at a time, every chunk starting from a zero state."""
    from gigapath_tpu.ops import block_sparse, ssd

    if fault == "carried_state_dropped":
        monkeypatch.setattr(ssd, "_advance", lambda state, decay, chunk_state: 0.0 * state)
    elif fault == "tile_bits_all_set":
        monkeypatch.setattr(block_sparse, "tile_lists", _every_bit_set(block_sparse.tile_lists))
    else:
        plant = _forced_window_dropped if fault == "forced_window_dropped" else _lowest_taken
        monkeypatch.setattr(block_sparse, "select_blocks", plant(block_sparse.select_blocks))
    line = _run(capsys, monkeypatch)
    assert line["correct"] is False
    assert any(c["value"] > 1.5 * c["limit"] for c in line["checks"].values()), line["checks"]


def test_the_counters_ride_on_the_adapter(monkeypatch):
    ctx, driver, window = _window(monkeypatch)
    kept = ctx.system.kept
    n = window["attempted"] + 2
    sparse = flops.sparse_layers(TINY)
    for name in ("selected_pairs", "kv_blocks_fetched", "kv_blocks_selected"):
        assert len(kept[name]) == n
        assert all(a.shape == (sparse, ctx.traffic["batch"]) and a.dtype == np.int32
                   for a in kept[name])
    tokens = ctx.traffic["tokens"]
    groups = TINY["num_key_value_heads"]
    # the jnp tier gathers, for every tile of 8 positions, the most blocks a
    # tile's list can name: 8 x top-12, or every block there is
    sp = TINY["sparse_config"]
    nb = -(-tokens // sp["block_size"])
    gathered = groups * -(-tokens // 8) * min(8 * sp["topk"], nb) * 8
    for pairs, fetched, named in zip(kept["selected_pairs"], kept["kv_blocks_fetched"],
                                     kept["kv_blocks_selected"]):
        assert (pairs == groups * flops.selected_pairs(TINY, tokens)).all()
        assert (fetched == gathered).all() and (named > 0).all()
    width = TINY["num_attention_heads"] * TINY["head_dim"]
    assert len(kept["core_rows"]) == n
    assert all(a.shape == (sparse, ctx.traffic["batch"], ctx.traffic["positions"], width)
               and a.dtype == np.float32 for a in kept["core_rows"])


def test_the_driver_compares_the_core_rows_of_the_requests_it_samples(monkeypatch):
    """Its requests are ``closed_loop.sampled``'s, the kept core rows are
    theirs (the warm-up's come first), and a sound program's rows read close
    to the reference's where rows of another request would not."""
    from benchmarks.drivers import closed_loop, closed_loop_core_rows

    ctx, driver, window = _window(monkeypatch)
    assert driver is closed_loop_core_rows
    outputs = window["_state"][3]
    picked = closed_loop_core_rows._sampled_requests(ctx, window)
    assert picked[-1] == len(outputs) - 1 and len(picked) == ctx.cell["correct"]["requests"]
    for idx, (_, rows, got) in zip(picked, closed_loop.sampled(ctx, window)):
        np.testing.assert_array_equal(got, outputs[idx][rows])
    sound = driver.check(ctx, window)
    assert sound["core_gap_mean"] < ctx.cell["correct"]["tiny_limits"]["core_gap_mean"]
    kept = ctx.system.kept["core_rows"]
    kept[-1] = kept[-1][:, :, ::-1]  # the last request's rows out of order
    assert driver.check(ctx, window)["core_gap_mean"] > sound["core_gap_mean"] + 0.1


def test_the_share_readers_on_synthetic_counters():
    """The window's requests and not the warm-up's, every layer, group and
    sequence alike; None where the system keeps no such counters."""
    sizes = {"num_key_value_heads": 2}
    L = 100
    full = 2 * L * (L + 1) // 2
    kept = {"selected_pairs": [np.full((1, 1), full, np.int32)] * 2
            + [np.full((1, 1), full // 4, np.int32)] * 3,
            "kv_blocks_fetched": [np.full((1, 1), 7, np.int32)] * 2
            + [np.full((1, 1), 30, np.int32)] * 3,
            "kv_blocks_selected": [np.full((1, 1), 7, np.int32)] * 2
            + [np.full((1, 1), 10, np.int32)] * 3}
    ctx = types.SimpleNamespace(system=types.SimpleNamespace(kept=kept), sizes=sizes)
    window = {"attempted": 3, "items": [L] * 3}
    assert selected_pair_share.read("selected_pair_share.sala", None, window, ctx) \
        == pytest.approx(full // 4 / full)
    assert kv_fetch_share.read("kv_fetch_share.sala", None, window, ctx) == pytest.approx(3.0)
    for reader in (selected_pair_share, kv_fetch_share):
        assert reader.read("x.sala", None, {"attempted": 0, "items": []}, ctx) is None
        for system in (object(), types.SimpleNamespace(kept={"received": [np.zeros((3, 4))]})):
            other = types.SimpleNamespace(system=system, sizes=sizes)
            assert reader.read("x.sala", None, window, other) is None


@pytest.mark.parametrize("reader,table,count", [
    (sparse_core_roofline, "block_sparse_attn_by_name", "sparse"),
    (lightning_roofline, "ssd_scan_by_name", "lightning"),
    (block_score_roofline, "block_score_by_name", "score")])
def test_a_roofline_reads_its_count_and_cannot_pass_100(reader, table, count):
    """A kernel that took exactly the least time reads 100: the core
    compute-bound at the cell's size (4.23 TFLOP against 0.18 GB), the scan
    bound by its bytes (0.21 TFLOP against 2.15 GB a layer), the block
    scores compute-bound (1.10 TFLOP against 1.08 GB); None without a
    trace, without peaks, or without the kernel (the parent's program,
    another system's cell)."""
    L = 65536
    window = {"attempted": 2, "items": [L] * 2, "work": 2 * L}
    if count == "sparse":
        least = 2 * flops.sparse_core_flops(CONFIG, L) / PEAKS["flops_per_s"]
        assert 2 * flops.sparse_core_bytes(CONFIG, L) / PEAKS["hbm_bytes_per_s"] < least
        bound = "compute"
    elif count == "score":
        least = 2 * flops.block_score_flops(CONFIG, L) / PEAKS["flops_per_s"]
        assert 2 * flops.block_score_bytes(CONFIG, L) / PEAKS["hbm_bytes_per_s"] < least
        bound = "compute"
    else:
        least = 2 * 3 * flops.lightning_bytes(CONFIG, L) / PEAKS["hbm_bytes_per_s"]
        assert 2 * 3 * flops.lightning_flops(CONFIG, L) / PEAKS["flops_per_s"] < least
        bound = "memory"
    seen = []
    trace = types.SimpleNamespace(kernel_seconds=lambda t: (seen.append(t), least)[1], n_devices=1)
    ctx = types.SimpleNamespace(system=object(), sizes=CONFIG, notes=[], peaks=PEAKS)
    assert reader.read("x.sala", trace, window, ctx) == pytest.approx(100.0)
    assert seen == [tables.kernel_table(table)] and bound in ctx.notes[0]
    nothing = types.SimpleNamespace(kernel_seconds=lambda t: 0.0, n_devices=1)
    for sizes in (CONFIG, tables.load("configs", "brumby14b_pp5")):
        ctx = types.SimpleNamespace(system=object(), sizes=sizes, notes=[], peaks=PEAKS)
        assert reader.read("x.sala", nothing, window, ctx) is None
        assert reader.read("x.sala", None, window, ctx) is None
    ctx = types.SimpleNamespace(system=object(), sizes=CONFIG, notes=[], peaks=None)
    assert reader.read("x.sala", trace, window, ctx) is None


def test_the_anchored_tables_take_the_kernels_and_not_their_readers():
    from benchmarks.lib.trace import TraceReduction

    ops = {"%block_sparse_attn.3 = bf16[1,65536,32,128]{3,2,1,0} custom-call(%a, %b, %c, %d, %e)": 2.0,
           "%ssd_scan_fwd.7 = bf16[1,65536,4096]{2,1,0} custom-call(%a, %b, %c, %d, %e, %f)": 4.0,
           "%fusion.9 = bf16[1,65536,4096]{2,1,0} fusion(%block_sparse_attn.3, %ssd_scan_fwd.7)": 8.0,
           "%block_score.4 = f32[1,2,1024,65536]{3,2,1,0} custom-call(%a, %b)": 16.0}
    reduction = TraceReduction(1.0, 1.0, 1, ops, ops, {}, [])
    assert reduction.kernel_seconds(tables.kernel_table("block_sparse_attn_by_name")) == 2.0
    assert reduction.kernel_seconds(tables.kernel_table("ssd_scan_by_name")) == 4.0
    assert reduction.kernel_seconds(tables.kernel_table("block_score_by_name")) == 16.0


def test_operation_counts_are_a_hand_count():
    """The counts at 65,536 tokens and depth 4: GEMMs 2,218.8 MFLOP a
    token (145.4 TFLOP), the selected core ~4.3, the compressed-key scores
    ~1.1, three lightning scans 0.62 at chunks of 128 (0.8 if the whole square
    of a chunk were counted), ~151.4 in all."""
    L = 65536
    gemms = 2 * 4096 * (4096 * 3 + 256 * 2) + 2 * 3 * 4096 * 16384 \
        + 3 * (2 * 4096 * 4096 * 5 + 2 * 3 * 4096 * 16384)
    assert gemms / 1e6 == pytest.approx(2218.8, abs=0.05)
    core, scores = flops.sparse_core_flops(CONFIG, L), flops.block_score_flops(CONFIG, L)
    scans = 3 * flops.lightning_flops(CONFIG, L)
    head = 2 * 16 * 4096 * 73448
    assert flops.lm_forward_flops(CONFIG, L, 16) == pytest.approx(gemms * L + core + scores + scans + head)
    assert core / 1e12 == pytest.approx(4.23, abs=0.01)
    assert scores / 1e12 == pytest.approx(1.10, abs=0.01)
    assert scans / 1e12 == pytest.approx(0.62, abs=0.01)
    assert flops.lm_forward_flops(CONFIG, L, 16) / 1e12 == pytest.approx(151.4, abs=0.1)
    # an exact top-64 of 64-token blocks keeps 12 % of the causal pairs at 64k
    assert flops.selected_pairs(CONFIG, L) / (L * (L + 1) / 2) == pytest.approx(0.1202, abs=1e-4)
    # at or under dense_len every causal pair counts, and no block scores
    assert flops.selected_pairs(CONFIG, 8192) == 8192 * 8193 // 2
    assert flops.block_score_flops(CONFIG, 8192) == 0.0
    assert flops.sparse_core_bytes(CONFIG, L) == L * (2 * 4096 + 2 * 256) * 2


def _other(value):
    return not value if isinstance(value, bool) else value + 1 if isinstance(value, int) \
        else value * 2 + 1


@pytest.mark.parametrize("key", [k if isinstance(k, str) else k[0] for k in CONFIG["built"]])
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


def test_the_adapter_checks_the_mixer_of_every_layer_it_holds(monkeypatch):
    from benchmarks.systems.lm import System

    config = copy.deepcopy(CONFIG)
    config["tiny"]["mixer_types"] = ["lightning-attn", "minicpm4"] + TINY["mixer_types"][2:]
    with pytest.raises(ValueError, match="mixer_types"):
        System(config, tiny=True)


@pytest.mark.parametrize("key", sorted(CONFIG["supported"]))
def test_the_adapter_refuses_a_file_whose_supported_key_differs(key):
    from benchmarks.systems.lm import System

    config = copy.deepcopy(CONFIG)
    value = config["supported"][key][0]
    config["tiny"][key] = "gelu" if isinstance(value, str) else not value
    with pytest.raises(ValueError, match=key):
        System(config, tiny=True)


def test_the_file_keeps_every_published_number_and_cuts_only_the_depth():
    published = {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32, "lightning_scale": "1/sqrt(d)",
        "lightning_use_rope": True, "max_position_embeddings": 524288,
        "model_type": "minicpm_sala", "num_attention_heads": 32, "num_hidden_layers": 32,
        "num_key_value_heads": 2, "qk_norm": True, "rand_init": False, "rms_norm_eps": 1e-06,
        "vocab_size": 73448, "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
        "mup_denominator": 32, "dim_model_base": 256, "tie_word_embeddings": False,
        "use_output_gate": True, "use_output_norm": True, "attn_use_output_gate": True}
    assert {k: CONFIG[k] for k in published} == published
    sparse_at = [i for i, kind in enumerate(CONFIG["mixer_types"]) if kind == "minicpm4"]
    assert sparse_at == [0, 9, 16, 17, 22, 29, 30, 31] and len(CONFIG["mixer_types"]) == 32
    assert CONFIG["reduced"] == ["depth"] and CONFIG["published"] == {"depth": 32}
    assert CONFIG["depth"] == 4 and CONFIG["share"] == {"depth": "depth"}
    assert CONFIG["mixer_types"][:4] == ["minicpm4"] + ["lightning-attn"] * 3
    entry = next(c for c in tables.manifest()["configs"] if c["name"] == "minicpm_sala_pp8")
    assert entry["reduced"] == ["depth"] and entry["source"] == CONFIG["source"]


# paths as the lowered program names them (tests/test_scope_names.py holds them)
_STACK = "lm_forward/MiniCPMSALALM"
_PATHS = [
    (f"{_STACK}/layers_0/self_attn/block_score/score/kernel_fwd/block_score", "block_score"),
    (f"{_STACK}/layers_0/self_attn/block_score/compress/reduce_sum", "block_score"),
    (f"{_STACK}/layers_0/self_attn/block_select/top_k", "block_select"),
    (f"{_STACK}/layers_0/self_attn/attn_core/kernel_fwd/block_sparse_attn", "sparse_core"),
    (f"{_STACK}/layers_0/self_attn/attn_core/sort", "sparse_core"),
    (f"{_STACK}/layers_2/self_attn/lightning/kernel_fwd/ssd_scan_fwd", "lightning"),
    (f"{_STACK}/layers_2/self_attn/out_norm/o_norm/mul", "lightning"),
    (f"{_STACK}/layers_2/self_attn/out_gate/gate_proj/dot_general", "attn_proj"),
    (f"{_STACK}/layers_2/self_attn/rope/concatenate", "attn_proj"),
    (f"{_STACK}/layers_0/self_attn/q_proj/dot_general", "attn_proj"),
    (f"{_STACK}/layers_0/self_attn/o_proj/dot_general", "attn_proj"),
    (f"{_STACK}/layers_1/mlp/input_linear/dot_general", "mlp"),
    (f"{_STACK}/layers_1/post_attention_layernorm/mul", "dense"),
    (f"{_STACK}/lm_head/lm_head/bpd,dv->bpv/dot_general", "dense"),
    (f"{_STACK}/rope/cos", "other"),
    (f"{_STACK}/embed_tokens/_take/gather", "other"),
]


@pytest.mark.parametrize("path,group", _PATHS, ids=[p.split("LM/")[1] for p, _ in _PATHS])
def test_scope_table_puts_each_path_in_its_group(path, group):
    from benchmarks.lib import scopes

    required = f"{_STACK}/layers_1/self_attn/lightning/kernel_fwd/ssd_scan_fwd"
    reduction = scopes.ScopeReduction(
        window_s=1.0, busy_s=1.0, n_devices=1, inherited_s=0.0, no_path_s=0.0, modules={},
        parse_s=0.0, op_self_s={(path, "fusion"): 0.25, (required, "fusion"): 0.5})
    seconds, _ = reduction.groups(scopes.table("sala"))
    assert seconds[group] == (0.75 if group == "lightning" else 0.25)
    bare = dataclasses.replace(reduction, op_self_s={(path.replace("lightning", "mixer"),
                                                      "fusion"): 1.0})
    if "lightning" in path:  # a program without the names gives nothing to read
        assert bare.groups(scopes.table("sala")) is None


def test_the_cell_lists_a_share_for_every_group_of_its_table():
    from benchmarks.lib import scopes

    cell = tables.load("workloads", CELL)
    groups = [g["name"] for g in scopes.table("sala")["groups"]]
    assert [m for m in cell["per_layer"] if m.startswith("scope_time_share.")] == [
        f"scope_time_share.{g}.sala" for g in groups]
    assert scopes.table("sala")["module"] == "jit_lm_forward"
    assert tables.cell_kind(cell) == "sala"


def test_the_cell_sends_its_traffic():
    """A 64k-token scoring client: the LM cells' keys with 65,536 tokens, two
    documents in flight, ids over the whole vocabulary, the LM driver."""
    cell = tables.load("workloads", CELL)
    traffic = tables.load("traffic", cell["traffic"])
    twin = tables.load("traffic", "closed_ids_b1_16k")
    assert {k: v for k, v in traffic.items() if k not in ("tokens", "tiny", "driver")} == {
        k: v for k, v in twin.items() if k not in ("tokens", "tiny", "driver")}
    assert traffic["tokens"] == 65536 and twin["driver"] == "closed_loop_lm"
    assert traffic["driver"] == "closed_loop_core_rows"
    assert CONFIG["reference_core_rows"] == "reference_minicpm_sala.forward"
    assert cell["chips"] == 1 and "2 in flight" in cell["why"]
    assert cell["correct"]["requests"] == 2 and cell["correct"]["rows"] == 16
    assert cell["correct"]["control"] == "fp8"
    assert cell["end_to_end"]["rate"] == "slide_tokens_per_s"
    entry = next(w for w in tables.manifest()["workloads"] if w["name"] == CELL)
    assert entry["config"] == "minicpm_sala_pp8" and entry["traffic"] == "closed_ids_b1_64k"
