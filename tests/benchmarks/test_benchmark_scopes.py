"""``benchmarks/lib/scopes.py`` and the layer metrics that read it: the
reduction of a trace by the program's own names, on one recorded trace per
cell kind (cut from PR 25's chip runs, the ``tf_op`` and ``program_id`` stats
kept) and on a synthetic one."""

import dataclasses
import json
import os
import shutil
import types

import pytest

from benchmarks import scope_report
from benchmarks.layer_metrics import device_ms_per_request, scope_time_share
from benchmarks.lib import scopes, tables
from benchmarks.lib import trace as T

FIXTURES = os.path.join(tables.BENCH_DIR, "fixtures")
KINDS = ("tile", "slide")
# what the two readers answer to; no cell's file lists them yet (PERF.md §7),
# benchmarks/scope_report.py reads them
METRICS = [name for kind in KINDS for name in scope_report.metric_names(kind)]


def _recorded(kind):
    with open(os.path.join(FIXTURES, f"{kind}.scopes.cut.json")) as f:
        return json.load(f)


def _xplane(kind):
    return os.path.join(FIXTURES, f"{kind}.scopes.cut.xplane.pb")


def _reduce(kind):
    rec = _recorded(kind)
    return scopes.reduce_scopes(_xplane(kind), [tuple(s) for s in rec["spans"]],
                                rec["sync_host_ns"])


def _context(kind, tmp_path):
    """What a reader sees of a run: the trace directory, the driver's spans,
    the marker's host time, and the notes it may add to."""
    rec = _recorded(kind)
    os.makedirs(tmp_path / "plugins", exist_ok=True)
    shutil.copy(_xplane(kind), tmp_path / "plugins" / "t.xplane.pb")
    return types.SimpleNamespace(
        trace_dir=str(tmp_path), sync_host_ns=rec["sync_host_ns"], notes=[],
        spans=types.SimpleNamespace(spans=[tuple(s) for s in rec["spans"]]))


def test_the_nine_metrics_by_name():
    assert METRICS == [
        "scope_time_share.attn_core.tile", "scope_time_share.dense.tile",
        "scope_time_share.other.tile", "device_ms_per_request.tile",
        "scope_time_share.attn_kernel.slide", "scope_time_share.attn_glue.slide",
        "scope_time_share.dense.slide", "scope_time_share.other.slide",
        "device_ms_per_request.slide"]


@pytest.mark.parametrize("cell", [w["name"] for w in tables.manifest()["workloads"]])
def test_scope_report_rehearsal_reads_nothing_from_a_cpu(capsys, cell):
    """The tool's control flow at the tiny size: the cell's own set-up and
    window under the profiler, the readers asked for the cell kind's names,
    and, with no device timeline, a line that leaves every one of them out."""
    rc = scope_report.main(["--workload", cell, "--seed", "3000000019", "--seconds", "0.2",
                            "--scope", "/attn_core/", "--tiny"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["workload"] == cell and line["seed"] == 3000000019
    assert line["requests"] > 0 and line["failed"] == 0
    assert line["metrics"] == {} and "busy_s" not in line


@pytest.mark.parametrize("kind", KINDS)
def test_recorded_trace_reduces_to_the_recorded_numbers(kind):
    r, want = _reduce(kind), _recorded(kind)["expected"]
    assert r.n_devices == want["n_devices"] == 1
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-12)
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert len(r.op_self_s) == want["n_ops"]
    seconds, _ = r.groups(scopes.table(kind))
    assert list(seconds) == [g["name"] for g in scopes.table(kind)["groups"]]
    assert seconds == pytest.approx(want["group_s"], rel=1e-9)
    assert r.inherited_s == pytest.approx(want["inherited_s"], rel=1e-9)
    assert r.no_path_s == pytest.approx(want["no_path_s"], rel=1e-6)
    assert {k: [len(v), sum(v)] for k, v in r.modules.items()} == {
        k: [n, pytest.approx(s, rel=1e-9)] for k, (n, s) in want["modules"].items()}
    for scope, s in want["scope_s"].items():
        assert r.seconds(f"/{scope}/") == pytest.approx(s, rel=1e-9)


@pytest.mark.parametrize("kind", KINDS)
def test_recorded_trace_reduces_the_same_every_time(kind):
    a, b = _reduce(kind), _reduce(kind)
    assert (a.busy_s, a.window_s, a.inherited_s, a.no_path_s) == (
        b.busy_s, b.window_s, b.inherited_s, b.no_path_s)
    assert a.op_self_s == b.op_self_s and a.modules == b.modules


@pytest.mark.parametrize("kind", KINDS)
def test_groups_hold_every_operation_once_and_agree_with_the_other_reduction(kind):
    rec = _recorded(kind)
    r = _reduce(kind)
    by_text = T.reduce_xplane(_xplane(kind), [tuple(s) for s in rec["spans"]],
                              rec["sync_host_ns"])
    seconds, paths = r.groups(scopes.table(kind))
    assert sum(seconds.values()) == pytest.approx(sum(r.op_self_s.values()), rel=1e-12)
    assert sum(seconds.values()) == pytest.approx(r.busy_s, rel=1e-9)  # no overlap but nesting
    assert {g: sum(p.values()) for g, p in paths.items()} == pytest.approx(seconds, rel=1e-9)
    # the same events, the same clock, the same window as lib/trace.py
    assert r.busy_s == pytest.approx(by_text.busy_s, rel=1e-5)
    assert sum(r.op_self_s.values()) == pytest.approx(sum(by_text.op_self_s.values()), rel=1e-5)
    # ISSUE 25's acceptance, on the recorded trace
    assert seconds[scopes.OTHER] / r.busy_s < (0.02 if kind == "tile" else 0.08)
    assert r.no_path_s / r.busy_s < 0.01


def test_kernels_by_name_are_the_kernels_by_what_they_return():
    rec = _recorded("slide")
    by_text = T.reduce_xplane(_xplane("slide"), [tuple(s) for s in rec["spans"]],
                              rec["sync_host_ns"])
    by_shape, by_name = tables.kernel_table("dilated_attn"), tables.kernel_table("dilated_fwd_by_name")

    def picked(table):
        return {name for name in by_text.op_total_s
                if dataclasses.replace(by_text, op_total_s={name: 1.0}).kernel_seconds(table)}

    assert picked(by_shape) == picked(by_name) and len(picked(by_name)) == 60  # 12 layers x 5 branches
    assert by_text.kernel_seconds(by_name) == pytest.approx(rec["expected"]["dilated_attn_s"], rel=1e-9)
    # and the scope group that names them reads the same seconds
    seconds, _ = _reduce("slide").groups(scopes.table("slide"))
    assert seconds["attn_kernel"] == pytest.approx(by_text.kernel_seconds(by_shape), rel=1e-5)


@pytest.mark.parametrize("metric", METRICS)
def test_readers_on_the_recorded_trace(metric, tmp_path):
    kind = metric.split(".")[-1]
    ctx, want = _context(kind, tmp_path), _recorded(kind)["expected"]
    reader = scope_time_share if metric.startswith("scope_time_share") else device_ms_per_request
    value = reader.read(metric, object(), {}, ctx)
    if reader is device_ms_per_request:
        n, s = want["modules"][scopes.table(kind)["module"]]
        assert value == pytest.approx(1e3 * s / n, rel=1e-9)
        assert (560 < value < 570) if kind == "tile" else (1500 < value < 1540)
    else:
        group = metric.split(".")[1]
        assert value == pytest.approx(100.0 * want["group_s"][group] / want["busy_s"], rel=1e-9)
    assert any(note.startswith(metric + ":") for note in ctx.notes)
    assert sum(note.startswith("scopes: second parse") for note in ctx.notes) == 1
    reader.read(metric, object(), {}, ctx)  # a second reader parses nothing anew
    assert sum(note.startswith("scopes: second parse") for note in ctx.notes) == 1


@pytest.mark.parametrize("kind", KINDS)
def test_a_cells_shares_add_up_to_100(kind, tmp_path):
    ctx = _context(kind, tmp_path)
    shares = {m: scope_time_share.read(m, object(), {}, ctx)
              for m in METRICS if m.startswith("scope_time_share") and m.endswith("." + kind)}
    assert len(shares) == (3 if kind == "tile" else 4)
    assert sum(shares.values()) == pytest.approx(100.0, abs=0.1)
    other_note, = [n for n in ctx.notes if n.startswith(f"scope_time_share.other.{kind}:")]
    assert "no path at all" in other_note and "a predecessor's path taken" in other_note


@pytest.mark.parametrize("metric", METRICS)
def test_readers_give_none_without_a_device_timeline(metric, tmp_path):
    reader = scope_time_share if metric.startswith("scope_time_share") else device_ms_per_request
    # a CPU rehearsal: the harness's own reduction found no device, so it passes None
    empty = types.SimpleNamespace(trace_dir=str(tmp_path), notes=[], sync_host_ns=0,
                                  spans=types.SimpleNamespace(spans=[("window", 0, 10)]))
    assert reader.read(metric, None, {}, empty) is None
    # a directory with no xplane in it, and one whose xplane has no device plane
    assert reader.read(metric, object(), {}, empty) is None
    from jax.profiler import ProfileData

    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace('planes { id: 1 name: "/host:CPU" }'))
    fresh = types.SimpleNamespace(**{**vars(empty), "notes": []})
    assert reader.read(metric, object(), {}, fresh) is None


_SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "program_id" } }
  event_metadata { key: 1 value { id: 1 name: "jit_bench_clock_sync(7)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_tile_encode(11)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_convert_element_type(12)" } }
  event_metadata { key: 10 value { id: 10 name: "%while.1 = f32[8]{0} while(f32[8]{0} %a)"
    stats { metadata_id: 1 str_value: "jit(tile_encode)/ViT/blocks_0/mlp/while:" } stats { metadata_id: 2 uint64_value: 11 } } }
  event_metadata { key: 11 value { id: 11 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %b)"
    stats { metadata_id: 1 str_value: "jit(tile_encode)/ViT/blocks_0/attn/attn_core/reduce_sum:" } stats { metadata_id: 2 uint64_value: 11 } } }
  event_metadata { key: 12 value { id: 12 name: "%copy.3 = f32[8]{0} copy(f32[8]{0} %gte)"
    stats { metadata_id: 2 uint64_value: 11 } } }
  event_metadata { key: 13 value { id: 13 name: "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %c)"
    stats { metadata_id: 1 str_value: "jit(tile_encode)/ViT/patch_embed/conv:" } stats { metadata_id: 2 uint64_value: 11 } } }
  event_metadata { key: 14 value { id: 14 name: "%copy.1 = bf16[8]{0} copy(bf16[8]{0} %args)"
    stats { metadata_id: 2 uint64_value: 12 } } }
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
          events { metadata_id: 1 offset_ps: 500000000 duration_ps: 500000000 }
          events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 900000000 }
          events { metadata_id: 3 offset_ps: 1900000000 duration_ps: 100000000 }
          events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 9000000000 }
          events { metadata_id: 2 offset_ps: 14000000000 duration_ps: 1000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
          events { metadata_id: 14 offset_ps: 1900000000 duration_ps: 100000000 }
          events { metadata_id: 10 offset_ps: 2000000000 duration_ps: 4000000000 }
          events { metadata_id: 11 offset_ps: 3000000000 duration_ps: 1000000000 }
          events { metadata_id: 12 offset_ps: 6500000000 duration_ps: 500000000 }
          events { metadata_id: 13 offset_ps: 8000000000 duration_ps: 2000000000 }
          events { metadata_id: 11 offset_ps: 11000000000 duration_ps: 5000000000 } }
}
"""


def _synthetic(tmp_path, text=_SYNTHETIC):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    # the marker ends at 1 ms on the trace's clock; the host saw it at 101 ms
    # of its own. Window 1.5 ms .. 13 ms of the trace.
    ms = 1_000_000
    return scopes.reduce_scopes(str(path), [("window", 101 * ms + ms // 2, 113 * ms),
                                            ("h2d", 101 * ms + ms // 2, 102 * ms)], 101 * ms)


def test_synthetic_trace_one_group_each_nesting_clipping_and_modules(tmp_path):
    r = _synthetic(tmp_path)
    assert r.window_s == pytest.approx(11.5e-3)
    # busy: copy.1 1.9..2, while 2..6, copy.3 6.5..7, fusion.4 8..10, fusion.2 11..13 (clipped from 16)
    assert r.busy_s == pytest.approx(8.6e-3)
    own = {(scopes.collapse(path), kind.split(" ")[0]): s for (path, kind), s in r.op_self_s.items()}
    assert own == pytest.approx({
        ("tile_encode/ViT/blocks_*/mlp/while", "while"): 3e-3,        # less the fusion inside
        ("tile_encode/ViT/blocks_*/attn/attn_core/reduce_sum", "fusion"): 3e-3,  # 1 nested + 2 clipped
        ("tile_encode/ViT/blocks_*/attn/attn_core/reduce_sum", "copy"): 0.5e-3,  # its predecessor's path
        ("tile_encode/ViT/patch_embed/conv", "fusion"): 2e-3,
        ("", "copy"): 0.1e-3,  # another program's, and nothing of that program before it
    })
    assert r.inherited_s == pytest.approx(0.5e-3) and r.no_path_s == pytest.approx(0.1e-3)
    seconds, paths = r.groups(scopes.table("tile"))
    assert seconds == pytest.approx({"attn_core": 3.5e-3, "dense": 3e-3, "other": 2.1e-3})
    assert sum(seconds.values()) == pytest.approx(r.busy_s)
    # every path in exactly one group
    assert sorted(p for group in paths.values() for p in group) == sorted(
        {path or "(no path)" for path, _ in own})
    assert paths["other"] == pytest.approx({"tile_encode/ViT/patch_embed/conv": 2e-3,
                                            "(no path)": 0.1e-3})
    # modules: the runs that began in the window, whole; the one at 14 ms began after it,
    # the one at 1 ms and the marker before it
    assert r.modules == {"jit_convert_element_type": [pytest.approx(0.1e-3)],
                         "jit_tile_encode": [pytest.approx(9e-3)]}
    assert r.seconds("/attn_core/") == pytest.approx(3.5e-3)


def test_a_program_without_the_scopes_gives_nothing_to_read(tmp_path):
    """The parent of PR 25 traced with this PR's benchmark files: flax's module
    paths are there, ``attn_core`` is not, and no share is reported."""
    r = _synthetic(tmp_path, _SYNTHETIC.replace("attn_core/", "").replace("jit_tile_encode", "jit_encode"))
    assert r.groups(scopes.table("tile")) is None
    ctx = types.SimpleNamespace(scope_reduction=r, notes=[])
    for metric in METRICS:
        if metric.endswith(".tile"):
            reader = (scope_time_share if metric.startswith("scope_time_share")
                      else device_ms_per_request)
            assert reader.read(metric, object(), {}, ctx) is None


@pytest.mark.parametrize("op_name,path", [
    ("jit(tile_encode)/VisionTransformer/blocks_3/attn/attn_core/reduce_sum:",
     "tile_encode/VisionTransformer/blocks_3/attn/attn_core/reduce_sum"),
    ("jit(loss)/transpose(jvp(dilated_attn))/branch_r2/kernel_dq/dilated_dq/pallas_call",
     "loss/dilated_attn/branch_r2/kernel_dq/dilated_dq/pallas_call"),
    ("jit(f)/jvp(layers_0/self_attn)/self_attn._attend/merge/jit(_where)/select_n:",
     "f/layers_0/self_attn/self_attn._attend/merge/_where/select_n"),
    ("", ""),
])
def test_scope_path_takes_the_transformations_off(op_name, path):
    assert scopes.scope_path(op_name) == path


@pytest.mark.parametrize("path,collapsed", [
    ("f/blocks_17/attn/attn_core/mul", "f/blocks_*/attn/attn_core/mul"),
    ("f/layers_3/self_attn/dilated_attn/branch_r16/pack/pad", "f/layers_*/self_attn/dilated_attn/branch_r16/pack/pad"),
    ("f/norm/mul", "f/norm/mul"),
])
def test_collapse_folds_layer_indices_and_keeps_ratios(path, collapsed):
    assert scopes.collapse(path) == collapsed
