"""``benchmarks/lib/host_spans.py`` on a synthetic span list and over the
recorded trace, the two readers that answer from it, and
``benchmarks/host_report.py --tiny`` on every cell."""

import json
import os
import types

import pytest

from benchmarks import host_report
from benchmarks import run as harness
from benchmarks.lib import host_spans, tables
from benchmarks.lib import trace as T

MANIFEST = tables.manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
MANIFEST_KINDS = sorted({tables.cell_kind(tables.load("workloads", cell)) for cell in CELLS})
SEED = 3000000019  # past 2**31, as the driver's seeds are
MS = 1_000_000


def _span(id, name, start_ms, end_ms, parent=None, thread=1, **fields):
    return types.SimpleNamespace(id=id, name=name, start_ns=start_ms * MS, end_ns=end_ms * MS,
                                 parent=parent, thread=thread, fields=fields)


def _synthetic():
    """Set-up (a compile under no span, then a first request that compiles)
    and a window [100, 200] ms holding two whole requests and a third cut."""
    return [
        _span(0, "compile", 1, 5, fun_name="jit(_fill)", cache="miss"),
        # the first request: the outer function's trace holds an inner one's
        _span(1, "request", 10, 90),
        _span(2, "prepare", 10, 12, parent=1),
        _span(3, "h2d", 12, 20, parent=1),
        _span(4, "dispatch", 20, 70, parent=1),
        _span(5, "trace", 30, 34, parent=4, fun_name="inner"),
        _span(6, "trace", 22, 40, parent=4, fun_name="slide_forward"),
        _span(7, "lower", 40, 48, parent=4, fun_name="jit(slide_forward)"),
        _span(8, "compile", 48, 68, parent=4, fun_name="jit(slide_forward)",
              cache="hit"),
        _span(9, "device_wait", 70, 88, parent=1),
        _span(10, "d2h", 88, 90, parent=1),
        # the window's requests
        _span(11, "request", 100, 140),
        _span(12, "h2d", 101, 111, parent=11),
        _span(13, "dispatch", 111, 112, parent=11),
        _span(14, "device_wait", 112, 138, parent=11),
        _span(15, "d2h", 138, 140, parent=11),
        _span(16, "request", 141, 181),
        _span(17, "h2d", 142, 150, parent=16),
        _span(18, "dispatch", 150, 153, parent=16),
        _span(19, "device_wait", 153, 180, parent=16),
        _span(20, "d2h", 180, 181, parent=16),
        _span(21, "request", 182, 230),
        _span(22, "h2d", 183, 193, parent=21),
        _span(23, "device_wait", 194, 229, parent=21),
        # another thread's span overlaps and takes nothing from this one's
        _span(24, "loader", 105, 160, thread=2),
    ]


def test_owned_stretches_never_overlap_within_a_thread_and_cover_every_span():
    spans = _synthetic()
    stretches = host_spans.owned(spans)
    for thread in (1, 2):
        mine = sorted((a, b) for s, a, b in stretches if s.thread == thread)
        assert all(a1 >= b0 for (_, b0), (a1, _) in zip(mine, mine[1:]))
    covered = sum(b - a for s, a, b in stretches if s.thread == 1)
    roots = sum(s.end_ns - s.start_ns for s in spans if s.parent is None and s.thread == 1)
    assert covered == roots
    assert host_spans.leaf_intervals(spans)[0] == ("compile", 1 * MS, 5 * MS)


def test_self_time_is_duration_less_what_the_children_cover():
    spans = _synthetic()
    first = [s for s in spans if 1 <= s.id <= 10]  # the first request and all beneath it
    selfs = host_spans.self_seconds(first)
    assert sum(selfs.values()) == pytest.approx(0.080)  # the root's duration
    assert "request" not in selfs                       # its children cover all of it
    assert selfs["dispatch"] == pytest.approx(0.050 - 0.018 - 0.008 - 0.020)
    assert selfs["trace"] == pytest.approx(0.018)   # the nested trace counted once
    assert selfs["h2d"] == pytest.approx(0.008)
    # clipped to the window: the third request's part inside it counts, cut at 200 ms
    inside = host_spans.self_seconds(spans, 100 * MS, 200 * MS)
    assert inside["h2d"] == pytest.approx(0.010 + 0.008 + 0.010)
    assert inside["device_wait"] == pytest.approx(0.026 + 0.027 + 0.006)
    assert inside["request"] == pytest.approx(0.001 + 0.001 + 0.002)
    assert inside["loader"] == pytest.approx(0.055)
    assert "compile" not in inside


def test_requests_and_phases_by_function():
    spans = _synthetic()
    assert [s.id for s in host_spans.requests(spans, 100 * MS, 200 * MS)] == [11, 16]
    assert len(host_spans.requests(spans)) == 4
    set_up = host_spans.phase_seconds(spans, hi=100 * MS)
    assert set_up["trace"] == pytest.approx({"slide_forward": 0.014, "inner": 0.004})
    assert set_up["lower"] == pytest.approx({"jit(slide_forward)": 0.008})
    assert set_up["compile"] == pytest.approx({"jit(_fill)": 0.004, "jit(slide_forward)": 0.020})
    assert host_spans.phase_seconds(spans, 100 * MS, 200 * MS) == {}


def _ctx_with_window(lo_ms, hi_ms):
    spans = T.HostSpans()
    spans.spans.append((T.WINDOW_SPAN, lo_ms * MS, hi_ms * MS))
    return types.SimpleNamespace(spans=spans)


@pytest.mark.parametrize("kind", MANIFEST_KINDS)
def test_readers_answer_from_the_recorders_spans(kind):
    window = {"program_spans": _synthetic()}
    ctx = _ctx_with_window(100, 200)
    values = {name: harness._layer_reader(name)(name, None, window, ctx)
              for name in host_report.metric_names(kind)}
    assert len(values) == 8 and all(name.endswith("." + kind) for name in values)
    assert values[f"host_self_ms_per_request.h2d.{kind}"] == pytest.approx(14.0)  # 28 ms / 2
    assert values[f"host_self_ms_per_request.dispatch.{kind}"] == pytest.approx(2.0)
    assert values[f"host_self_ms_per_request.device_wait.{kind}"] == pytest.approx(29.5)
    assert values[f"host_self_ms_per_request.d2h.{kind}"] == pytest.approx(1.5)
    assert values[f"host_self_ms_per_request.prepare.{kind}"] == 0.0
    assert values[f"setup_phase_s.trace.{kind}"] == pytest.approx(0.018)
    assert values[f"setup_phase_s.lower.{kind}"] == pytest.approx(0.008)
    assert values[f"setup_phase_s.compile.{kind}"] == pytest.approx(0.024)


@pytest.mark.parametrize("window", [
    {},                                    # a driver's window: no recorder ran
    {"program_spans": []},
    {"program_spans": _synthetic()[:1]},   # spans, and no request inside the window
])
def test_readers_return_none_and_never_raise_where_there_is_nothing_to_read(window):
    for ctx in (_ctx_with_window(100, 200), types.SimpleNamespace(spans=T.HostSpans())):
        for name in host_report.metric_names("slide"):
            value = harness._layer_reader(name)(name, None, window, ctx)
            if name.startswith("setup_phase_s") and window.get("program_spans") \
                    and ctx.spans.spans:
                assert value == pytest.approx(0.004 if ".compile." in name else 0.0)
            else:
                assert value is None


def test_the_traces_own_gap_attribution_runs_over_the_programs_spans():
    """The recorded slide trace with the program's leaf spans in the place of
    the driver's ``h2d`` / ``fetch``: ``reduce_xplane``, unedited, books the
    same idle time to the finer names."""
    fixtures = os.path.join(tables.BENCH_DIR, "fixtures")
    with open(os.path.join(fixtures, "slide_fwd_b16_10k.cut.json")) as f:
        recorded = json.load(f)
    window = next(s for s in recorded["spans"] if s[0] == T.WINDOW_SPAN)
    driver = [s for s in recorded["spans"] if s[0] != T.WINDOW_SPAN]
    program, n = [], 0
    for (_, h0, h1), (_, f0, f1) in zip(driver[0::2], driver[1::2]):
        root = types.SimpleNamespace(id=n, name="request", start_ns=h0, end_ns=f1, parent=None,
                                     thread=1, fields={})
        cuts = [("prepare", h0, h0 + 1000), ("h2d", h0 + 1000, h1), ("dispatch", h1, f0),
                ("device_wait", f0, f1 - 2 * MS), ("d2h", f1 - 2 * MS, f1)]
        program.append(root)
        program += [types.SimpleNamespace(id=n + 1 + i, name=name, start_ns=a, end_ns=b,
                                          parent=n, thread=1, fields={})
                    for i, (name, a, b) in enumerate(cuts)]
        n += 10
    leaves = host_spans.leaf_intervals(program)
    assert [name for name, _, _ in leaves[:5]] == [c for c in host_spans.REQUEST_SPANS]
    r = T.reduce_xplane(os.path.join(fixtures, "slide_fwd_b16_10k.cut.xplane.pb"),
                        [tuple(window)] + leaves, recorded["sync_host_ns"])
    gaps, want = dict(r.idle_gaps), recorded["expected"]["idle_gaps"]
    assert r.busy_s == pytest.approx(recorded["expected"]["busy_s"], rel=1e-12)
    assert gaps["prepare"] + gaps["h2d"] == pytest.approx(want["h2d"], rel=1e-9)
    assert gaps["device_wait"] + gaps.get("d2h", 0.0) == pytest.approx(want["fetch"], rel=1e-9)
    # what lay between the driver's spans is the dispatch now, and the rest no span's
    assert gaps.get("dispatch", 0.0) + gaps["between_spans"] == pytest.approx(
        want["between_spans"], rel=1e-9)
    assert sum(gaps.values()) == pytest.approx(sum(want.values()), rel=1e-9)


@pytest.mark.parametrize("cell", CELLS)
def test_host_report_tiny_prints_the_split_of_a_request(capsys, cell):
    import jax

    # set-up has to trace, lower and compile: not reuse what an earlier test
    # of this process compiled for the same model (the order xdist gives files)
    jax.clear_caches()
    rc = host_report.main(["--workload", cell, "--seed", str(SEED), "--seconds", "0.4", "--tiny"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(out[-1])
    kind = tables.cell_kind(tables.load("workloads", cell))
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert line["requests"] > 0 and line["window_compiles"] == 0
    rate = tables.load("workloads", cell)["end_to_end"]["rate"]
    assert line["entry_loop"][rate] > 0
    assert set(line["spans"]) == {"request", *host_spans.REQUEST_SPANS}
    assert line["spans"]["request"]["count"] == line["requests"]
    # leaf self times are the window, less the loop's own few lines between requests
    assert 0.8 * line["entry_loop"]["window_s"] < line["entry_loop"]["leaf_self_s"] \
        <= line["entry_loop"]["window_s"]
    assert set(line["metrics"]) == set(host_report.metric_names(kind))
    per_request = sum(line["metrics"][f"host_self_ms_per_request.{s}.{kind}"]
                      for s in host_spans.REQUEST_SPANS)
    assert per_request == pytest.approx(
        1e3 * line["entry_loop"]["leaf_self_s"] / line["requests"]
        - line["spans"]["request"]["self_ms_per_request"], rel=1e-6)
    set_up = line["compile_phases"]["set_up"]
    assert set(set_up) == set(host_spans.COMPILE_PHASES) and line["compile_phases"]["window"] == {}
    for phase in host_spans.COMPILE_PHASES:
        assert set_up[phase]["total_s"] == pytest.approx(
            line["metrics"][f"setup_phase_s.{phase}.{kind}"])
    assert any("forward" in f or "encode" in f for f, _ in set_up["compile"]["top"])
    assert line["overhead"]["recorder_off"] > 0 and line["overhead"]["recorder_on"] > 0
    assert line["overhead"]["failed"] == [0, 0]
    assert "device_idle_share" not in line  # a CPU has no device timeline to lay the spans on
