"""gigalint wiring: the tree stays clean, and the pass itself works.

Two contracts, both from ISSUE/acceptance:

1. ``python -m tools.gigalint gigapath_tpu scripts`` (and the wider
   gigapath_tpu+scripts+tests scan that lint.sh runs) exits 0 on this
   tree — every finding fixed or explicitly waived with a reason.
2. The seeded-violation fixture tree under tools/gigalint/selftest/
   makes the pass exit NONZERO with every rule class (GL001–GL005)
   firing at least once, while the negative controls stay clean.

These run in the default tier, so every ``pytest -q`` is also a lint run.
"""

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = "tools/gigalint/selftest/fixture"

sys.path.insert(0, REPO_ROOT)

from tools.gigalint.cli import run_lint  # noqa: E402


def test_acceptance_scan_is_clean():
    """The ISSUE acceptance command: gigapath_tpu + scripts, waivers on."""
    result = run_lint(["gigapath_tpu", "scripts"], root=REPO_ROOT)
    assert result.errors == []
    assert result.findings == [], "\n".join(f.text() for f in result.findings)
    assert result.exit_code == 0


def test_full_scan_with_tests_is_clean():
    """The lint.sh scan: tests/ included, so GL005 (pytest hygiene) and
    the test-file-induced trace roots are enforced too."""
    result = run_lint(["gigapath_tpu", "scripts", "tests"], root=REPO_ROOT)
    assert result.errors == []
    assert result.findings == [], "\n".join(f.text() for f in result.findings)
    # the waiver file is in active use — every entry must earn its keep
    assert result.waived, "expected the documented waivers to be exercised"


def test_fixture_tree_fires_every_rule_class():
    result = run_lint([FIXTURE], root=REPO_ROOT, waiver_file=None)
    assert result.exit_code != 0
    fired = {f.rule for f in result.findings}
    expected = {"GL001", "GL002", "GL003", "GL004", "GL005", "GL006",
                "GL007", "GL008", "GL009", "GL010", "GL011", "GL012",
                "GL013", "GL014", "GL015", "GL016", "GL017", "GL022",
                "GL023"}
    assert fired >= expected, (
        f"missing rule classes: {sorted(expected - fired)}"
    )


def test_fixture_negative_controls_stay_clean():
    result = run_lint([FIXTURE], root=REPO_ROOT, waiver_file=None)
    for f in result.findings:
        assert "negative_control" not in f.symbol, f.text()
        assert "test_fixture_fast_without_features" not in f.symbol, f.text()
        # GL017's function-name sanction: the fixture's snapshot_flags
        # twin reads a dispatch flag and must stay clean
        assert not (f.rule == "GL017" and "snapshot_flags" in f.symbol), (
            f.text()
        )


def test_fixture_specific_findings():
    """Each seeded violation is found at its seeded location."""
    result = run_lint([FIXTURE], root=REPO_ROOT, waiver_file=None)
    got = {(f.rule, f.path.rsplit("/", 1)[-1], f.symbol) for f in result.findings}
    expected = {
        ("GL001", "kernels.py", "env_helper"),       # direct read, reachable
        ("GL001", "kernels.py", "kernel_dispatch"),  # helper call + direct
        ("GL002", "kernels.py", "leaky"),
        # compound condition: an is-None guard must not shadow the leak
        ("GL002", "kernels.py", "leaky_compound"),
        ("GL003", "net.py", "uncovered_proj"),
        ("GL003", "net.py", "<anonymous>"),
        ("GL004", "net.py", "make_net"),
        ("GL004", "net.py", "eval"),
        ("GL004", "net.py", "except"),
        ("GL005", "test_hygiene.py", "test_fixture_flag_parity_slow"),
        ("GL005", "test_hygiene.py", "test_fixture_seq_parallel_slow"),
        ("GL006", "driver.py", "noisy_train_loop"),
        ("GL006", "driver.py", "<module>"),
        ("GL007", "driver.py", "undocumented_flag_knob"),
        # unfenced wall-clock deltas around device work (direct jit call
        # and a watchdog.wrap-bound handle)
        ("GL008", "timing.py", "timed_no_fence"),
        ("GL008", "timing.py", "timed_wrapped_no_fence"),
        # span(fence=None) is explicitly unfenced: no fence credit
        ("GL008", "timing.py", "timed_span_fence_none"),
        # seq-parallel collective without a _SEQ_COLLECTIVES entry (the
        # sanctioned twin in sanctioned_ring.py is the negative control)
        ("GL009", "ring.py", "ring_exchange_unregistered"),
        # open-ended jax.profiler pair outside obs/spans.py (the
        # fixture's own obs/spans.py twin is the negative control)
        ("GL010", "profiler.py", "trace_by_hand"),
        # signal.signal outside obs/flight.py (the fixture's own
        # obs/flight.py twin is the negative control)
        ("GL011", "handlers.py", "install_cleanup_handler"),
        # hand-rolled latency aggregation (time deltas -> list.append ->
        # sort) outside obs/ (the fixture's own obs/metrics.py twin is
        # the negative control, as are timing-without-sort and
        # sort-without-timing)
        ("GL012", "latency.py", "aggregate_latency_by_hand"),
        ("GL012", "latency.py", "aggregate_latency_sorted_copy"),
        # attribute-owned list (sorted(self._walls)) — the serving-stats
        # shape must not slip past a bare-Name-only sorted() check
        ("GL012", "latency.py", "LatencyStat.aggregate"),
        # unbounded hand-rolled inter-thread channels (the fixture's
        # own dist/boundary.py + serve/queue.py twins are the sanctioned
        # negative controls, rolling.py the no-threading deque control)
        ("GL013", "channels.py", "unbounded_queue_channel"),
        ("GL013", "channels.py", "unbounded_deque_channel"),
        # chunk reassembly inside a streaming-sanctioned module (the
        # fixture twins ops/streaming_prefill.py by path suffix; the
        # *dense_fallback* oracle stays a negative control)
        ("GL014", "streaming_prefill.py", "reassemble_chunks"),
        ("GL014", "streaming_prefill.py", "stack_chunks_for_readout"),
        # maxsize=-1 is Python's explicitly-INFINITE queue, not a bound
        ("GL013", "channels.py", "unbounded_queue_negative_maxsize"),
        # raw socket plumbing outside the sanctioned dist/transport.py
        # (whose fixture twin is the negative control for the
        # connection-primitive check)...
        ("GL015", "sockets.py", "open_raw_socket"),
        ("GL015", "sockets.py", "dial_without_deadline"),
        ("GL015", "sockets.py", "serve_with_socketserver"),
        ("GL015", "sockets.py", "recv_without_timeout"),
        # a 3-positional select.select(r, w, x) blocks forever: no
        # deadline credit (only selectors' select(timeout) or stdlib's
        # 4th positional count)
        ("GL015", "sockets.py", "select_without_timeout"),
        # ...and the deadline discipline fires EVEN inside the
        # sanctioned transport module
        ("GL015", "transport.py", "recv_without_deadline"),
        # raw low-precision casts outside the sanctioned quant/ package
        # (the fixture's own quant/qtensor.py twin is the negative
        # control, as are the bf16/uint8/int32 casts in lowprec.py)
        ("GL016", "lowprec.py", "cast_weights_by_hand"),
        ("GL016", "lowprec.py", "pack_activations"),
        ("GL016", "lowprec.py", "fp8_by_hand"),
        ("GL016", "lowprec.py", "stage_buffer"),
        # kernel-dispatch flag reads outside snapshot_flags
        # (dispatch.py::snapshot_flags is the function-name negative
        # control; host flags — models/host_flags.py holds the quant
        # tier and the chunked-prefill default — and dynamic names
        # stay out of scope)
        ("GL017", "dispatch.py", "read_variant_flag_by_hand"),
        ("GL017", "dispatch.py", "block_override_by_hand"),
        ("GL017", "dispatch.py", "helper_env_flag_read"),
        ("GL017", "dispatch.py", "subscript_read"),
        # untraced spans in dist/ library code (the fixture twins
        # dist/worker.py; the traced span and the manual ctx.add_span
        # call are the negative controls): a missing trace= kwarg and
        # an explicit trace=None both fall out of the fleet timeline
        ("GL022", "worker.py", "untraced_encode_span"),
        ("GL022", "worker.py", "untraced_none_span"),
        # hand-rolled running-moment accumulators (Welford triple by
        # hand) outside obs/ (the sketch-routed path, the mean-only
        # loop and the count-plus-product loop are the negative
        # controls)
        ("GL023", "moments.py", "running_moments_by_hand"),
        ("GL023", "moments.py", "MomentTracker.observe"),
    }
    assert expected <= got, f"missing: {sorted(expected - got)}"


def test_cli_json_output_and_exit_codes():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.gigalint", "--json", "--no-waivers",
         FIXTURE],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert proc.returncode == 1, proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"], "JSON output must carry the findings"
    assert all(
        {"rule", "path", "lineno", "symbol", "message"} <= set(f)
        for f in payload["findings"]
    )

    proc = subprocess.run(
        [sys.executable, "-m", "tools.gigalint", "gigapath_tpu", "scripts"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_waiver_without_reason_is_an_error(tmp_path):
    waivers = tmp_path / "WAIVERS"
    waivers.write_text("GL004 somewhere.py\n")  # no '-- reason'
    result = run_lint([FIXTURE], root=REPO_ROOT, waiver_file=str(waivers))
    assert any("justification" in e for e in result.errors)
    assert result.exit_code == 2


def test_waiver_suppresses_with_reason(tmp_path):
    waivers = tmp_path / "WAIVERS"
    waivers.write_text(
        "GL004 tools/gigalint/selftest/fixture/models/net.py::eval"
        " -- fixture: seeded violation\n"
    )
    result = run_lint([FIXTURE], root=REPO_ROOT, waiver_file=str(waivers))
    assert not any(
        f.rule == "GL004" and f.symbol == "eval" for f in result.findings
    )
    assert any(
        f.rule == "GL004" and f.symbol == "eval"
        and f.waived_by == "fixture: seeded violation"
        for f in result.waived
    )


def test_inline_waiver(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def f(path):\n"
        "    return eval(path)  # gigalint: waive GL004 -- test inline\n"
    )
    result = run_lint([str(bad)], root=REPO_ROOT, waiver_file=None)
    assert result.findings == []
    assert any(f.waived_by == "inline: test inline" for f in result.waived)


def test_lint_sh_exists_and_points_at_the_tool():
    script = os.path.join(REPO_ROOT, "scripts", "lint.sh")
    assert os.path.exists(script)
    with open(script) as f:
        body = f.read()
    assert "tools.gigalint" in body
    assert os.access(script, os.X_OK), "lint.sh must be executable"


# ---------------------------------------------------------------------------
# stale waivers: matched-but-unused entries are ERRORS, not warnings
# ---------------------------------------------------------------------------

STALE_FIXTURE_WAIVERS = "tools/gigalint/selftest/stale_waivers/WAIVERS"


def test_stale_waiver_fixture_classifies_all_three_ways():
    """The committed fixture seeds one USED entry, one STALE entry
    (glob in scope, suppresses nothing -> error, exit 2), and one
    OUT-OF-SCOPE entry (warning only)."""
    result = run_lint(
        ["tools/gigalint/selftest/fixture/models/timing.py"],
        root=REPO_ROOT, waiver_file=STALE_FIXTURE_WAIVERS,
        strict_waivers=True,
    )
    assert result.exit_code == 2
    stale = [e for e in result.errors if "stale waiver" in e]
    assert len(stale) == 1, result.errors
    assert "no_such_symbol_seeded_stale" in stale[0]
    # it names the waiver file line so the purge is one click away
    assert STALE_FIXTURE_WAIVERS + ":" in stale[0]
    assert result.unused_waivers == [
        "GL008 gigapath_tpu/models/no_such_file_seeded.py"
    ]
    # the used entry raised no complaint of either kind
    assert not any("USED" in e for e in result.errors)


def test_stale_waiver_silent_under_select():
    """With --select a waiver's rule may simply not have run — no stale
    errors, no unused warnings (pruning on partial evidence would break
    the full run)."""
    result = run_lint(
        ["tools/gigalint/selftest/fixture/models/timing.py"],
        root=REPO_ROOT, waiver_file=STALE_FIXTURE_WAIVERS,
        select=["GL004"], strict_waivers=True,
    )
    assert not any("stale waiver" in e for e in result.errors)
    assert result.unused_waivers == []


def test_repo_waiver_file_has_no_stale_entries():
    """The purge contract: lint.sh's canonical strict scan must never
    carry a matched-but-dead suppression at HEAD. (Strict only holds on
    the FULL scope — reachability rules draw evidence from tests/.)"""
    result = run_lint(["gigapath_tpu", "scripts", "tests"], root=REPO_ROOT,
                      strict_waivers=True)
    stale = [e for e in result.errors if "stale waiver" in e]
    assert stale == [], "\n".join(stale)
    assert result.exit_code == 0


# ---------------------------------------------------------------------------
# --jobs: parallel parsing is invisible in the output
# ---------------------------------------------------------------------------

def _fingerprint(result):
    return (
        [(f.rule, f.path, f.lineno, f.symbol, f.message)
         for f in result.findings],
        [(f.rule, f.path, f.lineno, f.symbol, f.waived_by)
         for f in result.waived],
        result.errors,
        result.scanned,
        result.unused_waivers,
    )


def test_jobs_output_is_deterministic():
    """Findings, waivers, errors and their ORDER are byte-identical at
    any parallelism — Executor.map pins module order to discovery
    order, and everything downstream sorts."""
    serial = run_lint([FIXTURE], root=REPO_ROOT, waiver_file=None, jobs=1)
    for jobs in (2, 8):
        parallel = run_lint(
            [FIXTURE], root=REPO_ROOT, waiver_file=None, jobs=jobs,
        )
        assert _fingerprint(parallel) == _fingerprint(serial), (
            f"jobs={jobs} changed the output"
        )
    assert serial.findings, "fixture scan should find the seeded violations"


def test_jobs_parse_errors_keep_position(tmp_path):
    """A syntactically broken file reports the same error at the same
    list position regardless of which worker hit it."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "a_ok.py").write_text("x = 1\n")
    (pkg / "broken.py").write_text("def f(:\n")
    (pkg / "z_ok.py").write_text("y = 2\n")
    results = [
        run_lint(["pkg"], root=str(tmp_path), waiver_file=None, jobs=jobs)
        for jobs in (1, 4)
    ]
    for r in results:
        assert r.scanned == 2
        assert len(r.errors) == 1 and "syntax error" in r.errors[0]
    assert results[0].errors == results[1].errors
