"""bench.py's failure contract: measure on a TPU, or fail.

A run that found no chip, met a ``device_kind`` it has no peak for, or
raised in any workload exits nonzero and prints no JSON line — there is no
probe child, no retry, no stale snapshot republished under another name.
On success the one JSON line names the device it ran on.
"""

import json
import os
import subprocess
import types

import pytest

import bench


@pytest.fixture(autouse=True)
def _obs_stream_in_tmp(tmp_path, monkeypatch):
    # bench.main() appends telemetry to the repo-root BENCH_OBS.jsonl and
    # writes the perf ledger to BENCH_LEDGER.json; tests must not pollute
    # the checkout
    monkeypatch.setattr(bench, "OBS_STREAM", str(tmp_path / "BENCH_OBS.jsonl"))
    monkeypatch.setattr(bench, "BENCH_LEDGER", str(tmp_path / "BENCH_LEDGER.json"))


def _stdout_lines(capsys):
    return capsys.readouterr().out.strip().splitlines()


@pytest.fixture
def fake_tpu(monkeypatch):
    """One described ``tpu`` device and a slide workload cut to nothing:
    ``run_bench`` walks its whole control flow (device check, peaks table,
    three workloads, payload) without compiling the flagship on the CPU.
    The tile phase is each test's to supply."""
    import jax

    from gigapath_tpu.models import slide_encoder
    from gigapath_tpu.utils import timing

    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite", id=0)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])
    create = slide_encoder.create_model
    monkeypatch.setattr(
        slide_encoder, "create_model",
        lambda path, arch, **kw: create("", "gigapath_slide_enc_tiny", **kw),
    )
    monkeypatch.setattr(bench, "N", 64)
    monkeypatch.setattr(
        timing, "chained_seconds_per_iter", lambda *a, **k: (0.5, 0.0)
    )
    return dev


def _tile_ok(peak, ledger=None):
    return 200.0, 0.5, 100.0, "analytic"


def test_non_tpu_platform_fails_without_a_value(capsys):
    """On the CPU platform the run raises before any workload: nonzero
    exit for ``python bench.py``, and nothing on stdout that a reader
    could take for a measurement."""
    with pytest.raises(RuntimeError, match="platform 'cpu'"):
        bench.main()
    out = _stdout_lines(capsys)
    assert out == [], f"a failed run must print no JSON line, got {out}"
    assert not os.path.exists(bench.BENCH_LEDGER)
    with open(bench.OBS_STREAM) as f:
        events = [json.loads(line) for line in f]
    end = [e for e in events if e.get("kind") == "run_end"]
    assert end and end[-1]["status"] == "error"


def test_unknown_device_kind_is_an_error_not_a_default():
    assert bench.chip_peak_flops("TPU v5 lite") == 197e12
    assert bench.chip_peak_flops("TPU v5e") == 197e12
    with pytest.raises(KeyError, match="no peak FLOP/s on record"):
        bench.chip_peak_flops("TPU v9 mega")
    with pytest.raises(KeyError):
        bench.chip_peak_flops("cpu")


def test_peak_takes_no_environment_override(monkeypatch):
    monkeypatch.setenv("TPU_PEAK_FLOPS", "1.0")
    assert bench.chip_peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        bench.chip_peak_flops("some future chip")


def test_unknown_kind_on_tpu_platform_fails_the_run(fake_tpu, capsys):
    fake_tpu.device_kind = "TPU v9 mega"
    with pytest.raises(KeyError, match="TPU v9 mega"):
        bench.main()
    assert _stdout_lines(capsys) == []


def test_tile_phase_exception_propagates(fake_tpu, capsys, monkeypatch):
    """A tile-encoder failure fails the run: no nulled tile fields beside
    a slide number."""

    def boom(peak, ledger=None):
        raise ValueError("tile phase exploded")

    monkeypatch.setattr(bench, "bench_tile_encoder", boom)
    with pytest.raises(ValueError, match="tile phase exploded"):
        bench.main()
    assert _stdout_lines(capsys) == []
    assert not os.path.exists(bench.BENCH_LEDGER)


def test_json_line_names_the_device(fake_tpu, capsys, monkeypatch):
    monkeypatch.setattr(bench, "bench_tile_encoder", _tile_ok)
    assert bench.main() == 0
    out = _stdout_lines(capsys)
    assert len(out) == 1, f"stdout must be exactly one JSON line, got {out}"
    payload = json.loads(out[0])
    assert payload["platform"] == "tpu"
    assert payload["device_kind"] == "TPU v5 lite"
    assert payload["device_count"] == 1
    assert payload["value"] == round(64 / 0.5, 1)
    assert payload["tile_tiles_per_sec"] == 200.0
    for gone in ("stale", "last_good", "last_good_value", "error"):
        assert gone not in payload


def test_bench_starts_no_child_process(fake_tpu, capsys, monkeypatch):
    """A chip belongs to one process: a probe child would take it before
    the parent does. Any attempt to start one fails this test."""

    def refuse(*a, **k):
        raise AssertionError("bench.py must not start a child process")

    for name in ("Popen", "run", "call", "check_call", "check_output"):
        monkeypatch.setattr(subprocess, name, refuse)
    monkeypatch.setattr(os, "system", refuse)
    monkeypatch.setattr(bench, "bench_tile_encoder", _tile_ok)
    assert bench.main() == 0
    assert len(_stdout_lines(capsys)) == 1


def test_success_embeds_ledger_and_headline_profile_fields(capsys, monkeypatch):
    """The success JSON line carries the ledger path plus headline
    compiled-FLOPs / peak-HBM fields WITHOUT breaking the
    one-line-stdout contract."""

    def fake_run_bench(runlog=None, ledger=None):
        # what run_bench returns after ledgering the slide forward
        return {
            "platform": "tpu",
            "device_kind": "TPU v5 lite",
            "device_count": 1,
            "metric": "slide_embed_tokens_per_sec",
            "value": 138400.0,
            "unit": "tokens/s",
            "peak_hbm_gb": 0.63,
            "compiled_flops": 3.0e12,
            "ledger": ledger.path if ledger is not None else None,
        }

    monkeypatch.setattr(bench, "run_bench", fake_run_bench)
    assert bench.main() == 0
    out = _stdout_lines(capsys)
    assert len(out) == 1, f"stdout must be exactly one JSON line, got {out}"
    payload = json.loads(out[0])
    assert payload["value"] == 138400.0
    assert payload["compiled_flops"] == 3.0e12
    assert payload["peak_hbm_gb"] == 0.63
    assert payload["ledger"] == bench.BENCH_LEDGER
    assert os.path.exists(bench.BENCH_LEDGER)
