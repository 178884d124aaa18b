"""Perf ledger subsystem: fingerprints, capture, canonical ledgers,
ledger_diff, and the flagship ledger's signals.

The ISSUE-4 acceptance contracts pinned here:

- the flagship ledger (``scripts/refresh_ledger.build_golden_ledger``,
  built fresh here: no golden file is committed — one that pins jaxpr
  primitive counts is broken by every PR on the dilated path and so
  guards nothing) carries the round-6, ring and fold signals;
- injecting a synthetic regression (doubling a branch's eqn count,
  inflating FLOPs, dropping a donation) flips the verdict JSON to
  failing;
- capture through ``CompileWatchdog`` adds no visible retraces and the
  first-signature-full / later-signatures-fingerprint policy holds.
"""

import copy
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from gigapath_tpu.obs import (
    CompileWatchdog,
    NullLedger,
    PerfLedger,
    RunLog,
    capture_profile,
    get_ledger,
    jaxpr_fingerprint,
)
from gigapath_tpu.obs.ledger import shape_signature, write_ledger

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))

import ledger_diff  # noqa: E402
import refresh_ledger  # noqa: E402



def read_events(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


# ---------------------------------------------------------------------------
# fingerprints & profiles
# ---------------------------------------------------------------------------

class TestFingerprint:
    def test_counts_primitives_with_fixed_columns(self):
        fp = jaxpr_fingerprint(lambda x: (x @ x.T).reshape(-1), jnp.ones((4, 4)))
        assert fp["eqns_total"] >= 2
        assert fp["primitives"]["reshape"] >= 1
        # the PERFORMANCE.md columns are always present, even at zero
        for col in ("transpose", "slice", "broadcast_in_dim", "pallas_call"):
            assert col in fp["primitives"]

    def test_recurses_into_sub_jaxprs(self):
        inner = jax.jit(lambda x: x.reshape(2, 2).T)
        fp = jaxpr_fingerprint(lambda x: inner(x) + 1, jnp.ones((4,)))
        # the reshape/transpose live inside the pjit sub-jaxpr
        assert fp["primitives"]["reshape"] >= 1
        assert fp["primitives"]["transpose"] >= 1

    def test_shape_signature(self):
        sig = shape_signature(
            (jnp.ones((2, 3)), {"w": 1, "b": 2}), {"y": jnp.ones(4)}
        )
        assert sig == "float32[2,3];tree{2};y=float32[4]"


class TestCaptureProfile:
    def test_full_profile_has_cost_memory_jaxpr(self):
        p = capture_profile(lambda x: (x @ x).sum(), jnp.ones((8, 8)))
        assert p["cost"]["flops"] > 0
        assert p["memory"]["argument_bytes"] > 0
        assert p["memory"]["peak_bytes"] >= p["memory"]["argument_bytes"]
        assert p["jaxpr"]["eqns_total"] > 0

    def test_trace_only_skips_compile(self):
        p = capture_profile(lambda x: x + 1, jnp.ones(4), full=False)
        assert "cost" not in p and "memory" not in p
        assert p["jaxpr"]["eqns_total"] >= 1

    def test_donated_buffer_accounting(self):
        fn = jax.jit(lambda x: x + 1, donate_argnums=0)
        p = capture_profile(fn, jnp.ones((128,)))
        assert p["memory"]["donated_bytes"] == 512.0
        # the donated input aliases the output: peak excludes it once
        assert p["memory"]["peak_bytes"] == pytest.approx(
            p["memory"]["argument_bytes"] + p["memory"]["temp_bytes"]
        )


# ---------------------------------------------------------------------------
# PerfLedger
# ---------------------------------------------------------------------------

class TestPerfLedger:
    def test_dedup_and_canonical_rewrite(self, tmp_path):
        path = str(tmp_path / "run.ledger.json")
        led = PerfLedger(path=path)
        fn = lambda x: (x * 2).sum()  # noqa: E731
        led.capture("step", fn, jnp.ones((2, 8)))
        led.capture("step", fn, jnp.ones((2, 8)))  # same signature: dedup
        led.capture("step", fn, jnp.ones((2, 16)))
        assert len(led.entries) == 2
        first = open(path, "rb").read()
        led.write()
        assert open(path, "rb").read() == first  # canonical: stable bytes
        doc = json.loads(first)
        assert doc["v"] == 1
        assert list(doc["entries"]) == sorted(doc["entries"])

    def test_full_then_fingerprint_policy(self, tmp_path):
        led = PerfLedger(path=str(tmp_path / "l.json"))
        fn = lambda x: x.sum()  # noqa: E731
        e1 = led.capture("step", fn, jnp.ones((4,)))
        e2 = led.capture("step", fn, jnp.ones((8,)))
        e3 = led.capture_full("step", fn, jnp.ones((16,)))
        assert e1["cost"] is not None and "memory" in e1
        assert "cost" not in e2  # later signature: fingerprint-only
        assert e3["cost"] is not None  # explicit full override
        # capture_full UPGRADES an existing fingerprint-only entry
        e2b = led.capture_full("step", fn, jnp.ones((8,)))
        assert e2b["cost"] is not None

    def test_deferred_autowrite(self, tmp_path):
        """bench's mode: captures buffer in memory, the file lands only
        on the explicit success-path write()."""
        path = str(tmp_path / "l.json")
        led = PerfLedger(path=path, autowrite=False)
        led.capture_full("f", lambda x: x.sum(), jnp.ones((4,)))
        assert not os.path.exists(path)
        led.write()
        assert os.path.exists(path)

    def test_ledger_path_derives_from_runlog(self, tmp_path):
        log = RunLog(str(tmp_path / "obs" / "run.jsonl"), driver="t",
                     run_id="r-1", echo=False)
        led = get_ledger(log)
        assert led.path == str(tmp_path / "obs" / "r-1.ledger.json")
        led.capture("f", lambda x: x, jnp.ones(2))
        assert os.path.exists(led.path)
        events = read_events(log.path)
        assert [ev["kind"] for ev in events] == ["compile_profile"]
        assert events[0]["name"] == "f"
        assert events[0]["jaxpr"]["eqns_total"] >= 0
        log.close()

    def test_null_ledger_under_obs_off(self, tmp_path, monkeypatch):
        from gigapath_tpu.obs import get_run_log

        monkeypatch.setenv("GIGAPATH_OBS", "0")
        log = get_run_log("t", out_dir=str(tmp_path))
        led = get_ledger(log)
        assert isinstance(led, NullLedger) and not isinstance(led, PerfLedger)
        assert led.capture("f", lambda x: x, jnp.ones(2)) is None
        assert led.write() is None
        assert list(tmp_path.iterdir()) == []  # no files, no obs dir

    def test_capture_failure_is_contained(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        log = RunLog(path, driver="t", echo=False)
        led = get_ledger(log)
        assert led.capture("bad", lambda x: x.no_such_attr, jnp.ones(2)) is None
        (ev,) = read_events(path)
        assert ev["kind"] == "compile_profile" and "error" in ev
        log.close()


class TestWatchdogLedgerHook:
    def test_wrap_ledgers_each_new_key(self, tmp_path):
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        led = get_ledger(log)
        fn = jax.jit(lambda x: x * 2)
        wd = CompileWatchdog("step", log, ledger=led)
        wrapped = wd.wrap(fn)
        for _ in range(3):
            wrapped(jnp.ones((2, 8)))
        wrapped(jnp.ones((2, 16)))
        assert len(led.entries) == 2
        # first key full, second fingerprint-only
        entries = [led.entries[k] for k in sorted(led.entries)]
        assert sum("cost" in e for e in entries) == 1
        log.close()

    def test_profile_method_for_record_surface_loops(self, tmp_path):
        led = PerfLedger(path=str(tmp_path / "l.json"))
        wd = CompileWatchdog("train_step", ledger=led)
        wd.record((1, 128), 0.5)
        wd.profile((1, 128), lambda x: x.sum(), jnp.ones((1, 128)))
        assert len(led.entries) == 1
        wd2 = CompileWatchdog("train_step")  # no ledger: a no-op
        wd2.profile((1, 128), lambda x: x.sum(), jnp.ones((1, 128)))


# ---------------------------------------------------------------------------
# ledger_diff
# ---------------------------------------------------------------------------

class TestLedgerDiff:
    def test_selftest_passes(self):
        assert ledger_diff.selftest() == 0

    def test_cli_missing_file_exits_2(self, tmp_path):
        missing = str(tmp_path / "nope.json")
        assert ledger_diff.main([missing, missing]) == 2

    def test_cli_roundtrip_and_verdict_json(self, tmp_path):
        led = PerfLedger(path=str(tmp_path / "a.json"))
        led.capture("f", lambda x: (x @ x).sum(), jnp.ones((8, 8)))
        base, cand = str(tmp_path / "a.json"), str(tmp_path / "b.json")
        doc = json.loads(open(base).read())
        write_ledger(doc, cand)
        out = str(tmp_path / "verdict.json")
        assert ledger_diff.main([base, cand, "--json", out]) == 0
        verdict = json.load(open(out))
        assert verdict["decision"]["ok"] is True

        # synthetic regression: eqn growth must flip the CLI to rc=1
        doc2 = copy.deepcopy(doc)
        entry = next(iter(doc2["entries"].values()))
        entry["jaxpr"]["eqns_total"] += 5
        write_ledger(doc2, cand)
        assert ledger_diff.main([base, cand, "--json", out]) == 1
        verdict = json.load(open(out))
        assert verdict["decision"]["ok"] is False


# ---------------------------------------------------------------------------
# the golden flagship ledger (ISSUE acceptance)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fresh_flagship():
    """Build the flagship ledger ONCE per test module (the expensive
    part: ~15 s of tracing + one tiny-slide-encoder compile on CPU)."""
    ledger, meta = refresh_ledger.build_golden_ledger()
    return {
        "v": 1, **meta,
        "entries": {k: ledger.entries[k] for k in sorted(ledger.entries)},
    }


def test_golden_covers_the_round6_signal(fresh_flagship):
    """The golden pins the round-6 PERFORMANCE.md table's machine form:
    the stream epilogue admits ZERO dense-glue transpose/slice/broadcast
    eqns while the dense fused path still materializes them."""
    entries = fresh_flagship["entries"]
    stream = next(v for k, v in entries.items()
                  if k.startswith("dilated_stream_fwd"))
    fused = next(v for k, v in entries.items()
                 if k.startswith("dilated_fused_fwd"))
    for prim in ("transpose", "slice", "broadcast_in_dim"):
        assert stream["jaxpr"]["primitives"][prim] == 0, prim
        assert fused["jaxpr"]["primitives"][prim] > 0, prim
    assert stream["jaxpr"]["eqns_total"] < fused["jaxpr"]["eqns_total"]
    slide = next(v for k, v in entries.items()
                 if k.startswith("slide_enc_tiny_fwd"))
    assert slide["cost"]["flops"] > 0
    assert slide["memory"]["peak_bytes"] > 0


def test_golden_covers_the_ring_signal(fresh_flagship):
    """The golden pins the ring acceptance: the ring path's traced
    program moves K/V exclusively by ppermute (ZERO all_gather — the
    hoisted counts gather does not exist on the unmasked golden shape),
    the reverse ring of the custom VJP adds its own permutes, and the
    gather baseline still materializes one all_gather per K/V tensor."""
    entries = fresh_flagship["entries"]

    def entry(prefix):
        return next(v for k, v in entries.items() if k.startswith(prefix))

    ring_fwd = entry("dilated_ring_fwd")["jaxpr"]["primitives"]
    ring_grad = entry("dilated_ring_grad")["jaxpr"]["primitives"]
    gather_fwd = entry("dilated_ring_gather_fwd")["jaxpr"]["primitives"]
    assert ring_fwd["all_gather"] == 0
    assert ring_fwd["ppermute"] > 0
    assert ring_grad["all_gather"] == 0
    assert ring_grad["ppermute"] > ring_fwd["ppermute"]  # reverse ring
    assert gather_fwd["all_gather"] == 2  # K and V, full segment
    assert gather_fwd["ppermute"] == 0


def test_golden_covers_the_fold_signal(fresh_flagship):
    """The golden pins the Pallas streaming-fold acceptance (ISSUE 20):
    the Pallas fold traces ZERO dense mask equations (masks become
    in-kernel iota comparisons inside the opaque pallas_call) while the
    jnp control still materializes square bool masks — and the compiled
    Pallas fold lowers with strictly fewer temp bytes than the jnp fold
    at the 16k smoke geometry."""
    entries = fresh_flagship["entries"]

    def entry(prefix):
        return next(v for k, v in entries.items() if k.startswith(prefix))

    jnp_e = entry("stream_fold_jnp|")
    pallas_e = entry("stream_fold_pallas|")
    assert jnp_e["jaxpr"]["mask"] > 0
    assert pallas_e["jaxpr"]["mask"] == 0
    assert pallas_e["jaxpr"]["primitives"]["pallas_call"] >= 1
    assert jnp_e["jaxpr"]["primitives"].get("pallas_call", 0) == 0
    # grads keep the discipline: stored-lse bwd, still zero dense masks
    assert entry("stream_fold_jnp_grad")["jaxpr"]["mask"] > 0
    assert entry("stream_fold_pallas_grad")["jaxpr"]["mask"] == 0
    # the compiled-memory half of the acceptance pin
    assert pallas_e["memory"]["temp_bytes"] < jnp_e["memory"]["temp_bytes"]
    assert pallas_e["memory"]["peak_bytes"] < jnp_e["memory"]["peak_bytes"]


def test_synthetic_regression_flips_verdict(tmp_path, fresh_flagship):
    """Acceptance: doubling a branch's eqn count in a copy of the
    flagship ledger flips the ledger_diff verdict JSON to failing."""
    base = str(tmp_path / "flagship.json")
    write_ledger(fresh_flagship, base)
    regressed = copy.deepcopy(fresh_flagship)
    key = next(k for k in regressed["entries"]
               if k.startswith("dilated_stream_fwd"))
    entry = regressed["entries"][key]
    entry["jaxpr"]["eqns_total"] *= 2
    entry["jaxpr"]["primitives"]["pallas_call"] *= 2
    cand = str(tmp_path / "regressed.json")
    write_ledger(regressed, cand)
    out = str(tmp_path / "verdict.json")
    rc = ledger_diff.main([base, cand, "--json", out])
    assert rc == 1
    verdict = json.load(open(out))
    assert verdict["decision"]["ok"] is False
    assert any("pallas_call" in line for line in verdict["decision"]["regressed"])


def test_refresh_refuses_to_overwrite_on_regression(tmp_path, monkeypatch,
                                                    fresh_flagship):
    """scripts/refresh_ledger.sh contract: regeneration that would regress
    the golden exits 1 and leaves the file untouched unless --force."""
    golden_doc = fresh_flagship
    fresh = copy.deepcopy(golden_doc)
    key = next(iter(fresh["entries"]))
    fresh["entries"][key]["jaxpr"]["eqns_total"] += 100  # a would-be regression

    class FakeLedger:
        entries = fresh["entries"]

    meta = {k: v for k, v in fresh.items() if k not in ("v", "entries")}
    monkeypatch.setattr(refresh_ledger, "build_golden_ledger",
                        lambda: (FakeLedger(), meta))
    target = str(tmp_path / "golden.json")
    write_ledger(golden_doc, target)
    before = open(target, "rb").read()
    assert refresh_ledger.regenerate(target, force=False) == 1
    assert open(target, "rb").read() == before  # untouched
    assert refresh_ledger.regenerate(target, check=True) == 1  # --check agrees
    assert refresh_ledger.regenerate(target, force=True) == 0
    assert json.load(open(target))["entries"][key]["jaxpr"]["eqns_total"] == \
        fresh["entries"][key]["jaxpr"]["eqns_total"]
