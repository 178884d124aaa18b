"""Model-health observability contracts (ISSUE 19).

Four surfaces, each pinned both ways:

- **numerics** (`gigapath_tpu.obs.numerics`): flag-off the step lowers
  to byte-identical HLO (the summaries are never traced); flag-on the
  summaries are shape-static, so steps 2..N reuse step 1's executable —
  zero retraces. NaN propagation is explicit: a non-finite layer owns
  the worst-absmax verdict.
- **EmbeddingSketch** (`gigapath_tpu.obs.drift`): Chan's merge is
  associative and equivalent to single-pass folding; save/load is
  bit-exact (restart-resume keeps producing the same sketch); a
  tampered artifact is refused loudly (`CorruptDriftArtifact`).
- **DriftSentinel + `embedding_drift` detector**: a chaos-shifted
  serve fires EXACTLY ONE anomaly (with flight dump) per regime —
  transition-edged, terminal status never fires; a clean serve fires
  none.
- **anytime peeks** (`StreamingEncoderSession.peek`): provisional
  embeddings converge to the finalized one as the frontier advances,
  and the full-frontier peek is BIT-exact vs `finalize()` (identical
  op sequence) — the anchor of the `serve.stream_confidence` surface.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapath_tpu.obs.drift import (
    CorruptDriftArtifact,
    DriftSentinel,
    EmbeddingSketch,
    cosine,
    drift_scores,
)
from gigapath_tpu.obs.numerics import (
    NumericsMonitor,
    group_summaries,
    numerics_enabled,
    numerics_layers,
    numerics_scalars,
    split_numerics,
)
from gigapath_tpu.obs.runlog import RunLog
from gigapath_tpu.obs.telemetry import step_scalars


def _read_events(path):
    with open(path, encoding="utf-8") as fh:
        return [json.loads(line) for line in fh if line.strip()]


# ---------------------------------------------------------------------------
# numerics: in-graph summaries behind GIGAPATH_NUMERICS
# ---------------------------------------------------------------------------


def _toy_params():
    return {
        "encoder": {"w": jnp.ones((4, 4)) * 0.5, "b": jnp.zeros((4,))},
        "head": {"w": jnp.ones((4, 2)) * 0.25},
    }


def _make_step(numerics_on: bool):
    """The finetune gate, verbatim shape: a Python bool decides at
    trace time whether the summary reductions exist in the program."""

    def loss_fn(params, x):
        h = x @ params["encoder"]["w"] + params["encoder"]["b"]
        return jnp.sum(jnp.square(h @ params["head"]["w"]))

    @jax.jit
    def step(params, x):
        loss, grads = jax.value_and_grad(loss_fn)(params, x)
        tel = step_scalars(loss=loss, grads=grads)
        if numerics_on:
            tel.update(numerics_scalars(grads=grads))
        return loss, tel

    return step


class TestNumericsFlag:
    def test_default_off(self, monkeypatch):
        monkeypatch.delenv("GIGAPATH_NUMERICS", raising=False)
        assert not numerics_enabled()
        monkeypatch.setenv("GIGAPATH_NUMERICS", "1")
        assert numerics_enabled()
        monkeypatch.setenv("GIGAPATH_NUMERICS", "0")
        assert not numerics_enabled()

    def test_flag_off_hlo_byte_identical(self):
        """numerics_on=False must lower to the same PROGRAM as a build
        without the branch at all: the lowered text without debug info
        (no source locations, no enclosing-function names) is
        byte-equal."""

        def loss_fn(params, x):
            h = x @ params["encoder"]["w"] + params["encoder"]["b"]
            return jnp.sum(jnp.square(h @ params["head"]["w"]))

        # the pre-ISSUE-19 step body, no numerics branch anywhere
        @jax.jit
        def step(params, x):
            loss, grads = jax.value_and_grad(loss_fn)(params, x)
            tel = step_scalars(loss=loss, grads=grads)
            return loss, tel

        args = (_toy_params(), jnp.ones((3, 4)))

        def hlo(fn):
            return fn.lower(*args).as_text()

        reference = hlo(step)
        assert hlo(_make_step(False)) == reference
        # sanity: flag-on is a different program (the reductions exist)
        assert hlo(_make_step(True)) != reference

    def test_flag_on_zero_retraces(self):
        """The summaries are shape-static functions of the pytree, so
        repeated steps share one executable."""
        step = _make_step(True)
        params = _toy_params()
        for i in range(3):
            _, tel = step(params, jnp.ones((3, 4)) * (i + 1))
        assert step._cache_size() == 1
        # every scalar left the step as a 0-d device array, float()-able
        # only at the sync point the caller picks
        synced = {k: float(v) for k, v in tel.items()}
        assert any(k.startswith("num.grad.") for k in synced)

    def test_group_summaries_values_and_nan(self):
        tree = {
            "clean": {"w": jnp.asarray([3.0, -4.0])},
            "broken": {"w": jnp.asarray([1.0, jnp.nan, 2.0, 8.0])},
        }
        out = {k: float(v) for k, v in
               group_summaries(tree, prefix="num.grad").items()}
        assert out["num.grad.clean.finite_frac"] == 1.0
        assert out["num.grad.clean.absmax"] == 4.0
        assert out["num.grad.clean.rms"] == pytest.approx(
            np.sqrt((9 + 16) / 2))
        assert out["num.grad.broken.finite_frac"] == 0.75
        # absmax must PROPAGATE the NaN, not mask it behind the 8.0
        assert np.isnan(out["num.grad.broken.absmax"])

    def test_split_monitor_and_nan_wins_worst(self, tmp_path):
        tel = {"loss": 1.5, "grad_norm": 0.3,
               "num.grad.a.finite_frac": 1.0, "num.grad.a.absmax": 3.5,
               "num.grad.a.rms": 0.7,
               "num.grad.b.finite_frac": 0.5,
               "num.grad.b.absmax": float("nan"), "num.grad.b.rms": 0.1}
        rest, num = split_numerics(tel)
        assert set(rest) == {"loss", "grad_norm"}
        assert len(num) == 6
        assert numerics_layers(num)["grad.b"]["finite_frac"] == 0.5

        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        mon = NumericsMonitor(log, name="t")
        record = mon.emit(40, num)
        log.close()
        assert mon.emitted == 1
        assert record["worst_finite_frac"] == 0.5
        # max() is order-dependent with NaN; the monitor must not be
        assert np.isnan(record["worst_absmax"])
        assert record["layers"]["grad.a"]["absmax"] == 3.5
        assert mon.emit(41, {"loss": 1.0}) is None  # nothing numeric


# ---------------------------------------------------------------------------
# EmbeddingSketch: merge algebra + artifact discipline
# ---------------------------------------------------------------------------


def _filled(rng, dim=6, n=20, loc=0.0):
    sk = EmbeddingSketch(dim)
    for _ in range(n):
        sk.update(rng.normal(loc, 1.0, dim))
    return sk


class TestEmbeddingSketch:
    def test_merge_associative_and_matches_single_pass(self):
        rng = np.random.default_rng(3)
        data = rng.normal(size=(30, 6))
        a, b, c = EmbeddingSketch(6), EmbeddingSketch(6), EmbeddingSketch(6)
        whole = EmbeddingSketch(6)
        for i, row in enumerate(data):
            (a, b, c)[i % 3].update(row)
            whole.update(row)
        left = a.merge(b).merge(c)
        right = a.merge(b.merge(c))
        for merged in (left, right):
            assert merged.count == whole.count == 30
            np.testing.assert_allclose(merged.mean, whole.mean,
                                       rtol=0, atol=1e-9)
            np.testing.assert_allclose(merged.m2, whole.m2,
                                       rtol=1e-9, atol=1e-9)
            np.testing.assert_array_equal(merged.hist, whole.hist)
        np.testing.assert_allclose(left.mean, right.mean, atol=1e-12)
        # merge is non-mutating: a is untouched
        assert a.count == 10

    def test_merge_geometry_mismatch_refused(self):
        with pytest.raises(ValueError):
            EmbeddingSketch(4).merge(EmbeddingSketch(5))

    def test_save_load_restart_resume_bit_exact(self, tmp_path):
        rng = np.random.default_rng(7)
        sk = _filled(rng, n=12)
        path = str(tmp_path / "baseline")
        sk.save(path)
        resumed = EmbeddingSketch.load(path)
        assert resumed.count == sk.count
        np.testing.assert_array_equal(resumed.mean, sk.mean)
        np.testing.assert_array_equal(resumed.m2, sk.m2)
        np.testing.assert_array_equal(resumed.hist, sk.hist)
        # restart-resume: both continue over the same stream and stay
        # BIT-exact — a restarted serving process loses nothing
        tail = np.random.default_rng(11).normal(size=(8, 6))
        for row in tail:
            sk.update(row)
            resumed.update(row)
        np.testing.assert_array_equal(resumed.mean, sk.mean)
        np.testing.assert_array_equal(resumed.m2, sk.m2)
        np.testing.assert_array_equal(resumed.hist, sk.hist)
        # overwrite-in-place goes through the same atomic swap
        resumed.save(path)
        assert EmbeddingSketch.load(path).count == 20

    def test_corrupt_artifact_refused(self, tmp_path):
        rng = np.random.default_rng(5)
        path = str(tmp_path / "baseline")
        _filled(rng).save(path)
        npz = path + "/sketch.npz"
        with open(npz, "rb") as fh:
            blob = bytearray(fh.read())
        blob[len(blob) // 2] ^= 0xFF
        with open(npz, "wb") as fh:
            fh.write(blob)
        with pytest.raises(CorruptDriftArtifact):
            EmbeddingSketch.load(path)

    def test_missing_manifest_refused(self, tmp_path):
        with pytest.raises(CorruptDriftArtifact):
            EmbeddingSketch.load(str(tmp_path / "nowhere"))

    def test_quantile_and_tail(self):
        sk = EmbeddingSketch(1, bins=8, hi=8.0)
        for v in (1.0, 2.0, 3.0, 4.0):
            sk.update(np.asarray([v]))
        assert sk.quantile(0.99) >= 4.0
        assert sk.mass_above(100.0) == 0.0
        assert sk.mass_above(0.0) == 1.0


# ---------------------------------------------------------------------------
# DriftSentinel + embedding_drift detector: both ways
# ---------------------------------------------------------------------------


class TestDriftSentinel:
    def _run(self, tmp_path, shift):
        from gigapath_tpu.obs.anomaly import (
            AnomalyConfig,
            attach_anomaly_engine,
        )

        rng = np.random.default_rng(2)
        baseline = _filled(rng, n=24)
        log = RunLog(str(tmp_path / f"run{shift}.jsonl"), driver="t",
                     echo=False)
        attach_anomaly_engine(log, config=AnomalyConfig(capture_budget=0))
        sentinel = DriftSentinel(baseline, log, every=2, threshold=3.0,
                                 min_count=2, name="t.drift")
        for _ in range(8):
            sentinel.observe(rng.normal(shift, 1.0, 6))
        sentinel.emit_status(reason="final")
        log.close()
        events = _read_events(str(tmp_path / f"run{shift}.jsonl"))
        anomalies = [e for e in events if e.get("kind") == "anomaly"
                     and e.get("detector") == "embedding_drift"]
        return sentinel, events, anomalies

    def test_forced_drift_fires_exactly_one_with_flight(self, tmp_path):
        sentinel, events, anomalies = self._run(tmp_path, shift=7.0)
        assert sentinel.alarming
        assert sentinel.scores["mean_shift"] > 3.0
        # transition-edged: 4 scoring points past the threshold, ONE
        # anomaly; the terminal final=True status never fires
        assert len(anomalies) == 1
        assert anomalies[0]["flight"]
        assert anomalies[0]["name"] == "t.drift"
        finals = [e for e in events if e.get("kind") == "drift"
                  and e.get("final")]
        assert len(finals) == 1 and finals[0]["alarming"]

    def test_clean_serve_fires_none(self, tmp_path):
        sentinel, events, anomalies = self._run(tmp_path, shift=0.0)
        assert not sentinel.alarming
        assert anomalies == []
        # the terminal status still lands, so reports render drift
        # health on clean runs too
        assert any(e.get("kind") == "drift" and e.get("final")
                   for e in events)

    def test_scores_shape(self):
        rng = np.random.default_rng(9)
        base, cur = _filled(rng), _filled(rng, loc=4.0)
        scores = drift_scores(cur, base)
        assert set(scores) == {"mean_shift", "cosine_dist", "tail_mass"}
        assert scores["mean_shift"] > 1.0
        assert 0.0 <= scores["cosine_dist"] <= 2.0
        assert drift_scores(base, base)["cosine_dist"] == 0.0

    def test_min_count_gates_scoring(self, tmp_path):
        rng = np.random.default_rng(4)
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        sentinel = DriftSentinel(_filled(rng), log, every=1, threshold=0.1,
                                 min_count=6, name="t.drift")
        for _ in range(5):
            sentinel.observe(rng.normal(9.0, 1.0, 6))
        assert sentinel.scores is None and not sentinel.alarming
        sentinel.observe(rng.normal(9.0, 1.0, 6))
        log.close()
        assert sentinel.alarming


# ---------------------------------------------------------------------------
# anytime peeks: provisional-vs-final convergence
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    """One param init shared by the whole class — init dominates the
    per-test cost, and every test here builds its own fresh session on
    top of the same frozen (inner, params) pair."""
    from gigapath_tpu.models.classification_head import get_model
    from gigapath_tpu.utils.registry import create_model_from_registry

    _, params = get_model(
        input_dim=16, latent_dim=32, feat_layer="1", n_classes=2,
        model_arch="gigapath_slide_enc_tiny", dtype=None,
    )
    inner = create_model_from_registry(
        "gigapath_slide_enc_tiny", in_chans=16, global_pool=False,
        dtype=None,
    )
    return inner, params


def _fresh_session(tiny_model, n_tiles, chunk_tiles, runlog=None):
    from gigapath_tpu.models.streaming_encoder import StreamingEncoderSession

    inner, params = tiny_model
    return StreamingEncoderSession(
        inner, params["slide_encoder"], n_tiles,
        chunk_tiles=chunk_tiles, runlog=runlog,
    )


class TestAnytimePeek:
    def test_peek_before_any_fold_raises(self, tiny_model):
        session = _fresh_session(tiny_model, 16, 8)
        with pytest.raises(RuntimeError):
            session.peek()

    @pytest.mark.slow
    def test_convergence_monotone_and_full_frontier_bit_exact(
            self, tiny_model):
        # compiles one executable per frontier shape (4 peeks +
        # finalize) — the faster sibling in the default tier is
        # test_submitter_confidence_surface, which drives the same
        # peek path through the serve wiring with fewer frontiers
        n_tiles, chunk_tiles = 32, 8
        session = _fresh_session(tiny_model, n_tiles, chunk_tiles)
        rng = np.random.default_rng(0)
        feats = rng.normal(size=(n_tiles, 16)).astype(np.float32)
        coords = rng.uniform(0, 1000, (n_tiles, 2)).astype(np.float32)

        peeks = []
        for idx in range(4):
            a, b = idx * chunk_tiles, (idx + 1) * chunk_tiles
            session.feed(idx, feats[a:b], coords[a:b])
            peeks.append(np.asarray(session.peek()[-1],
                                    np.float32).reshape(-1))
            assert np.isfinite(session.lse_spread())
        final = np.asarray(session.finalize()[-1], np.float32).reshape(-1)

        confidences = [cosine(p, final) for p in peeks]
        # provisional answers converge toward the final one as the
        # frontier advances: the last pre-complete peek is at least as
        # confident as the first (the serve.stream_confidence claim)
        assert confidences[-2] >= confidences[0] - 1e-6
        assert confidences[-2] > 0.5
        # ... and the full-frontier peek IS the final answer, bit-exact
        # (identical op sequence — the convergence anchor)
        np.testing.assert_array_equal(peeks[-1], final)
        assert confidences[-1] == pytest.approx(1.0, abs=1e-6)

    def test_submitter_confidence_surface(self, tiny_model, tmp_path):
        """The serve wiring end-to-end: peeks emit `stream_peek`
        events, finalize scores provisional-vs-final into
        `stream_result` + the `serve.stream_confidence` histogram."""
        from gigapath_tpu.obs.metrics import MetricsRegistry
        from gigapath_tpu.serve.streaming import StreamingSubmitter

        inner, params = tiny_model
        run_path = str(tmp_path / "run.jsonl")
        log = RunLog(run_path, driver="t", echo=False)
        registry = MetricsRegistry(runlog=log, interval_s=0)
        sub = StreamingSubmitter(inner, params["slide_encoder"],
                                 chunk_tiles=8, runlog=log, peek_every=1,
                                 metrics=registry)
        rng = np.random.default_rng(1)
        n_tiles = 24
        feats = rng.normal(size=(n_tiles, 16)).astype(np.float32)
        coords = rng.uniform(0, 1000, (n_tiles, 2)).astype(np.float32)
        session = sub.open("s0", n_tiles)
        for idx in range(3):
            session.feed(idx, feats[idx * 8:(idx + 1) * 8],
                         coords[idx * 8:(idx + 1) * 8])
        out = session.result()
        assert out["last_layer_embed"].shape[-1] == 32
        registry.flush(reason="final")
        log.close()

        events = _read_events(run_path)
        peeks = [e for e in events if e.get("kind") == "stream_peek"]
        # cadence 1, 3 chunks: peeks at frontiers 1 and 2 (a peek at
        # the full frontier would duplicate the result)
        assert [e["frontier"] for e in peeks] == [1, 2]
        assert peeks[0]["cos_prev"] is None
        assert isinstance(peeks[1]["cos_prev"], float)
        results = [e for e in events if e.get("kind") == "stream_result"]
        assert len(results) == 1 and results[0]["peeks"] == 2
        assert 0.0 < results[0]["confidence_last"] <= 1.0
        assert (results[0]["confidence_last"]
                >= results[0]["confidence_first"] - 1e-6)
        snap = [e for e in events if e.get("kind") == "metrics"][-1]
        hist = snap["histograms"]["serve.stream_confidence"]
        assert hist["count"] == 2
