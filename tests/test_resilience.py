"""Fault-tolerance layer (gigapath_tpu/resilience): chaos injection,
hardened checkpoints, non-finite guard, serving self-healing (ISSUE 8
acceptance).

The pinned invariants:

- **kill-and-resume parity**: a chaos-injected SIGTERM at step k in a
  real CPU driver run (subprocess — the signal actually kills it),
  then ``resume="auto"``, reproduces the uninterrupted run's final
  params BIT-exact, with no duplicated or skipped optimizer steps and
  zero unexpected retraces;
- **corrupt-checkpoint fallback**: a chaos-corrupted latest checkpoint
  is skipped with an ``anomaly`` event and the scan falls back to the
  previous valid one;
- **non-finite guard**: a chaos-forced NaN step is a zero-update skip
  (params bit-unchanged across it, ``nonfinite_step`` anomaly emitted)
  with zero retraces, and the guard-off step compiles to BYTE-identical
  HLO vs the pre-guard program;
- **poisoned-batch bisection**: one poisoned slide in a serve batch
  fails exactly ONE future; the other slides return parity-correct
  embeddings.

All fault paths are driven by ``GIGAPATH_CHAOS`` — deterministic,
seeded injection, never luck.
"""

import glob
import json
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapath_tpu.obs.runlog import NullRunLog, RunLog, fail_run
from gigapath_tpu.resilience import (
    ChaosError,
    ChaosInjector,
    NullChaos,
    ResilientCheckpointer,
    SkipStepMonitor,
    get_chaos,
    guard_update,
    nonfinite_guard_enabled,
)
from gigapath_tpu.resilience.chaos import corrupt_checkpoint_dir
from gigapath_tpu.serve.health import (
    BreakerOpenError,
    CircuitBreaker,
    DeadlineExceededError,
    LoadSheddedError,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read_events(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def run_events(out_dir):
    """Events of the newest (non-flight) run file under out_dir/obs."""
    files = [
        p for p in glob.glob(os.path.join(out_dir, "obs", "*.jsonl"))
        if not os.path.basename(p).startswith("flight-")
    ]
    assert files, f"no run files under {out_dir}/obs"
    return read_events(max(files, key=os.path.getmtime))


def events_of(events, kind, **match):
    out = [ev for ev in events if ev.get("kind") == kind]
    for k, v in match.items():
        out = [ev for ev in out if ev.get(k) == v]
    return out


# ---------------------------------------------------------------------------
# chaos spec parsing (the injection grammar is an interface: pin it)
# ---------------------------------------------------------------------------

class TestChaosSpec:
    def test_unset_is_null_and_falsy(self, monkeypatch):
        monkeypatch.delenv("GIGAPATH_CHAOS", raising=False)
        chaos = get_chaos()
        assert isinstance(chaos, NullChaos) and not chaos
        # every consult is a no-op
        assert chaos.batch_fault(0) is None
        assert chaos.poisoned(["a"]) is None
        assert not chaos.corrupts_checkpoint()
        chaos.loader_fault(3)  # does not raise

    def test_spec_round_trip(self, monkeypatch):
        monkeypatch.setenv(
            "GIGAPATH_CHAOS",
            "nan_loss@3,corrupt_batch@5,sigterm@7,fail_loader@2x2,"
            "slow_loader@4:0.0,corrupt_ckpt,poison@slide9,seed=11",
        )
        chaos = get_chaos()
        assert isinstance(chaos, ChaosInjector) and chaos
        assert chaos.batch_fault(3) == "nan"
        assert chaos.batch_fault(5) == "corrupt"
        assert chaos.batch_fault(4) is None
        assert chaos.poisoned(["slide1", "slide9"]) == "slide9"
        assert chaos.poisoned(["slide1"]) is None
        assert chaos.seed == 11
        # fail_loader@2x2: exactly two raises, then heals
        with pytest.raises(ChaosError):
            chaos.loader_fault(2)
        with pytest.raises(ChaosError):
            chaos.loader_fault(2)
        chaos.loader_fault(2)  # healed
        chaos.loader_fault(4)  # slow (0.0s) but no raise
        # corrupt_ckpt fires exactly once per run
        assert chaos.corrupts_checkpoint()
        assert not chaos.corrupts_checkpoint()

    def test_unknown_token_raises(self):
        with pytest.raises(ValueError, match="unknown injector"):
            ChaosInjector("explode@4")

    def test_batch_faults_poison_a_copy(self):
        chaos = ChaosInjector("nan_loss@0,corrupt_batch@1")
        x = np.zeros((4, 4), np.float32)
        nan = chaos.apply_batch_fault("nan", x)
        big = chaos.apply_batch_fault("corrupt", x)
        assert not np.isfinite(nan).all()
        assert np.abs(big).max() >= 1e30
        assert not x.any()  # the original batch is untouched

    def test_corrupt_checkpoint_dir_skips_manifest(self, tmp_path):
        d = tmp_path / "ckpt"
        d.mkdir()
        (d / "manifest.json").write_text("{}")
        (d / "payload.bin").write_bytes(b"\x00" * 64)
        target = corrupt_checkpoint_dir(str(d), seed=0)
        assert os.path.basename(target) == "payload.bin"
        assert (d / "manifest.json").read_text() == "{}"
        assert (d / "payload.bin").read_bytes() != b"\x00" * 64


# ---------------------------------------------------------------------------
# hardened checkpoints: atomic, verified, rotated, resumable
# ---------------------------------------------------------------------------

def _state(step, scale=1.0):
    return {
        "params": {"w": np.full((4,), scale, np.float32)},
        "step": np.asarray(step),
    }


class TestResilientCheckpointer:
    def test_save_restore_round_trip_and_manifest(self, tmp_path):
        ckpt = ResilientCheckpointer(str(tmp_path / "c"))
        path = ckpt.save(3, _state(3, 1.5))
        assert os.path.isdir(path) and ckpt.verify(path)
        # atomic: no tmp dirs survive the rename
        assert not [n for n in os.listdir(ckpt.dir) if n.startswith(".tmp-")]
        state = ckpt.restore(path, _state(0))
        np.testing.assert_array_equal(
            np.asarray(state["params"]["w"]), np.full((4,), 1.5, np.float32)
        )
        # restored leaves are DEVICE arrays: numpy leaves would land in a
        # different pjit cache entry and retrace every shape once after
        # a resume
        assert all(
            isinstance(leaf, jax.Array)
            for leaf in jax.tree_util.tree_leaves(state)
        )

    def test_verify_catches_corruption(self, tmp_path):
        ckpt = ResilientCheckpointer(str(tmp_path / "c"))
        path = ckpt.save(1, _state(1))
        assert ckpt.verify(path)
        corrupt_checkpoint_dir(path, seed=0)
        assert not ckpt.verify(path)

    def test_rotation_keeps_last_k_plus_best(self, tmp_path):
        ckpt = ResilientCheckpointer(str(tmp_path / "c"), keep=2)
        for step in range(1, 6):
            ckpt.save(step, _state(step))
            if step == 2:
                ckpt.mark_best(step, 0.9)
        steps = [s for s, _ in ckpt.checkpoints()]
        # keep-last-2 is {4, 5}; the best pointer pins 2 outside the
        # rotation window
        assert steps == [2, 4, 5]
        assert ckpt.best()["name"] == "ckpt-00000002"

    def test_restore_latest_falls_back_past_corruption(self, tmp_path):
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        ckpt = ResilientCheckpointer(str(tmp_path / "c"), runlog=log)
        ckpt.save(1, _state(1, 1.0))
        ckpt.save(2, _state(2, 2.0))
        corrupt_checkpoint_dir(ckpt.path_for(2), seed=0)
        state, step = ckpt.restore_latest(_state(0))
        assert step == 1
        np.testing.assert_array_equal(
            np.asarray(state["params"]["w"]), np.ones((4,), np.float32)
        )
        events = read_events(log.path)
        (anom,) = events_of(events, "anomaly", detector="corrupt_checkpoint")
        assert anom["step"] == 2
        (rec,) = events_of(events, "recovery", action="resume")
        assert rec["step"] == 1 and rec["fallbacks"] == 1

    def test_restore_latest_empty_dir_returns_none(self, tmp_path):
        ckpt = ResilientCheckpointer(str(tmp_path / "c"))
        assert ckpt.restore_latest(_state(0)) is None

    def test_chaos_corrupts_exactly_the_latest(self, tmp_path):
        chaos = ChaosInjector("corrupt_ckpt")
        ckpt = ResilientCheckpointer(str(tmp_path / "c"), chaos=chaos)
        ckpt.save(1, _state(1, 1.0))
        ckpt.save(2, _state(2, 2.0))
        state, step = ckpt.restore_latest(_state(0))
        assert step == 1  # latest was chaos-corrupted, scan fell back

    def test_same_step_resave_keeps_the_valid_checkpoint(self, tmp_path):
        """An emergency save racing the periodic save it just made (same
        step) must NOT destroy-and-rewrite the valid checkpoint: the old
        rmtree-before-rename left a window with no valid latest at all."""
        ckpt = ResilientCheckpointer(str(tmp_path / "c"))
        p1 = ckpt.save(5, _state(5))
        manifest = os.path.join(p1, "manifest.json")
        before = os.stat(manifest).st_mtime_ns
        assert ckpt.save(5, _state(5)) == p1
        assert os.stat(manifest).st_mtime_ns == before  # untouched
        assert ckpt.verify(p1)
        # a CORRUPT same-step checkpoint is fair game for replacement
        corrupt_checkpoint_dir(p1, seed=0)
        assert not ckpt.verify(p1)
        assert ckpt.save(5, _state(5)) == p1
        assert ckpt.verify(p1)

    def test_sigterm_callback_saves_emergency_checkpoint(self, tmp_path):
        """The handler-side half without a real signal (the subprocess
        acceptance test covers real delivery): arming registers with
        obs/flight and the armed callback lands a verified save."""
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        ckpt = ResilientCheckpointer(str(tmp_path / "c"), runlog=log)
        armed = ckpt.arm_sigterm_checkpoint(lambda: (7, _state(7)))
        try:
            assert armed and ckpt._sigterm_cb is not None
            # not a graceful claim: the supervisor's kill is honored
            assert ckpt._sigterm_cb(int(signal.SIGTERM)) is False
            assert [s for s, _ in ckpt.checkpoints()] == [7]
            (rec,) = events_of(
                read_events(log.path), "recovery",
                action="emergency_checkpoint",
            )
            assert rec["step"] == 7
        finally:
            ckpt.disarm()
        assert ckpt._sigterm_cb is None


# ---------------------------------------------------------------------------
# non-finite guard: in-graph skip-step, monitor, HLO identity
# ---------------------------------------------------------------------------

class TestNonFiniteGuard:
    def test_guard_selects_old_on_nonfinite_new_on_finite(self):
        old = {"w": jnp.zeros((3,))}
        new = {"w": jnp.ones((3,))}
        grads = {"w": jnp.ones((3,))}

        state, skipped = guard_update(jnp.float32(0.5), grads, old, new)
        np.testing.assert_array_equal(np.asarray(state["w"]), 1.0)
        assert float(skipped) == 0.0

        state, skipped = guard_update(jnp.float32(np.nan), grads, old, new)
        np.testing.assert_array_equal(np.asarray(state["w"]), 0.0)
        assert float(skipped) == 1.0

        bad_grads = {"w": jnp.array([1.0, np.inf, 1.0])}
        state, skipped = guard_update(jnp.float32(0.5), bad_grads, old, new)
        np.testing.assert_array_equal(np.asarray(state["w"]), 0.0)
        assert float(skipped) == 1.0

    def test_guard_adds_zero_retraces(self):
        """Finite and non-finite batches run the SAME program — the
        skip is a data-dependent select, never a recompile."""

        @jax.jit
        def step(loss, grads, old, new):
            return guard_update(loss, grads, old, new)

        old, new = {"w": jnp.zeros((3,))}, {"w": jnp.ones((3,))}
        grads = {"w": jnp.ones((3,))}
        step(jnp.float32(1.0), grads, old, new)
        step(jnp.float32(np.nan), grads, old, new)
        step(jnp.float32(np.inf), grads, old, new)
        assert step._cache_size() == 1

    def test_guard_off_hlo_byte_identical(self):
        """The guard is a host-side CONSTRUCTION choice: guard=False
        lowers to the byte-identical program vs the pre-guard step.
        Compared without debug info: the step body physically moved
        into ``_make_train_step``, so source locations and enclosing
        function names necessarily differ while the PROGRAM must not."""
        import optax

        from gigapath_tpu.models.classification_head import get_model
        from gigapath_tpu.train_gigapath import _make_train_step

        model, params = get_model(
            input_dim=16, latent_dim=32, feat_layer="1", n_classes=2,
            model_arch="gigapath_slide_enc_tiny", freeze=False,
            dtype=jnp.bfloat16,
        )
        tx = optax.adamw(1e-3)
        opt_state = tx.init(params)

        # the pre-PR step body, verbatim (named `step`: the module is
        # named after it — the comparison is BYTE equality)
        @jax.jit
        def step(params, opt_state, x, c, y, rng):
            def loss_fn(p):
                logits = model.apply(
                    {"params": p}, x, c, deterministic=False,
                    rngs={"dropout": rng},
                )
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, y
                ).mean()

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        args = (
            params, opt_state, jnp.zeros((1, 8, 16)), jnp.zeros((1, 8, 2)),
            jnp.zeros((1,), jnp.int32), jax.random.PRNGKey(0),
        )

        def hlo(fn):
            return fn.lower(*args).as_text()

        reference = hlo(step)
        assert hlo(_make_train_step(model, tx, guard=False)) == reference
        # sanity: the guard-ON program is a different one
        assert hlo(_make_train_step(model, tx, guard=True)) != reference

    def test_enabled_flag_semantics(self, monkeypatch):
        monkeypatch.delenv("GIGAPATH_NONFINITE_GUARD", raising=False)
        assert nonfinite_guard_enabled()  # default ON
        monkeypatch.setenv("GIGAPATH_NONFINITE_GUARD", "0")
        assert not nonfinite_guard_enabled()


class TestSignalSafeRunLog:
    def test_event_from_signal_writes_when_uncontended(self, tmp_path):
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        assert log.event_from_signal("recovery", action="drain") is not None
        (ev,) = events_of(read_events(log.path), "recovery", action="drain")
        assert ev["action"] == "drain"

    def test_event_from_signal_drops_on_contention_not_deadlocks(
        self, tmp_path
    ):
        """The SIGTERM recovery callbacks run on the main thread, which
        may be suspended INSIDE event() holding the write lock — the
        signal path must try-acquire and drop, never block forever (the
        FlightRecorder.dump_from_signal discipline)."""
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        assert log._lock.acquire()
        try:
            assert log.event_from_signal("recovery", action="drain") is None
        finally:
            log._lock.release()

    def test_null_runlog_has_the_signal_surface(self):
        log = NullRunLog(driver="t", echo=False)
        assert log.event_from_signal("recovery", action="x") is None
        log.echo_from_signal("quiet")  # echo=False: no output, no raise


class TestSkipStepMonitor:
    def test_counts_and_orders_rollback_after_m(self, tmp_path):
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        mon = SkipStepMonitor(log, rollback_after_skips=3)
        assert mon.observe(0, 0.0) is None
        assert mon.observe(1, 1.0) is None
        assert mon.observe(2, 1.0) is None
        assert mon.observe(3, 1.0) == "rollback"
        # counts PERFORMED rollbacks (the driver reports back), not
        # orders — an order with nothing to restore must not inflate it
        assert mon.skip_count == 3 and mon.rollback_count == 0
        mon.rollback_performed()
        assert mon.rollback_count == 1
        # a finite step resets the consecutive counter
        assert mon.observe(4, 1.0) is None
        assert mon.observe(5, 0.0) is None
        assert mon.observe(6, 1.0) is None
        skips = events_of(read_events(log.path), "recovery",
                          action="skip_step")
        assert [ev["consecutive"] for ev in skips] == [1, 2, 3, 1, 1]

    def test_zero_disables_rollback(self, tmp_path):
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        mon = SkipStepMonitor(log, rollback_after_skips=0)
        for i in range(6):
            assert mon.observe(i, 1.0) is None

    def test_rollback_without_checkpoint_is_loud_not_counted(self, tmp_path):
        """An ordered rollback with no checkpoint to restore (the default
        checkpoint_every=0 run) must surface an event, not dissolve into
        a silent no-op counted as a performed rollback."""
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        mon = SkipStepMonitor(log, rollback_after_skips=1)
        assert mon.observe(0, 1.0) == "rollback"
        mon.rollback_unavailable(0)
        assert mon.rollback_count == 0
        (ev,) = events_of(read_events(log.path), "recovery",
                          action="rollback_unavailable")
        assert ev["step"] == 0


# ---------------------------------------------------------------------------
# serving self-healing: breaker, shedding, deadlines, bisection
# ---------------------------------------------------------------------------

class TestCircuitBreaker:
    def test_opens_after_n_failures_probes_and_closes(self):
        br = CircuitBreaker(failures=2, cooldown_s=10.0)
        assert br.admit(16, now=0.0) == "dispatch"
        assert br.record_failure(16, now=0.0) is None
        assert br.record_failure(16, now=0.0) == "open"
        assert br.trips == 1
        # open: fail fast until the cooldown elapses
        assert br.admit(16, now=5.0) == "reject"
        assert br.admit(16, now=10.0) == "probe"
        # one probe at a time
        assert br.admit(16, now=10.0) == "reject"
        assert br.record_success(16) == "close"
        assert br.admit(16, now=11.0) == "dispatch"

    def test_failed_probe_reopens_with_fresh_cooldown(self):
        br = CircuitBreaker(failures=1, cooldown_s=10.0)
        assert br.record_failure(16, now=0.0) == "open"
        assert br.admit(16, now=10.0) == "probe"
        assert br.record_failure(16, now=10.0) == "open"
        assert br.trips == 2
        assert br.admit(16, now=15.0) == "reject"
        assert br.admit(16, now=20.0) == "probe"

    def test_buckets_are_independent(self):
        br = CircuitBreaker(failures=1, cooldown_s=10.0)
        assert br.record_failure(16, now=0.0) == "open"
        assert br.admit(32, now=0.0) == "dispatch"

    def test_success_resets_consecutive(self):
        br = CircuitBreaker(failures=2, cooldown_s=10.0)
        br.record_failure(16, now=0.0)
        br.record_success(16)
        assert br.record_failure(16, now=0.0) is None  # back to 1


@pytest.fixture(scope="module")
def tiny_model():
    from gigapath_tpu.models.classification_head import get_model

    # f32: the 1e-5 bisection-parity bar is a float32 statement
    return get_model(
        input_dim=16, latent_dim=32, feat_layer="1", n_classes=2,
        model_arch="gigapath_slide_enc_tiny", dtype=None,
    )


def _forward_fn(model):
    def forward(p, embeds, coords, pad_mask):
        return model.apply({"params": p}, embeds, coords,
                           pad_mask=pad_mask, deterministic=True)

    return forward


def _serve_config(tmp_path, **overrides):
    from gigapath_tpu.serve import ServeConfig

    base = dict(
        max_batch=4, max_wait_s=0.01, bucket_min=16, bucket_growth=2.0,
        bucket_max=64, bucket_align=16, feature_dim=16, artifact_dir=None,
    )
    base.update(overrides)
    return ServeConfig(**base)


def _slides(rng, lengths):
    return [
        (
            f"s{i}_n{n}",
            rng.normal(size=(n, 16)).astype(np.float32),
            rng.uniform(0, 25000, (n, 2)).astype(np.float32),
        )
        for i, n in enumerate(lengths)
    ]


class TestServeSelfHealing:
    def test_poisoned_batch_bisection_isolates_one_future(
        self, tiny_model, rng, tmp_path, monkeypatch
    ):
        """ISSUE 8 acceptance: one poisoned slide in a coalesced batch
        fails exactly ONE future (ChaosError); the other slides return
        embeddings parity-equal to the exact forward."""
        from gigapath_tpu.serve import SlideService

        model, params = tiny_model
        slides = _slides(rng, [5, 7, 9])  # one bucket (16), one batch
        poisoned_id = slides[1][0]
        monkeypatch.setenv("GIGAPATH_CHAOS", f"poison@{poisoned_id}")
        service = SlideService(
            _forward_fn(model), params, config=_serve_config(tmp_path),
            out_dir=str(tmp_path), identity="tiny",
        )
        futs = [service.submit(*s) for s in slides]
        while service.step(drain=True):
            pass
        with pytest.raises(ChaosError):
            futs[1].result(timeout=10)
        for (sid, f, c), fut in zip(slides, futs):
            if sid == poisoned_id:
                continue
            exact = np.asarray(model.apply(
                {"params": params}, f[None], c[None], deterministic=True,
            ), np.float32)[0]
            np.testing.assert_allclose(
                np.asarray(fut.result(timeout=10), np.float32), exact,
                atol=1e-5,
            )
        assert service.poisoned_requests == 1
        assert service.bisections >= 1
        events = read_events(service.runlog.path)
        assert events_of(events, "recovery", action="bisect")
        (poison_ev,) = events_of(events, "recovery",
                                 action="poisoned_request")
        assert poison_ev["slide_id"] == poisoned_id
        # bisection re-dispatches at the same bucket shape: no compile
        # beyond the one bucket's executable
        assert service.aot.compiled_count == 1
        assert service.watchdog.unexpected_retraces == []
        service.close()

    def test_load_shedding_rejects_past_token_budget(
        self, tiny_model, rng, tmp_path, monkeypatch
    ):
        from gigapath_tpu.serve import SlideService

        monkeypatch.delenv("GIGAPATH_CHAOS", raising=False)
        model, params = tiny_model
        service = SlideService(
            _forward_fn(model), params,
            config=_serve_config(tmp_path, shed_tokens=16),
            out_dir=str(tmp_path), identity="tiny",
        )
        a, b = _slides(rng, [5, 7])
        f1 = service.submit(*a)   # 16 padded tokens queued
        f2 = service.submit(*b)   # 16 + 16 > 16 -> shed
        with pytest.raises(LoadSheddedError):
            f2.result(timeout=10)
        assert service.shed_count == 1
        while service.step(drain=True):
            pass
        assert np.isfinite(np.asarray(f1.result(timeout=10))).all()
        (shed_ev,) = events_of(read_events(service.runlog.path),
                               "recovery", action="shed")
        assert shed_ev["budget"] == 16
        service.close()

    def test_shedding_never_rejects_cache_hits_or_joins(
        self, tiny_model, rng, tmp_path, monkeypatch
    ):
        """The shed check runs AFTER the cache/pending probes: a repeat
        of a cached (or in-flight) slide adds zero queue load and must
        be served even when the queue is past the token budget —
        shedding exactly the hot repeated traffic the cache exists for
        would be self-defeating."""
        from gigapath_tpu.serve import SlideService

        monkeypatch.delenv("GIGAPATH_CHAOS", raising=False)
        model, params = tiny_model
        service = SlideService(
            _forward_fn(model), params,
            config=_serve_config(tmp_path, shed_tokens=16),
            out_dir=str(tmp_path), identity="tiny",
        )
        a, b = _slides(rng, [5, 7])
        f1 = service.submit(*a)          # 16 padded tokens queued
        j1 = service.submit(*a)          # identical content: in-flight
        assert j1 is f1                  # join, not shed, at full budget
        while service.step(drain=True):
            pass
        assert np.isfinite(np.asarray(f1.result(timeout=10))).all()
        f2 = service.submit(*b)          # queue empty again: accepted
        h1 = service.submit(*a)          # cached now; queue is at budget
        assert h1.result(timeout=10) is not None  # hit served, not shed
        assert service.shed_count == 0
        while service.step(drain=True):
            pass
        assert np.isfinite(np.asarray(f2.result(timeout=10))).all()
        service.close()

    def test_deadline_fails_expired_requests_at_dispatch(
        self, tiny_model, rng, tmp_path, monkeypatch
    ):
        import time

        from gigapath_tpu.serve import SlideService

        monkeypatch.delenv("GIGAPATH_CHAOS", raising=False)
        model, params = tiny_model
        service = SlideService(
            _forward_fn(model), params,
            config=_serve_config(tmp_path, deadline_s=0.01),
            out_dir=str(tmp_path), identity="tiny",
        )
        (sid, f, c) = _slides(rng, [5])[0]
        fut = service.submit(sid, f, c)
        time.sleep(0.05)  # one-sided: only needs wait > deadline
        while service.step(drain=True):
            pass
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=10)
        assert service.deadline_failures == 1
        assert events_of(read_events(service.runlog.path), "recovery",
                         action="deadline")
        service.close()

    def test_breaker_trips_probes_and_closes_through_service(
        self, tiny_model, rng, tmp_path, monkeypatch
    ):
        """A persistently failing bucket opens its breaker (later
        batches fail fast), and a half-open probe closes it again once
        the poison clears."""
        from gigapath_tpu.serve import SlideService

        model, params = tiny_model
        slides = _slides(rng, [5, 7, 9])
        monkeypatch.setenv("GIGAPATH_CHAOS", f"poison@{slides[0][0]}")
        service = SlideService(
            _forward_fn(model), params,
            config=_serve_config(
                tmp_path, max_batch=1, breaker_failures=1,
                breaker_cooldown_s=3600.0,
            ),
            out_dir=str(tmp_path), identity="tiny",
        )
        f0 = service.submit(*slides[0])  # poisoned singleton: trips
        while service.step(drain=True):
            pass
        with pytest.raises(ChaosError):
            f0.result(timeout=10)
        assert service.breaker.state(16) == "open"
        f1 = service.submit(*slides[1])  # open breaker: fail fast
        while service.step(drain=True):
            pass
        with pytest.raises(BreakerOpenError):
            f1.result(timeout=10)
        # cooldown elapses -> this dispatch is THE half-open probe; the
        # poison is gone, so success closes the breaker
        service.breaker._entry(16)["opened_at"] = -1e9
        f2 = service.submit(*slides[2])
        while service.step(drain=True):
            pass
        assert np.isfinite(np.asarray(f2.result(timeout=10))).all()
        assert service.breaker.state(16) == "closed"
        events = read_events(service.runlog.path)
        assert events_of(events, "recovery", action="breaker_open")
        assert events_of(events, "recovery", action="breaker_shed")
        assert events_of(events, "recovery", action="breaker_probe")
        assert events_of(events, "recovery", action="breaker_close")
        service.close()

    def test_draining_service_rejects_new_submits(
        self, tiny_model, rng, tmp_path, monkeypatch
    ):
        from gigapath_tpu.serve import SlideService

        monkeypatch.delenv("GIGAPATH_CHAOS", raising=False)
        model, params = tiny_model
        service = SlideService(
            _forward_fn(model), params, config=_serve_config(tmp_path),
            out_dir=str(tmp_path), identity="tiny",
        )
        a, b = _slides(rng, [5, 7])
        f1 = service.submit(*a)
        service._draining = True  # what the SIGTERM chain flips
        with pytest.raises(RuntimeError, match="draining"):
            service.submit(*b)
        while service.step(drain=True):
            pass
        assert np.isfinite(np.asarray(f1.result(timeout=10))).all()
        service.close()

    def test_repeat_sigterm_escalates_past_a_stuck_drain(
        self, tiny_model, tmp_path, monkeypatch
    ):
        """The FIRST SIGTERM claims a graceful drain; a REPEAT is the
        operator escalating past a drain that isn't finishing (hung
        dispatch) and must NOT re-claim — the chain proceeds to the
        prior disposition (process death)."""
        from gigapath_tpu.serve import SlideService

        monkeypatch.delenv("GIGAPATH_CHAOS", raising=False)
        model, params = tiny_model
        service = SlideService(
            _forward_fn(model), params, config=_serve_config(tmp_path),
            out_dir=str(tmp_path), identity="tiny",
        )
        service._arm_signal_drain()
        try:
            assert service._sigterm_cb is not None
            assert service._sigterm_cb(int(signal.SIGTERM)) is True
            assert service._draining
            assert service._sigterm_cb(int(signal.SIGTERM)) is False
        finally:
            service.close()


# ---------------------------------------------------------------------------
# data-loader hardening: bounded same-sample retry, skip with event
# ---------------------------------------------------------------------------

class TestLoaderHardening:
    @pytest.fixture
    def dataset(self, tmp_path, rng, monkeypatch):
        import h5py
        import pandas as pd

        from gigapath_tpu.data.slide_dataset import SlideDataset

        root = tmp_path / "h5_files"
        root.mkdir()
        rows = []
        for i in range(3):
            with h5py.File(root / f"slide_{i}.h5", "w") as f:
                f.create_dataset(
                    "features",
                    data=rng.normal(size=(8, 16)).astype(np.float32),
                )
                f.create_dataset(
                    "coords",
                    data=rng.integers(0, 5000, (8, 2)).astype(np.float32),
                )
            rows.append({"slide_id": f"slide_{i}.svs",
                         "pat_id": f"pat_{i}", "label": ["neg", "pos"][i % 2]})
        cfg = {"setting": "multi_class",
               "label_dict": {"neg": 0, "pos": 1}, "max_tiles": 10}

        def make(retry=3):
            df = pd.DataFrame(rows)
            return SlideDataset(
                df, str(root), splits=df["pat_id"].tolist(),
                task_config=cfg, retry=retry, retry_backoff_s=0.0,
            )

        return make

    def test_transient_failure_heals_within_retry(self, dataset,
                                                  monkeypatch):
        monkeypatch.setenv("GIGAPATH_CHAOS", "fail_loader@1x1")
        ds = dataset(retry=3)
        sample = ds.get_sample_with_try(1)
        assert sample is not None and sample["imgs"].shape == (8, 16)

    def test_exhausted_retries_skip_with_recovery_event(
        self, dataset, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("GIGAPATH_CHAOS", "fail_loader@1x9")
        ds = dataset(retry=2)
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        ds.set_runlog(log)
        assert ds.get_sample_with_try(1) is None  # skipped, not raised
        (ev,) = events_of(read_events(log.path), "recovery",
                          action="data_retry")
        assert ev["index"] == 1 and ev["attempts"] == 2
        assert "ChaosError" in ev["error"]
        # the other samples are untouched
        assert ds.get_sample_with_try(0) is not None

    def test_no_chaos_no_runlog_still_works(self, dataset, monkeypatch):
        monkeypatch.delenv("GIGAPATH_CHAOS", raising=False)
        ds = dataset()
        assert ds.get_sample_with_try(2) is not None


# ---------------------------------------------------------------------------
# the shared driver failure tail
# ---------------------------------------------------------------------------

class TestFailRun:
    def test_error_emergency_and_terminal_run_end(self, tmp_path):
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        saved = []

        def emergency():
            saved.append(True)
            return str(tmp_path / "emergency")

        fail_run(log, "driver.train", ValueError("boom"),
                 emergency=emergency)
        events = read_events(log.path)
        assert saved == [True]
        (err,) = events_of(events, "error")
        assert err["where"] == "driver.train" and "boom" in err["error"]
        (rec,) = events_of(events, "recovery",
                           action="emergency_checkpoint")
        assert rec["path"].endswith("emergency")
        (end,) = events_of(events, "run_end")
        assert end["status"] == "error"
        # ordering: error first, terminal run_end last
        kinds = [ev["kind"] for ev in events]
        assert kinds.index("error") < kinds.index("run_end")

    def test_broken_emergency_does_not_mask_the_tail(self, tmp_path):
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)

        def broken():
            raise OSError("disk gone")

        fail_run(log, "driver.train", ValueError("boom"), emergency=broken)
        events = read_events(log.path)
        assert not events_of(events, "recovery")
        (end,) = events_of(events, "run_end")
        assert end["status"] == "error"

    def test_null_runlog_is_a_no_op(self):
        fail_run(NullRunLog(driver="t", echo=False), "x", ValueError("y"),
                 emergency=lambda: "p")


# ---------------------------------------------------------------------------
# signal chaining (obs/flight): callbacks after dumps, graceful claims
# ---------------------------------------------------------------------------

class TestSignalCallbacks:
    def test_callbacks_run_after_dumps_and_graceful_claim_wins(
        self, tmp_path, monkeypatch
    ):
        from gigapath_tpu.obs import flight

        order = []
        monkeypatch.setattr(flight, "_SIGNAL_INSTALLED", True)
        monkeypatch.setattr(flight, "_SIGNAL_FLIGHTS", [])
        monkeypatch.setattr(flight, "_SIGNAL_CALLBACKS", [])

        rec = flight.FlightRecorder(
            RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        )
        real_dump = rec.dump_from_signal
        monkeypatch.setattr(
            rec, "dump_from_signal",
            lambda reason: (order.append("dump"), real_dump(reason))[1],
        )
        flight._SIGNAL_FLIGHTS.append(rec)

        def checkpoint_cb(signum):
            order.append("checkpoint")
            return False

        def drain_cb(signum):
            order.append("drain")
            return True  # graceful claim: the process must NOT die

        assert flight.register_signal_callback(checkpoint_cb)
        assert flight.register_signal_callback(drain_cb)
        # direct handler invocation: the graceful claim returns before
        # the prior disposition (which would kill this pytest process)
        flight._on_sigterm(int(signal.SIGTERM), None)
        assert order == ["dump", "checkpoint", "drain"]

        flight.unregister_signal_callback(checkpoint_cb)
        flight.unregister_signal_callback(drain_cb)
        assert not flight._SIGNAL_CALLBACKS

    def test_broken_callback_is_contained(self, monkeypatch):
        from gigapath_tpu.obs import flight

        monkeypatch.setattr(flight, "_SIGNAL_INSTALLED", True)
        monkeypatch.setattr(flight, "_SIGNAL_FLIGHTS", [])
        monkeypatch.setattr(flight, "_SIGNAL_CALLBACKS", [])
        ran = []

        def broken(signum):
            raise RuntimeError("handler bug")

        def graceful(signum):
            ran.append(True)
            return True

        flight.register_signal_callback(broken)
        flight.register_signal_callback(graceful)
        flight._on_sigterm(int(signal.SIGTERM), None)  # must not raise
        assert ran == [True]


# ---------------------------------------------------------------------------
# MonitorScore persistence (satellite): resumed finetune keeps its best
# ---------------------------------------------------------------------------

class TestMonitorScorePersistence:
    def test_best_score_rides_the_checkpoint(self, tmp_path):
        from gigapath_tpu.utils.checkpoint import MonitorScore

        ckpt = str(tmp_path / "best_ckpt")
        mon = MonitorScore()
        state = {"params": {"w": np.ones((2,), np.float32)}}
        assert mon(0.7, state, ckpt)        # first score always saves
        assert not mon(0.5, state, ckpt)    # worse: no overwrite
        assert mon(0.9, state, ckpt)

        # a NEW process re-arms from the persisted best
        resumed = MonitorScore.from_checkpoint(ckpt)
        assert resumed.best_score == pytest.approx(0.9)
        # the resumed run's first, WORSE epoch cannot overwrite the best
        assert not resumed(0.8, state, ckpt)
        assert resumed(0.95, state, ckpt)

    def test_missing_checkpoint_is_a_fresh_monitor(self, tmp_path):
        from gigapath_tpu.utils.checkpoint import MonitorScore

        mon = MonitorScore.from_checkpoint(str(tmp_path / "nope"))
        assert mon.best_score is None

    def test_sidecar_is_written_and_state_is_the_fallback(self, tmp_path):
        """Re-arming reads the O(1) ``.best.json`` sidecar, not a full
        Orbax restore of the params pytree; a lost sidecar falls back to
        the ``best_score`` persisted inside the checkpoint state."""
        from gigapath_tpu.utils.checkpoint import MonitorScore

        ckpt = str(tmp_path / "best_ckpt")
        mon = MonitorScore()
        assert mon(0.7, {"params": {"w": np.ones((2,), np.float32)}}, ckpt)
        side = MonitorScore._sidecar(ckpt)
        assert os.path.isfile(side)
        os.remove(side)
        resumed = MonitorScore.from_checkpoint(ckpt)
        assert resumed.best_score == pytest.approx(0.7)

    def test_legacy_checkpoint_without_best_score(self, tmp_path):
        from gigapath_tpu.utils.checkpoint import (
            MonitorScore,
            save_checkpoint,
        )

        ckpt = str(tmp_path / "legacy")
        save_checkpoint(ckpt, {"params": {"w": np.ones((2,), np.float32)}})
        mon = MonitorScore.from_checkpoint(ckpt)
        assert mon.best_score is None


# ---------------------------------------------------------------------------
# ISSUE 8 acceptance: the real-driver chaos runs (train_gigapath on CPU)
# ---------------------------------------------------------------------------

_DRIVER = """\
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
from gigapath_tpu.train_gigapath import train_model
train_model({feature_dir!r}, {labels!r}, {outdir!r}, num_epochs=2,
            latent_dim=32, model_arch="gigapath_slide_enc_tiny",
            feat_layer="1", freeze_pretrained=False, checkpoint_every=2)
print("COMPLETED")
"""


@pytest.fixture(scope="class")
def train_fixture(tmp_path_factory):
    """Cached slide features + labels for train_model: two slides of the
    SAME tile count, so every driver run compiles exactly one step
    executable (retrace accounting stays unambiguous)."""
    from gigapath_tpu.utils.checkpoint import save_checkpoint

    root = tmp_path_factory.mktemp("resilience_driver")
    feature_dir = str(root / "features")
    rng = np.random.default_rng(0)
    rows = []
    for i in range(2):
        sid = f"s{i}"
        save_checkpoint(
            os.path.join(feature_dir, f"{sid}_features"),
            {"features": rng.normal(size=(8, 16)).astype(np.float32),
             "coords": rng.normal(size=(8, 2)).astype(np.float32)},
        )
        rows.append((sid, i % 2))
    labels = str(root / "labels.csv")
    with open(labels, "w") as fh:
        fh.write("slide_id,label\n")
        for sid, lab in rows:
            fh.write(f"{sid},{lab}\n")
    return root, feature_dir, labels


def _train(feature_dir, labels, outdir, **kwargs):
    from gigapath_tpu.train_gigapath import train_model

    base = dict(num_epochs=2, latent_dim=32,
                model_arch="gigapath_slide_enc_tiny", feat_layer="1",
                freeze_pretrained=False, checkpoint_every=2)
    base.update(kwargs)
    return train_model(feature_dir, labels, str(outdir), **base)


def _final_params(outdir):
    from gigapath_tpu.utils.checkpoint import restore_checkpoint

    return restore_checkpoint(os.path.join(str(outdir), "model"))


def _unexpected_retraces(outdir):
    return [ev for ev in run_events(str(outdir))
            if ev["kind"] == "compile" and ev.get("unexpected")]


class TestKillAndResumeAcceptance:
    def test_sigterm_kill_then_resume_is_bit_exact(self, train_fixture,
                                                   monkeypatch):
        """The acceptance chain: (1) uninterrupted baseline; (2) chaos
        SIGTERM after step 1 in a REAL subprocess driver run — the
        handler chain lands an emergency checkpoint, then the process
        dies by the signal; (3) ``resume="auto"`` completes the
        remaining steps; final params match the baseline BIT-exact with
        zero unexpected retraces (no duplicated or skipped optimizer
        steps — any divergence in the rng chain, step cursor or
        opt_state would break float equality)."""
        root, feature_dir, labels = train_fixture
        monkeypatch.delenv("GIGAPATH_CHAOS", raising=False)

        baseline_dir = root / "out-baseline"
        _train(feature_dir, labels, baseline_dir)

        run_dir = root / "out-run"
        env = dict(os.environ)
        env.update({"GIGAPATH_CHAOS": "sigterm@1", "JAX_PLATFORMS": "cpu",
                    "PYTHONPATH": REPO_ROOT})
        script = _DRIVER.format(repo=REPO_ROOT, feature_dir=feature_dir,
                                labels=labels, outdir=str(run_dir))
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env, capture_output=True,
            text=True, timeout=600,
        )
        # killed BY the signal, after the emergency checkpoint landed
        assert "COMPLETED" not in proc.stdout
        assert proc.returncode != 0
        ckpts = glob.glob(os.path.join(str(run_dir), "ckpts", "ckpt-*"))
        assert ckpts, f"no emergency checkpoint; stderr: {proc.stderr[-2000:]}"
        killed_events = run_events(str(run_dir))
        (em,) = events_of(killed_events, "recovery",
                          action="emergency_checkpoint")
        assert em["step"] == 2  # steps 0 and 1 completed, then SIGTERM

        _train(feature_dir, labels, run_dir, resume="auto")
        resumed_events = run_events(str(run_dir))
        (res,) = events_of(resumed_events, "recovery", action="resume")
        assert res["step"] == 2
        assert _unexpected_retraces(run_dir) == []

        base_leaves = jax.tree_util.tree_leaves(_final_params(baseline_dir))
        run_leaves = jax.tree_util.tree_leaves(_final_params(run_dir))
        assert len(base_leaves) == len(run_leaves)
        for a, b in zip(base_leaves, run_leaves):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_corrupt_latest_falls_back_with_anomaly(self, train_fixture,
                                                    monkeypatch):
        """Chaos corrupts the LATEST checkpoint before the resume scan:
        the scan emits a ``corrupt_checkpoint`` anomaly and lands on the
        previous valid one."""
        root, feature_dir, labels = train_fixture
        run_dir = root / "out-corrupt"
        monkeypatch.delenv("GIGAPATH_CHAOS", raising=False)
        _train(feature_dir, labels, run_dir, checkpoint_every=1)

        monkeypatch.setenv("GIGAPATH_CHAOS", "corrupt_ckpt")
        _train(feature_dir, labels, run_dir, resume="auto",
               checkpoint_every=0)
        events = run_events(str(run_dir))
        (anom,) = events_of(events, "anomaly",
                            detector="corrupt_checkpoint")
        assert anom["step"] == 4   # the corrupted latest
        (res,) = events_of(events, "recovery", action="resume")
        assert res["step"] == 3 and res["fallbacks"] == 1


class TestNanStepAcceptance:
    def test_chaos_nan_step_is_skipped_with_zero_retraces(
        self, train_fixture, monkeypatch
    ):
        """A chaos-forced NaN batch becomes a zero-update skip: params
        and opt_state are BIT-unchanged across the skipped step (the
        optimizer count does not advance — no phantom step), the step
        event is tagged, the ``nonfinite_step`` anomaly fires, and the
        whole run pays zero unexpected retraces."""
        root, feature_dir, labels = train_fixture
        run_dir = root / "out-nan"
        monkeypatch.setenv("GIGAPATH_CHAOS", "nan_loss@1")
        result = _train(feature_dir, labels, run_dir, checkpoint_every=1,
                        keep_checkpoints=8)
        assert np.isfinite(result["loss_history"]).all()  # skip excluded

        events = run_events(str(run_dir))
        (nan_step,) = [ev for ev in events
                       if ev["kind"] == "step" and ev.get("nonfinite")]
        assert nan_step["step"] == 1
        assert events_of(events, "anomaly", detector="nonfinite_step")
        (skip,) = events_of(events, "recovery", action="skip_step")
        assert skip["step"] == 1
        assert _unexpected_retraces(run_dir) == []
        (end,) = events_of(events, "run_end")
        assert end["skipped_steps"] == 1 and end["status"] == "ok"

        # ckpt-1 = after step 0 (finite), ckpt-2 = after step 1 (the
        # skip): params and opt_state bit-equal across the skipped step
        ckpt = ResilientCheckpointer(os.path.join(str(run_dir), "ckpts"))
        before, _ = ckpt.restore(ckpt.path_for(1)), 1
        after = ckpt.restore(ckpt.path_for(2))
        for key in ("params", "opt_state"):
            for a, b in zip(jax.tree_util.tree_leaves(before[key]),
                            jax.tree_util.tree_leaves(after[key])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # ...but the run kept moving: the NEXT step did update
        third = ckpt.restore(ckpt.path_for(3))
        assert any(
            not np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree_util.tree_leaves(after["params"]),
                            jax.tree_util.tree_leaves(third["params"]))
        )

    def test_persistent_nan_rolls_back_to_checkpoint(self, train_fixture,
                                                     monkeypatch):
        root, feature_dir, labels = train_fixture
        run_dir = root / "out-rollback"
        monkeypatch.setenv("GIGAPATH_CHAOS", "nan_loss@1,nan_loss@2")
        monkeypatch.setenv("GIGAPATH_GUARD_ROLLBACK_AFTER", "2")
        result = _train(feature_dir, labels, run_dir, checkpoint_every=1)
        events = run_events(str(run_dir))
        (rb,) = events_of(events, "recovery", action="rollback")
        assert rb["step"] == 2  # second consecutive skip ordered it
        # the rollback's internal checkpoint scan must NOT telemetry a
        # "resume" — this run was never killed and resumed
        assert events_of(events, "recovery", action="resume") == []
        (end,) = events_of(events, "run_end")
        assert end["skipped_steps"] == 2 and end["rollbacks"] == 1
        assert np.isfinite(result["loss_history"]).all()
