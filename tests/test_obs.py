"""Observability subsystem: RunLog events, watchdog, heartbeat, report.

The load-bearing contracts from ISSUE 2's acceptance criteria:

- a forced stall produces a ``stall`` event (the hung-runtime-call
  defense is actually armed);
- an instrumented step function compiles exactly as many times as the
  uninstrumented one across two buckets (telemetry adds NO retraces);
- ``scripts/obs_report.py`` renders throughput / compile-share / retrace
  sections from a real run's JSONL (the finetune smoke test's run in the
  slow tier; a watchdog-produced run in the default tier).
"""

import io
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapath_tpu.obs import (
    EVENT_KINDS,
    SCHEMA_VERSION,
    CompileWatchdog,
    Heartbeat,
    NullRunLog,
    RunLog,
    get_ledger,
    get_run_log,
    span,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))

import obs_report  # noqa: E402


def read_events(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


# ---------------------------------------------------------------------------
# RunLog
# ---------------------------------------------------------------------------

class TestRunLog:
    def test_schema_versioned_events(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        log = RunLog(path, driver="test", echo=False)
        log.run_start(config={"lr": 1e-4, "name": "t"}, probe_devices=False)
        log.step(0, wall_s=0.25, synced=True, loss=1.5)
        log.eval_event(0, auroc=0.9)
        log.run_end(status="ok")
        events = read_events(path)
        assert [ev["kind"] for ev in events] == [
            "run_start", "step", "eval", "run_end",
        ]
        for ev in events:
            assert ev["v"] == SCHEMA_VERSION
            assert ev["run"] == log.run_id
            assert isinstance(ev["t"], float)
            assert ev["kind"] in EVENT_KINDS
        assert events[0]["config"] == {"lr": 1e-4, "name": "t"}
        assert events[0]["jax_version"] == jax.__version__
        assert events[1] == {**events[1], "step": 0, "wall_s": 0.25,
                             "synced": True, "loss": 1.5}
        assert events[-1]["status"] == "ok" and events[-1]["wall_s"] >= 0

    def test_device_scalars_become_floats(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        log = RunLog(path, driver="test", echo=False)
        log.step(1, loss=jnp.float32(2.5), grad_norm=jnp.ones(())[None])
        (ev,) = read_events(path)
        assert ev["loss"] == 2.5 and ev["grad_norm"] == 1.0

    def test_writes_survive_close_and_threads(self, tmp_path):
        import threading

        path = str(tmp_path / "run.jsonl")
        log = RunLog(path, driver="test", echo=False)
        threads = [
            threading.Thread(target=lambda i=i: log.step(i)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        log.close()
        log.step(99)  # post-close: swallowed, not a crash
        events = read_events(path)
        assert sorted(ev["step"] for ev in events) == list(range(8))

    def test_echo_single_format_includes_wall_and_step(self, capsys):
        log = NullRunLog(driver="finetune")
        log.echo("Loss: 1.0", step=40)
        out = capsys.readouterr().out
        assert out.startswith("[finetune +")
        assert "s step 40] Loss: 1.0" in out

    def test_null_runlog_accepts_every_call_shape(self, capsys):
        null = NullRunLog(driver="bench")
        null.run_start(config={"a": 1}, probe_devices=False)
        null.step(0, wall_s=0.1, synced=True)
        null.compile_event("fn", (1, 2), 0.5, count=1, unexpected=False)
        null.eval_event(0, auroc=1.0)
        null.heartbeat(last_step=0)
        null.stall(last_step=0, since_progress_s=1.0, deadline_s=0.5)
        null.error("here", ValueError("x"))
        null.run_end(status="ok", value=1)
        null.close()
        null.echo("still prints")  # opt-out never silences the console
        assert "still prints" in capsys.readouterr().out


class TestGetRunLog:
    def test_env_opt_out(self, tmp_path, monkeypatch):
        monkeypatch.setenv("GIGAPATH_OBS", "0")
        log = get_run_log("t", out_dir=str(tmp_path))
        assert isinstance(log, NullRunLog) and not isinstance(log, RunLog)
        assert not os.path.exists(tmp_path / "obs")

    def test_default_on_writes_run_start(self, tmp_path, monkeypatch):
        monkeypatch.delenv("GIGAPATH_OBS", raising=False)
        log = get_run_log("t", out_dir=str(tmp_path), echo=False,
                          probe_devices=False)
        assert isinstance(log, RunLog)
        assert os.path.dirname(log.path) == str(tmp_path / "obs")
        events = read_events(log.path)
        assert events[0]["kind"] == "run_start"
        assert events[0]["driver"] == "t"
        log.close()

    def test_obs_dir_env_fallback(self, tmp_path, monkeypatch):
        monkeypatch.delenv("GIGAPATH_OBS", raising=False)
        monkeypatch.setenv("GIGAPATH_OBS_DIR", str(tmp_path / "central"))
        log = get_run_log("t", echo=False, probe_devices=False)
        assert str(tmp_path / "central") == os.path.dirname(log.path)
        log.close()

    def test_shared_run_id_pins_multihost_merge_key(self, tmp_path, monkeypatch):
        """GIGAPATH_OBS_RUN_ID: every rank logs under ONE run id (the
        obs_report merge key) while writing its own per-process file —
        the suffix is host+pid, NOT the rank, so get_run_log never
        touches the backend at driver start (and containerized ranks
        that all run as pid 1 still get distinct files)."""
        monkeypatch.delenv("GIGAPATH_OBS", raising=False)
        monkeypatch.setenv("GIGAPATH_OBS_RUN_ID", "mh-run-1")
        log = get_run_log("t", out_dir=str(tmp_path), echo=False,
                          probe_devices=False)
        assert log.run_id == "mh-run-1"
        base = os.path.basename(log.path)
        assert base.startswith("mh-run-1-")
        assert base.endswith(f"-p{os.getpid()}.jsonl")
        events = read_events(log.path)
        assert events[0]["run"] == "mh-run-1"
        log.close()


# ---------------------------------------------------------------------------
# CompileWatchdog
# ---------------------------------------------------------------------------

class TestCompileWatchdog:
    def test_wrap_counts_one_compile_per_shape(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        log = RunLog(path, driver="t", echo=False)
        fn = jax.jit(lambda x: x * 2)
        wd = CompileWatchdog("fn", log)
        wrapped = wd.wrap(fn)
        for _ in range(3):
            wrapped(jnp.ones((2, 8)))
        wrapped(jnp.ones((2, 16)))
        compiles = [ev for ev in read_events(path) if ev["kind"] == "compile"]
        assert len(compiles) == 2
        assert all(not ev["unexpected"] for ev in compiles)
        assert len(wd.first_call_sec) == 2
        assert wd.compile_seconds_total() > 0

    def test_unexpected_retrace_flagged(self, tmp_path):
        """Cache growth on an already-seen key = silent retrace, flagged."""
        path = str(tmp_path / "run.jsonl")
        log = RunLog(path, driver="t", echo=False)
        fn = jax.jit(lambda x: x + 1)
        wd = CompileWatchdog("fn", log)
        # key_fn collapses all shapes to one key: the second (different)
        # shape recompiles under a key the watchdog saw as compiled
        wrapped = wd.wrap(fn, key_fn=lambda *a, **k: "constant")
        wrapped(jnp.ones((4,)))
        wrapped(jnp.ones((8,)))
        compiles = [ev for ev in read_events(path) if ev["kind"] == "compile"]
        assert [ev["unexpected"] for ev in compiles] == [False, True]
        assert wd.unexpected_retraces == ["constant"]
        assert "unexpected" in wd.summary()

    def test_bucket_surface_matches_old_compile_log(self):
        """The BucketCompileLog-shaped surface the finetune loop drives."""
        wd = CompileWatchdog("train_step")
        assert wd.is_new((1, 128))
        wd.record((1, 128), 1.25)
        assert not wd.is_new((1, 128))
        wd.record((1, 128), None)  # steady, untimed
        wd.record((1, 128), 0.01)  # steady, timed
        wd.record((1, 256), 0.75)
        summary = wd.summary()
        assert "compile 1.25s" in summary and "compile 0.75s" in summary

    def test_zero_retrace_overhead_parity(self):
        """ISSUE acceptance: the instrumented step compiles exactly as many
        times as the uninstrumented one across two buckets."""

        def step(params, x):
            return params["w"] * jnp.sum(x), {"norm": jnp.sum(x**2)}

        params = {"w": jnp.float32(2.0)}
        buckets = [jnp.ones((1, 128)), jnp.ones((1, 256))]

        bare = jax.jit(step)
        for x in buckets * 3:
            bare(params, x)

        instrumented = jax.jit(step)
        wd = CompileWatchdog("step", fn=instrumented)
        wrapped = wd.wrap(instrumented)
        for x in buckets * 3:
            wrapped(params, x)

        assert bare._cache_size() == instrumented._cache_size() == 2
        assert sum(wd.compile_count.values()) == 2
        assert wd.unexpected_retraces == []


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class TestSpans:
    def test_nested_spans_emit_path_depth_duration(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        log = RunLog(path, driver="t", echo=False)
        with span("epoch", log, epoch=0):
            with span("step", log) as sp:
                sp.note(bucket="(1, 128)")
        events = read_events(path)
        # inner span closes first
        assert [ev["name"] for ev in events] == ["step", "epoch"]
        step, epoch = events
        assert step["path"] == "epoch/step" and step["depth"] == 2
        assert epoch["path"] == "epoch" and epoch["depth"] == 1
        assert step["bucket"] == "(1, 128)" and epoch["epoch"] == 0
        assert step["dur_s"] >= 0 and epoch["dur_s"] >= step["dur_s"]
        assert step["rank"] == 0 and step["fenced"] is False
        assert step["status"] == "ok"
        log.close()

    def test_fence_blocks_and_exposes_dur(self, tmp_path):
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        fn = jax.jit(lambda x: (x * 2).sum())
        with span("step", log, fence=True) as sp:
            out = sp.fence(fn(jnp.ones((4,))))
        assert float(out) == 8.0
        assert sp.dur_s is not None and sp.dur_s >= 0
        (ev,) = read_events(log.path)
        assert ev["fenced"] is True and ev["dur_s"] == sp.dur_s
        log.close()

    def test_fence_value_passed_directly(self, tmp_path):
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        x = jnp.ones((4,))
        with span("sync", log, fence=x):
            pass
        (ev,) = read_events(log.path)
        assert ev["fenced"] is True
        log.close()

    def test_fence_failure_still_emits_span_event(self, tmp_path, monkeypatch):
        """A device error surfacing at the fence sync must not eat the
        span event (the obs layer exists for the failure moment) and must
        not raise a NEW exception the unfenced driver would never see."""
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)

        def boom(_):
            raise RuntimeError("device exploded at sync")

        monkeypatch.setattr(jax, "block_until_ready", boom)
        with span("step", log, fence=True) as sp:
            sp.fence(jnp.ones(2))
        (ev,) = read_events(log.path)
        assert ev["status"] == "error"
        assert "device exploded" in ev["fence_error"]
        assert sp.dur_s is not None
        log.close()

    def test_error_status_recorded_and_reraised(self, tmp_path):
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        with pytest.raises(ValueError):
            with span("boom", log):
                raise ValueError("x")
        (ev,) = read_events(log.path)
        assert ev["status"] == "error" and ev["dur_s"] >= 0
        log.close()

    def test_caller_fields_cannot_shadow_span_schema(self, tmp_path):
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        with span("eval", log, status="pending", depth=42):
            pass
        (ev,) = read_events(log.path)
        assert ev["status"] == "ok" and ev["depth"] == 1  # schema wins
        assert ev["field_status"] == "pending" and ev["field_depth"] == 42
        log.close()

    def test_rank_is_an_explicit_override_not_a_field(self, tmp_path):
        """``rank`` graduated from shadowable free-form field to a named
        span parameter (the dist worker processes tag spans with their
        WORKER index — jax process index is 0 for every group on one
        machine). Default stays the process index."""
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        with span("chunk", log, rank=3):
            pass
        with span("chunk", log):
            pass
        first, second = read_events(log.path)
        assert first["rank"] == 3 and "field_rank" not in first
        assert second["rank"] == 0
        log.close()

    def test_null_runlog_is_true_noop(self):
        null = NullRunLog(driver="t", echo=False)
        with span("step", null, fence=True) as sp:
            sp.fence(jnp.ones(2))
            sp.note(a=1)
        assert sp.dur_s is None  # no clock reads, no event, no fence
        with span("bare", None) as sp2:
            pass
        assert sp2.dur_s is None

    def test_null_path_reads_no_clock_and_blocks_on_nothing(self, monkeypatch):
        """No recorder, no recording runlog: not one clock read and no
        ``block_until_ready``, whatever ``fence`` says."""
        from gigapath_tpu.obs import spans

        here = threading.get_ident()  # a thread an earlier test left behind is not the null path
        real_time, real_sync = spans.time, jax.block_until_ready

        class NoClock:
            def __getattr__(self, name):
                if threading.get_ident() != here:
                    return getattr(real_time, name)
                raise AssertionError(f"time.{name} read on the null path")

        def no_sync(value):
            if threading.get_ident() != here:
                return real_sync(value)
            raise AssertionError("block_until_ready on the null path")

        monkeypatch.setattr(spans, "time", NoClock())
        monkeypatch.setattr(jax, "block_until_ready", no_sync)
        x = jnp.ones(2)
        for runlog in (None, NullRunLog(driver="t", echo=False)):
            with span("request", runlog):
                with span("h2d", runlog, fence=True) as sp:
                    sp.fence(x)
                with span("device_wait", runlog, fence=x):
                    pass
            assert sp is spans._NULL_SPAN and sp.start_ns is None


# ---------------------------------------------------------------------------
# the in-memory recorder: where a span was, and what caused it
# ---------------------------------------------------------------------------

class TestRecorder:
    def _two_requests(self):
        from gigapath_tpu.obs import spans

        x = jnp.ones((4,))  # made (and whatever it compiles, compiled) outside the recorder
        with spans.record() as rec:
            for _ in range(2):
                with span("request"):
                    with span("prepare"):
                        pass
                    with span("h2d", fence=True) as sp:
                        sp.fence(x)
                    with span("dispatch"):
                        with span("inner"):
                            time.sleep(0.002)
        return rec.spans

    @staticmethod
    def _root_of(recorded):
        """``{span id: the id of the root its chain of parents ends at}``."""
        by_id = {s.id: s for s in recorded}

        def root(s):
            return s.id if s.parent is None else root(by_id[s.parent])

        return {s.id: root(s) for s in recorded}

    def test_schema_start_end_and_parent(self):
        recorded = self._two_requests()
        by_id = {s.id: s for s in recorded}
        assert len(by_id) == len(recorded) == 10
        roots = [s for s in recorded if s.parent is None]
        assert [s.name for s in roots] == ["request", "request"]
        root_of = self._root_of(recorded)  # every span lies under one of the two
        assert sorted(root_of.values()) == sorted([r.id for r in roots] * 5)
        for s in recorded:
            assert s.start_ns <= s.end_ns
            if s.parent is not None:
                parent = by_id[s.parent]
                assert parent.start_ns <= s.start_ns and s.end_ns <= parent.end_ns
        inner = next(s for s in recorded if s.name == "inner")
        assert by_id[inner.parent].name == "dispatch"
        assert inner.end_ns - inner.start_ns >= 2_000_000

    def test_self_times_add_up_to_the_roots_duration(self):
        from benchmarks.lib import host_spans

        recorded = self._two_requests()
        root_of = self._root_of(recorded)
        for root in (s for s in recorded if s.parent is None):
            mine = [s for s in recorded if root_of[s.id] == root.id]
            total_ns = round(1e9 * sum(host_spans.self_seconds(mine).values()))
            assert abs(total_ns - (root.end_ns - root.start_ns)) <= len(mine)

    def test_runlog_event_mirror_and_recorder_share_one_interval(self, tmp_path):
        from gigapath_tpu.obs import spans

        class Mirror:
            def add_span(self, name, t0, t1, **args):
                self.got = (name, t0, t1)

        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        mirror = Mirror()
        with spans.record() as rec:
            with span("fold", log, trace=mirror, chunk=3) as sp:
                pass
        (ev,) = read_events(log.path)
        (kept,) = rec.spans
        assert (sp.start_ns, sp.end_ns) == (kept.start_ns, kept.end_ns)
        assert "start_ns" not in ev and "end_ns" not in ev  # nothing reads them there
        assert ev["dur_s"] == sp.dur_s == round((kept.end_ns - kept.start_ns) / 1e9, 6)
        assert mirror.got == ("fold", kept.start_ns / 1e9, kept.end_ns / 1e9)
        assert kept.fields["chunk"] == 3 and kept.fields["status"] == "ok"
        log.close()

    def test_perf_counter_is_the_monotonic_clock(self):
        """``serve/`` and ``dist/`` file their own ``add_span`` intervals by
        ``time.monotonic``; the mirror files ``perf_counter_ns / 1e9`` beside
        them. On Linux both read CLOCK_MONOTONIC."""
        gaps = []
        for _ in range(5):
            a = time.monotonic()
            b = time.perf_counter_ns() / 1e9
            gaps.append(abs(b - a))
        assert min(gaps) < 1e-3

    def test_compile_phases_are_children_of_the_span_that_paid(self):
        from jax._src import monitoring

        from gigapath_tpu.obs import spans

        fn = jax.jit(lambda x: jnp.tanh(x) * 3)
        x = jnp.ones((5, 7))
        with spans.record() as rec:
            assert rec._on_duration in monitoring._event_duration_secs_listeners
            for _ in range(2):
                with span("request"):
                    with span("dispatch"):
                        fn(x)
        assert rec._on_duration not in monitoring._event_duration_secs_listeners
        assert rec._on_event not in monitoring._event_listeners
        first, second = [s for s in rec.spans if s.name == "dispatch"]
        children = {s.name: s for s in rec.spans if s.parent == first.id}
        assert set(children) == {"trace", "lower", "compile"}
        for child in children.values():
            assert first.start_ns <= child.start_ns <= child.end_ns <= first.end_ns
            assert "<lambda>" in child.fields["fun_name"]
        assert not [s for s in rec.spans if s.parent == second.id]  # nothing retraced

    def test_one_recorder_at_a_time_and_none_left_behind(self):
        from gigapath_tpu.obs import spans

        with spans.record():
            with pytest.raises(RuntimeError):
                with spans.record():
                    pass
        assert spans._RECORDER is None
        with span("after") as sp:
            pass
        assert sp.dur_s is None  # the null span again


# ---------------------------------------------------------------------------
# zero-overhead contracts (ISSUE 4 acceptance)
# ---------------------------------------------------------------------------

class TestZeroOverhead:
    def test_obs_off_spans_and_ledger_add_zero_retraces_and_no_files(
        self, tmp_path, monkeypatch
    ):
        """GIGAPATH_OBS=0: the fully instrumented loop (runlog + watchdog
        + ledger + fenced spans) compiles exactly as often as the bare
        loop and leaves NOTHING on disk."""
        monkeypatch.setenv("GIGAPATH_OBS", "0")

        def step(params, x):
            return params["w"] * jnp.sum(x)

        params = {"w": jnp.float32(2.0)}
        buckets = [jnp.ones((1, 128)), jnp.ones((1, 256))]

        bare = jax.jit(step)
        for x in buckets * 3:
            bare(params, x)

        runlog = get_run_log("t", out_dir=str(tmp_path))
        ledger = get_ledger(runlog)
        instrumented = jax.jit(step)
        wd = CompileWatchdog("step", runlog, fn=instrumented, ledger=ledger)
        wrapped = wd.wrap(instrumented)
        for i, x in enumerate(buckets * 3):
            with span("step", runlog, fence=True) as sp:
                out = sp.fence(wrapped(params, x))
            runlog.step(i, wall_s=sp.dur_s, synced=True, loss=float(out))
        runlog.run_end(status="ok", ledger_path=ledger.path)

        assert bare._cache_size() == instrumented._cache_size() == 2
        assert sum(wd.compile_count.values()) == 2
        assert wd.unexpected_retraces == []
        assert list(tmp_path.iterdir()) == [], "obs-off run left artifacts"

    def test_recorder_on_adds_zero_retraces_and_the_same_program(self):
        """A recorder installed and fenced spans around every call: the
        function compiles as often as the bare one and lowers to the same
        text."""
        from gigapath_tpu.obs import spans

        def make():  # two functions of one name: jit keeps its cache by function
            def step(params, x):
                return params["w"] * jnp.sum(x)

            return jax.jit(step)

        params = {"w": jnp.float32(2.0)}
        buckets = [jnp.ones((1, 128)), jnp.ones((1, 256))]
        bare = make()
        for x in buckets * 3:
            bare(params, x)
        recorded = make()
        with spans.record() as rec:
            for x in buckets * 3:
                with span("step", fence=True) as sp:
                    sp.fence(recorded(params, x))
            text = recorded.lower(params, buckets[0]).as_text()
        assert bare._cache_size() == recorded._cache_size() == 2
        assert text == bare.lower(params, buckets[0]).as_text()
        assert sum(s.name == "compile" for s in rec.spans) == 2

    def test_obs_on_instrumented_hlo_is_identical(self, tmp_path):
        """With obs ON, watching + ledgering a function must not alter
        its traced program: the compiled HLO of the watched function is
        byte-identical to an unwatched twin, and no extra call-cache
        entries appear."""

        def step(params, x):
            return params["w"] * jnp.sum(x)

        params = {"w": jnp.float32(2.0)}
        x = jnp.ones((1, 128))

        bare = jax.jit(step)
        bare(params, x)

        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        ledger = get_ledger(log)
        watched = jax.jit(step)
        wd = CompileWatchdog("step", log, fn=watched, ledger=ledger)
        wrapped = wd.wrap(watched)
        with span("step", log, fence=True) as sp:
            sp.fence(wrapped(params, x))
        assert len(ledger.entries) == 1  # the profile was captured

        assert watched._cache_size() == bare._cache_size() == 1
        hlo_bare = bare.lower(params, x).compile().as_text()
        hlo_watched = watched.lower(params, x).compile().as_text()
        assert hlo_bare == hlo_watched
        log.close()


# ---------------------------------------------------------------------------
# in-graph telemetry
# ---------------------------------------------------------------------------

class TestTelemetry:
    def test_step_scalars_inside_jit(self):
        from gigapath_tpu.obs.telemetry import step_scalars

        @jax.jit
        def step(params, x):
            loss = (params["w"] * x).sum()
            grads = jax.grad(lambda p: (p["w"] * x).sum())(params)
            return step_scalars(loss=loss, grads=grads, params=params,
                                extra=jnp.float32(3.0))

        out = step({"w": jnp.full((4,), 2.0)}, jnp.ones((4,)))
        assert set(out) == {"loss", "grad_norm", "param_norm", "extra"}
        assert float(out["loss"]) == 8.0
        assert float(out["grad_norm"]) == pytest.approx(2.0)  # ||[1,1,1,1]||
        assert float(out["param_norm"]) == pytest.approx(4.0)
        assert float(out["extra"]) == 3.0

    def test_tree_norm_empty_and_bf16(self):
        from gigapath_tpu.obs.telemetry import tree_norm

        assert float(tree_norm({})) == 0.0
        # bf16 leaves accumulate in fp32
        n = tree_norm({"a": jnp.full((256,), 0.01, jnp.bfloat16)})
        assert float(n) == pytest.approx(0.16, rel=0.05)

    def test_moe_scalars_matches_host_collector_keys(self, rng):
        from gigapath_tpu.obs.telemetry import moe_scalars
        from gigapath_tpu.ops.moe.moe_layer import MOELayer
        from gigapath_tpu.utils.profiling import collect_moe_metadata

        layer = MOELayer(embed_dim=16, ffn_dim=32, num_experts=4, top1=True)
        x = jnp.asarray(rng.normal(size=(1, 8, 16)), jnp.float32)
        params = layer.init(jax.random.PRNGKey(0), x)["params"]
        _, mods = layer.apply({"params": params}, x, mutable=["intermediates"])
        in_graph = moe_scalars(mods["intermediates"])
        host = collect_moe_metadata(mods["intermediates"])
        assert set(host) <= set(in_graph)
        for k, v in host.items():
            assert float(np.asarray(in_graph[k]).reshape(())) == pytest.approx(v)


# ---------------------------------------------------------------------------
# Heartbeat / stall
# ---------------------------------------------------------------------------

class TestHeartbeat:
    def test_forced_stall_emits_stall_event(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        log = RunLog(path, driver="t", echo=False)
        with Heartbeat(log, interval_s=0.05, stall_after_s=0.15, name="t") as hb:
            hb.beat(7)
            time.sleep(0.5)  # no further beats: exceed the deadline
        kinds = [ev["kind"] for ev in read_events(path)]
        assert "stall" in kinds
        assert "heartbeat" in kinds
        stall = next(ev for ev in read_events(path) if ev["kind"] == "stall")
        assert stall["last_step"] == 7
        assert stall["since_progress_s"] >= 0.15
        assert stall["deadline_s"] == 0.15
        assert hb.stall_count == 1

    def test_steady_beats_prevent_stall(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        log = RunLog(path, driver="t", echo=False)
        with Heartbeat(log, interval_s=0.05, stall_after_s=0.3, name="t") as hb:
            for i in range(8):
                hb.beat(i)
                time.sleep(0.05)
        events = read_events(path)
        assert not any(ev["kind"] == "stall" for ev in events)
        assert any(ev["kind"] == "heartbeat" for ev in events)

    def test_recovery_rearms_stall_detection(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        log = RunLog(path, driver="t", echo=False)
        with Heartbeat(log, interval_s=10.0, stall_after_s=0.12, name="t") as hb:
            time.sleep(0.3)   # first stall
            hb.beat(1)        # recovery
            time.sleep(0.3)   # second stall episode
        stalls = [ev for ev in read_events(path) if ev["kind"] == "stall"]
        assert len(stalls) == 2


# ---------------------------------------------------------------------------
# obs_report
# ---------------------------------------------------------------------------

def _render(paths, run=None):
    buf = io.StringIO()
    events = []
    for p in paths:
        events.extend(obs_report.load_events(p, run_id=run))
    events.sort(key=lambda ev: ev.get("t", 0.0))
    rc = obs_report.render(events, out=buf)
    return rc, buf.getvalue()


class TestObsReport:
    def test_report_from_instrumented_jit_run(self, tmp_path):
        """Default-tier sibling of the finetune-smoke report test: a real
        jitted fn drives the watchdog + runlog, and the report renders
        throughput, compile-share and retrace sections from the file."""
        path = str(tmp_path / "run.jsonl")
        log = RunLog(path, driver="t", echo=False)
        log.run_start(config={"purpose": "report test"}, probe_devices=False)
        fn = jax.jit(lambda x: (x * 2).sum())
        wd = CompileWatchdog("step", log)
        wrapped = wd.wrap(fn)
        for i in range(12):
            x = jnp.ones((1, 128 if i % 2 == 0 else 256))
            t0 = time.time()
            wrapped(x)
            log.step(i, wall_s=time.time() - t0, synced=True, loss=1.0 / (i + 1))
        log.run_end(status="ok")

        rc, text = _render([path])
        assert rc == 0
        assert "== throughput ==" in text and "p50" in text
        assert "== compile ==" in text and "% of run wall" in text
        assert "retrace table" in text
        assert "steps: 12" in text

    def test_selftest_passes(self):
        assert obs_report.selftest() == 0

    def test_cli_on_missing_file_exits_2(self):
        assert obs_report.main(["/nonexistent/run.jsonl"]) == 2

    def test_run_filter_on_multi_run_stream(self, tmp_path):
        path = str(tmp_path / "stream.jsonl")
        a = RunLog(path, driver="bench", run_id="run-a", echo=False)
        a.step(0, wall_s=0.1, synced=True)
        a.close()
        b = RunLog(path, driver="bench", run_id="run-b", echo=False)
        b.step(0, wall_s=0.2, synced=True)
        b.close()
        rc, text = _render([path], run="run-a")
        assert rc == 0
        assert "run-a" in text and "run-b" not in text


@pytest.mark.slow
def test_obs_report_on_finetune_smoke(tmp_path, rng):
    """ISSUE acceptance: the finetune smoke test's own run JSONL renders a
    report with throughput, compile-share and retrace sections."""
    import glob

    import h5py
    import pandas as pd

    from gigapath_tpu.finetune.main import main

    root = tmp_path / "h5_files"
    root.mkdir()
    rows = []
    for i in range(8):
        n_tiles = 12 + i
        with h5py.File(root / f"s{i}.h5", "w") as f:
            f.create_dataset(
                "features", data=rng.normal(size=(n_tiles, 16)).astype(np.float32)
            )
            f.create_dataset(
                "coords", data=rng.integers(0, 2000, (n_tiles, 2)).astype(np.float32)
            )
        rows.append(
            {"slide_id": f"s{i}.svs", "pat_id": f"p{i}", "label": ["neg", "pos"][i % 2]}
        )
    csv_path = tmp_path / "dataset.csv"
    pd.DataFrame(rows).to_csv(csv_path, index=False)
    yaml_path = tmp_path / "task.yaml"
    yaml_path.write_text(
        "name: toy\nsetting: multi_class\n"
        "label_dict:\n  neg: 0\n  pos: 1\nmax_tiles: 64\nshuffle_tiles: false\n"
    )
    save_dir = str(tmp_path / "out")
    main(
        [
            "--task_cfg_path", str(yaml_path),
            "--dataset_csv", str(csv_path),
            "--root_path", str(root),
            "--split_dir", str(tmp_path / "splits"),
            "--save_dir", save_dir,
            "--model_arch", "gigapath_slide_enc_tiny",
            "--input_dim", "16",
            "--latent_dim", "32",
            "--feat_layer", "1",
            "--folds", "1",
            "--epochs", "1",
            "--warmup_epochs", "1",
            "--gc", "2",
            "--val_r", "0.25",
            "--model_select", "val",
            "--report_to", "jsonl",
            "--dropout", "0.0",
            "--drop_path_rate", "0.0",
        ]
    )
    runs = glob.glob(os.path.join(save_dir, "**", "obs", "*.jsonl"), recursive=True)
    assert runs, "the finetune run must leave an obs JSONL artifact"
    rc, text = _render([runs[0]])
    assert rc == 0
    events = read_events(runs[0])
    kinds = {ev["kind"] for ev in events}
    assert {"run_start", "step", "compile", "eval", "run_end"} <= kinds
    # in-graph scalars rode the synced step events or epoch telemetry
    assert "== throughput ==" in text
    assert "== compile ==" in text and "retrace table" in text
    assert "== timeline ==" in text
