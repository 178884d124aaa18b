"""Anomaly engine, flight recorder, triggered profiler capture, history.

The load-bearing contracts from ISSUE 6's acceptance criteria:

- every detector flips BOTH ways on synthetic step streams (fires on
  the seeded anomaly, stays quiet on the healthy twin);
- a forced error / anomaly dumps the flight recorder with the buffered
  context, and the dump budget bounds a flapping trigger;
- the profiler-capture budget bounds trace captures, and captures stop
  after K steps;
- a forced stall in a real CPU driver run (inference) produces an
  ``anomaly`` event, a flight dump and a profiler trace dir — while the
  obs-off twin produces none of the three and compiles exactly as
  often;
- obs off / anomaly off leaves NOTHING on disk and adds zero retraces.
"""

import glob
import json
import logging
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapath_tpu.obs import (
    AnomalyConfig,
    AnomalyEngine,
    NullAnomalyEngine,
    NullRunLog,
    RunLog,
    attach_anomaly_engine,
    get_run_log,
)
from gigapath_tpu.obs.watchdog import CompileWatchdog

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))


def read_events(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def anomaly_events(path, detector=None):
    out = [ev for ev in read_events(path) if ev["kind"] == "anomaly"]
    if detector is not None:
        out = [ev for ev in out if ev.get("detector") == detector]
    return out


def make_engine(tmp_path, **cfg_overrides):
    """RunLog + engine with test-friendly thresholds; profiler capture
    off unless the test opts in."""
    cfg = AnomalyConfig(capture_budget=0, warmup_steps=4, cooldown_steps=4)
    for k, v in cfg_overrides.items():
        setattr(cfg, k, v)
    log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
    engine = attach_anomaly_engine(log, config=cfg)
    return log, engine


# ---------------------------------------------------------------------------
# detectors: each one flips both ways on a synthetic step stream
# ---------------------------------------------------------------------------

class TestDetectors:
    def test_step_time_spike_fires_and_steady_stream_does_not(self, tmp_path):
        log, engine = make_engine(tmp_path)
        for i in range(10):
            log.step(i, wall_s=0.01, synced=True)
        assert anomaly_events(log.path) == []  # healthy twin: quiet
        log.step(10, wall_s=0.2, synced=True)  # 20x the EWMA
        (ev,) = anomaly_events(log.path, "step_time_spike")
        assert ev["step"] == 10
        assert ev["value"] == 0.2
        assert ev["baseline"] == pytest.approx(0.01, rel=0.1)
        assert ev["flight"]  # the reaction fired too
        log.close()

    def test_spike_needs_warmup(self, tmp_path):
        log, _ = make_engine(tmp_path, warmup_steps=8)
        log.step(0, wall_s=0.01, synced=True)
        log.step(1, wall_s=5.0, synced=True)  # huge, but unbaselined
        assert anomaly_events(log.path, "step_time_spike") == []
        log.close()

    def test_unsynced_walls_never_spike(self, tmp_path):
        """Unsynced wall_s is dispatch time under async dispatch —
        spiking on it would be pure noise."""
        log, _ = make_engine(tmp_path)
        for i in range(10):
            log.step(i, wall_s=0.01, synced=True)
        log.step(10, wall_s=0.9, synced=False)
        assert anomaly_events(log.path) == []
        log.close()

    def test_compile_paying_step_is_exempt_and_kept_out_of_baseline(
        self, tmp_path
    ):
        """A new bucket's first synced step legitimately carries minutes
        of XLA compile wall — not a spike, and not baseline input."""
        log, _ = make_engine(tmp_path)
        for i in range(10):
            log.step(i, wall_s=0.01, synced=True)
        log.compile_event("step", (1, 256), 4.0, count=1)
        log.step(10, wall_s=4.0, synced=True)  # the compile-paying step
        assert anomaly_events(log.path) == []
        # ... and it did not poison the EWMA: a real spike still fires
        # against the 0.01 baseline
        log.step(11, wall_s=0.01, synced=True)
        log.step(12, wall_s=0.3, synced=True)
        (ev,) = anomaly_events(log.path, "step_time_spike")
        assert ev["baseline"] == pytest.approx(0.01, rel=0.1)
        log.close()

    def test_spike_baselines_are_bucket_keyed(self, tmp_path):
        """Bucketed training runs order-of-magnitude different walls per
        bucket — crossing buckets must not read as a spike, but a spike
        WITHIN a bucket must."""
        log, _ = make_engine(tmp_path)
        for i in range(8):  # interleaved buckets, 8 samples each
            log.step(2 * i, wall_s=0.01, synced=True, bucket="(1, 128)")
            log.step(2 * i + 1, wall_s=0.5, synced=True, bucket="(1, 4096)")
        assert anomaly_events(log.path) == []  # 50x across buckets: fine
        log.step(16, wall_s=0.2, synced=True, bucket="(1, 128)")
        (ev,) = anomaly_events(log.path, "step_time_spike")
        assert ev["bucket"] == "(1, 128)"
        assert ev["baseline"] == pytest.approx(0.01, rel=0.1)
        log.close()

    def test_cooldown_bounds_anomalies_per_bad_regime(self, tmp_path):
        log, _ = make_engine(tmp_path, cooldown_steps=100)
        for i in range(10):
            log.step(i, wall_s=0.01, synced=True)
        for i in range(10, 16):
            log.step(i, wall_s=0.5, synced=True)  # persistently bad
        assert len(anomaly_events(log.path, "step_time_spike")) == 1
        log.close()

    def test_throughput_dip_fires_and_recovers(self, tmp_path):
        """Fed directly with records carrying controlled arrival times
        (runlog.event stamps real wall clocks — useless for this)."""
        log, engine = make_engine(tmp_path, dip_factor=3.0)
        t = 1000.0
        for i in range(10):  # steady 10 steps/s baseline
            engine.on_event({"kind": "step", "step": i, "t": t})
            t += 0.1
        assert anomaly_events(log.path) == []
        for i in range(10, 20):  # collapse to 0.5 steps/s
            engine.on_event({"kind": "step", "step": i, "t": t})
            t += 2.0
        dips = anomaly_events(log.path, "throughput_dip")
        assert dips, "sustained slowdown must fire the dip detector"
        assert dips[0]["value"] < dips[0]["baseline"]
        log.close()

    def test_single_pause_does_not_dip(self, tmp_path):
        """One long gap (an eval epoch) must not burn the budget."""
        log, engine = make_engine(tmp_path, dip_factor=3.0)
        t = 1000.0
        for i in range(10):
            engine.on_event({"kind": "step", "step": i, "t": t})
            t += 0.1
        t += 30.0  # one eval-sized pause
        for i in range(10, 14):  # back to full speed
            engine.on_event({"kind": "step", "step": i, "t": t})
            t += 0.1
        assert anomaly_events(log.path, "throughput_dip") == []
        log.close()

    def test_stall_event_becomes_anomaly(self, tmp_path):
        log, _ = make_engine(tmp_path)
        log.stall(last_step=7, since_progress_s=1.5, deadline_s=0.5)
        (ev,) = anomaly_events(log.path, "stall")
        assert ev["value"] == 1.5 and ev["threshold"] == 0.5
        # heartbeats alone never fire it
        log.heartbeat(last_step=8, since_progress_s=0.1)
        assert len(anomaly_events(log.path, "stall")) == 1
        log.close()

    def test_unexpected_retrace_becomes_anomaly(self, tmp_path):
        log, _ = make_engine(tmp_path)
        log.compile_event("step", (1, 128), 0.5, count=1, unexpected=False)
        assert anomaly_events(log.path) == []  # expected compiles: quiet
        log.compile_event("step", (1, 128), 0.4, count=2, unexpected=True)
        (ev,) = anomaly_events(log.path, "unexpected_retrace")
        assert ev["fn"] == "step" and ev["compile_count"] == 2
        # the rolling compile-share context rides every anomaly event
        assert ev["compile_share"] is not None and ev["compile_share"] > 0
        log.close()

    def test_memory_watermark_growth_fires_plateau_does_not(self, tmp_path):
        log, _ = make_engine(
            tmp_path, watermark_factor=1.5, watermark_min_delta=1000.0
        )
        mb = 1 << 20
        for _ in range(5):  # flat watermark: quiet
            log.heartbeat(last_step=1, mem_peak_bytes=100 * mb)
        assert anomaly_events(log.path) == []
        log.heartbeat(last_step=2, mem_peak_bytes=170 * mb)  # 1.7x
        (ev,) = anomaly_events(log.path, "memory_watermark")
        assert ev["value"] == 170 * mb and ev["baseline"] == 100 * mb
        # re-armed at the fired level: the same plateau stays quiet...
        log.heartbeat(last_step=3, mem_peak_bytes=171 * mb)
        assert len(anomaly_events(log.path, "memory_watermark")) == 1
        log.close()

    def test_watermark_growth_survives_cooldown_suppression(self, tmp_path):
        """A growth observation whose _fire was suppressed by cooldown
        must NOT re-arm the baseline — once the cooldown expires the
        (still-standing) growth fires against the original baseline."""
        log, _ = make_engine(
            tmp_path, watermark_factor=1.5, watermark_min_delta=1000.0,
            cooldown_steps=4,
        )
        log.heartbeat(last_step=0, mem_peak_bytes=100_000)  # baseline
        log.heartbeat(last_step=1, mem_peak_bytes=200_000)  # fires, re-arms
        assert len(anomaly_events(log.path, "memory_watermark")) == 1
        log.heartbeat(last_step=2, mem_peak_bytes=400_000)  # cooldown: mute
        assert len(anomaly_events(log.path, "memory_watermark")) == 1
        for i in range(4):  # step events advance the cooldown clock
            log.step(i, wall_s=0.01, synced=True)
        log.heartbeat(last_step=6, mem_peak_bytes=400_000)  # plateau at 4x
        events = anomaly_events(log.path, "memory_watermark")
        assert len(events) == 2, "the muted growth must fire after cooldown"
        assert events[1]["baseline"] == 200_000.0  # not silently re-armed
        log.close()

    def test_anomaly_events_are_never_detector_input(self, tmp_path):
        """The engine's own output must not feed back into detection
        (a spike anomaly creating more anomalies forever)."""
        log, engine = make_engine(tmp_path)
        for i in range(10):
            log.step(i, wall_s=0.01, synced=True)
        log.step(10, wall_s=0.5, synced=True)
        n = len(anomaly_events(log.path))
        time.sleep(0.02)
        assert len(anomaly_events(log.path)) == n
        log.close()


class TestSloBurnDetector:
    """The slo_burn detector reacts to SloTracker transition events —
    the full service-level loop (forced-slow dispatch -> one anomaly +
    reactions) is pinned in tests/test_serve_obs.py; these are the
    engine-side edges."""

    def test_burning_transition_fires_with_reactions(self, tmp_path):
        log, engine = make_engine(tmp_path)
        for i in range(3):
            log.step(i, wall_s=0.01, synced=True)
        log.event("slo", name="serve", burning=True, target_s=0.05,
                  budget=0.25, burn_short=4.0, burn_long=4.0,
                  threshold=1.5, latency_s=0.5)
        (ev,) = anomaly_events(log.path, "slo_burn")
        assert ev["value"] == 4.0 and ev["baseline"] == 1.5
        assert ev["target_s"] == 0.05
        assert ev["flight"] and os.path.exists(ev["flight"])
        log.close()

    def test_final_status_and_recovery_never_fire(self, tmp_path):
        log, engine = make_engine(tmp_path)
        # terminal status events are marked final — never detector input
        log.event("slo", name="serve", burning=True, final=True,
                  target_s=0.05, burn_short=9.0, burn_long=9.0)
        # a recovery transition is not an anomaly either
        log.event("slo", name="serve", burning=False, target_s=0.05,
                  burn_short=0.0, burn_long=0.0)
        assert anomaly_events(log.path, "slo_burn") == []
        log.close()

    def test_cooldown_bounds_flapping_slo(self, tmp_path):
        log, engine = make_engine(tmp_path, cooldown_steps=100)
        for i in range(3):
            log.step(i, wall_s=0.01, synced=True)
        for _ in range(4):  # a flapping tracker re-enters burning
            log.event("slo", name="serve", burning=True, target_s=0.05,
                      burn_short=4.0, burn_long=4.0, threshold=1.5)
        assert len(anomaly_events(log.path, "slo_burn")) == 1
        log.close()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlight:
    def test_error_event_dumps_context(self, tmp_path):
        log, engine = make_engine(tmp_path)
        for i in range(5):
            log.step(i, wall_s=0.01, synced=True)
        assert not os.path.exists(engine.flight.path)  # healthy: no file
        log.error("driver.place", ValueError("boom"))
        assert os.path.exists(engine.flight.path)
        records = read_events(engine.flight.path)
        assert records[0]["kind"] == "flight_meta"
        assert records[0]["reason"] == "error"
        dumped_kinds = [r["kind"] for r in records[1:]]
        assert dumped_kinds.count("step") == 5  # the context came along
        assert "error" in dumped_kinds
        log.close()

    def test_ring_is_bounded_and_dumps_dedup(self, tmp_path):
        log, engine = make_engine(tmp_path, flight_capacity=8)
        for i in range(50):
            log.step(i, wall_s=0.01, synced=True)
        log.error("a", ValueError("x"))
        first = read_events(engine.flight.path)
        assert first[0]["events"] <= 8 + 1  # ring capacity bounds context
        log.step(50, wall_s=0.01, synced=True)
        log.error("b", ValueError("y"))
        records = read_events(engine.flight.path)
        metas = [r for r in records if r["kind"] == "flight_meta"]
        assert [m["dump"] for m in metas] == [1, 2]
        # the second dump carries only events SINCE the first
        second_steps = [
            r for r in records[len(first):] if r["kind"] == "step"
        ]
        assert [r["step"] for r in second_steps] == [50]
        log.close()

    def test_shared_run_id_keeps_per_process_flight_and_trace_names(
        self, tmp_path, monkeypatch
    ):
        """Under GIGAPATH_OBS_RUN_ID every rank's run FILE carries a
        -<host>-p<pid> suffix; the flight file and trace dirs must
        inherit it so concurrent ranks never interleave into one
        post-mortem artifact."""
        monkeypatch.delenv("GIGAPATH_OBS", raising=False)
        monkeypatch.setenv("GIGAPATH_OBS_RUN_ID", "mh-run-1")
        log = get_run_log("t", out_dir=str(tmp_path), echo=False,
                          probe_devices=False)
        stem = os.path.splitext(os.path.basename(log.path))[0]
        assert f"-p{os.getpid()}" in stem
        assert os.path.basename(log.flight.path) == f"flight-{stem}.jsonl"
        trace_dir = log.anomaly._next_trace_dir_locked("x")
        assert os.path.basename(trace_dir).startswith(f"{stem}-x-")
        log.close()

    def test_dump_budget_exhaustion(self, tmp_path):
        log, engine = make_engine(tmp_path, flight_max_dumps=2)
        for i in range(6):
            log.step(i, wall_s=0.01, synced=True)
            log.error(f"e{i}", ValueError("x"))
        metas = [
            r for r in read_events(engine.flight.path)
            if r["kind"] == "flight_meta"
        ]
        assert len(metas) == 2  # the flapping trigger hit the budget
        log.close()


# ---------------------------------------------------------------------------
# triggered profiler capture
# ---------------------------------------------------------------------------

class TestProfilerCapture:
    @pytest.mark.slow
    def test_anomaly_triggers_capture_that_stops_after_k_steps(self, tmp_path):
        """Slow tier: compiles inside an open jax.profiler trace. The
        default tier covers capture via the budget/flag tests and the
        driver acceptance test below."""
        log, engine = make_engine(
            tmp_path, capture_budget=2, capture_steps=2
        )
        fn = jax.jit(lambda x: (x * 2).sum())
        for i in range(10):
            log.step(i, wall_s=0.01, synced=True)
        log.step(10, wall_s=0.5, synced=True)  # spike -> arm capture
        (ev,) = anomaly_events(log.path, "step_time_spike")
        assert ev["trace_dir"]
        for i in range(11, 15):  # trace runs across the next K steps
            fn(jnp.ones((4,)))
            log.step(i, wall_s=0.01, synced=True)
        log.run_end(status="ok")
        assert engine.trace_dirs == [ev["trace_dir"]]
        files = glob.glob(os.path.join(ev["trace_dir"], "**", "*"),
                          recursive=True)
        assert any("xplane" in f for f in files), (
            "the capture must leave real trace files"
        )

    def test_capture_budget_exhaustion(self, tmp_path):
        """Two firing detectors, budget 1 -> exactly one trace dir."""
        log, engine = make_engine(
            tmp_path, capture_budget=1, capture_steps=1, cooldown_steps=2
        )
        for i in range(10):
            log.step(i, wall_s=0.01, synced=True)
        log.step(10, wall_s=0.5, synced=True)   # spike 1: captures
        for i in range(11, 16):
            log.step(i, wall_s=0.01, synced=True)
        log.step(16, wall_s=0.9, synced=True)   # spike 2: budget gone
        log.run_end(status="ok")
        spikes = anomaly_events(log.path, "step_time_spike")
        assert len(spikes) == 2
        assert len(engine.trace_dirs) == 1
        assert spikes[1]["trace_dir"] is None

    def test_profile_flag_captures_first_n_steps(self, tmp_path, monkeypatch):
        monkeypatch.delenv("GIGAPATH_OBS", raising=False)
        monkeypatch.setenv("GIGAPATH_PROFILE", "2")
        log = get_run_log("t", out_dir=str(tmp_path), echo=False,
                          probe_devices=False)
        engine = log.anomaly
        assert isinstance(engine, AnomalyEngine)
        for i in range(4):
            log.step(i, wall_s=0.01, synced=True)
        log.run_end(status="ok")
        assert len(engine.trace_dirs) == 1
        assert "profile_flag" in engine.trace_dirs[0]
        assert os.path.isdir(engine.trace_dirs[0])


# ---------------------------------------------------------------------------
# zero-overhead / obs-off contracts
# ---------------------------------------------------------------------------

class TestZeroOverhead:
    def test_obs_off_means_no_engine_no_files(self, tmp_path, monkeypatch):
        monkeypatch.setenv("GIGAPATH_OBS", "0")
        log = get_run_log("t", out_dir=str(tmp_path))
        assert isinstance(log, NullRunLog) and not isinstance(log, RunLog)
        assert isinstance(attach_anomaly_engine(log), NullAnomalyEngine)
        for i in range(12):
            log.step(i, wall_s=0.01 if i != 10 else 9.9, synced=True)
        log.error("x", ValueError("boom"))
        log.run_end(status="ok")
        assert list(tmp_path.iterdir()) == [], "obs-off left artifacts"

    def test_anomaly_off_keeps_obs_on(self, tmp_path, monkeypatch):
        monkeypatch.delenv("GIGAPATH_OBS", raising=False)
        monkeypatch.setenv("GIGAPATH_ANOMALY", "0")
        log = get_run_log("t", out_dir=str(tmp_path), echo=False,
                          probe_devices=False)
        assert isinstance(log, RunLog)
        assert isinstance(
            getattr(log, "anomaly", NullAnomalyEngine()), NullAnomalyEngine
        )
        for i in range(12):
            log.step(i, wall_s=0.01 if i != 10 else 9.9, synced=True)
        log.run_end(status="ok")
        events = read_events(log.path)
        assert [e for e in events if e["kind"] == "anomaly"] == []
        assert not glob.glob(str(tmp_path / "obs" / "flight-*"))
        assert not glob.glob(str(tmp_path / "obs" / "traces" / "*"))

    def test_engine_attached_adds_zero_retraces(self, tmp_path):
        """The full closed loop (engine + flight + spike firing) watches
        a jitted step that compiles exactly as often as the bare twin —
        the engine is pure host-side event consumption."""

        def step(params, x):
            return params["w"] * jnp.sum(x)

        params = {"w": jnp.float32(2.0)}
        buckets = [jnp.ones((1, 128)), jnp.ones((1, 256))]

        bare = jax.jit(step)
        for x in buckets * 6:
            bare(params, x)

        log, engine = make_engine(tmp_path)
        instrumented = jax.jit(step)
        wd = CompileWatchdog("step", log, fn=instrumented)
        wrapped = wd.wrap(instrumented)
        for i, x in enumerate(buckets * 6):
            wall = 0.01 if i != 10 else 0.7  # seed a spike mid-run
            wrapped(params, x)
            log.step(i, wall_s=wall, synced=True)
        log.run_end(status="ok")

        assert anomaly_events(log.path, "step_time_spike"), (
            "the spike must actually have fired for this test to bite"
        )
        assert bare._cache_size() == instrumented._cache_size() == 2
        assert sum(wd.compile_count.values()) == 2
        assert wd.unexpected_retraces == []

    def test_watched_hlo_identical_with_engine_attached(self, tmp_path):
        def step(params, x):
            return params["w"] * jnp.sum(x)

        params = {"w": jnp.float32(2.0)}
        x = jnp.ones((1, 128))
        bare = jax.jit(step)
        bare(params, x)

        log, _ = make_engine(tmp_path)
        watched = jax.jit(step)
        wd = CompileWatchdog("step", log, fn=watched)
        wrapped = wd.wrap(watched)
        wrapped(params, x)
        log.close()
        assert (
            bare.lower(params, x).compile().as_text()
            == watched.lower(params, x).compile().as_text()
        )


# ---------------------------------------------------------------------------
# heartbeat memory watermarks (satellite)
# ---------------------------------------------------------------------------

class TestHeartbeatWatermarks:
    def test_cpu_backend_heartbeats_carry_no_mem_fields(self, tmp_path):
        from gigapath_tpu.obs import Heartbeat

        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        with Heartbeat(log, interval_s=0.05, stall_after_s=10.0,
                       name="t") as hb:
            hb.beat(1)
            time.sleep(0.2)
        hbs = [ev for ev in read_events(log.path) if ev["kind"] == "heartbeat"]
        assert hbs
        assert all("mem_peak_bytes" not in ev for ev in hbs), (
            "CPU memory_stats() is None — the field must be absent, not 0"
        )
        log.close()

    def test_watermarks_ride_heartbeats_when_backend_reports(
        self, tmp_path, monkeypatch
    ):
        from gigapath_tpu.obs import Heartbeat

        class FakeDev:
            def __init__(self, peak, in_use):
                self._s = {"peak_bytes_in_use": peak, "bytes_in_use": in_use}

            def memory_stats(self):
                return self._s

        monkeypatch.setattr(
            jax, "devices", lambda: [FakeDev(300, 120), FakeDev(500, 80)]
        )
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        with Heartbeat(log, interval_s=0.05, stall_after_s=10.0,
                       name="t") as hb:
            hb.beat(1)
            time.sleep(0.2)
        hbs = [ev for ev in read_events(log.path) if ev["kind"] == "heartbeat"]
        assert hbs
        assert hbs[-1]["mem_peak_bytes"] == 500.0   # max across devices
        assert hbs[-1]["mem_bytes_in_use"] == 200.0  # summed
        log.close()

    def test_memory_watermarks_helper_guards(self, monkeypatch):
        from gigapath_tpu.obs.heartbeat import memory_watermarks

        assert memory_watermarks() == {}  # CPU: stats are None

        def boom():
            raise RuntimeError("backend exploded")

        monkeypatch.setattr(jax, "devices", boom)
        assert memory_watermarks() == {}  # never raises into the beat

    def test_env_tunable_deadlines(self, monkeypatch):
        from gigapath_tpu.obs import Heartbeat

        monkeypatch.setenv("GIGAPATH_OBS_HEARTBEAT_S", "1.5")
        monkeypatch.setenv("GIGAPATH_OBS_STALL_S", "7.5")
        hb = Heartbeat(NullRunLog())
        assert hb.interval_s == 1.5 and hb.stall_after_s == 7.5
        explicit = Heartbeat(NullRunLog(), interval_s=9.0, stall_after_s=90.0)
        assert explicit.interval_s == 9.0  # explicit args win
        monkeypatch.setenv("GIGAPATH_OBS_STALL_S", "not-a-number")
        assert Heartbeat(NullRunLog()).stall_after_s == 300.0  # safe fallback


# ---------------------------------------------------------------------------
# acceptance: a real CPU driver run, closed loop end to end
# ---------------------------------------------------------------------------

def _feature_files(tmp_path, n_slides=4, n_tiles=12, dim=16):
    import torch

    rng = np.random.default_rng(0)
    feat_dir = tmp_path / "features"
    feat_dir.mkdir()
    for i in range(n_slides):
        torch.save(
            {
                "features": torch.from_numpy(
                    rng.normal(size=(n_tiles, dim)).astype(np.float32)
                ),
                "coords": torch.from_numpy(
                    rng.integers(0, 1000, (n_tiles, 2)).astype(np.float32)
                ),
            },
            feat_dir / f"s{i}_features.pt",
        )
    return str(feat_dir)


def _tiny_inference_model():
    from gigapath_tpu.inference import load_model

    return load_model(
        "", input_dim=16, latent_dim=32, feat_layer="1", n_classes=2,
        model_arch="gigapath_slide_enc_tiny",
    )


class _CompileCounter(logging.Handler):
    """Counts XLA compiles of the driver's jitted ``forward`` via
    jax_log_compiles — backend truth, independent of obs being on."""

    def __init__(self):
        super().__init__()
        self.count = 0

    def emit(self, record):
        msg = record.getMessage()
        if "Finished XLA compilation of jit(forward)" in msg:
            self.count += 1


def _run_inference_driver(tmp_path, monkeypatch, stall_slide=2,
                          stall_s=0.7):
    """Drive gigapath_tpu.inference over tiny synthetic slides, forcing
    a stall (slow feature load) on one slide. Returns the compile count
    observed at the XLA layer."""
    import gigapath_tpu.inference as inference

    feat_dir = _feature_files(tmp_path)
    model, params = _tiny_inference_model()

    real_load = inference._load_features
    calls = {"n": 0}

    def slow_load(path):
        calls["n"] += 1
        if calls["n"] == stall_slide + 1:
            time.sleep(stall_s)  # the forced stall: one hung "RPC"
        return real_load(path)

    monkeypatch.setattr(inference, "_load_features", slow_load)

    counter = _CompileCounter()
    logger = logging.getLogger("jax._src.dispatch")
    logger.addHandler(counter)
    prev_level = logger.level
    logger.setLevel(logging.DEBUG)
    jax.config.update("jax_log_compiles", True)
    try:
        out_csv = str(tmp_path / "out" / "predictions.csv")
        os.makedirs(os.path.dirname(out_csv), exist_ok=True)
        # exact-shape path: this acceptance pair pins the slide-at-a-time
        # driver's compile accounting (the bucketed serving path has its
        # own compile-count pins in tests/test_serve.py)
        df = inference.run_inference(model, params, feat_dir, out_csv,
                                     use_buckets=False)
    finally:
        jax.config.update("jax_log_compiles", False)
        logger.setLevel(prev_level)
        logger.removeHandler(counter)
    assert df is not None and len(df) == 4
    return counter.count


def test_inference_driver_stall_produces_anomaly_flight_and_trace(
    tmp_path, monkeypatch
):
    """ISSUE 6 acceptance (tier-1 by requirement): a forced stall in a
    CPU driver run produces an anomaly event, a flight dump and
    (capture enabled) a profiler trace dir."""
    monkeypatch.delenv("GIGAPATH_OBS", raising=False)
    monkeypatch.delenv("GIGAPATH_ANOMALY", raising=False)
    monkeypatch.setenv("GIGAPATH_OBS_HEARTBEAT_S", "0.05")
    monkeypatch.setenv("GIGAPATH_OBS_STALL_S", "0.2")
    monkeypatch.setenv("GIGAPATH_PROFILE", "1")  # capture from step 1 too

    _run_inference_driver(tmp_path, monkeypatch)

    obs_dir = tmp_path / "out" / "obs"
    runs = glob.glob(str(obs_dir / "inference-*.jsonl"))
    runs = [p for p in runs if "flight-" not in os.path.basename(p)]
    assert len(runs) == 1
    events = read_events(runs[0])
    kinds = {ev["kind"] for ev in events}
    assert {"run_start", "step", "compile", "stall", "anomaly",
            "run_end"} <= kinds

    # 1) the anomaly event (stall detector)
    stall_anomalies = [
        ev for ev in events
        if ev["kind"] == "anomaly" and ev["detector"] == "stall"
    ]
    assert stall_anomalies, "the forced stall must fire the detector"

    # 2) the flight dump, carrying the context around the stall (the
    # first stall fires during the first slide's compile, so the buffer
    # holds the run_start/heartbeat prefix — context, whatever it was)
    flights = glob.glob(str(obs_dir / "flight-*.jsonl"))
    assert len(flights) == 1
    flight_records = read_events(flights[0])
    assert flight_records[0]["kind"] == "flight_meta"
    assert flight_records[0]["reason"] == "stall"
    assert len(flight_records) > 1, "the dump must carry context events"

    # 3) the profiler trace dir(s), with real trace files inside
    trace_dirs = glob.glob(str(obs_dir / "traces" / "*"))
    assert trace_dirs, "GIGAPATH_PROFILE=1 must leave a capture dir"
    trace_files = glob.glob(str(obs_dir / "traces" / "**" / "*"),
                            recursive=True)
    assert any("xplane" in f for f in trace_files)

    # compile accounting, as far as the driver promises it: every slide
    # shares one shape -> one compile event, and the watchdog saw no
    # unexpected retrace (how many XLA compiles the ledger's own AOT
    # profile costs is the backend's business; the obs-off twin below
    # pins the forward's)
    compile_events = [ev for ev in events if ev["kind"] == "compile"]
    assert len(compile_events) == 1
    assert not any(ev.get("unexpected") for ev in compile_events)

    # obs_report renders the anomalies section from the artifact
    import obs_report

    import io

    buf = io.StringIO()
    assert obs_report.render(read_events(runs[0]), out=buf) == 0
    text = buf.getvalue()
    assert "== anomalies ==" in text and "STALL" in text


def test_inference_driver_obs_off_twin_is_silent_and_compiles_the_same(
    tmp_path, monkeypatch
):
    """The obs-off twin of the run above: same forced stall, no anomaly
    event, no flight file, no trace dir anywhere in the tree — and the
    same XLA compile count minus exactly the ledger's documented AOT
    profile (i.e. zero retraces either way)."""
    monkeypatch.setenv("GIGAPATH_OBS", "0")
    monkeypatch.setenv("GIGAPATH_OBS_HEARTBEAT_S", "0.05")
    monkeypatch.setenv("GIGAPATH_OBS_STALL_S", "0.2")
    monkeypatch.setenv("GIGAPATH_PROFILE", "1")  # must be inert when obs off

    compiles = _run_inference_driver(tmp_path, monkeypatch)

    # none of the three artifacts exist anywhere under the test tree
    left = [
        os.path.relpath(p, str(tmp_path))
        for p in glob.glob(str(tmp_path / "**" / "*"), recursive=True)
        if os.path.isfile(p)
    ]
    parts = {seg for p in left for seg in p.split(os.sep)}
    assert "obs" not in parts and "traces" not in parts, left
    assert not any(seg.startswith("flight-") for seg in parts), left
    assert not any("anomaly" in p for p in left), left
    assert [os.path.basename(p) for p in left].count("predictions.csv") == 1
    # 4 same-shape slides -> exactly ONE compile of forward, never a
    # retrace
    assert compiles == 1
