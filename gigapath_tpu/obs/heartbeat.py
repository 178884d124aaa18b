"""Liveness heartbeat + stall monitor.

The failure mode this exists for: a blocking runtime call
(``jax.devices()``, a ``block_until_ready``, a collective whose peer died)
hangs with no deadline, the train loop stops advancing, and nothing in the
process says so — the run just goes quiet. A
background daemon thread cannot un-hang the RPC, but it can make the
hang *observable*: periodic ``heartbeat`` events keep timestamped proof
of liveness in the run artifact, and a ``stall`` event fires the moment
no step completes within the deadline, so both a human tail and
``scripts/obs_report.py`` can see exactly when progress stopped.

Usage::

    with Heartbeat(runlog, interval_s=30, stall_after_s=300) as hb:
        for step, batch in enumerate(loader):
            ...
            hb.beat(step)

``beat()`` is a lock + two assignments — safe to call every step. One
``stall`` event per stall episode; a later ``beat`` re-arms it so a
recovered run can flag a second stall.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Dict, Optional

from gigapath_tpu.obs.locktrace import make_lock


def env_seconds(name: str, default: float) -> float:
    """Host-side env override for the heartbeat deadlines (read once, at
    Heartbeat construction = driver start — never at trace time).
    Public: drivers with their own historical defaults (finetune's
    60/600) call this with those defaults instead of Heartbeat's."""
    from gigapath_tpu.obs.runlog import env_number

    return env_number(name, default)


def memory_watermarks() -> Dict[str, float]:
    """Device-memory watermarks via ``device.memory_stats()``, for the
    heartbeat events the anomaly engine's watermark detector reads.

    Guarded three ways (this runs on the heartbeat daemon thread):
    jax must already be imported, ``memory_stats()`` may be None
    (CPU backend reports none), and any backend error returns ``{}`` —
    probing memory must never be the call that hangs a run (the
    backend-init RPC this obs layer exists to survive is triggered by
    the first ``jax.devices()``; by the time heartbeats carry a step,
    the driver already initialized it).
    """
    if "jax" not in sys.modules:
        return {}
    try:
        import jax

        stats = [d.memory_stats() for d in jax.devices()]
    except Exception:
        return {}
    peaks = [s.get("peak_bytes_in_use") for s in stats if s]
    in_use = [s.get("bytes_in_use") for s in stats if s]
    out: Dict[str, float] = {}
    peaks = [p for p in peaks if p is not None]
    in_use = [b for b in in_use if b is not None]
    if peaks:
        out["mem_peak_bytes"] = float(max(peaks))
    if in_use:
        out["mem_bytes_in_use"] = float(sum(in_use))
    return out


class Heartbeat:
    def __init__(self, runlog, *, interval_s: Optional[float] = None,
                 stall_after_s: Optional[float] = None, name: str = "train"):
        self.runlog = runlog  # gigarace: type gigapath_tpu.obs.runlog.RunLog
        # env-tunable defaults so EVERY driver's deadlines can be bent
        # without a CLI surface (a forced-stall repro, a tight CI run);
        # explicit arguments win
        if interval_s is None:
            interval_s = env_seconds("GIGAPATH_OBS_HEARTBEAT_S", 30.0)
        if stall_after_s is None:
            stall_after_s = env_seconds("GIGAPATH_OBS_STALL_S", 300.0)
        self.interval_s = float(interval_s)
        self.stall_after_s = float(stall_after_s)
        self.name = name
        self.stall_count = 0
        self._last_beat = time.time()
        self._last_step: Optional[int] = None
        self._stalled = False
        self._lock = make_lock("gigapath_tpu.obs.heartbeat.Heartbeat._lock")
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "Heartbeat":
        if self._thread is not None:
            return self
        # under the lock even though the monitor thread does not exist
        # yet: restarts race a stop()ing monitor's final read
        with self._lock:
            self._last_beat = time.time()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"obs-heartbeat-{self.name}"
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def __enter__(self) -> "Heartbeat":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- progress ---------------------------------------------------------
    def beat(self, step: Optional[int] = None) -> None:
        """Record progress; re-arms stall detection after a recovery."""
        with self._lock:
            self._last_beat = time.time()
            if step is not None:
                self._last_step = step
            self._stalled = False

    # -- monitor thread ---------------------------------------------------
    def _tick_s(self) -> float:
        # poll fast enough to hit the stall deadline promptly even with
        # sub-second test configs, without spinning
        return max(0.01, min(self.interval_s, self.stall_after_s) / 4.0)

    def _run(self) -> None:
        next_hb = time.time() + self.interval_s
        while not self._stop.wait(timeout=self._tick_s()):
            now = time.time()
            with self._lock:
                since = now - self._last_beat
                step = self._last_step
                stalled = self._stalled
            if since >= self.stall_after_s and not stalled:
                with self._lock:
                    self._stalled = True
                self.stall_count += 1
                self.runlog.stall(
                    last_step=step,
                    since_progress_s=round(since, 3),
                    deadline_s=self.stall_after_s,
                )
                self.runlog.echo(
                    f"[stall] {self.name}: no step completed in "
                    f"{since:.1f}s (deadline {self.stall_after_s:.1f}s); "
                    f"last step {step}"
                )
            if now >= next_hb:
                # watermarks only once the run has made step progress:
                # before the first beat the backend may not be up, and
                # jax.devices() from this daemon thread must never be
                # the call that initializes (or hangs on) it
                mem = memory_watermarks() if step is not None else {}
                self.runlog.heartbeat(
                    last_step=step, since_progress_s=round(since, 3), **mem
                )
                next_hb = now + self.interval_s
