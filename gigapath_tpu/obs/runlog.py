"""Run-scoped structured telemetry: schema-versioned JSONL events.

Every run of a driver (finetune, pretrain, train_gigapath, linear probe,
inference, bench) becomes a machine-readable artifact: one JSONL file of
events a tool can fold into a report (``scripts/obs_report.py``), instead
of the reference stack's loose prints, which leave nothing behind when a
run dies.

Event kinds (schema v1, one JSON object per line, every record carries
``v``/``run``/``kind``/``t``):

- ``run_start``  — config + environment manifest (jax version, backend,
  device kind/count) emitted once at driver start;
- ``step``       — one training/inference step: ``step``, ``wall_s``
  (host wall seconds for this step), ``synced`` (whether the host
  blocked on the device this step — wall times of unsynced steps are
  dispatch times under async dispatch), plus free-form scalars;
- ``compile``    — XLA compile observed by the watchdog (fn, key,
  seconds, running count, ``unexpected`` retrace flag);
- ``compile_profile`` — compiled-artifact perf profile (XLA cost/memory
  analysis + jaxpr fingerprint) captured by the perf ledger
  (:mod:`gigapath_tpu.obs.ledger`);
- ``span``       — one closed host span (:mod:`gigapath_tpu.obs.spans`):
  name, nesting path/depth, monotonic ``dur_s``, ``fenced`` (device
  sync before the clock read), per-host ``rank``;
- ``eval``       — evaluation metrics at an epoch/step;
- ``heartbeat``  — periodic liveness from the background monitor;
- ``stall``      — no progress within the deadline (a hung blocking
  runtime call made visible);
- ``anomaly``    — a detector of the anomaly engine fired
  (:mod:`gigapath_tpu.obs.anomaly`): step-time spike, stall, unexpected
  retrace, memory-watermark growth, throughput dip — with the reaction
  taken (flight-dump path, scheduled profiler capture);
- ``serve_dispatch`` — one coalesced batch through a serving executable
  (:mod:`gigapath_tpu.serve`): bucket, slides/capacity (occupancy),
  per-slide queue waits, wall seconds, executable provenance;
- ``cache_hit``  — a serving request short-circuited by the
  content-hash embedding cache (no forward pass);
- ``metrics``    — one atomic snapshot of the typed metrics registry
  (:mod:`gigapath_tpu.obs.metrics`): counters, gauges, and
  exponential-bucket histograms with p50/p90/p99 — periodic
  (``GIGAPATH_METRICS_INTERVAL_S``) plus a final flush at ``run_end``;
- ``slo``        — an SLO burn-rate transition or terminal status from
  the :class:`~gigapath_tpu.obs.metrics.SloTracker` (target latency,
  budget, short/long-window burn) — ``burning: true`` transitions feed
  the anomaly engine's ``slo_burn`` detector;
- ``trace``      — the per-run request-trace export
  (:mod:`gigapath_tpu.obs.reqtrace`): path of the Perfetto-loadable
  Chrome-trace JSON plus trace/span/dropped totals;
- ``backpressure`` — the cross-stage boundary channel's producer ran
  out of consumer credits and BLOCKED (:mod:`gigapath_tpu.dist.boundary`):
  channel, seq, ``credits`` (0 at emission), queue depth, capacity —
  one event per blocking episode, the "consumer is falling behind"
  signal;
- ``worker_lost`` — a fleet member's lease expired
  (:mod:`gigapath_tpu.dist.membership`): worker, stage, seconds past
  expiry, last renewal — fires the anomaly engine's ``worker_lost``
  detector and precedes the ``recovery action="reassign"`` event;
- ``consumer_lost`` — a restarted slide-stage consumer found its dead
  predecessor's mid-slide checkpoint (:mod:`gigapath_tpu.dist.pipeline`):
  stage, reason, the stale lease's pid/renewal — fires the anomaly
  engine's ``consumer_lost`` detector and precedes the
  ``recovery action="consumer_resume"`` event;
- ``clock_sync`` — one cross-process clock-offset estimate for a
  transport link (:mod:`gigapath_tpu.obs.clock`): link, offset/rtt/
  uncertainty seconds, sample count, reconnect epoch — what
  ``obs/fleet.py`` aligns per-process timelines with;
- ``numerics``   — per-layer in-graph numerics summary
  (:mod:`gigapath_tpu.obs.numerics`): finite fraction, absmax, rms per
  top-level param subtree, synced at the driver's existing sync points
  (the ``step_scalars`` discipline) behind the ``GIGAPATH_NUMERICS``
  host flag;
- ``drift``      — an embedding-drift transition or terminal status
  from the :class:`~gigapath_tpu.obs.drift.DriftSentinel`
  (standardized mean shift, cosine distance, tail mass vs a persisted
  baseline sketch) — ``alarming: true`` transitions feed the anomaly
  engine's ``embedding_drift`` detector;
- ``stream_peek`` — one anytime read of a streaming slide serve
  (``StreamingEncoderSession.peek()``): fold frontier, provisional-
  embedding cosine vs the previous peek, layer-0 branch LSE spread —
  the provisional half of the ``serve.stream_confidence`` surface;
- ``error``      — exception surfaced by a driver;
- ``run_end``    — terminal status + summary payload.

``RunLog`` is the writing half; ``NullRunLog`` is the zero-overhead
opt-out (events no-op; the console echo stays, so opting out of
telemetry never silences the training console). Construct via
:func:`get_run_log`, which reads the ``GIGAPATH_OBS`` env flag ONCE at
driver start — never call it from traced code (gigalint GL001).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Dict, Optional

from gigapath_tpu.obs.locktrace import attach_locktrace, make_lock

SCHEMA_VERSION = 1

EVENT_KINDS = (
    "run_start", "step", "compile", "compile_profile", "span", "eval",
    "heartbeat", "stall", "anomaly", "recovery", "serve_dispatch",
    "cache_hit", "metrics", "slo", "trace", "clock_sync", "backpressure",
    "worker_lost", "consumer_lost", "numerics", "drift", "stream_peek",
    "error", "run_end",
)


def console(msg: str, *, stream=None) -> None:
    """The single sanctioned console sink for library code (GL006): every
    former bare ``print`` in ``gigapath_tpu/`` routes through here (or
    through :meth:`RunLog.echo`, which calls here), so console output can
    be redirected or silenced in one place."""
    out = stream if stream is not None else sys.stdout
    print(msg, file=out, flush=True)  # gigalint: waive GL006 -- the one sanctioned console sink


def _to_scalar(value: Any) -> Any:
    """Best-effort JSON-safe scalar: 0-d/1-element arrays -> float.

    Device arrays sync when read — callers must only pass device values
    at points where the host already blocks (see finetune/training.py's
    20-iteration sync)."""
    if isinstance(value, (bool, int, float, str)) or value is None:
        return value
    if isinstance(value, dict):
        return {str(k): _to_scalar(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_to_scalar(v) for v in value]
    try:
        import numpy as np

        arr = np.asarray(value)
        if arr.size == 1:
            return float(arr.reshape(()))
        return arr.tolist()
    except Exception:
        return repr(value)


class NullRunLog:
    """Telemetry opt-out: every event is a no-op; echo keeps printing."""

    path: Optional[str] = None
    run_id: str = "null"

    def __init__(self, driver: str = "run", echo: bool = True,
                 echo_stream=None):
        self.driver = driver
        self._echo = echo
        self._echo_stream = echo_stream
        self._t0 = time.time()

    # -- events (all no-ops; permissive signatures so every RunLog call
    # site works unchanged against the opt-out) --------------------------
    def event(self, *args, **fields) -> None:
        return None

    run_start = step = compile_event = eval_event = heartbeat = stall = \
        recovery = error = run_end = event_from_signal = event

    def add_observer(self, fn) -> None:
        """No-op: the opt-out stream has no events to observe."""
        return None

    def add_closer(self, fn) -> None:
        return None

    def close(self) -> None:
        return None

    # -- console echo ----------------------------------------------------
    def echo(self, msg: str, *, step: Optional[int] = None) -> None:
        """One console line, single format: ``[driver +WALLs step N] msg``.

        The format is shared by every driver (satellite: train_gigapath
        and finetune/training previously disagreed on sec/it
        conventions) — wall time is seconds since run start."""
        if not self._echo:
            return
        head = f"[{self.driver} +{time.time() - self._t0:.1f}s"
        if step is not None:
            head += f" step {step}"
        console(head + f"] {msg}", stream=self._echo_stream)

    def echo_from_signal(self, msg: str) -> None:
        """Signal-handler-safe echo: a raw ``os.write`` to stderr — the
        buffered echo stream's internal lock may be held by the very
        frame the signal interrupted, and a buffered write would
        deadlock on it."""
        if not self._echo:
            return
        try:
            os.write(2, f"[{self.driver}] {msg}\n".encode())
        except OSError:
            pass


class RunLog(NullRunLog):
    """Appends schema-versioned JSONL events to a per-run file.

    Thread-safe (the heartbeat monitor writes from a background thread);
    every write is flushed so a killed/hung run still leaves a complete
    prefix on disk — the artifact exists precisely when the run dies.
    """

    def __init__(self, path: str, *, driver: str = "run",
                 run_id: Optional[str] = None, echo: bool = True,
                 echo_stream=None):
        super().__init__(driver=driver, echo=echo, echo_stream=echo_stream)
        self.path = path
        self.run_id = run_id or _default_run_id(driver)
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        self._fh = open(path, "a", encoding="utf-8")
        self._lock = make_lock("gigapath_tpu.obs.runlog.RunLog._lock")
        self._closed = False
        self._observers: list = []
        self._closers: list = []

    # -- observers (the anomaly engine / flight recorder tap) ------------
    def add_observer(self, fn) -> None:
        """Subscribe ``fn(record)`` to every event written to this log.
        Observers run on the EMITTING thread, outside the write lock (so
        an observer may itself emit events — the anomaly engine does),
        and must never raise into the driver: exceptions are contained.
        """
        self._observers.append(fn)

    def add_closer(self, fn) -> None:
        """Register a callback run once when the log closes (run_end or
        explicit close) — the hook the anomaly engine uses to stop an
        open profiler capture and detach cleanly."""
        self._closers.append(fn)

    # -- core ------------------------------------------------------------
    def event(self, kind: str, **fields) -> Optional[Dict[str, Any]]:
        record = {
            "v": SCHEMA_VERSION,
            "run": self.run_id,
            "kind": kind,
            "t": round(time.time(), 6),
        }
        record.update({k: _to_scalar(v) for k, v in fields.items()})
        line = json.dumps(record)
        with self._lock:
            if self._closed:
                return record
            self._fh.write(line + "\n")
            self._fh.flush()
        for observer in list(self._observers):
            try:
                observer(record)  # gigarace: calls AnomalyEngine.on_event, FlightRecorder.on_event
            except Exception:  # observers must never take a run down
                pass
        return record

    def event_from_signal(self, kind: str, **fields) -> Optional[Dict[str, Any]]:
        """Signal-handler-safe event (the SIGTERM recovery callbacks):
        the handler runs ON the main thread, which may be suspended
        INSIDE :meth:`event` holding the write lock — a blocking acquire
        would deadlock and make the process unkillable by the very
        SIGTERM it is handling (``FlightRecorder.dump_from_signal``'s
        discipline). Try briefly and drop the record on contention —
        losing one event beats hanging the shutdown — and skip the
        observers (an observer may emit events of its own)."""
        record = {
            "v": SCHEMA_VERSION,
            "run": self.run_id,
            "kind": kind,
            "t": round(time.time(), 6),
        }
        record.update({k: _to_scalar(v) for k, v in fields.items()})
        line = json.dumps(record)
        if not self._lock.acquire(timeout=1.0):
            return None
        try:
            if self._closed:
                return record
            self._fh.write(line + "\n")
            self._fh.flush()
        finally:
            self._lock.release()
        return record

    def close(self) -> None:
        closers, self._closers = self._closers, []
        for closer in closers:
            try:
                closer()
            except Exception:  # closing obs must never take a run down
                pass
        with self._lock:
            if not self._closed:
                self._closed = True
                self._fh.close()

    # -- typed events ----------------------------------------------------
    def run_start(self, config: Optional[dict] = None, *,
                  probe_devices: bool = True, **fields):
        """Environment manifest. ``probe_devices=False`` skips the
        ``jax.devices()`` call for drivers (bench) that must control when
        backend init happens — the init RPC can hang indefinitely."""
        manifest: Dict[str, Any] = {"driver": self.driver, "pid": os.getpid()}
        try:
            import jax

            manifest["jax_version"] = jax.__version__
            if probe_devices:
                devices = jax.devices()
                manifest["backend"] = devices[0].platform
                manifest["device_kind"] = devices[0].device_kind
                manifest["device_count"] = len(devices)
                manifest["process_index"] = int(jax.process_index())
        except Exception as e:  # manifest is best-effort, never fatal
            manifest["manifest_error"] = f"{type(e).__name__}: {e}"
        if config is not None:
            manifest["config"] = {
                k: _to_scalar(v) for k, v in dict(config).items()
            }
        manifest.update(fields)
        return self.event("run_start", **manifest)

    def step(self, step: int, *, wall_s: Optional[float] = None,
             synced: bool = False, **scalars):
        return self.event("step", step=int(step), wall_s=wall_s,
                          synced=synced, **scalars)

    def compile_event(self, fn: str, key, seconds: Optional[float], *,
                      count: int = 1, unexpected: bool = False):
        return self.event("compile", fn=fn, key=_key_str(key),
                          seconds=seconds, count=count,
                          unexpected=unexpected)

    def eval_event(self, step: int, **metrics):
        return self.event("eval", step=int(step), **metrics)

    def heartbeat(self, *, last_step=None, since_progress_s=None, **fields):
        return self.event("heartbeat", last_step=last_step,
                          since_progress_s=since_progress_s, **fields)

    def stall(self, *, last_step=None, since_progress_s=None,
              deadline_s=None, **fields):
        return self.event("stall", last_step=last_step,
                          since_progress_s=since_progress_s,
                          deadline_s=deadline_s, **fields)

    def recovery(self, action: str, **fields):
        """One recovery action taken by the fault-tolerance layer
        (:mod:`gigapath_tpu.resilience` / the serving self-healing):
        skip_step, rollback, rollback_unavailable, resume,
        emergency_checkpoint, data_retry, shed, deadline, bisect,
        poisoned_request, breaker_*, drain, reassign, reconnect,
        consumer_resume —
        rendered by ``scripts/obs_report.py``'s ``== recovery ==``."""
        return self.event("recovery", action=action, **fields)

    def error(self, where: str, err: BaseException):
        return self.event("error", where=where,
                          error=f"{type(err).__name__}: {err}")

    def run_end(self, status: str = "ok", **fields):
        rec = self.event("run_end", status=status,
                         wall_s=round(time.time() - self._t0, 3), **fields)
        self.close()
        return rec


def fail_run(runlog, where: str, err: BaseException, *,
             emergency=None) -> None:
    """The ONE driver-failure tail (every driver's ``except Exception``
    dedupes onto this): ``error`` event (which triggers the anomaly
    engine's flight dump for free — error events are a dump trigger),
    then — when the driver has live train state — an emergency
    checkpoint via the zero-arg ``emergency()`` callable (returns the
    saved path; failures contained — a broken disk must not mask the
    original exception), then the terminal ``run_end(status="error")``.
    The caller re-raises; this function never swallows."""
    runlog.error(where, err)
    if emergency is not None:
        try:
            path = emergency()
            if path:
                runlog.recovery(action="emergency_checkpoint",
                                where=where, path=str(path))
        except Exception:
            pass
    runlog.run_end(status="error")


def _key_str(key) -> str:
    """Stable short string for a compile key (bucket tuple, shape, ...)."""
    if isinstance(key, str):
        return key
    return repr(key)


def _default_run_id(driver: str) -> str:
    """The one run-id format (shared by RunLog and get_run_log)."""
    return (
        f"{driver}-{time.strftime('%Y%m%dT%H%M%S', time.gmtime())}"
        f"-p{os.getpid()}"
    )


def env_number(name: str, default: float) -> float:
    """The obs layer's one numeric-env parser (heartbeat deadlines,
    profiler capture knobs): unset/blank/unparseable -> ``default``.
    Host-side, read at driver start — never at trace time."""
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return float(default)
    try:
        return float(raw)
    except ValueError:
        return float(default)


def env_on_by_default(name: str) -> bool:
    """Shared truthiness for the obs layer's opt-OUT flags
    (``GIGAPATH_OBS``, ``GIGAPATH_ANOMALY``): unset -> ON; set to
    ''/'0'/'false'/'no' -> OFF; anything else -> ON. Matches the repo's
    env_flag truthiness (ops/common.py) for set values, but defaults on
    because the artifact is the point of the subsystem."""
    raw = os.environ.get(name)
    if raw is None:
        return True
    return raw.strip().lower() not in ("", "0", "false", "no")


def _obs_enabled() -> bool:
    return env_on_by_default("GIGAPATH_OBS")


def get_run_log(driver: str, out_dir: Optional[str] = None, *,
                config: Optional[dict] = None, echo: bool = True,
                echo_stream=None, probe_devices: bool = True,
                path: Optional[str] = None, run_start: bool = True):
    """Build the run's telemetry sink. Reads ``GIGAPATH_OBS`` ONCE, here,
    at driver start — never at trace time (gigalint GL001-clean because
    no driver entry point is trace-reachable).

    File placement: explicit ``path`` wins; else ``<out_dir>/obs/`` (or
    ``$GIGAPATH_OBS_DIR``, or the system temp dir) gets a per-run file
    named after the run id.

    Multi-host runs: ``GIGAPATH_OBS_RUN_ID`` (host-side, read here once)
    pins one shared run id across ranks, so per-rank JSONL files merge
    on run id in ``scripts/obs_report.py``; each rank still writes its
    own file (the shared-id filename gains a ``-<host>-p<pid>`` suffix —
    hostname because containerized ranks commonly share pid 1, and
    deliberately NOT the rank: reading ``jax.process_index()`` here
    would initialize the backend at driver start, exactly the hang
    ``probe_devices=False`` exists to avoid, and before distributed init
    every rank would answer 0. Rank tagging rides the span events, which
    fire once device work is already underway).
    """
    if not _obs_enabled():
        return NullRunLog(driver=driver, echo=echo, echo_stream=echo_stream)
    shared_id = os.environ.get("GIGAPATH_OBS_RUN_ID") or None
    if path is None:
        if out_dir is not None:
            base = os.path.join(out_dir, "obs")
        elif os.environ.get("GIGAPATH_OBS_DIR"):
            base = os.environ["GIGAPATH_OBS_DIR"]  # used verbatim
        else:
            import tempfile

            base = os.path.join(tempfile.gettempdir(), "gigapath_obs")
        run_id = shared_id or _default_run_id(driver)
        if shared_id:
            import re
            import socket

            host = re.sub(r"[^A-Za-z0-9.-]", "-", socket.gethostname())[:32]
            fname = f"{run_id}-{host}-p{os.getpid()}"
        else:
            fname = run_id
        path = os.path.join(base, f"{fname}.jsonl")
        log = RunLog(path, driver=driver, run_id=run_id, echo=echo,
                     echo_stream=echo_stream)
    else:
        log = RunLog(path, driver=driver, run_id=shared_id, echo=echo,
                     echo_stream=echo_stream)
    # the closed loop (anomaly engine + flight recorder + triggered
    # profiler capture) rides the event stream of every recording run;
    # its own env gates (GIGAPATH_ANOMALY / GIGAPATH_PROFILE) are read
    # inside attach, here, once, at driver start — and the layer must
    # never be the thing that takes a run down. Attached BEFORE the
    # run_start below so the manifest (config, backend, device count)
    # lands in the flight recorder's ring: a post-mortem dump without
    # provenance is half a post-mortem
    try:
        from gigapath_tpu.obs.anomaly import attach_anomaly_engine

        attach_anomaly_engine(log)
    except Exception:
        pass
    # the lock-order sanitizer's summary rides the same stream: one
    # ``locktrace`` event at close when GIGAPATH_LOCKTRACE=1 (no-op
    # otherwise), rendered by obs_report's ``== locks ==`` section and
    # consumed by ``python -m tools.gigarace --validate``
    attach_locktrace(log)
    if run_start:
        log.run_start(config=config, probe_devices=probe_devices)
    return log
