"""Run-scoped observability: structured JSONL telemetry for every driver.

- :mod:`gigapath_tpu.obs.runlog` — ``RunLog`` / ``NullRunLog`` / the
  ``get_run_log`` env-gated factory and the sanctioned ``console`` sink;
- :mod:`gigapath_tpu.obs.watchdog` — ``CompileWatchdog`` retrace/compile
  accounting (subsumes the old finetune ``BucketCompileLog``);
- :mod:`gigapath_tpu.obs.heartbeat` — ``Heartbeat`` liveness/stall monitor;
- :mod:`gigapath_tpu.obs.telemetry` — in-graph scalar helpers (grad/param
  norms, MoE gating stats) that add no device round-trips or retraces;
- :mod:`gigapath_tpu.obs.ledger` — compiled-artifact perf ledger: XLA
  cost/memory analysis + jaxpr fingerprints as ``compile_profile``
  events, folded into a canonical per-run ledger JSON that
  ``scripts/ledger_diff.py`` diffs across commits;
- :mod:`gigapath_tpu.obs.spans` — nestable ``span`` context manager
  (a ``perf_counter_ns`` start and end, optional device fence, per-host
  rank tag; ``record()`` keeps the spans in memory with their parent,
  and JAX's trace / lower / compile phases as children of the span that
  paid for them) plus the ``jax.profiler`` trace/annotate
  passthroughs (the GL010-sanctioned ``start_trace``/``stop_trace`` entry
  points live here);
- :mod:`gigapath_tpu.obs.anomaly` — the closed loop: an ``AnomalyEngine``
  taps the event stream, fires detectors (step-time spike, stall,
  unexpected retrace, memory-watermark growth, throughput dip), and
  reacts — ``anomaly`` events, flight-recorder dumps
  (:mod:`gigapath_tpu.obs.flight`), budgeted profiler captures;
- :mod:`gigapath_tpu.obs.history` — the cross-run perf-history surface:
  fold BENCH/MULTICHIP snapshots and per-run ledgers into one
  append-only trend file that ``scripts/perf_history.py`` gates on;
- :mod:`gigapath_tpu.obs.metrics` — typed metrics registry (counters,
  gauges, exponential-bucket histograms with atomic snapshot/merge,
  JSON + Prometheus exporters, periodic ``metrics`` events) and the
  :class:`~gigapath_tpu.obs.metrics.SloTracker` whose burn-rate
  transitions feed the anomaly engine's ``slo_burn`` detector — plus
  the ONE shared :func:`~gigapath_tpu.obs.metrics.percentile`
  implementation (GL012);
- :mod:`gigapath_tpu.obs.numerics` — in-graph per-layer numerics
  telemetry (finite fraction / absmax / rms behind the
  ``GIGAPATH_NUMERICS`` host flag, riding the ``step_scalars``
  discipline) emitted as schema'd ``numerics`` events;
- :mod:`gigapath_tpu.obs.drift` — the embedding-drift sentinel:
  mergeable :class:`~gigapath_tpu.obs.drift.EmbeddingSketch` baselines
  (manifest-verified artifacts), drift scores as metrics gauges, and
  transition-edged ``drift`` events feeding the anomaly engine's
  ``embedding_drift`` detector;
- :mod:`gigapath_tpu.obs.reqtrace` — end-to-end request tracing:
  ``RequestTrace`` contexts with stable ``trace_id``/``span_id`` pairs
  threaded submit -> queue -> dispatch -> forward -> cache store ->
  resolution, exported per run as Perfetto-loadable Chrome-trace JSON.

Fold a run's JSONL into a human report with ``scripts/obs_report.py``.
"""

from gigapath_tpu.obs.anomaly import (
    AnomalyConfig,
    AnomalyEngine,
    NullAnomalyEngine,
    attach_anomaly_engine,
)
from gigapath_tpu.obs.drift import (
    CorruptDriftArtifact,
    DriftSentinel,
    EmbeddingSketch,
    drift_scores,
)
from gigapath_tpu.obs.flight import FlightRecorder
from gigapath_tpu.obs.heartbeat import Heartbeat, memory_watermarks
from gigapath_tpu.obs.ledger import (
    LEDGER_SCHEMA_VERSION,
    NullLedger,
    PerfLedger,
    capture_profile,
    get_ledger,
    jaxpr_fingerprint,
)
from gigapath_tpu.obs.metrics import (
    Histogram,
    MetricsRegistry,
    NullMetricsRegistry,
    NullSloTracker,
    SloTracker,
    get_metrics,
    merge_snapshots,
    percentile,
)
from gigapath_tpu.obs.numerics import (
    NumericsMonitor,
    numerics_enabled,
    numerics_scalars,
    split_numerics,
)
from gigapath_tpu.obs.reqtrace import (
    RequestTrace,
    TraceCollector,
    get_tracer,
)
from gigapath_tpu.obs.runlog import (
    EVENT_KINDS,
    SCHEMA_VERSION,
    NullRunLog,
    RunLog,
    console,
    get_run_log,
)
from gigapath_tpu.obs.spans import (
    Span,
    annotate,
    span,
    start_trace,
    stop_trace,
    trace,
)
from gigapath_tpu.obs.watchdog import CompileWatchdog

__all__ = [
    "EVENT_KINDS",
    "LEDGER_SCHEMA_VERSION",
    "SCHEMA_VERSION",
    "AnomalyConfig",
    "AnomalyEngine",
    "CompileWatchdog",
    "CorruptDriftArtifact",
    "DriftSentinel",
    "EmbeddingSketch",
    "FlightRecorder",
    "Heartbeat",
    "Histogram",
    "MetricsRegistry",
    "NullAnomalyEngine",
    "NullLedger",
    "NullMetricsRegistry",
    "NullRunLog",
    "NullSloTracker",
    "NumericsMonitor",
    "PerfLedger",
    "RequestTrace",
    "RunLog",
    "SloTracker",
    "Span",
    "TraceCollector",
    "annotate",
    "attach_anomaly_engine",
    "capture_profile",
    "console",
    "drift_scores",
    "get_ledger",
    "get_metrics",
    "get_run_log",
    "get_tracer",
    "jaxpr_fingerprint",
    "memory_watermarks",
    "merge_snapshots",
    "numerics_enabled",
    "numerics_scalars",
    "percentile",
    "span",
    "split_numerics",
    "start_trace",
    "stop_trace",
    "trace",
]
