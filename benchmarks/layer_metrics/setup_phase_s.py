"""Drivers: set-up's seconds by compile phase:
``setup_phase_s.<trace|lower|compile>.<kind>`` is what the host spent tracing,
lowering to MLIR, or in the backend's compile (a load from the persistent
cache included) before the window opened, from the spans the program's
recorder files for ``jax.monitoring``'s duration events
(``gigapath_tpu/obs/spans.py``), nested functions counted once
(``lib/host_spans.phase_seconds``).

Reads ``window["program_spans"]``, which ``benchmarks/host_report.py``'s
window holds (its recorder is installed before ``run.prepare``) and the
drivers' windows do not: None where it is absent."""

from benchmarks.lib import host_spans


def read(metric, trace, window, ctx):
    spans = window.get("program_spans")
    interval = host_spans.window_interval(ctx)
    if not spans or interval is None:
        return None
    by_function = host_spans.phase_seconds(spans, hi=interval[0]).get(metric.split(".")[1], {})
    return sum(by_function.values())
