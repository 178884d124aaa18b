"""Drivers: the host's part of a request by what the host was doing, in ms a
request: ``host_self_ms_per_request.<prepare|h2d|dispatch|device_wait|d2h>.<kind>``
is the self time (``lib/host_spans.py``) of the program's own span of that
name (``gigapath_tpu/pipeline.py``'s entry halves) inside the window, over the
``request`` spans that lie whole inside it. ``h2d`` ends when the bytes are on
the device and ``device_wait`` when the outputs are ready, so in a
one-at-a-time loop the five add up to the request.

Reads ``window["program_spans"]``, the recorder's spans, which
``benchmarks/host_report.py``'s window holds and the drivers' windows do not:
None where it is absent."""

from benchmarks.lib import host_spans


def read(metric, trace, window, ctx):
    spans = window.get("program_spans")
    interval = host_spans.window_interval(ctx)
    if not spans or interval is None:
        return None
    lo, hi = interval
    n = len(host_spans.requests(spans, lo, hi))
    if n == 0:
        return None
    return 1e3 * host_spans.self_seconds(spans, lo, hi).get(metric.split(".")[1], 0.0) / n
