"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the layer
metrics read: the union of device-busy intervals, idle share, per-operation
sums, and the idle gaps by what the benchmark's driver was doing.

The finished form of ``gigapath_tpu/utils/profiling.xla_op_totals`` (which
sums the "XLA Ops" line and stops there). Reads the file with
``jax.profiler.ProfileData`` and nothing else.

The host tracer stays off: with it on, the TPU runtime logs every block of
the host-to-device layout change (400,000 events for one batch of 128 tiles)
and a traced request takes 1.5 s where an untraced one takes 0.6 s (my chip
run, PR 24). So the driver's own spans (``HostSpans``: ``window``, ``h2d``,
``fetch``, ``loader_wait``) are taken by the host's clock, and the two clocks
are tied by one marker program (``clock_sync``) run right after the profiler
starts: its end on the device's timeline and the moment the host saw it done
are the same instant, to the ~0.1 ms a completion takes to be noticed.
Everything is clipped to the ``window`` span.
"""

from __future__ import annotations

import dataclasses
import contextlib
import functools
import glob
import os
import re
import time

WINDOW_SPAN = "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC_NAME = "bench_clock_sync"
MIN_GAP_S = 1e-4


@dataclasses.dataclass
class TraceReduction:
    window_s: float
    busy_s: float            # union of device-busy intervals, mean over devices
    n_devices: int
    op_total_s: dict         # operation (its HLO text in the trace) -> summed duration, mean over devices
    op_self_s: dict          # the same less the time of operations nested inside
    host_span_s: dict        # driver annotation -> summed duration inside the window
    idle_gaps: list          # [(what the host was doing, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, table: dict) -> float:
        """Summed inclusive duration of the operations a name table picks
        (``kernels/<kernel>.json``): every ``all`` substring and, where
        given, one of the ``regex`` patterns found in the operation's name
        (its HLO text), and no ``none`` substring."""
        patterns = [re.compile(p) for p in table.get("regex", ())]
        total = 0.0
        for name, seconds in self.op_total_s.items():
            if not all(s in name for s in table.get("all", ())):
                continue
            if any(s in name for s in table.get("none", ())):
                continue
            if patterns and not any(p.search(name) for p in patterns):
                continue
            total += seconds
        return total

    def breakdown(self, top: int = 10) -> dict:
        """Self time by kind of operation (``op_kind``), and the idle gaps."""
        kinds = {}
        for name, seconds in self.op_self_s.items():
            kind = op_kind(name)
            kinds[kind] = kinds.get(kind, 0.0) + seconds
        ops = sorted(kinds.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in self.idle_gaps[:top]],
        }


class HostSpans:
    """The driver's own spans, by the host's clock (``perf_counter_ns``)."""

    def __init__(self):
        self.spans = []  # (name, start_ns, end_ns)

    @contextlib.contextmanager
    def span(self, name: str):
        start = time.perf_counter_ns()
        try:
            yield
        finally:
            self.spans.append((name, start, time.perf_counter_ns()))


@functools.lru_cache(maxsize=1)
def _sync_program():
    import jax

    def bench_clock_sync(x):  # the name the reduction looks for (SYNC_NAME)
        return x + 1

    return jax.jit(bench_clock_sync)


def clock_sync() -> int:
    """Run the marker program and return the host's clock at the moment it
    was seen done. Call once before the profiler starts (it compiles) and
    once right after."""
    import jax.numpy as jnp

    _sync_program()(jnp.zeros((8, 128), jnp.float32)).block_until_ready()
    return time.perf_counter_ns()


_HLO = re.compile(r"^%(?P<base>.+?)(?:\.\d+)? = (?P<out>.*?) (?P<op>[\w\-]+)\(")


def op_kind(name: str) -> str:
    """An operation's kind, for adding up its instances across layers:
    ``%convolution_add_fusion.39 = bf16[128,197,8192]{...} fusion(...)`` ->
    ``convolution_add_fusion fusion bf16[128,197,8192]``. Layouts go, shapes
    stay: they tell a layer's GEMMs apart, and an attention kernel (out, lse)
    from a pack kernel."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    out = re.sub(r"\{[^{}]*\}", "", m.group("out"))
    return f"{m.group('base')} {m.group('op')} {out}"[:160]


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def _is_device(plane) -> bool:
    return plane.name.startswith("/device:") and any(
        line.name == OPS_LINE for line in plane.lines
    )


def reduce_xplane(path: str, host_spans, sync_host_ns: int):
    """``host_spans``: ``HostSpans.spans``; ``sync_host_ns``: what
    ``clock_sync`` returned right after the profiler started. Returns None
    where the trace holds no device timeline (a CPU rehearsal)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = list(data.planes)
    devices = [p for p in planes if _is_device(p)]
    sync_device_ns = None
    for plane in devices[:1]:
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                if SYNC_NAME in ev.name:
                    sync_device_ns = ev.start_ns + ev.duration_ns
                    break
    if sync_device_ns is None:
        return None
    shift = sync_device_ns - sync_host_ns  # host clock -> trace clock
    spans = [(a + shift, b + shift, name) for name, a, b in host_spans
             if name != WINDOW_SPAN]
    windows = [(a + shift, b + shift) for name, a, b in host_spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found {len(windows)}")
    lo, hi = windows[0]

    busy_ns, totals, selfs, merged_first = 0.0, {}, {}, None
    for plane in devices:
        events = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a, b = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if b > a:
                    events.append((a, b, ev.name))
        merged = _union((a, b) for a, b, _ in events)
        busy_ns += sum(b - a for a, b in merged)
        if merged_first is None:
            merged_first = merged
        # self time: an operation's span less what is nested inside it
        events.sort(key=lambda e: (e[0], -(e[1] - e[0])))
        stack = []
        for a, b, name in events:
            while stack and stack[-1][1] <= a:
                stack.pop()
            totals[name] = totals.get(name, 0.0) + (b - a)
            selfs[name] = selfs.get(name, 0.0) + (b - a)
            if stack:
                parent = stack[-1][2]
                selfs[parent] -= min(b, stack[-1][1]) - a
            stack.append((a, b, name))
    n = max(len(devices), 1)

    host = {}
    for a, b, name in spans:
        a, b = _clip(a, b, lo, hi)
        if b > a:
            host[name] = host.get(name, 0.0) + (b - a) / 1e9

    # idle gaps of the first device, shared out among the driver's spans that
    # lie over them; what no span covers is the host between spans
    gaps = {}
    edges = [lo] + [t for ab in (merged_first or []) for t in ab] + [hi]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if (g1 - g0) / 1e9 < MIN_GAP_S:
            continue
        left = g1 - g0
        for a, b, name in spans:
            cover = min(b, g1) - max(a, g0)
            if cover > 0:
                gaps[name] = gaps.get(name, 0.0) + cover / 1e9
                left -= cover
        if left > 0:
            gaps["between_spans"] = gaps.get("between_spans", 0.0) + left / 1e9
    return TraceReduction(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_ns / n / 1e9,
        n_devices=len(devices),
        op_total_s={k: v / n / 1e9 for k, v in totals.items()},
        op_self_s={k: v / n / 1e9 for k, v in selfs.items()},
        host_span_s=host,
        idle_gaps=sorted(gaps.items(), key=lambda kv: -kv[1]),
    )
