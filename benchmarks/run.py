"""The benchmark's one entry point.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, JAX initialised once. Everything that belongs to one cell, one
configuration, one traffic mix or one per-layer metric is a file found by
name (``benchmarks/README.md``); this file knows none of them.

``--tiny`` is the CPU rehearsal (tiny presets, the program's own CPU paths,
platform printed as it is): it proves the control flow, never a result.
``--list`` prints what the harness finds.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()  # set-up counts from here: imports are part of it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import trace as trace_lib  # noqa: E402
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")
TRACE_DIR = os.path.join(BENCH_DIR, ".trace")


class CompileMeter:
    """JAX's own compile counter (copied from ``chip_smoke.py``): backend
    compiles, a persistent-cache hit included, and the cache's hit / miss
    events."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event: str, **_):
        if event.endswith("/cache_hits"):
            self.cache_hits += 1
        elif event.endswith("/cache_misses"):
            self.cache_misses += 1


class Context:
    """What a driver and the layer-metric readers get from the harness."""

    def __init__(self, args, cell, config, traffic, system, meter, peaks):
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.tiny = bool(args.trace), args.tiny
        self.cell, self.config, self.traffic = cell, config, traffic
        self.system, self.compile_meter, self.peaks = system, meter, peaks
        self.sizes = system.sizes
        self.setup_s = None
        self.notes = []
        self.phases = [("imports_and_devices", time.perf_counter() - _T_START)]
        self.spans = trace_lib.HostSpans()
        self.sync_host_ns = None
        self.trace_dir = os.path.join(TRACE_DIR, cell["name"])

    def make_weights(self, shapes):
        from benchmarks.lib.weights import make_weights

        return make_weights(shapes, self.seed)

    def mark(self, phase: str):
        """A set-up phase ended: its name and the seconds since process start."""
        self.phases.append((phase, time.perf_counter() - _T_START))

    def setup_done(self):
        m = self.compile_meter
        self.setup_s = time.perf_counter() - _T_START
        self.setup_compiles = (m.compiles, m.cache_hits, m.cache_misses)

    @contextlib.contextmanager
    def tracing(self):
        """The profiler around the window in a ``--trace 1`` run, nothing
        otherwise. The host and Python tracers stay off (``lib/trace.py``
        says why); the marker program ties the host's clock to the trace's."""
        if not self.trace:
            yield
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.sync_host_ns = trace_lib.clock_sync()
        try:
            yield
        finally:
            jax.profiler.stop_trace()


def _layer_reader(metric: str):
    """``step_mfu.tile`` -> ``benchmarks/layer_metrics/step_mfu.py``."""
    return importlib.import_module("benchmarks.layer_metrics." + metric.split(".")[0]).read


def _listing() -> dict:
    from benchmarks.lib import tables

    return {
        "workloads": tables.names("workloads"),
        "configs": tables.names("configs"),
        "traffic": tables.names("traffic"),
        "drivers": tables.names("drivers", ".py"),
        "systems": tables.names("systems", ".py"),
        "layer_metrics": tables.names("layer_metrics", ".py"),
        "kernels": tables.names("kernels"),
        "scopes": tables.names("scopes"),
    }


def prepare(args):
    """Everything before the driver runs: the cell's files, the compile
    cache, the look for a chip, the system under test. Returns ``(context,
    driver module)``, or None where the cell's chips are not there."""
    from benchmarks.lib import peaks as peaks_lib
    from benchmarks.lib import tables

    cell = tables.load("workloads", args.workload)
    config = tables.load("configs", cell["config"])
    traffic = tables.load("traffic", cell["traffic"])
    if args.tiny:
        traffic = {**traffic, **traffic["tiny"]}

    # the compile cache at its one fixed place inside the checkout, whatever
    # the environment says: JAX reads the variable when it is first imported,
    # and the program's own helper (utils/compile_cache.py) keeps to it too.
    # A caller that imported JAX already (a test) keeps its cache and its
    # environment as they are.
    if "jax" not in sys.modules:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    # uncapped and taking every program (an environment may cap the size)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    devices = jax.devices()
    platform, chips = devices[0].platform, int(cell["chips"])
    if not args.tiny and (platform != "tpu" or len(devices) < chips):
        print(f"benchmarks/run.py: cell {cell['name']} needs {chips} TPU chip(s); "
              f"JAX found {len(devices)} x {platform}", file=sys.stderr)
        return None
    peaks = None if args.tiny and platform != "tpu" else peaks_lib.peaks_for(
        devices[0].device_kind)

    system = importlib.import_module("benchmarks.systems." + config["system"]).System(
        config, args.tiny)
    ctx = Context(args, cell, config, traffic, system, CompileMeter(), peaks)
    ctx.devices = devices[:chips]
    if ctx.trace:
        trace_lib.clock_sync()  # compiles the marker program: set-up, not window
    return ctx, importlib.import_module("benchmarks.drivers." + traffic["driver"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.lib import report, tables

    if args.list:
        print(json.dumps(_listing()))
        return 0
    if not args.workload:
        ap.error("--workload is required")
    units = {m["name"]: m["unit"] for kind in ("end_to_end", "per_layer")
             for m in tables.manifest()[kind]}
    prepared = prepare(args)
    if prepared is None:
        return 3
    ctx, driver = prepared
    cell, system, devices = ctx.cell, ctx.system, ctx.devices
    platform = devices[0].platform

    window = driver.run(ctx)

    # peak_bytes_in_use counts live buffers (weights, inputs, outputs) and not
    # a running program's temporaries, which the runtime reserves apart
    # (peak_bytes_reserved: 7.30 GB for the slide forward whose compiled
    # temporaries are 7.50 GB; my chip run, PR 24). The peak on the chip is
    # their sum, to the extent the two peaks coincide.
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max((s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
                       for s in stats), default=0)
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}

    metrics, breakdown = {}, None
    if ctx.trace:
        reduction = trace_lib.reduce_xplane(
            trace_lib.newest_xplane(ctx.trace_dir), ctx.spans.spans, ctx.sync_host_ns)
        if reduction is not None:
            device["busy_s"] = reduction.busy_s
            device["window_s"] = reduction.window_s
            breakdown = reduction.breakdown()
        for name in cell["per_layer"]:
            value = _layer_reader(name)(name, reduction, window, ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    else:
        rate = cell["end_to_end"]["rate"]
        metrics[rate] = {"value": window["work"] / window["seconds"], "unit": units[rate]}
        metrics["setup_s"] = {"value": ctx.setup_s, "unit": units["setup_s"]}

    # the comparison, once the window has closed and the peak has been read
    t_check = time.perf_counter()
    values = driver.check(ctx, window)
    limits = cell["correct"]["tiny_limits" if args.tiny else "limits"]
    checks = {
        name: {"value": values[name], "limit": limit, "ok": bool(values[name] <= limit)}
        for name, limit in limits.items()
    }
    correct = window["failed"] == 0 and all(c["ok"] for c in checks.values())

    for note in ctx.notes:
        print(note, file=sys.stderr)
    print(f"memory_stats: {json.dumps(stats[0])}", file=sys.stderr)
    print("set-up phases, seconds since process start: "
          + ", ".join(f"{name} {t:.1f}" for name, t in ctx.phases), file=sys.stderr)
    print(f"cell {cell['name']} seed {args.seed}: window {window['seconds']:.3f} s, "
          f"{window['attempted']} requests, {window['work']} {system.unit}, "
          f"set-up {ctx.setup_s:.1f} s with {ctx.setup_compiles[0]} compiles "
          f"(cache hits {ctx.setup_compiles[1]}, misses {ctx.setup_compiles[2]}), "
          f"compiles in window {window['compiles']}, check {time.perf_counter() - t_check:.1f} s",
          file=sys.stderr)
    result = {
        "correct": correct,
        "attempted": window["attempted"],
        "failed": window["failed"],
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    report.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
