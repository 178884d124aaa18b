"""What the host does in a request through the program's own entry, by the
program's own spans.

    python3 benchmarks/host_report.py --workload <cell> --seed <n> [--seconds 30] [--tiny]

The cell's own set-up through ``run.prepare`` (weights from ``--seed``, the
cell's host batches), then not the cell's driver but the program's entry: the
per-request function ``pipeline.run_inference_with_*`` itself calls
(``tile_encoder_request`` / ``slide_encoder_request`` / ``lm_request``: host
batch in -> ``<entry>_to_device`` -> the jitted function -> ``<entry>_to_host``),
one request at a time as the entries send them, for ``--seconds``, under the
profiler and a recorder of the program's spans (``gigapath_tpu/obs/spans.py``).
The recorder is installed before set-up, so set-up's tracing, lowering and
compiles are in it too. One JSON line on standard output:

- ``spans``: per span name its count, total seconds, shortest / median /
  longest ms and self ms a request inside the window (``lib/host_spans.py``);
- ``idle_gaps``: the device's idle time by the leaf span the host was in
  (``lib/trace.reduce_xplane``'s attribution, unedited, over the program's
  spans; ``between_spans`` is what no span covers), ``device_idle_share`` and
  the busy and window seconds of the entry's loop;
- ``entry_loop``: its rate, under the name of the cell's rate;
- ``compile_phases``: ``trace`` / ``lower`` / ``compile`` seconds by function,
  in set-up and in the window;
- ``metrics``: the names ``layer_metrics/host_self_ms_per_request.py`` and
  ``setup_phase_s.py`` answer to;
- ``overhead``: the same loop for two more windows with no profiler, in
  halves with the recorder off, on, on, off (a drift of the host cancels):
  the rate each state gave, and the self ms a request of the untraced
  recorded halves (what the profiler itself adds to a span shows against
  ``spans``).

Not part of a benchmark run, for the reason ``scope_report.py`` gives: until a
``benchmark`` PR lists these metrics (PERF.md §7) this is how they are read.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOP_FUNCTIONS = 8


def metric_names(kind: str) -> list:
    """The names the two readers answer to for a cell kind."""
    from benchmarks.lib import host_spans

    return [f"host_self_ms_per_request.{span}.{kind}" for span in host_spans.REQUEST_SPANS
            ] + [f"setup_phase_s.{phase}.{kind}" for phase in host_spans.COMPILE_PHASES]


def entry_request(system_name: str):
    """The program's per-request function for a cell's system, taking the
    cell's host batch as the adapter's ``host_batch`` makes it."""
    from gigapath_tpu import pipeline

    if system_name == "tile_encoder":
        return lambda fn, params, batch: pipeline.tile_encoder_request(
            fn, params, batch, batch.shape[0])
    if system_name == "slide_encoder":
        return lambda fn, params, batch: pipeline.slide_encoder_request(fn, params, *batch)
    return lambda fn, params, batch: pipeline.lm_request(fn, params, *batch)


def _finite(answer) -> bool:
    import numpy as np

    values = answer.values() if isinstance(answer, dict) else [answer]
    return all(np.isfinite(v).all() for v in values)


def loop(ctx, send, batches, order, seconds: float) -> dict:
    """The entry's own loop: one request at a time until ``seconds`` are up.
    Between two requests it does nothing but pick the next: the answers are
    kept, and :func:`settle` looks at them once the window is closed."""
    served, answers = [], []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        which = int(order[len(served) % len(batches)])
        answers.append(send(batches[which]))
        served.append(which)
    seconds = time.perf_counter() - t0
    work = sum(ctx.system.work(batches[w]) for w in served)
    return {"seconds": seconds, "attempted": len(served), "answers": answers, "work": work,
            "rate": work / seconds}


def settle(record: dict) -> dict:
    """A loop's record with its answers counted (``failed``) and let go."""
    record["failed"] = sum(not _finite(answer) for answer in record.pop("answers"))
    return record


def _top(by_function: dict) -> dict:
    ranked = sorted(by_function.items(), key=lambda kv: -kv[1])
    return {"total_s": sum(by_function.values()), "functions": len(ranked),
            "top": [[name, seconds] for name, seconds in ranked[:TOP_FUNCTIONS]]}


def main(argv=None) -> int:
    from benchmarks import run as harness

    # the recorder needs JAX before run.prepare has named the compile cache:
    # name it here as prepare would, so that set-up is a run.py run's set-up
    if "jax" not in sys.modules:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    import numpy as np

    from benchmarks.lib import host_spans, tables
    from benchmarks.lib import trace as trace_lib
    from gigapath_tpu.obs import spans as program_spans

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    with program_spans.record() as rec:
        prepared = harness.prepare(argparse.Namespace(
            workload=args.workload, seed=args.seed, seconds=args.seconds, trace=1,
            tiny=args.tiny))
        if prepared is None:
            return 3
        ctx, _ = prepared
        system, traffic = ctx.system, ctx.traffic
        kind = tables.cell_kind(ctx.cell)

        # ---- set-up: a copy of drivers/closed_loop.py `run`'s (weights, batches,
        # order, two warm-ups) and of the one line drivers/closed_loop_lm.py puts
        # in its place (the LM's own weights). Until a `benchmark` PR splits a
        # `setup(ctx)` out of the driver for both to call (PERF.md §7 (iii)), a
        # change to either has to be made here too; `kind` below is the suffix
        # the cell's metric names carry, and `_layer_reader` the harness's own.
        if traffic["driver"] == "closed_loop_lm":
            from benchmarks.lib import weights_lm

            params = weights_lm.make_weights(system.param_shapes(), ctx.seed)
        else:
            params = ctx.make_weights(system.param_shapes())
        fn = system.make_fn()
        rng = np.random.default_rng(ctx.seed)
        batches = [system.host_batch(rng, traffic) for _ in range(int(traffic["distinct_batches"]))]
        order = rng.permutation(len(batches))
        request = entry_request(ctx.config["system"])

        def send(batch):
            return request(fn, params, batch)

        for batch in batches[:2]:  # the window's one shape, twice
            send(batch)
        ctx.setup_done()

        # ---- the traced window
        with ctx.tracing():
            with ctx.spans.span(trace_lib.WINDOW_SPAN):
                compiles_before = ctx.compile_meter.compiles
                window = loop(ctx, send, batches, order, ctx.seconds)
        settle(window)
        window["compiles"] = ctx.compile_meter.compiles - compiles_before
        window["program_spans"] = list(rec.spans)

    # ---- the recorder's cost: the same loop untraced, recorder off, on, on, off
    halves, untraced = {"off": [], "on": []}, []
    for state in ("off", "on", "on", "off"):
        with program_spans.record() if state == "on" else contextlib.nullcontext() as on_rec:
            halves[state].append(settle(loop(ctx, send, batches, order, ctx.seconds / 2)))
        if on_rec is not None:
            untraced.append(on_rec.spans)
    off, on = ({key: sum(h[key] for h in halves[state])
                for key in ("seconds", "attempted", "failed", "work")} for state in ("off", "on"))
    off["rate"], on["rate"] = off["work"] / off["seconds"], on["work"] / on["seconds"]

    spans = window["program_spans"]
    lo, hi = host_spans.window_interval(ctx)
    leaves = [leaf for leaf in host_spans.leaf_intervals(spans) if leaf[2] > lo and leaf[1] < hi]
    reduction = trace_lib.reduce_xplane(
        trace_lib.newest_xplane(ctx.trace_dir), ctx.spans.spans + leaves, ctx.sync_host_ns)
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    n_requests = len(host_spans.requests(spans, lo, hi))
    selfs = host_spans.self_seconds(spans, lo, hi)
    inside = [s for s in spans if s.start_ns >= lo and s.end_ns <= hi]
    by_name = {}
    for name in sorted({s.name for s in inside}):
        mine = [s for s in inside if s.name == name]
        took = sorted(s.end_ns - s.start_ns for s in mine)
        by_name[name] = {
            "count": len(mine),
            "total_s": sum(took) / 1e9,
            "ms": [took[0] / 1e6, took[len(took) // 2] / 1e6, took[-1] / 1e6],
            "self_ms_per_request": 1e3 * selfs.get(name, 0.0) / max(n_requests, 1),
        }
    metrics = {}
    for name in metric_names(kind):
        value = harness._layer_reader(name)(name, reduction, window, ctx)
        if value is not None:
            metrics[name] = value

    devices = ctx.devices
    line = {
        "workload": args.workload, "seed": args.seed,
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind},
        "requests": window["attempted"], "failed": window["failed"],
        "window_compiles": window["compiles"], "setup_s": ctx.setup_s,
        "entry_loop": {ctx.cell["end_to_end"]["rate"]: window["rate"],
                       "window_s": (hi - lo) / 1e9,
                       "leaf_self_s": sum(selfs.values())},
        "spans": by_name,
        "compile_phases": {
            "set_up": {p: _top(f) for p, f in host_spans.phase_seconds(spans, hi=lo).items()},
            "window": {p: _top(f) for p, f in host_spans.phase_seconds(spans, lo, hi).items()},
            # what the persistent cache answered to set-up's backend compiles
            "set_up_cache": {answer: sum(s.name == "compile" and s.end_ns <= lo
                                         and s.fields.get("cache") == answer for s in spans)
                             for answer in ("hit", "miss")},
        },
        "metrics": metrics,
        "overhead": {"recorder_off": off["rate"], "recorder_on": on["rate"],
                     "on_over_off": on["rate"] / off["rate"],
                     "halves": {state: [h["rate"] for h in hs] for state, hs in halves.items()},
                     # the same split with no profiler running beside the host
                     "self_ms_per_request_untraced": {
                         name: 1e3 * sum(host_spans.self_seconds(r).get(name, 0.0)
                                         for r in untraced) / on["attempted"]
                         for name in (host_spans.REQUEST, *host_spans.REQUEST_SPANS)},
                     "requests": [off["attempted"], on["attempted"]],
                     "failed": [off["failed"], on["failed"]]},
    }
    if reduction is not None:
        line.update(device_idle_share=100.0 * reduction.idle_share, busy_s=reduction.busy_s,
                    idle_gaps=dict(reduction.idle_gaps))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
