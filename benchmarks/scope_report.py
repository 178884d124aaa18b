"""One traced window of a cell, reduced by the program's own names.

    python3 benchmarks/scope_report.py --workload <cell> --seed <n> [--seconds 30]
                                       [--scope <regex>]... [--tiny]

The cell's own set-up and window under the profiler, exactly as a ``--trace 1``
run of ``run.py`` drives them, then ``lib/scopes.py``'s reduction through the
readers ``layer_metrics/scope_time_share.py`` and ``device_ms_per_request.py``:
every group of the cell kind's table (``scopes/<tile|slide>.json``) and the
device's time per request, one JSON line on standard output, the readers'
notes (a group's ten heaviest scope paths) on standard error. Each ``--scope``
adds the self seconds of the operations whose path holds the pattern
(``/branch_r2/``, ``/branch_r2/.*/pack/``).

Not part of a benchmark run: ``run.py`` reports the names a cell's file lists,
and a PR that changes the program may not edit that file, so until a
``benchmark`` PR lists these metrics (PERF.md §7) this is how they are read.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def metric_names(kind: str) -> list:
    """The names the two readers answer to for a cell kind."""
    from benchmarks.lib import scopes

    return [f"scope_time_share.{g['name']}.{kind}" for g in scopes.table(kind)["groups"]
            ] + [f"device_ms_per_request.{kind}"]


def main(argv=None) -> int:
    from benchmarks import run as harness
    from benchmarks.lib import scopes, tables
    from benchmarks.lib import trace as trace_lib

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--scope", action="append", default=[])
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    prepared = harness.prepare(argparse.Namespace(
        workload=args.workload, seed=args.seed, seconds=args.seconds, trace=1, tiny=args.tiny))
    if prepared is None:
        return 3
    ctx, driver = prepared
    kind = tables.cell_kind(ctx.cell)
    window = driver.run(ctx)
    trace = trace_lib.reduce_xplane(
        trace_lib.newest_xplane(ctx.trace_dir), ctx.spans.spans, ctx.sync_host_ns)
    metrics = {}
    for name in metric_names(kind):
        value = harness._layer_reader(name)(name, trace, window, ctx)
        if value is not None:
            metrics[name] = value
    reduction = scopes.for_run(ctx) if trace is not None else None
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    for note in ctx.notes:
        print(note, file=sys.stderr)
    line = {"workload": args.workload, "seed": args.seed, "requests": window["attempted"],
            "failed": window["failed"], "metrics": metrics}
    if reduction is not None:
        line.update(window_s=reduction.window_s, busy_s=reduction.busy_s,
                    no_path_s=reduction.no_path_s, inherited_s=reduction.inherited_s,
                    parse_s=reduction.parse_s,
                    scope_s={p: reduction.seconds(p) for p in args.scope})
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
