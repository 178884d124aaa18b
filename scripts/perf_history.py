#!/usr/bin/env python
"""Fold perf snapshots into PERF_HISTORY.json and gate on the trend.

    python scripts/perf_history.py ingest --label r06 \
        --bench BENCH_r06.json --multichip MULTICHIP_r06.json \
        --ledger out/obs/run.ledger.json
    python scripts/perf_history.py check [--json verdict.json] [--baseline prev]
    python scripts/perf_history.py --selftest                 # run by scripts/lint.sh

The history file (``PERF_HISTORY.json``, repo root, tracked) is
append-only: each round's BENCH/MULTICHIP snapshots and any per-run
ledgers land as labeled points keyed ``name|qualifier`` — the same key
shape as the perf ledger. ``check`` renders a ``ledger_diff``-shaped
decision table: the latest measured point per entry is judged against
the best (default) or previous measured point per metric, with
regression directions per metric class (throughput/MFU up-is-good,
bytes/FLOPs/eqns down-is-good, a lost donation is a regression). Stale
points (failed rounds, unmeasured values) keep their provenance but
never move the trend. Improvements never fail.

Pure stdlib (the folding logic lives in ``gigapath_tpu.obs.history``,
itself jax-free). Exit 0 on ok, 1 on trend regressions, 2 on unreadable
input / usage errors.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from gigapath_tpu.obs import history  # noqa: E402

DEFAULT_HISTORY = os.path.join(REPO_ROOT, "PERF_HISTORY.json")


def _load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def _load_or_new(path: str) -> dict:
    if os.path.exists(path):
        return history.load_history(path)
    return history.new_history()


# ---------------------------------------------------------------------------
# commands
# ---------------------------------------------------------------------------

def cmd_ingest(args) -> int:
    try:
        doc = _load_or_new(args.history)
        if args.bench:
            history.fold_bench(doc, _load_json(args.bench), args.label,
                               source=os.path.basename(args.bench),
                               force=args.force)
        if args.multichip:
            history.fold_multichip(doc, _load_json(args.multichip),
                                   args.label,
                                   source=os.path.basename(args.multichip),
                                   force=args.force)
        if args.serve:
            serve_snapshot = _load_json(args.serve)
            history.fold_serve(doc, serve_snapshot, args.label,
                               source=os.path.basename(args.serve),
                               force=args.force)
            # the same smoke payload also carries the metrics-snapshot
            # latency keys (e2e/dispatch/queue-wait p50/p90/p99) — one
            # ingest lands BOTH the throughput (serve|smoke) and the
            # tail-latency (serve|latency) trend entries
            history.fold_serve_latency(
                doc, serve_snapshot, args.label,
                source=os.path.basename(args.serve), force=args.force,
            )
        if args.dist:
            history.fold_dist(doc, _load_json(args.dist), args.label,
                              source=os.path.basename(args.dist),
                              force=args.force)
        if args.fleet:
            history.fold_fleet(doc, _load_json(args.fleet), args.label,
                               source=os.path.basename(args.fleet),
                               force=args.force)
        if args.drift:
            history.fold_drift(doc, _load_json(args.drift), args.label,
                               source=os.path.basename(args.drift),
                               force=args.force)
        if args.prefill:
            history.fold_prefill(doc, _load_json(args.prefill), args.label,
                                 source=os.path.basename(args.prefill),
                                 force=args.force)
        if args.tile:
            history.fold_tile(doc, _load_json(args.tile), args.label,
                              source=os.path.basename(args.tile),
                              force=args.force)
        for path in args.ledger or []:
            history.fold_ledger(doc, _load_json(path), args.label,
                                source=os.path.basename(path),
                                force=args.force)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    history.write_history(doc, args.history)
    print(f"perf_history: ingested label '{args.label}' -> {args.history}")
    return 0


def render(verdict: dict, out=None) -> None:
    out = out or sys.stdout
    w = out.write
    dec = verdict["decision"]
    w(f"perf_history: {verdict['history_entries']} entries, "
      f"baseline={verdict['thresholds']['baseline']} "
      f"rel_tol={verdict['thresholds']['rel_tol']}, "
      f"{dec['regressions']} regression(s), "
      f"{dec['improvements']} improvement(s)\n")
    for line in dec["regressed"]:
        w(f"  REGRESSION {line}\n")
    for line in dec["improved"]:
        w(f"  improvement {line}\n")
    for note in verdict.get("notes", []):
        w(f"  note {note}\n")
    w("verdict: " + ("OK\n" if dec["ok"] else "REGRESSED\n"))


def cmd_check(args) -> int:
    try:
        doc = history.load_history(args.history)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    verdict = history.trend_verdict(doc, rel_tol=args.rel_tol,
                                    baseline=args.baseline)
    verdict["history"] = os.path.abspath(args.history)
    render(verdict)
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(verdict, f, indent=1)
            f.write("\n")
    return 0 if verdict["decision"]["ok"] else 1


# ---------------------------------------------------------------------------
# selftest
# ---------------------------------------------------------------------------

def selftest() -> int:
    """Synthesize a history, assert the trend gate flips both ways
    (throughput dip = regression, memory growth = regression, stale
    points invisible, improvements never fail) and that append-only
    refuses label reuse — the history half of scripts/lint.sh."""
    doc = history.new_history()
    history.fold_bench(
        doc, {"rc": 0, "parsed": {"metric": "m", "value": 100.0,
                                  "mfu": 0.2, "peak_hbm_gb": 1.0}}, "r01")
    history.fold_bench(
        doc, {"rc": 0, "parsed": {"metric": "m", "value": 120.0,
                                  "mfu": 0.25, "peak_hbm_gb": 1.0}}, "r02")
    # a failed round must land stale and stay invisible to the gate
    history.fold_bench(doc, {"rc": 1, "parsed": None}, "r03")
    clean = history.trend_verdict(doc)
    if not clean["decision"]["ok"] or clean["decision"]["regressions"]:
        print("perf_history selftest FAILED: improving history not clean",
              file=sys.stderr)
        render(clean, out=sys.stderr)
        return 1
    lines = clean["decision"]["improved"] + clean["decision"]["regressed"]
    if any("r03" in line for line in lines):
        print("perf_history selftest FAILED: stale point moved the trend",
              file=sys.stderr)
        return 1

    # a throughput dip + memory growth in a NEW measured round must flip
    history.fold_bench(
        doc, {"rc": 0, "parsed": {"metric": "m", "value": 80.0,
                                  "mfu": 0.25, "peak_hbm_gb": 1.4}}, "r04")
    bad = history.trend_verdict(doc)
    dec = bad["decision"]
    want = ["value 120.0", "peak_hbm_gb 1.0"]
    missing = [w for w in want
               if not any(w in line for line in dec["regressed"])]
    if dec["ok"] or missing:
        print(f"perf_history selftest FAILED: ok={dec['ok']}, "
              f"undetected: {missing}", file=sys.stderr)
        render(bad, out=sys.stderr)
        return 1

    # baseline=prev view: r04 vs r02 (r03 is stale) — same regressions
    prev = history.trend_verdict(doc, baseline="prev")
    if prev["decision"]["ok"]:
        print("perf_history selftest FAILED: prev-baseline blind",
              file=sys.stderr)
        return 1

    # ledger folding + eqn-count trend direction
    ldoc = {"entries": {"step|f32[1,8]": {
        "jaxpr": {"eqns_total": 100},
        "cost": {"flops": 1e6, "bytes_accessed": 2e6},
        "memory": {"peak_bytes": 3e6, "donated_bytes": 4096.0},
    }}}
    history.fold_ledger(doc, ldoc, "r05")
    worse = {"entries": {"step|f32[1,8]": {
        "jaxpr": {"eqns_total": 130},
        "cost": {"flops": 1e6, "bytes_accessed": 2e6},
        "memory": {"peak_bytes": 3e6, "donated_bytes": 0.0},
    }}}
    history.fold_ledger(doc, worse, "r06")
    v = history.trend_verdict(doc)
    for needle in ("jaxpr.eqns_total", "memory.donated_bytes"):
        if not any(needle in line for line in v["decision"]["regressed"]):
            print(f"perf_history selftest FAILED: {needle} regression "
                  "undetected", file=sys.stderr)
            return 1

    # serve_smoke folding: a CPU point is stale (keys present, trend
    # blind to it); on-chip points trend, and a throughput dip flips
    serve_doc = history.new_history()
    history.fold_serve(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "cpu", "slides_per_sec": 3.0,
                             "cache_hit_rate": 1.0}}, "r01")
    point = serve_doc["entries"]["serve|smoke"]["points"][0]
    if not point.get("stale") or "slides_per_sec" not in point["metrics"]:
        print("perf_history selftest FAILED: CPU serve point must be "
              "stale WITH metric keys", file=sys.stderr)
        return 1
    history.fold_serve(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "tpu", "slides_per_sec": 100.0,
                             "occupancy_mean": 0.9}}, "r02")
    history.fold_serve(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "tpu", "slides_per_sec": 50.0,
                             "occupancy_mean": 0.9}}, "r03")
    sv = history.trend_verdict(serve_doc)
    if sv["decision"]["ok"] or not any(
        "slides_per_sec 100.0" in line for line in sv["decision"]["regressed"]
    ):
        print("perf_history selftest FAILED: serve throughput dip "
              "undetected", file=sys.stderr)
        render(sv, out=sys.stderr)
        return 1
    if any("r01" in line for line in sv["decision"]["regressed"]):
        print("perf_history selftest FAILED: stale CPU serve point moved "
              "the trend", file=sys.stderr)
        return 1

    # serve|latency folding: the latency keys land under their own
    # entry, CPU points stale WITH keys, and a p99 regression (tail
    # latency UP) flips the gate while an improvement never does
    history.fold_serve_latency(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "cpu", "e2e_p99_s": 9.0,
                             "queue_wait_p99_s": 0.5}}, "r01")
    lat_points = serve_doc["entries"]["serve|latency"]["points"]
    if not lat_points[0].get("stale") or "e2e_p99_s" not in \
            lat_points[0]["metrics"]:
        print("perf_history selftest FAILED: CPU latency point must be "
              "stale WITH metric keys", file=sys.stderr)
        return 1
    history.fold_serve_latency(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "tpu", "e2e_p50_s": 0.1,
                             "e2e_p99_s": 0.5, "dispatch_p99_s": 0.2}},
        "r02")
    history.fold_serve_latency(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "tpu", "e2e_p50_s": 0.1,
                             "e2e_p99_s": 1.5, "dispatch_p99_s": 0.1}},
        "r03")
    lv = history.trend_verdict(serve_doc)
    if lv["decision"]["ok"] or not any(
        "serve|latency: e2e_p99_s 0.5" in line
        for line in lv["decision"]["regressed"]
    ):
        print("perf_history selftest FAILED: e2e_p99_s tail regression "
              "undetected", file=sys.stderr)
        render(lv, out=sys.stderr)
        return 1
    if any("dispatch_p99_s" in line for line in lv["decision"]["regressed"]):
        print("perf_history selftest FAILED: an IMPROVED dispatch p99 "
              "counted as a regression", file=sys.stderr)
        return 1

    # dist_smoke folding: same shared staleness policy (CPU dryrun =
    # stale with keys), and a boundary-throughput dip flips the gate
    history.fold_dist(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "cpu", "chunks_per_sec": 4.0,
                             "recover_extra_s": 1.5}}, "r01")
    dist_points = serve_doc["entries"]["dist|smoke"]["points"]
    if not dist_points[0].get("stale") or "chunks_per_sec" not in \
            dist_points[0]["metrics"]:
        print("perf_history selftest FAILED: CPU dist point must be "
              "stale WITH metric keys", file=sys.stderr)
        return 1
    history.fold_dist(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "tpu", "chunks_per_sec": 200.0,
                             "recover_extra_s": 1.0}}, "r02")
    history.fold_dist(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "tpu", "chunks_per_sec": 90.0,
                             "recover_extra_s": 1.0}}, "r03")
    dv = history.trend_verdict(serve_doc)
    if dv["decision"]["ok"] or not any(
        "dist|smoke: chunks_per_sec 200.0" in line
        for line in dv["decision"]["regressed"]
    ):
        print("perf_history selftest FAILED: dist boundary-throughput "
              "dip undetected", file=sys.stderr)
        render(dv, out=sys.stderr)
        return 1

    # dist|trace folding (dist_smoke --fleet-json): same shared
    # staleness policy (CPU fleet = stale with keys), and a wire-share
    # GROWTH on the merged critical path flips the gate
    history.fold_fleet(
        serve_doc,
        {"rc": 0, "backend": "cpu", "chunks_per_sec": 60.0,
         "wire_share": 0.07, "backpressure_share": 0.0,
         "fold_share": 0.34}, "r01")
    fleet_points = serve_doc["entries"]["dist|trace"]["points"]
    if not fleet_points[0].get("stale") or "wire_share" not in \
            fleet_points[0]["metrics"]:
        print("perf_history selftest FAILED: CPU fleet point must be "
              "stale WITH metric keys", file=sys.stderr)
        return 1
    history.fold_fleet(
        serve_doc,
        {"rc": 0, "backend": "tpu", "chunks_per_sec": 500.0,
         "wire_share": 0.05, "backpressure_share": 0.01,
         "fold_share": 0.30}, "r02")
    history.fold_fleet(
        serve_doc,
        {"rc": 0, "backend": "tpu", "chunks_per_sec": 500.0,
         "wire_share": 0.25, "backpressure_share": 0.01,
         "fold_share": 0.30}, "r03")
    fv = history.trend_verdict(serve_doc)
    if fv["decision"]["ok"] or not any(
        "dist|trace: wire_share 0.05" in line
        for line in fv["decision"]["regressed"]
    ):
        print("perf_history selftest FAILED: fleet wire-share growth "
              "undetected", file=sys.stderr)
        render(fv, out=sys.stderr)
        return 1

    # prefill|stream folding: same shared staleness policy (CPU point =
    # stale with keys), and fold-executable memory growth flips the gate
    history.fold_prefill(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "cpu", "stream_temp_mb": 2.0,
                             "peak_ratio": 0.3}}, "r01")
    pre_points = serve_doc["entries"]["prefill|stream"]["points"]
    if not pre_points[0].get("stale") or "stream_temp_mb" not in \
            pre_points[0]["metrics"]:
        print("perf_history selftest FAILED: CPU prefill point must be "
              "stale WITH metric keys", file=sys.stderr)
        return 1
    history.fold_prefill(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "tpu", "stream_temp_mb": 2.0,
                             "stream_peak_mb": 8.0, "peak_ratio": 0.3}},
        "r02")
    history.fold_prefill(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "tpu", "stream_temp_mb": 6.0,
                             "stream_peak_mb": 8.0, "peak_ratio": 0.9}},
        "r03")
    pv = history.trend_verdict(serve_doc)
    if pv["decision"]["ok"] or not any(
        "prefill|stream: stream_temp_mb 2.0" in line
        for line in pv["decision"]["regressed"]
    ):
        print("perf_history selftest FAILED: prefill fold-executable "
              "memory growth undetected", file=sys.stderr)
        render(pv, out=sys.stderr)
        return 1
    if not any(
        "prefill|stream: peak_ratio 0.3" in line
        for line in pv["decision"]["regressed"]
    ):
        print("perf_history selftest FAILED: prefill peak_ratio "
              "regression undetected", file=sys.stderr)
        return 1

    # tile|quant folding: same shared staleness policy (a CPU parity
    # run = stale with keys), a throughput dip flips the gate, and a
    # cosine-drift GROWTH (quality regression) flips it too
    history.fold_tile(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "cpu", "int8_tiles_per_sec": 5.0,
                             "cosine_drift": 1e-5}}, "r01")
    tile_points = serve_doc["entries"]["tile|quant"]["points"]
    if not tile_points[0].get("stale") or "cosine_drift" not in \
            tile_points[0]["metrics"]:
        print("perf_history selftest FAILED: CPU tile point must be "
              "stale WITH metric keys", file=sys.stderr)
        return 1
    history.fold_tile(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "tpu", "bf16_tiles_per_sec": 240.0,
                             "int8_tiles_per_sec": 400.0,
                             "cosine_drift": 1e-5}}, "r02")
    history.fold_tile(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "tpu", "bf16_tiles_per_sec": 240.0,
                             "int8_tiles_per_sec": 250.0,
                             "cosine_drift": 5e-3}}, "r03")
    tv = history.trend_verdict(serve_doc)
    missing_tile = [
        needle for needle in
        ("tile|quant: cosine_drift 1e-05", "tile|quant: int8_tiles_per_sec")
        if not any(needle in line for line in tv["decision"]["regressed"])
    ]
    if tv["decision"]["ok"] or missing_tile:
        print(f"perf_history selftest FAILED: tile|quant regressions "
              f"undetected: {missing_tile}", file=sys.stderr)
        render(tv, out=sys.stderr)
        return 1

    # serve|drift folding (serve_smoke --drift): same shared staleness
    # policy (CPU smoke = stale with keys), a drift-score GROWTH flips
    # the gate, and a confidence DROP (the anytime surface got less
    # trustworthy) flips it too
    history.fold_drift(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "cpu", "drift_mean_shift": 0.2,
                             "stream_confidence_last": 0.99}}, "r01")
    drift_points = serve_doc["entries"]["serve|drift"]["points"]
    if not drift_points[0].get("stale") or "drift_mean_shift" not in \
            drift_points[0]["metrics"]:
        print("perf_history selftest FAILED: CPU drift point must be "
              "stale WITH metric keys", file=sys.stderr)
        return 1
    history.fold_drift(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "tpu", "drift_mean_shift": 0.2,
                             "drift_tail_mass": 0.01,
                             "stream_confidence_first": 0.90,
                             "stream_confidence_last": 0.99}}, "r02")
    history.fold_drift(
        serve_doc,
        {"rc": 0, "parsed": {"backend": "tpu", "drift_mean_shift": 2.5,
                             "drift_tail_mass": 0.01,
                             "stream_confidence_first": 0.90,
                             "stream_confidence_last": 0.60}}, "r03")
    drv = history.trend_verdict(serve_doc)
    missing_drift = [
        needle for needle in
        ("serve|drift: drift_mean_shift 0.2",
         "serve|drift: stream_confidence_last 0.99")
        if not any(needle in line for line in drv["decision"]["regressed"])
    ]
    if drv["decision"]["ok"] or missing_drift:
        print(f"perf_history selftest FAILED: serve|drift regressions "
              f"undetected: {missing_drift}", file=sys.stderr)
        render(drv, out=sys.stderr)
        return 1
    if any("drift_tail_mass" in line for line in drv["decision"]["regressed"]):
        print("perf_history selftest FAILED: an UNCHANGED tail mass "
              "counted as a regression", file=sys.stderr)
        return 1

    # append-only: reusing a label without force must refuse
    try:
        history.fold_bench(
            doc, {"rc": 0, "parsed": {"metric": "m", "value": 1.0}}, "r02")
    except ValueError:
        pass
    else:
        print("perf_history selftest FAILED: label reuse not refused",
              file=sys.stderr)
        return 1
    # ... and force replaces IN PLACE: a re-measured OLD round must not
    # become the trend gate's "latest" candidate
    history.fold_bench(
        doc, {"rc": 0, "parsed": {"metric": "m", "value": 119.0}}, "r02",
        force=True)
    labels = [p["label"] for p in doc["entries"]["bench|slide_embed"]["points"]]
    if labels != ["r01", "r02", "r03", "r04"]:
        print(f"perf_history selftest FAILED: force reordered points "
              f"({labels})", file=sys.stderr)
        return 1
    v2 = history.trend_verdict(doc)
    if v2["decision"]["ok"] or not any(
        "(r04)" in line for line in v2["decision"]["regressed"]
    ):
        print("perf_history selftest FAILED: force-replacing an old round "
              "masked the latest round's regression", file=sys.stderr)
        return 1
    # ... and round-trips through the canonical writer
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "PERF_HISTORY.json")
        history.write_history(doc, path)
        again = history.load_history(path)
        if again["entries"].keys() != doc["entries"].keys():
            print("perf_history selftest FAILED: write/load round-trip",
                  file=sys.stderr)
            return 1
    print("perf_history selftest OK")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python scripts/perf_history.py",
        description="Append-only perf history + trend regression gate",
    )
    ap.add_argument("--selftest", action="store_true",
                    help="verify the trend gate on a synthetic history")
    sub = ap.add_subparsers(dest="command")

    p_ing = sub.add_parser("ingest", help="append one labeled round")
    p_ing.add_argument("--history", default=DEFAULT_HISTORY)
    p_ing.add_argument("--label", required=True,
                       help="round label (e.g. r06) — append-only")
    p_ing.add_argument("--bench", default=None, help="BENCH snapshot JSON")
    p_ing.add_argument("--multichip", default=None,
                       help="MULTICHIP snapshot JSON")
    p_ing.add_argument("--serve", default=None,
                       help="serve_smoke snapshot JSON "
                       "(scripts/serve_smoke.py --json output)")
    p_ing.add_argument("--dist", default=None,
                       help="dist_smoke snapshot JSON "
                       "(scripts/dist_smoke.py --json output) -> the "
                       "dist|smoke boundary trend entry")
    p_ing.add_argument("--fleet", default=None,
                       help="fleet-trace snapshot JSON "
                       "(scripts/dist_smoke.py --fleet-json output) -> the "
                       "dist|trace trend entry (cross-process critical-path "
                       "shares over the merged timeline)")
    p_ing.add_argument("--drift", default=None,
                       help="serve_smoke --drift snapshot JSON -> the "
                       "serve|drift trend entry (model health: drift "
                       "scores vs baseline + anytime-confidence summary)")
    p_ing.add_argument("--prefill", default=None,
                       help="long_context_smoke --stream snapshot JSON "
                       "-> the prefill|stream trend entry "
                       "(streaming-vs-dense memory decision table)")
    p_ing.add_argument("--tile", default=None,
                       help="ab_tile snapshot JSON (scripts/ab_tile.py "
                       "--json output) -> the tile|quant trend entry "
                       "(quantized tile tier: throughput + drift)")
    p_ing.add_argument("--ledger", action="append", default=None,
                       help="per-run ledger JSON (repeatable)")
    p_ing.add_argument("--force", action="store_true",
                       help="replace an existing label (re-measured round)")

    p_chk = sub.add_parser("check", help="trend regression gate")
    p_chk.add_argument("--history", default=DEFAULT_HISTORY)
    p_chk.add_argument("--rel-tol", type=float, default=0.05,
                       help="relative tolerance per metric (default 0.05)")
    p_chk.add_argument("--baseline", choices=("best", "prev"),
                       default="best",
                       help="judge the latest point against the best-ever "
                       "(default) or the previous measured point")
    p_chk.add_argument("--json", default="",
                       help="also write the verdict JSON here")

    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if args.command == "ingest":
        return cmd_ingest(args)
    if args.command == "check":
        return cmd_check(args)
    ap.error("provide a command (ingest | check) or --selftest")
    return 2


if __name__ == "__main__":
    sys.exit(main())
