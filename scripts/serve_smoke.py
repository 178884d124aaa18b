#!/usr/bin/env python
"""Serving-stack smoke: N concurrent synthetic slides of mixed lengths
through the full queue -> bucket -> AOT -> cache path (ROADMAP item 1's
acceptance driver).

    python scripts/serve_smoke.py                       # 32 slides, 8 lengths, tiny arch
    python scripts/serve_smoke.py --json SERVE_SMOKE.json
    python scripts/serve_smoke.py --arch gigapath_slide_enc12l768d \
        --input-dim 1536 --latent-dim 768 --bucket-min 1024   # flagship (chip day)

Three phases, each with hard assertions (exit 1 + structured JSON on
violation, bench.py-style):

1. **cold serve**: ``--slides`` synthetic slides of ``--distinct-lengths``
   distinct tile counts submitted from ``--threads`` concurrent
   threads; the service must compile exactly ONE executable per bucket
   touched (watchdog-pinned: zero unexpected retraces, compile count ==
   buckets used).
2. **repeat serve**: every distinct slide re-submitted under a new
   request id; the dispatch count must NOT move — repeats are served
   from the content-hash cache without a forward pass.
3. **warm restart** (skip with ``--no-warm-restart``): a fresh service
   over the same artifact dir serves one slide per bucket with ZERO
   compiles — every executable loads from its persisted artifact.

The cold run's obs artifacts are part of the acceptance (PR 9): the
typed metrics snapshot must carry queue-wait / dispatch / end-to-end
latency histograms with p50/p90/p99, the per-run request-trace export
must be Perfetto-loadable with ``submit -> queue -> dispatch ->
forward`` spans nesting inside each request under a stable
``trace_id``, and the SLO contract is asserted both ways: a
``--slow-dispatch-s`` run (chaos ``slow_dispatch@*`` host-side sleeps)
fires EXACTLY ONE ``slo_burn`` anomaly (flight dump + armed profiler
capture), a clean run fires none.

Emits one JSON line (stdout; ``--json`` also writes a file) whose
metric keys (`slides_per_sec`, `occupancy_mean`, `cache_hit_rate`,
`queue_wait_p50_s`, ..., plus the latency keys `e2e_p{50,90,99}_s`,
`dispatch_p{50,99}_s`, `queue_wait_p99_s`) are what
``scripts/perf_history.py ingest --serve`` folds into PERF_HISTORY.json
(`serve|smoke` + `serve|latency` entries) — CPU runs land as stale
points (keys without trend weight) until a chip round measures them
for real.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

# THE shared nearest-rank percentile (gigalint GL012: one
# implementation — obs_report.py and the metrics registry use the same)
from gigapath_tpu.obs.metrics import percentile  # noqa: E402


def make_slides(n_slides: int, lengths: List[int], dim: int, seed: int):
    """(slide_id, feats [N, D], coords [N, 2]) per slide, lengths cycled."""
    rng = np.random.default_rng(seed)
    slides = []
    for i in range(n_slides):
        n = lengths[i % len(lengths)]
        slides.append((
            f"slide_{i:04d}_n{n}",
            rng.normal(size=(n, dim)).astype(np.float32),
            rng.uniform(0, 25000, (n, 2)).astype(np.float32),
        ))
    return slides


def pick_lengths(ladder, k: int) -> List[int]:
    """k distinct tile counts spread over the ladder: rung boundaries
    (exact fits), off-rung interiors, and the N=1 edge."""
    rungs = list(ladder.rungs)
    lengths = [1, rungs[0]]                      # the edge + an exact fit
    for rung, prev in zip(rungs[1:], rungs[:-1]):
        lengths.append(prev + max(1, (rung - prev) // 3))  # interior
        lengths.append(rung)                                # boundary
    # dedup, keep order, then cycle-extend if the ladder is too short
    seen, out = set(), []
    for n in lengths:
        if n not in seen:
            seen.add(n)
            out.append(n)
    i = 0
    max_tries = 8 * (k + len(out))  # bounded: fall through when the
    while len(out) < k and i < max_tries:   # neighborhood runs dry
        cand = out[1 + (i % max(len(out) - 1, 1))] - 1 - i // len(out)
        i += 1
        if cand >= 1 and cand not in seen:
            seen.add(cand)
            out.append(cand)
    if len(out) < k:
        # exhaustive sweep of every representable length, then give a
        # real error instead of looping forever on an impossible ask
        for cand in range(1, rungs[-1] + 1):
            if len(out) >= k:
                break
            if cand not in seen:
                seen.add(cand)
                out.append(cand)
    if len(out) < k:
        raise ValueError(
            f"ladder {rungs} only admits {rungs[-1]} distinct tile "
            f"counts; cannot pick {k} distinct lengths"
        )
    return out[:k]


def run(args) -> dict:
    import jax

    from gigapath_tpu.inference import load_model
    from gigapath_tpu.serve import ServeConfig, SlideService

    out_dir = args.out_dir or tempfile.mkdtemp(prefix="serve_smoke_")
    artifact_dir = args.artifact_dir or os.path.join(out_dir, "artifacts")
    model, params = load_model(
        "", input_dim=args.input_dim, latent_dim=args.latent_dim,
        feat_layer=args.feat_layer, n_classes=args.n_classes,
        model_arch=args.arch,
    )

    def forward(p, embeds, coords, pad_mask):
        return model.apply({"params": p}, embeds, coords,
                           pad_mask=pad_mask, deterministic=True)

    slo_overrides = {}
    if args.slo_target_s > 0:
        # smoke SLO policy: tight windows + a low event floor so a short
        # CPU run can prove the burn detector both ways (the production
        # defaults are minutes-scale; ServeConfig docstring)
        slo_overrides = dict(
            slo_target_s=args.slo_target_s, slo_budget=0.25,
            slo_burn_threshold=1.5, slo_short_window_s=30.0,
            slo_long_window_s=60.0, slo_min_events=4,
        )
    chaos_prev = os.environ.get("GIGAPATH_CHAOS")
    if args.slow_dispatch_s > 0:
        # forced-slow run: every dispatch sleeps host-side inside its
        # span (resilience.chaos slow_dispatch@*) — the injected
        # latency must fire EXACTLY ONE slo_burn anomaly below. The env
        # is restored after the COLD service is built: the injection
        # targets phase 1, not the warm-restart service of phase 3
        spec = f"slow_dispatch@*:{args.slow_dispatch_s}"
        os.environ["GIGAPATH_CHAOS"] = (
            f"{chaos_prev},{spec}" if chaos_prev else spec
        )
    config = ServeConfig.from_env(
        max_batch=args.max_batch, max_wait_s=args.max_wait_s,
        bucket_min=args.bucket_min, bucket_growth=args.bucket_growth,
        bucket_max=args.bucket_max, bucket_align=args.bucket_align,
        feature_dim=args.input_dim, artifact_dir=artifact_dir,
        **slo_overrides,
    )
    identity = f"{args.arch}|{args.feat_layer}|{args.n_classes}"
    service = SlideService(forward, params, config=config,
                           out_dir=out_dir, identity=identity)
    if args.slow_dispatch_s > 0:
        # cold service built (get_chaos read the spec): restore the env
        # so the warm-restart service is NOT chaos-slowed and the
        # caller's environment is left as found
        if chaos_prev is None:
            os.environ.pop("GIGAPATH_CHAOS", None)
        else:
            os.environ["GIGAPATH_CHAOS"] = chaos_prev
    lengths = pick_lengths(service.ladder, args.distinct_lengths)
    slides = make_slides(args.slides, lengths, args.input_dim, args.seed)
    expected_buckets = sorted({
        service.ladder.bucket_for(f.shape[0]) for _, f, _ in slides
    })

    payload: dict = {
        "metric": "serve_smoke",
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "arch": args.arch,
        "slides": len(slides),
        "distinct_lengths": len(lengths),
        "lengths": lengths,
        "expected_buckets": expected_buckets,
        "max_batch": args.max_batch,
        "obs": getattr(service.runlog, "path", None),
    }

    # -- phase 1: cold serve, concurrent submitters -----------------------
    with service:
        t0 = time.monotonic()
        with ThreadPoolExecutor(max_workers=args.threads) as pool:
            futures = list(pool.map(
                lambda s: service.submit(*s), slides
            ))
        results = [f.result(timeout=args.timeout_s) for f in futures]
        jax.block_until_ready(results)  # host numpy already; explicit fence
        cold_s = time.monotonic() - t0

        stats = service.stats()
        payload.update(
            cold_wall_s=round(cold_s, 4),
            slides_per_sec=round(len(slides) / cold_s, 4),
            dispatches=stats["dispatches"],
            buckets_used=stats["buckets_used"],
            compiled_executables=stats["compiled_executables"],
            unexpected_retraces=stats["unexpected_retraces"],
            compile_seconds_total=round(stats["compile_seconds_total"], 4),
        )
        if stats["unexpected_retraces"]:
            raise AssertionError(
                f"mid-serve retrace: {service.watchdog.unexpected_retraces}"
            )
        if stats["compiled_executables"] != len(expected_buckets):
            raise AssertionError(
                f"compiled {stats['compiled_executables']} executables for "
                f"{len(expected_buckets)} buckets ({expected_buckets})"
            )

        # -- phase 2: repeats must be cache hits, not dispatches ----------
        dispatches_before = service.dispatch_count
        repeats = [
            (f"repeat_{sid}", feats, coords)
            for sid, feats, coords in slides[: args.repeats]
        ]
        repeat_futs = [service.submit(*s) for s in repeats]
        repeat_results = [f.result(timeout=args.timeout_s)
                          for f in repeat_futs]
        if service.dispatch_count != dispatches_before:
            raise AssertionError(
                f"repeated slides triggered "
                f"{service.dispatch_count - dispatches_before} dispatch(es) "
                "— the content-hash cache failed to short-circuit"
            )
        for i, r_new in enumerate(repeat_results):
            if not np.allclose(
                np.asarray(results[i]), np.asarray(r_new), atol=0.0
            ):
                raise AssertionError("cached result != computed result")
        cache = service.cache.stats()
        payload.update(
            repeats=len(repeats),
            cache_hits=cache["hits"],
            cache_hit_rate=round(
                cache["hits"] / (cache["hits"] + cache["misses"]), 4
            ),
        )

        # queue-wait / occupancy / dispatch-wall distributions out of
        # the run artifact (EXACT per-request/per-dispatch values — the
        # trend keys below must not inherit the metrics histogram's
        # factor-2 bucket quantization, which would let a 1% drift
        # across a bucket boundary read as a 100% trend regression)
        waits: List[float] = []
        occs: List[float] = []
        dispatch_walls: List[float] = []
        run_path = getattr(service.runlog, "path", None)
        if run_path and os.path.exists(run_path):
            with open(run_path, encoding="utf-8") as fh:
                for line in fh:
                    try:
                        ev = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if ev.get("kind") == "serve_dispatch":
                        waits.extend(ev.get("queue_wait_s") or [])
                        if ev.get("occupancy") is not None:
                            occs.append(float(ev["occupancy"]))
                        if ev.get("wall_s") is not None:
                            dispatch_walls.append(float(ev["wall_s"]))
        waits.sort()
        dispatch_walls.sort()
        payload.update(
            occupancy_mean=round(sum(occs) / len(occs), 4) if occs else None,
            queue_wait_p50_s=percentile(waits, 0.50) if waits else None,
            queue_wait_p90_s=percentile(waits, 0.90) if waits else None,
        )

        # -- the metrics snapshot (obs/metrics.py): queue-wait, dispatch
        # and end-to-end latency histograms with p50/p90/p99 — the keys
        # `perf_history.py ingest --serve` folds into the serve|latency
        # trend entry. Skipped (like every obs artifact below) when the
        # run opted out of obs/metrics — the obs-off twin must leave
        # NO metrics surface, not a failed assertion
        from gigapath_tpu.obs.metrics import MetricsRegistry

        snap = service.metrics.snapshot()
        hists = snap.get("histograms", {})
        # gate on the registry actually being real: obs on but
        # GIGAPATH_METRICS=0 is a legitimate opt-out, not a failed run
        if run_path and isinstance(service.metrics, MetricsRegistry):
            for want in ("serve.queue_wait_s", "serve.dispatch_s",
                         "serve.e2e_s"):
                if not hists.get(want, {}).get("count"):
                    raise AssertionError(
                        f"metrics snapshot missing observations for {want} "
                        "(obs on but the registry saw no latency?)"
                    )
            payload["metrics"] = {
                "counters": snap.get("counters", {}),
                "histograms": {
                    name: {k: h.get(k) for k in
                           ("count", "p50", "p90", "p99", "max")}
                    for name, h in hists.items()
                },
            }
            # trend keys from the EXACT distributions (the histogram
            # quantiles above are conservative bucket upper bounds —
            # right for a live SLO gate, too coarse for a 5%-tolerance
            # trend). e2e comes from the trace export below
            payload.update(
                dispatch_p50_s=percentile(dispatch_walls, 0.50)
                if dispatch_walls else None,
                dispatch_p99_s=percentile(dispatch_walls, 0.99)
                if dispatch_walls else None,
                queue_wait_p99_s=percentile(waits, 0.99) if waits else None,
                slo_burn_entries=service.stats()["slo_burn_entries"],
            )

    # -- the run artifact half of the acceptance: a Perfetto-loadable
    # trace whose spans nest submit -> queue -> dispatch -> forward per
    # request with stable trace_ids, and the slo_burn contract (exactly
    # one anomaly on the forced-slow run, none on a clean run). The
    # service owns its runlog, so close() above ran run_end -> closers
    # (metrics final flush, trace export)
    if run_path and os.path.exists(run_path):
        trace_path = os.path.splitext(run_path)[0] + ".trace.json"
        if not os.path.exists(trace_path):
            raise AssertionError(f"no request-trace export at {trace_path}")
        with open(trace_path, encoding="utf-8") as fh:
            tdoc = json.load(fh)
        spans_by_tid: dict = {}
        for tev in tdoc.get("traceEvents", []):
            if tev.get("ph") == "X":
                spans_by_tid.setdefault(tev["tid"], []).append(tev)
        nested = 0
        e2e_s: List[float] = []  # exact per-dispatched-request end-to-end
        for tid, tevs in spans_by_tid.items():
            roots = [e for e in tevs if e["name"] == "request"]
            if len(roots) != 1:
                raise AssertionError(
                    f"trace track {tid}: want one request root, got "
                    f"{len(roots)}"
                )
            root = roots[0]
            lo, hi = root["ts"], root["ts"] + root["dur"]
            tids = {e["args"].get("trace_id") for e in tevs}
            if tids != {root["args"]["trace_id"]}:
                raise AssertionError(
                    f"trace track {tid}: unstable trace_id(s) {tids}"
                )
            names = {e["name"] for e in tevs}
            if {"submit", "queue", "dispatch", "forward"} <= names:
                nested += 1
                e2e_s.append(root["dur"] / 1e6)
                for e in tevs:
                    if not (lo - 0.5 <= e["ts"]
                            and e["ts"] + e["dur"] <= hi + 0.5):
                        raise AssertionError(
                            f"span {e['name']} escapes its request "
                            f"(track {tid})"
                        )
        if nested == 0:
            raise AssertionError(
                "no request trace carries the full submit->queue->"
                "dispatch->forward span chain"
            )
        e2e_s.sort()
        payload.update(trace_json=trace_path,
                       trace_requests=len(spans_by_tid),
                       trace_nested_requests=nested,
                       e2e_p50_s=percentile(e2e_s, 0.50),
                       e2e_p90_s=percentile(e2e_s, 0.90),
                       e2e_p99_s=percentile(e2e_s, 0.99))

        slo_burns = []
        with open(run_path, encoding="utf-8") as fh:
            for line in fh:
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if (ev.get("kind") == "anomaly"
                        and ev.get("detector") == "slo_burn"):
                    slo_burns.append(ev)
        payload["slo_burn_anomalies"] = len(slo_burns)
        if args.slow_dispatch_s > 0:
            if len(slo_burns) != 1:
                raise AssertionError(
                    f"forced-slow run fired {len(slo_burns)} slo_burn "
                    "anomalies (want exactly 1)"
                )
            if not slo_burns[0].get("flight"):
                raise AssertionError("slo_burn anomaly took no flight dump")
            if not slo_burns[0].get("trace_dir"):
                raise AssertionError(
                    "slo_burn anomaly armed no profiler capture"
                )
            payload["slo_burn_flight"] = slo_burns[0]["flight"]
            payload["slo_burn_trace_dir"] = slo_burns[0]["trace_dir"]
        elif slo_burns:
            raise AssertionError(
                f"clean run fired {len(slo_burns)} slo_burn anomalies "
                "(want none)"
            )

    # -- phase 3: warm restart loads artifacts, compiles nothing ----------
    if not args.no_warm_restart:
        warm = SlideService(forward, params, config=config,
                            out_dir=out_dir, identity=identity)
        try:
            per_bucket = {}
            for sid, feats, coords in slides:
                per_bucket.setdefault(
                    warm.ladder.bucket_for(feats.shape[0]),
                    (sid, feats, coords),
                )
            futs = [warm.submit(f"warm_{sid}", feats, coords)
                    for sid, feats, coords in per_bucket.values()]
            warm.drain()
            for f in futs:
                f.result(timeout=args.timeout_s)
            wstats = warm.stats()
            payload.update(
                warm_loaded_executables=wstats["loaded_executables"],
                warm_compiled_executables=wstats["compiled_executables"],
            )
            if wstats["compiled_executables"] != 0:
                raise AssertionError(
                    f"warm restart compiled "
                    f"{wstats['compiled_executables']} executable(s) — "
                    "cold start must be an artifact load, not a retrace"
                )
            if wstats["loaded_executables"] != len(per_bucket):
                raise AssertionError(
                    f"warm restart loaded {wstats['loaded_executables']} of "
                    f"{len(per_bucket)} persisted executables"
                )
        finally:
            warm.close()
    return payload


def run_drift(args) -> dict:
    """Model-health leg (ISSUE 19), standalone with ``--drift-slides``:

    A. **baseline**: ``--drift-slides`` synthetic slides through the
       REAL streaming-prefill path (anytime peeks on); the finalized
       embeddings build an :class:`EmbeddingSketch` baseline persisted
       with the manifest discipline and re-loaded (round-trip must be
       bit-exact).
    B. **clean serve**: the same slides re-served with a
       :class:`DriftSentinel` on the loaded baseline — zero drift by
       construction, so the run must fire NO ``embedding_drift``
       anomaly.
    C. **forced drift**: a fresh sentinel whose served embeddings are
       chaos-shifted by ``--drift-shift`` before it sees them — must
       fire EXACTLY ONE ``embedding_drift`` anomaly with a flight dump.

    The payload's ``drift_*`` keys are the CLEAN-phase scores (the
    trendable health numbers) and ``stream_confidence_*`` summarize the
    provisional-vs-final cosines — what ``perf_history.py ingest
    --drift`` folds into the ``serve|drift`` entry.
    """
    import jax

    from gigapath_tpu.models.classification_head import get_model
    from gigapath_tpu.obs.anomaly import AnomalyConfig, attach_anomaly_engine
    from gigapath_tpu.obs.drift import DriftSentinel, EmbeddingSketch
    from gigapath_tpu.obs.metrics import MetricsRegistry
    from gigapath_tpu.obs.runlog import RunLog
    from gigapath_tpu.serve.streaming import StreamingSubmitter
    from gigapath_tpu.utils.registry import create_model_from_registry

    out_dir = args.out_dir or tempfile.mkdtemp(prefix="drift_smoke_")
    os.makedirs(out_dir, exist_ok=True)
    run_path = os.path.join(out_dir, "drift_run.jsonl")
    log = RunLog(run_path, driver="drift_smoke", echo=False)
    # closed loop armed, profiler capture disabled (CPU smoke weight)
    attach_anomaly_engine(log, config=AnomalyConfig(capture_budget=0))
    registry = MetricsRegistry(runlog=log, interval_s=0)

    _, params = get_model(
        input_dim=args.input_dim, latent_dim=args.latent_dim,
        feat_layer=args.feat_layer, n_classes=args.n_classes,
        model_arch=args.arch, dtype=None,
    )
    inner = create_model_from_registry(
        args.arch, in_chans=args.input_dim, global_pool=False, dtype=None,
    )
    n_tiles, chunk_tiles = args.drift_tiles, args.drift_chunk_tiles
    rng = np.random.default_rng(args.seed)
    slides = [
        (f"drift_{i:03d}",
         rng.normal(size=(n_tiles, args.input_dim)).astype(np.float32),
         rng.uniform(0, 25000, (n_tiles, 2)).astype(np.float32))
        for i in range(args.drift_slides)
    ]

    def serve(submitter, prefix: str):
        finals = []
        for sid, feats, coords in slides:
            session = submitter.open(f"{prefix}_{sid}", n_tiles)
            for c0 in range(0, n_tiles, chunk_tiles):
                idx = c0 // chunk_tiles
                session.feed(idx, feats[c0:c0 + chunk_tiles],
                             coords[c0:c0 + chunk_tiles])
            out = session.result()
            finals.append(np.asarray(out["last_layer_embed"],
                                     np.float32).reshape(-1))
        return finals

    payload: dict = {
        "metric": "drift_smoke",
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "arch": args.arch,
        "drift_slides": len(slides),
        "drift_tiles": n_tiles,
        "chunk_tiles": chunk_tiles,
        "obs": run_path,
    }

    # -- phase A: baseline sketch off the real streaming path -------------
    base_sub = StreamingSubmitter(
        inner, params["slide_encoder"], chunk_tiles=chunk_tiles,
        runlog=log, peek_every=args.drift_peek_every, metrics=registry,
    )
    finals = serve(base_sub, "base")
    dim = finals[0].shape[0]
    baseline = EmbeddingSketch(dim)
    for emb in finals:
        baseline.update(emb)
    sketch_dir = os.path.join(out_dir, "drift_baseline")
    baseline.save(sketch_dir)
    loaded = EmbeddingSketch.load(sketch_dir)
    if (loaded.count != baseline.count
            or not np.array_equal(loaded.mean, baseline.mean)
            or not np.array_equal(loaded.m2, baseline.m2)
            or not np.array_equal(loaded.hist, baseline.hist)):
        raise AssertionError(
            f"baseline sketch save/load round-trip not bit-exact "
            f"({sketch_dir})"
        )
    payload.update(embedding_dim=dim, baseline_sketch=sketch_dir,
                   baseline_count=loaded.count)

    # -- phase B: clean serve — same slides, zero drift, no anomaly -------
    every = max(2, len(slides) // 2)
    sentinel = DriftSentinel(
        loaded, log, metrics=registry, every=every,
        threshold=args.drift_threshold, min_count=every,
        name="serve.drift",
    )
    clean_sub = StreamingSubmitter(
        inner, params["slide_encoder"], chunk_tiles=chunk_tiles,
        runlog=log, drift=sentinel, peek_every=args.drift_peek_every,
        metrics=registry,
    )
    serve(clean_sub, "clean")
    if sentinel.alarming:
        raise AssertionError(
            f"clean re-serve alarmed the drift sentinel "
            f"(scores {sentinel.scores})"
        )
    sentinel.emit_status(reason="clean")
    clean_scores = sentinel.scores or {}
    payload.update(
        drift_mean_shift=clean_scores.get("mean_shift"),
        drift_cosine_dist=clean_scores.get("cosine_dist"),
        drift_tail_mass=clean_scores.get("tail_mass"),
        drift_threshold=sentinel.threshold,
    )

    # -- phase C: forced drift — chaos-shifted embeddings, ONE anomaly ----
    forced = DriftSentinel(
        EmbeddingSketch.load(sketch_dir), log, metrics=registry,
        every=every, threshold=args.drift_threshold, min_count=every,
        name="serve.drift.forced",
    )

    class _ChaosShift:
        """The injection point: the REAL result() wiring feeds the
        sentinel, this shim shifts what it sees."""

        def observe(self, emb):
            return forced.observe(
                np.asarray(emb, np.float64) + args.drift_shift
            )

    forced_sub = StreamingSubmitter(
        inner, params["slide_encoder"], chunk_tiles=chunk_tiles,
        runlog=log, drift=_ChaosShift(),
        peek_every=args.drift_peek_every, metrics=registry,
    )
    serve(forced_sub, "forced")
    if not forced.alarming:
        raise AssertionError(
            f"chaos shift {args.drift_shift} failed to alarm the "
            f"sentinel (scores {forced.scores})"
        )
    forced.emit_status(reason="forced")
    payload["forced_mean_shift"] = (forced.scores or {}).get("mean_shift")

    registry.flush(reason="final")
    log.run_end(status="ok")

    # -- the both-ways anomaly contract off the run artifact --------------
    drift_anomalies = []
    confidence_first: List[float] = []
    confidence_last: List[float] = []
    peeks = 0
    with open(run_path, encoding="utf-8") as fh:
        for line in fh:
            try:
                ev = json.loads(line)
            except json.JSONDecodeError:
                continue
            kind = ev.get("kind")
            if kind == "anomaly" and ev.get("detector") == "embedding_drift":
                drift_anomalies.append(ev)
            elif kind == "stream_peek":
                peeks += 1
            elif kind == "stream_result":
                if ev.get("confidence_first") is not None:
                    confidence_first.append(float(ev["confidence_first"]))
                if ev.get("confidence_last") is not None:
                    confidence_last.append(float(ev["confidence_last"]))
    payload["embedding_drift_anomalies"] = len(drift_anomalies)
    if len(drift_anomalies) != 1:
        raise AssertionError(
            f"want exactly 1 embedding_drift anomaly (the forced leg), "
            f"got {len(drift_anomalies)} — clean legs must stay silent"
        )
    anomaly = drift_anomalies[0]
    if anomaly.get("name") != "serve.drift.forced":
        raise AssertionError(
            f"the anomaly fired on sentinel '{anomaly.get('name')}', "
            "not the chaos-shifted one"
        )
    if not anomaly.get("flight"):
        raise AssertionError("embedding_drift anomaly took no flight dump")
    payload["drift_flight"] = anomaly["flight"]
    if args.drift_peek_every > 0:
        if not peeks:
            raise AssertionError("peek cadence on but no stream_peek events")
        if not confidence_last:
            raise AssertionError(
                "peeked serves recorded no provisional-vs-final confidence"
            )
        confidence_first.sort()
        confidence_last.sort()
        payload.update(
            stream_peeks=peeks,
            stream_confidence_first=percentile(confidence_first, 0.50),
            stream_confidence_last=percentile(confidence_last, 0.50),
        )
    return payload


def main(argv=None) -> int:
    from gigapath_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(
        prog="python scripts/serve_smoke.py",
        description="Concurrent synthetic slides through the serving stack",
    )
    ap.add_argument("--slides", type=int, default=32)
    ap.add_argument("--distinct-lengths", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=8,
                    help="re-submitted slides that must be cache hits")
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-wait-s", type=float, default=0.05)
    ap.add_argument("--bucket-min", type=int, default=32)
    ap.add_argument("--bucket-growth", type=float, default=2.0)
    ap.add_argument("--bucket-max", type=int, default=512)
    ap.add_argument("--bucket-align", type=int, default=32,
                    help="tiny-arch default; use 128 for flagship shapes")
    ap.add_argument("--arch", default="gigapath_slide_enc_tiny")
    ap.add_argument("--input-dim", type=int, default=16)
    ap.add_argument("--latent-dim", type=int, default=32)
    ap.add_argument("--feat-layer", default="1")
    ap.add_argument("--n-classes", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout-s", type=float, default=300.0)
    ap.add_argument("--out-dir", default=None,
                    help="obs + artifact root (default: fresh temp dir)")
    ap.add_argument("--artifact-dir", default=None,
                    help="persisted-executable dir (default: <out>/artifacts)")
    ap.add_argument("--no-warm-restart", action="store_true")
    ap.add_argument("--slo-target-s", type=float, default=0.0,
                    help="end-to-end latency SLO target in seconds "
                    "(0 = SLO off); the smoke applies a tight "
                    "test-friendly burn policy around it")
    ap.add_argument("--slow-dispatch-s", type=float, default=0.0,
                    help="FORCED-SLOW run: every dispatch sleeps this "
                    "many seconds host-side (chaos slow_dispatch@*) — "
                    "must fire exactly one slo_burn anomaly (flight "
                    "dump + profiler capture); combine with "
                    "--slo-target-s")
    ap.add_argument("--drift-slides", type=int, default=0,
                    help="model-health leg (replaces the serve phases): "
                    "this many slides through the streaming path three "
                    "times — baseline sketch, clean re-serve (no "
                    "anomaly), chaos-shifted serve (exactly one "
                    "embedding_drift anomaly)")
    ap.add_argument("--drift-shift", type=float, default=8.0,
                    help="per-dim chaos shift applied to the forced "
                    "leg's served embeddings before the sentinel sees "
                    "them")
    ap.add_argument("--drift-threshold", type=float, default=4.0,
                    help="DriftSentinel mean-shift threshold (in "
                    "baseline standard deviations)")
    ap.add_argument("--drift-tiles", type=int, default=32,
                    help="tiles per drift-leg slide")
    ap.add_argument("--drift-chunk-tiles", type=int, default=8,
                    help="streaming chunk size for the drift leg")
    ap.add_argument("--drift-peek-every", type=int, default=2,
                    help="anytime-peek cadence (folded chunks) for the "
                    "drift leg; 0 = no peeks")
    ap.add_argument("--json", default=None, help="also write the payload here")
    args = ap.parse_args(argv)
    if args.slow_dispatch_s > 0 and args.slo_target_s <= 0:
        # without a target there is no tracker and the end-of-run
        # "exactly one slo_burn" assertion is a GUARANTEED failure —
        # refuse up front instead of after a full cold-compile sweep
        ap.error("--slow-dispatch-s requires --slo-target-s > 0 (the "
                 "forced-slow run exists to fire the SLO burn detector)")

    try:
        payload = run_drift(args) if args.drift_slides > 0 else run(args)
        payload["rc"] = 0
    except Exception as e:
        payload = {
            "metric": "drift_smoke" if args.drift_slides > 0
            else "serve_smoke",
            "rc": 1,
            "error": f"{type(e).__name__}: {e}",
        }
    line = json.dumps(payload, sort_keys=True)
    print(line)
    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            fh.write(line + "\n")
    return payload["rc"]


if __name__ == "__main__":
    sys.exit(main())
