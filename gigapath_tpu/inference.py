"""Batch inference driver over cached slide-feature files.

Parity with reference ``docker/workspace/prov-gigapath/inference.py``: load a
trained classification checkpoint, iterate ``*_features.pt`` files (or orbax
feature dirs), softmax-classify, write a csv of ``slide_id`` /
``predicted_label`` / ``confidence`` and print the label distribution +
mean-confidence stats (``run_inference:37-79``).

Two execution paths:

- **bucketed (default)**: slides route through the serving stack's
  shape-bucket ladder and request coalescer (:mod:`gigapath_tpu.serve`)
  — padded ``[batch_size, N_bucket, D]`` batches with key-padding
  masks, one AOT executable per bucket instead of one jit retrace per
  distinct tile count, and ``--batch_size`` actually batches (the
  reference accepted the flag and ignored it). Repeated slides are
  served from the content-hash embedding cache without a forward pass.
- **exact-shape** (``--no-buckets``): the original slide-at-a-time
  jit path — one compile per distinct N — kept as the fallback and the
  parity oracle the bucketed path is tested against.
"""

from __future__ import annotations

import argparse
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np

from gigapath_tpu.obs import (
    CompileWatchdog,
    Heartbeat,
    console,
    get_ledger,
    get_metrics,
    get_run_log,
    span,
)
from gigapath_tpu.obs.runlog import fail_run


def load_model(
    model_path: str,
    input_dim: int = 1536,
    latent_dim: int = 768,
    feat_layer: str = "11",
    n_classes: int = 2,
    model_arch: str = "gigapath_slide_enc12l768d",
    **kwargs,
):
    """Build the classification head and load a checkpoint
    (reference ``load_model:18-34``)."""
    from gigapath_tpu.finetune.predict import _load_params_into_model
    from gigapath_tpu.models.classification_head import get_model

    model, params = get_model(
        input_dim=input_dim,
        latent_dim=latent_dim,
        feat_layer=feat_layer,
        n_classes=n_classes,
        model_arch=model_arch,
        dtype=jnp.bfloat16,
        **kwargs,
    )
    if model_path:
        params = _load_params_into_model(model_path, params)
    return model, params


def _load_features(path: str):
    """-> (features [N, D], coords [N, 2] or None)."""

    def to_np(t):
        return np.asarray(t.detach().cpu().numpy() if hasattr(t, "detach") else t)

    if path.endswith(".pt"):
        import torch

        t = torch.load(path, map_location="cpu", weights_only=False)
        if isinstance(t, dict):
            feats = t.get("features", t.get("tile_embeds"))
            assert feats is not None, f"{path}: no 'features'/'tile_embeds' key"
            coords = t.get("coords")
            return to_np(feats), None if coords is None else to_np(coords)
        return to_np(t), None
    from gigapath_tpu.utils.checkpoint import restore_checkpoint

    state = restore_checkpoint(path)
    if isinstance(state, dict):
        return np.asarray(state["features"]), state.get("coords")
    return np.asarray(state), None


def _feature_stream(feature_files, prefetch: int, runlog):
    """Yield ``(idx, path, feats, coords)`` for every feature file.

    ``prefetch == 0``: plain synchronous loads (the historical driver).
    ``prefetch > 0``: a loader thread runs ahead through the dist
    boundary's bounded :class:`~gigapath_tpu.dist.boundary.MemoryChannel`
    — at most ``prefetch`` slides in flight (credit-based, so a slow
    device backpressures the loader onto the obs bus instead of into
    unbounded host memory), IO overlapped with dispatch either way.
    """
    if prefetch <= 0:
        for idx, path in enumerate(feature_files):
            feats, coords = _load_features(path)
            yield idx, path, feats, coords
        return

    import threading

    from gigapath_tpu.dist.boundary import (
        BoundaryConfig,
        EmbeddingChunk,
        MemoryChannel,
    )

    channel = MemoryChannel(BoundaryConfig(capacity=int(prefetch)),
                            runlog=runlog, name="inference.prefetch")
    failure: list = []

    def load():
        try:
            for idx, path in enumerate(feature_files):
                feats, coords = _load_features(path)
                feats = np.asarray(feats, np.float32)
                # digest=False: an intra-process handoff cannot corrupt,
                # and sha256 over a 10^5-tile slide would tax the hot
                # path the prefetch exists to speed up
                channel.send(EmbeddingChunk.build(
                    os.path.basename(path), idx, 0, feats.shape[0], feats,
                    coords=None if coords is None
                    else np.asarray(coords, np.float32),
                    producer="loader", digest=False,
                ))
        except BaseException as e:  # surfaced on the consuming thread
            failure.append(e)
        finally:
            channel.close()

    loader = threading.Thread(target=load, name="inference-prefetch",
                              daemon=True)
    loader.start()
    served = 0
    try:
        while served < len(feature_files):
            chunk = channel.recv(timeout=1.0)
            if chunk is None:
                if failure:
                    raise failure[0]
                continue
            yield (chunk.chunk_id, feature_files[chunk.chunk_id],
                   chunk.payload, chunk.coords)
            channel.ack(chunk.seq)
            served += 1
        if failure:
            raise failure[0]
    finally:
        channel.close()
        loader.join(timeout=10)


def _coords_or_zeros(feats, coords, runlog, warned: list):
    """The ONE coords-defaulting policy for every inference path: None
    becomes zeros (positional signal collapses to one grid cell), with
    one warning per run (``warned`` is the shared mutable flag)."""
    if coords is None:
        if not warned:
            runlog.echo(
                "Warning: feature files carry no coords; using zeros "
                "(positional signal collapses to one grid cell)"
            )
            warned.append(True)
        coords = np.zeros((feats.shape[0], 2), np.float32)
    return np.asarray(coords, np.float32)


def _results_df(results, output_file, runlog, **run_end_fields):
    """Shared CSV + summary tail of both inference paths. A write
    failure (disk full, permissions) is contained like any other run
    failure: ``error`` event + terminal ``run_end(status='error')``, so
    the anomaly engine's error-triggered flight dump and obs_report's
    terminal-status accounting see it."""
    import pandas as pd

    results_df = pd.DataFrame(results)
    try:
        results_df.to_csv(output_file, index=False)
    except Exception as e:
        fail_run(runlog, "inference.results", e)
        raise
    label_counts = {
        str(k): int(v)
        for k, v in results_df["predicted_label"].value_counts().items()
    }
    runlog.echo(f"Inference results saved to {output_file}")
    runlog.echo(f"Label distribution: {label_counts}")
    runlog.echo(f"Mean confidence: {results_df['confidence'].mean():.4f}")
    runlog.run_end(
        status="ok", n_slides=len(results),
        label_distribution=str(label_counts),
        mean_confidence=float(results_df["confidence"].mean()),
        **run_end_fields,
    )
    return results_df


def _run_inference_bucketed(model, params, feature_files, output_file,
                            runlog, batch_size: int, prefetch: int = 0):
    """Bucketed path: the serving stack's ladder + coalescer + AOT
    executables + content-hash cache, driven synchronously.

    Submits stream one file at a time and full buckets dispatch
    immediately (``step()`` after every submit), so at most
    ``batch_size`` slides per bucket are resident at once — the memory
    shape of the old slide-at-a-time loop, times the batch the
    ``--batch_size`` flag always promised.
    """
    from gigapath_tpu.serve import ServeConfig, SlideService

    def serve_forward(p, embeds, coords, pad_mask):
        return model.apply({"params": p}, embeds, coords,
                           pad_mask=pad_mask, deterministic=True)

    config = ServeConfig.from_env(
        max_batch=int(batch_size),
        # an offline batch driver has no latency bound: the serving
        # default (50 ms) would deadline-dispatch batch-of-1 whenever a
        # feature file takes longer than that to load. Full buckets
        # still dispatch eagerly; partials flush in the final drain().
        max_wait_s=float("inf"),
        feature_dim=int(getattr(model, "input_dim", 1536)),
    )
    identity = (
        f"{getattr(model, 'model_arch', type(model).__name__)}"
        f"|feat{getattr(model, 'feat_layer', '?')}"
        f"|cls{getattr(model, 'n_classes', '?')}"
    )
    service = SlideService(serve_forward, params, config=config, runlog=runlog,
                           identity=identity, name="serve")
    results = []
    warned: list = []
    exact_forward = None  # lazily jitted; only oversized slides pay it
    try:
        with Heartbeat(runlog, name="inference") as heartbeat:
            futures = []
            for idx, path, feats, coords in _feature_stream(
                feature_files, prefetch, runlog
            ):
                slide_id = os.path.basename(path).replace("_features.pt", "")
                feats = np.asarray(feats, np.float32)
                coords = _coords_or_zeros(feats, coords, runlog, warned)
                if feats.shape[0] > service.ladder.rungs[-1]:
                    # larger than the ladder's top rung: submit() would
                    # refuse it and abort the run — serve THIS slide on
                    # the exact-shape fallback (one extra compile, like
                    # the old driver) and keep the batch going
                    runlog.echo(
                        f"Warning: {slide_id} has {feats.shape[0]} tiles, "
                        f"above the ladder's top rung "
                        f"{service.ladder.rungs[-1]}; serving it on the "
                        "exact-shape fallback (raise "
                        "GIGAPATH_SERVE_BUCKET_MAX to bucket it)"
                    )
                    from concurrent.futures import Future

                    if exact_forward is None:
                        exact_forward = jax.jit(
                            lambda p, e, c: model.apply(
                                {"params": p}, e, c, deterministic=True
                            )
                        )
                    logits = np.asarray(exact_forward(
                        params, jnp.asarray(feats[None]),
                        jnp.asarray(coords[None])
                    ), np.float32)[0]
                    fut: Future = Future()
                    fut.set_result(logits)
                    futures.append((slide_id, fut))
                else:
                    futures.append((slide_id, service.submit(
                        slide_id, feats, coords
                    )))
                while service.step():  # dispatch any filled buckets now
                    pass
                heartbeat.beat(idx)
            # flush the partial batches — one step() per beat, not one
            # opaque drain(): each flush can pay a fresh AOT compile
            # plus a full padded forward, and a beat-less multi-minute
            # drain would trip the stall detector on a healthy run
            drained = len(feature_files)
            while True:
                n = service.step(drain=True)
                if n == 0 and service.queue.pending() == 0:
                    break
                drained += 1
                heartbeat.beat(drained)
            for slide_id, fut in futures:
                logits = np.asarray(fut.result(), np.float32)
                probs = np.asarray(jax.nn.softmax(logits, axis=-1))
                pred = int(probs.argmax())
                results.append({
                    "slide_id": slide_id,
                    "predicted_label": pred,
                    "confidence": float(probs[pred]),
                })
    except Exception as e:
        fail_run(runlog, "inference.run_inference", e)
        raise
    finally:
        service.close()
    stats = service.stats()
    return _results_df(
        results, output_file, runlog,
        compile_seconds_total=stats["compile_seconds_total"],
        dispatches=stats["dispatches"],
        buckets_used=stats["buckets_used"],
        cache_hits=stats["cache"]["hits"],
        unexpected_retraces=stats["unexpected_retraces"],
        ledger_path=service.ledger.path,
    )


def _run_inference_streaming(model, params, feature_files, output_file,
                             runlog, chunk_tiles: int, prefetch: int = 0):
    """Streaming chunked-prefill path (``--stream``): every slide folds
    through chunk-shaped stage executables via the serve streaming
    submitter — slide-encoder attention temporaries stay O(chunk)
    regardless of tile count, and slides of EVERY length share the same
    compiled programs (the exact-shape path compiles per distinct N;
    the bucket path pads to a rung). ``--prefetch`` composes: the
    loader thread runs ahead through the bounded dist-boundary channel
    while resident slides fold. The bucketed and exact paths remain the
    fallbacks and the parity oracles."""
    from gigapath_tpu.serve.streaming import (
        head_streaming_submitter,
        streaming_head_logits,
    )

    submitter = head_streaming_submitter(
        model, params, chunk_tiles=chunk_tiles or None, runlog=runlog,
    )
    metrics = get_metrics(runlog)
    slide_walls = metrics.histogram("inference.slide_wall_s")
    results = []
    warned: list = []
    try:
        with Heartbeat(runlog, name="inference") as heartbeat:
            for idx, path, feats, coords in _feature_stream(
                feature_files, prefetch, runlog
            ):
                slide_id = os.path.basename(path).replace("_features.pt", "")
                feats = np.asarray(feats, np.float32)
                coords = _coords_or_zeros(feats, coords, runlog, warned)
                with span("slide", runlog, fence=True) as sp:
                    session = submitter.open(slide_id, feats.shape[0])
                    for i, (a, b) in enumerate(session.session.tile_bounds):
                        session.feed(i, feats[a:b], coords[a:b])
                    logits = sp.fence(streaming_head_logits(
                        model, params, session.result()
                    ))
                probs = np.asarray(jax.nn.softmax(
                    jnp.asarray(logits), axis=-1))[0]
                pred = int(probs.argmax())
                results.append({
                    "slide_id": slide_id,
                    "predicted_label": pred,
                    "confidence": float(probs[pred]),
                })
                runlog.step(
                    idx, wall_s=sp.dur_s, synced=True,
                    n_tiles=int(feats.shape[0]),
                    n_chunks=session.session.n_chunks,
                    predicted_label=pred, confidence=float(probs[pred]),
                )
                if sp.dur_s is not None:
                    slide_walls.observe(sp.dur_s)
                metrics.maybe_flush()
                heartbeat.beat(idx)
    except Exception as e:
        fail_run(runlog, "inference.run_inference", e)
        raise
    return _results_df(
        results, output_file, runlog,
        streamed_slides=submitter.served,
        chunk_tiles=submitter.chunk_tiles,
    )


def run_inference(
    model,
    params,
    feature_dir: str,
    output_file: str,
    *,
    use_buckets: bool = True,
    batch_size: int = 16,
    prefetch: int = 0,
    stream: bool = False,
    stream_chunk: int = 0,
):
    """Classify every ``*_features.pt`` in ``feature_dir``
    (reference ``run_inference:37-79``). ``use_buckets`` routes through
    the serving stack (module docstring); False is the exact-shape
    oracle path. ``prefetch > 0`` overlaps feature IO with dispatch
    through the dist boundary's bounded channel (at most that many
    slides in flight — backpressure instead of unbounded run-ahead)."""
    feature_files = sorted(glob.glob(os.path.join(feature_dir, "*_features.pt")))
    if not feature_files:
        console(f"No feature files found in {feature_dir}")
        return None

    runlog = get_run_log(
        "inference", out_dir=os.path.dirname(os.path.abspath(output_file)),
        config={"feature_dir": feature_dir, "output_file": output_file,
                "n_slides": len(feature_files), "buckets": bool(use_buckets),
                "batch_size": int(batch_size), "prefetch": int(prefetch),
                "stream": bool(stream)},
    )
    if stream:
        return _run_inference_streaming(
            model, params, feature_files, output_file, runlog,
            chunk_tiles=int(stream_chunk), prefetch=prefetch,
        )
    if use_buckets:
        return _run_inference_bucketed(
            model, params, feature_files, output_file, runlog, batch_size,
            prefetch=prefetch,
        )

    @jax.jit
    def forward(params, embeds, coords):
        return model.apply({"params": params}, embeds, coords, deterministic=True)

    # variable-length slides -> one compile per distinct N; the watchdog
    # turns that invisible first-slide pause into compile events and the
    # ledger records each new shape's compiled cost/memory profile
    ledger = get_ledger(runlog)
    watchdog = CompileWatchdog("inference.forward", runlog, ledger=ledger)
    instrumented_forward = watchdog.wrap(forward)
    # typed metrics (obs/metrics.py): per-slide wall histogram; the
    # final snapshot flushes inside run_end via the registry's closer
    metrics = get_metrics(runlog)
    slide_walls = metrics.histogram("inference.slide_wall_s")

    results = []
    warned: list = []
    try:
        with Heartbeat(runlog, name="inference") as heartbeat:
            for idx, path in enumerate(feature_files):
                # fenced span (GL008): dur_s covers load + dispatch +
                # device execution for this slide
                with span("slide", runlog, fence=True) as sp:
                    feats, coords = _load_features(path)
                    coords = _coords_or_zeros(feats, coords, runlog,
                                              warned)[None]
                    feats = feats[None]  # [1, N, D]
                    logits = np.asarray(
                        sp.fence(instrumented_forward(
                            params, jnp.asarray(feats), jnp.asarray(coords)
                        )),
                        np.float32,
                    )
                probs = np.asarray(jax.nn.softmax(logits, axis=-1))[0]
                pred = int(probs.argmax())
                results.append(
                    {
                        "slide_id": os.path.basename(path).replace("_features.pt", ""),
                        "predicted_label": pred,
                        "confidence": float(probs[pred]),
                    }
                )
                runlog.step(
                    idx, wall_s=sp.dur_s, synced=True,
                    n_tiles=int(feats.shape[1]), predicted_label=pred,
                    confidence=float(probs[pred]),
                )
                if sp.dur_s is not None:
                    slide_walls.observe(sp.dur_s)
                metrics.maybe_flush()
                heartbeat.beat(idx)
    except Exception as e:
        fail_run(runlog, "inference.run_inference", e)
        raise

    return _results_df(
        results, output_file, runlog,
        compile_seconds_total=watchdog.compile_seconds_total(),
        ledger_path=ledger.path,
    )


def main(argv=None):
    from gigapath_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(description="GigaPath model inference")
    parser.add_argument("--model_path", type=str, required=True)
    parser.add_argument("--feature_dir", type=str, required=True)
    parser.add_argument("--output_file", type=str, default="predictions.csv")
    parser.add_argument(
        "--batch_size", type=int, default=16,
        help="Slides coalesced per padded bucket batch (the serving "
        "stack's max_batch; ignored under --no-buckets, where slides "
        "are processed one at a time)",
    )
    parser.add_argument(
        "--no-buckets", dest="no_buckets", action="store_true",
        help="Exact-shape fallback/oracle path: one jit compile per "
        "distinct tile count, no batching, no padding",
    )
    parser.add_argument(
        "--stream", action="store_true",
        help="Streaming chunked prefill: fold each slide through "
        "chunk-shaped stage executables (O(chunk) attention "
        "temporaries, one compiled program set for every slide "
        "length). Defaults ON when GIGAPATH_CHUNKED_PREFILL is set.",
    )
    parser.add_argument(
        "--stream-chunk", type=int, default=0,
        help="Tiles per streaming-prefill chunk (0 = the "
        "GIGAPATH_PREFILL_CHUNK host flag, default 2048)",
    )
    parser.add_argument(
        "--prefetch", type=int, default=0,
        help="Overlap feature-file IO with dispatch: a loader thread "
        "runs at most this many slides ahead through the dist "
        "boundary's bounded channel (0 = synchronous loads; bucketed "
        "path only)",
    )
    parser.add_argument("--num_classes", type=int, default=2)
    parser.add_argument("--model_arch", type=str, default="gigapath_slide_enc12l768d")
    args = parser.parse_args(argv)
    model, params = load_model(
        args.model_path, n_classes=args.num_classes, model_arch=args.model_arch
    )
    # GIGAPATH_CHUNKED_PREFILL makes streaming the default route
    from gigapath_tpu.models.streaming_encoder import chunked_prefill_default

    stream = bool(args.stream or chunked_prefill_default())
    return run_inference(
        model, params, args.feature_dir, args.output_file,
        use_buckets=not args.no_buckets, batch_size=args.batch_size,
        prefetch=args.prefetch, stream=stream,
        stream_chunk=args.stream_chunk,
    )


if __name__ == "__main__":
    main()
