#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

One process drives the main path once through the entry points a user
calls, at the full width of the flagship pair (ViT-G/14 tile encoder ->
``gigapath_slide_enc12l768d``), with weights from the entry points' seeded
random init and synthetic inputs from ``--seed``:

- **A** the compiled Pallas kernels against the float32 reference
  (``gigapath_tpu/utils/kernel_checks.py``, shared with
  ``scripts/tpu_selfcheck.py``);
- **B** tiles -> slide embedding through ``gigapath_tpu/pipeline.py``;
- **C** fine-tune steps through ``gigapath_tpu/finetune/main.py``;
- **D** serving through ``gigapath_tpu/serve`` (``SlideService``), then a
  second service answering from the reloaded ``.aot`` artifacts.

stdout carries one JSON object per phase and, as the LAST line, exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
That line is printed only when every phase passed on a ``tpu`` platform. Any
phase failing, or a program that was meant to run the kernels holding zero
``tpu_custom_call``s, ends the run nonzero. Without an accelerator the script
exits nonzero and prints no result.

``--chips 4`` runs ONLY the sharded phase and its one-chip comparison on a
four-chip host (the last line then reports ``"count": 4``).

``--tiny`` is the CPU rehearsal (``JAX_PLATFORMS=cpu python chip_smoke.py
--tiny``): every phase at ``gigapath_slide_enc_tiny`` size with Pallas in
interpret mode. It exists to find wrong paths and arguments before a chip
call and can never end in ``"ok": true``: its last line is ``"ok": false``
with the device that was found, and the exit code is nonzero.
"""

import argparse
import contextlib
import gc
import glob
import io
import json
import math
import os
import re
import shutil
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
EXIT_PHASE_FAILED = 1  # a phase ran and failed: no later phase runs
EXIT_NO_CHIP = 2      # no accelerator: nothing printed on stdout
EXIT_REHEARSAL = 3    # --tiny ran to its end (which is never a success)


_OUT = sys.stdout  # rebound by main(): the stream the JSON lines go to


def emit(obj: dict) -> None:
    print(json.dumps(obj), file=_OUT, flush=True)


class PhaseFailed(RuntimeError):
    """A phase ran and its check did not hold."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


# ---------------------------------------------------------------------------
# measurement plumbing
# ---------------------------------------------------------------------------

class CompileMeter:
    """JAX's own compile counters, summed per phase: backend compiles (a
    persistent-cache hit is counted too, at its retrieval time) and the
    cache's hit/miss events."""

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

    def snapshot(self):
        return (self.compiles, self.seconds, self.cache_hits, self.cache_misses)


def custom_calls(compiled) -> int:
    from gigapath_tpu.obs.ledger import custom_calls_of

    n = custom_calls_of(compiled)
    require(n is not None, "compiled program text unreadable")
    return n


def run_phase(name: str, fn, meter: CompileMeter, device) -> dict:
    """Run one phase, print its line, free what it left on the device.
    An exception propagates: there is no continuing past a failed phase."""
    import jax

    c0, s0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    # the library's console lines go to stderr: stdout is the JSON lines'
    with contextlib.redirect_stdout(sys.stderr):
        info = fn()
    seconds = time.perf_counter() - t0
    c1, s1, h1, m1 = meter.snapshot()
    gc.collect()
    jax.clear_caches()
    stats = device.memory_stats() or {}
    row = {
        "phase": name, "ok": True,
        "seconds": round(seconds, 2),
        "compile_seconds": round(s1 - s0, 2),
        "run_seconds": round(seconds - (s1 - s0), 2),
        "compiles": c1 - c0,
        "compile_cache_hits": h1 - h0,
        "compile_cache_misses": m1 - m0,
        # lifetime peak of the process so far / resident after clean-up
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_in_use_after": stats.get("bytes_in_use"),
        **info,
    }
    emit(row)
    return row


def env_line(args, devices, cache_dir: str, out_dir: str) -> dict:
    import jax
    import jaxlib

    from gigapath_tpu import native

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = None
    return {
        "phase": "env",
        "mode": "tiny-rehearsal" if args.tiny else f"chips={args.chips}",
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu,
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start": len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0,
        "out_dir": out_dir,
        "seed": args.seed,
        # host-side tile ops: the C++ build or its exact numpy fallback
        "native_tile_ops": "built" if native.available() else "numpy-fallback",
    }


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

class Sizes:
    """Everything that differs between the chip run and the CPU rehearsal."""

    def __init__(self, tiny: bool):
        self.tiny = tiny
        if tiny:
            self.tile_arch, self.slide_arch = "vit_tile_enc_test", "gigapath_slide_enc_tiny"
            self.tile_batch, self.tile_batches, self.img = 4, 2, 32
            self.feature_dim, self.latent_dim, self.feat_layer = 32, 32, "1"
            self.slide_tokens = 96
            self.train_tiles, self.test_tiles = [20, 28, 40, 60], [24]
            self.train_buckets = {32, 64}
            self.serve_lengths = [100, 120, 200, 250]
            self.serve_bucket_min = 128
            self.sp_tokens, self.sp_heads, self.sp_head_dim = 256, 4, 8
            self.sp_segments, self.sp_ratios = [32, 64, 128, 256], [1, 2, 2, 4]
            self.spmd_tokens, self.spmd_depth = 64, 2
            self.spmd_segments, self.spmd_ratios = [16, 64], "[1, 2]"
        else:
            self.tile_arch, self.slide_arch = "gigapath_tile_enc", "gigapath_slide_enc12l768d"
            self.tile_batch, self.tile_batches, self.img = 128, 2, 224
            self.feature_dim, self.latent_dim, self.feat_layer = 1536, 768, "11"
            self.slide_tokens = 10240
            # PANDA-like: two slides per ragged bucket (8,192 / 16,384)
            self.train_tiles, self.test_tiles = [5000, 7800, 10000, 12000], [6500]
            self.train_buckets = {8192, 16384}
            self.serve_lengths = [3000, 3500, 11000, 12000]  # buckets 4,096 / 16,384
            self.serve_bucket_min = 1024
            from gigapath_tpu.models.longnet_config import flagship_geometry

            g = flagship_geometry()
            self.sp_tokens, self.sp_heads, self.sp_head_dim = 65536, g["heads"], g["head_dim"]
            # the arch's schedule at max_wsi_size=131072: [1024, 4096, 16384,
            # 65536, 262144]. Sequence parallelism segments each shard on
            # its own, so it equals the one-chip op only where every local
            # segment divides the shard; the default 262144 schedule's 5,792
            # does not divide 16,384 (ops/dilated_attention.py warns).
            from gigapath_tpu.models.slide_encoder import get_optimal_segment_length

            self.sp_segments = get_optimal_segment_length(131072, 256)
            self.sp_ratios = g["dilated_ratios"]
            self.spmd_tokens, self.spmd_depth = 16384, 12
            self.spmd_segments = self.spmd_ratios = None  # the arch's own


# ---------------------------------------------------------------------------
# phase A: kernels against the float32 reference
# ---------------------------------------------------------------------------

def phase_a(args, sizes: Sizes, out_dir: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gigapath_tpu.ops import dilated_attention as da
    from gigapath_tpu.ops.pallas_streaming import pallas_pair_partial
    from gigapath_tpu.utils import kernel_checks
    from gigapath_tpu.utils.timing import chained_seconds_per_iter

    geom = kernel_checks.TINY if sizes.tiny else kernel_checks.flagship()
    rows = kernel_checks.run_kernel_checks(geom, seed=args.seed)
    with open(os.path.join(out_dir, "phase_a_checks.json"), "w") as f:
        json.dump(rows, f, indent=1)
    failed = [r for r in rows if not r["ok"]]
    require(not failed, f"kernel checks outside tolerance: {failed}")

    # kernels present in the compiled programs
    H, Dh, N = geom.heads, geom.head_dim, geom.bench_len
    SEGS, RATIOS = list(geom.segment_lengths), list(geom.dilated_ratios)
    rng = np.random.default_rng(args.seed)
    q, k, v = (jnp.asarray(rng.normal(size=(1, N, H, Dh)), jnp.bfloat16)
               for _ in range(3))

    def fused_loss(x, y, z, vl):
        o = da.dilated_attention_fused(x, y, z, SEGS, RATIOS, valid_len=vl)
        return (o.astype(jnp.float32) ** 2).mean()

    fused = jax.jit(lambda x, y, z: da.dilated_attention_fused(x, y, z, SEGS, RATIOS))
    C = geom.fold_chunk
    qc = q[:, :C]
    calls = {
        "fused_fwd": custom_calls(fused.lower(q, k, v).compile()),
        "fused_grad_traced_valid_len": custom_calls(
            jax.jit(jax.value_and_grad(fused_loss, argnums=(0, 1, 2)))
            .lower(q, k, v, jnp.asarray([N - 64], jnp.int32)).compile()
        ),
        "bhld_fwd": custom_calls(
            jax.jit(lambda x, y, z: da.dilated_attention_bhld(
                x, y, z, SEGS, RATIOS, use_pallas=True
            )).lower(q, k, v).compile()
        ),
        "pair_partial": custom_calls(
            jax.jit(lambda x: pallas_pair_partial(
                x, x, x, jnp.int32(0), jnp.int32(0),
                segment_len=int(SEGS[-1]), ratio=int(RATIOS[-1]),
            )).lower(qc).compile()
        ),
    }
    if not sizes.tiny:
        zero = [name for name, n in calls.items() if n == 0]
        require(not zero, f"programs compiled without any tpu_custom_call: {zero}")

    # utils/timing.py: does plain host timing around block_until_ready
    # agree with the chained-fori_loop recipe on this device?
    def chain_step(carry, y, z):
        return carry + (fused(carry, y, z).sum() * 1e-30).astype(carry.dtype)

    chained, overhead = chained_seconds_per_iter(
        chain_step, q, args=(k, v), iters_low=1 if sizes.tiny else 2,
        iters_high=2 if sizes.tiny else 12, repeats=1 if sizes.tiny else 2,
    )
    jax.block_until_ready(fused(q, k, v))
    walls = []
    for _ in range(2 if sizes.tiny else 20):
        t0 = time.perf_counter()
        jax.block_until_ready(fused(q, k, v))
        walls.append(time.perf_counter() - t0)
    host = float(np.median(walls))

    worst = max(rows, key=lambda r: r["max_abs_err"] / r["atol"])
    return {
        "checks": len(rows),
        "reference": "jnp tier, float32 inputs, default_matmul_precision('highest')",
        "max_abs_err": max(r["max_abs_err"] for r in rows),
        "worst_vs_tolerance": {k_: worst[k_] for k_ in ("name", "max_abs_err", "atol")},
        "tpu_custom_calls": calls,
        "timing_fused_fwd": {
            "tokens": N,
            "chained_fori_loop_seconds_per_iter": chained,
            "chained_fixed_overhead_seconds": overhead,
            "host_block_until_ready_median_seconds": host,
            "host_over_chained": host / chained,
        },
        "rows_file": os.path.join(out_dir, "phase_a_checks.json"),
    }


# ---------------------------------------------------------------------------
# phase B: tiles -> slide embedding (gigapath_tpu/pipeline.py)
# ---------------------------------------------------------------------------

def phase_b(args, sizes: Sizes, out_dir: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gigapath_tpu import pipeline

    (tile_model, tile_params), (slide_model, slide_params) = (
        pipeline.load_tile_slide_encoder(
            tile_arch=sizes.tile_arch, slide_arch=sizes.slide_arch
        )
    )
    rng = np.random.default_rng(args.seed)
    B, S = sizes.tile_batch, sizes.img
    encode = pipeline.tile_encode_fn(tile_model)
    imgs_aval = jax.ShapeDtypeStruct((B, S, S, 3), jnp.bfloat16)
    tile_calls = custom_calls(encode.lower(tile_params, imgs_aval).compile())
    if not sizes.tiny:  # the rehearsal's heads of 8 ride the jnp tier
        require(tile_calls > 0, "tile encoder compiled without any tpu_custom_call")
    tile_embeds = []
    for _ in range(sizes.tile_batches):
        imgs = jnp.asarray(rng.normal(size=(B, S, S, 3)), jnp.bfloat16)
        out = np.asarray(encode(tile_params, imgs), np.float32)
        require(out.shape == (B, sizes.feature_dim), f"tile embeds {out.shape}")
        require(bool(np.isfinite(out).all()), "non-finite tile embeddings")
        tile_embeds.append(out)
    tile_embeds = np.concatenate(tile_embeds)
    n_tile_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(tile_params))
    del tile_params, encode, imgs
    gc.collect()

    # the slide stage at N synthetic embeddings: the encoded tiles lead,
    # seeded normal rows (same scale) fill up to N
    N, D = sizes.slide_tokens, sizes.feature_dim
    feats = rng.normal(size=(N, D)).astype(np.float32) * float(tile_embeds.std())
    feats[: tile_embeds.shape[0]] = tile_embeds
    coords = rng.uniform(0, 250000, (N, 2)).astype(np.float32)
    forward = pipeline.slide_forward_fn(slide_model)
    slide_calls = custom_calls(forward.lower(
        slide_params,
        jax.ShapeDtypeStruct((1, N, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, N, 2), jnp.float32),
    ).compile())
    if not sizes.tiny:
        require(slide_calls > 0, "slide forward compiled without any tpu_custom_call")
    outputs = pipeline.run_inference_with_slide_encoder(
        feats, coords, slide_model, slide_params
    )
    last = outputs["last_layer_embed"]
    embed_dim = slide_model.embed_dim
    require(last.shape == (1, embed_dim), f"slide embedding {last.shape}")
    for name, emb in outputs.items():
        require(bool(np.isfinite(emb).all()), f"non-finite {name}")
    return {
        "tile_arch": sizes.tile_arch, "tile_params": n_tile_params,
        "tile_batches": sizes.tile_batches, "tile_batch": B,
        "tile_embeds_shape": list(tile_embeds.shape),
        "tile_tpu_custom_calls": tile_calls,
        "slide_arch": sizes.slide_arch, "slide_tokens": N,
        "slide_embedding_shape": list(last.shape),
        "slide_layers_returned": len(outputs) - 1,
        "slide_tpu_custom_calls": slide_calls,
        "slide_embedding_abs_mean": float(np.abs(last).mean()),
    }


# ---------------------------------------------------------------------------
# phase C: fine-tune steps (finetune/main.py -> training.py)
# ---------------------------------------------------------------------------

def phase_c(args, sizes: Sizes, out_dir: str) -> dict:
    import pandas as pd

    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from panda_subset_bench import make_dataset

    from gigapath_tpu.finetune.main import main as finetune_main

    base = os.path.join(out_dir, "finetune")
    tiles = sizes.train_tiles + sizes.test_tiles
    csv_path, yaml_path, root = make_dataset(
        base, tile_counts=tiles, feature_dim=sizes.feature_dim, seed=args.seed
    )
    # fixed split: every train bucket is met twice, the test pass once
    n_train = len(sizes.train_tiles)
    split_dir = os.path.join(base, "splits")
    os.makedirs(split_dir, exist_ok=True)
    ids = [f"s{i}.svs" for i in range(len(tiles))]
    for name, members in (("train", ids[:n_train]), ("val", []), ("test", ids[n_train:])):
        pd.DataFrame({"slide_id": members}).to_csv(
            os.path.join(split_dir, f"{name}_0.csv")
        )
    save_dir = os.path.join(base, "out")
    console = _ConsoleTee(sys.stderr)
    with contextlib.redirect_stdout(console):
        finetune_main([
            "--task_cfg_path", yaml_path, "--dataset_csv", csv_path,
            "--root_path", root, "--pre_split_dir", split_dir,
            "--save_dir", save_dir,
            "--model_arch", sizes.slide_arch,
            "--input_dim", str(sizes.feature_dim),
            "--latent_dim", str(sizes.latent_dim),
            "--feat_layer", sizes.feat_layer,
            # reference recipe (run_panda.sh:14-20), gc 1 so that every
            # step is an optimizer step
            "--blr", "0.002", "--layer_decay", "0.95", "--optim_wd", "0.05",
            "--dropout", "0.1", "--drop_path_rate", "0.0", "--gc", "1",
            "--warmup_epochs", "1", "--epochs", "1",
            "--model_select", "last_epoch", "--lr_scheduler", "cosine",
            "--folds", "1", "--val_r", "0", "--max_wsi_size", "250000",
            "--seed", str(args.seed), "--report_to", "jsonl",
        ])

    logs = glob.glob(os.path.join(save_dir, "**", "fold_0", "obs", "*.jsonl"), recursive=True)
    require(len(logs) == 1, f"expected one finetune run log, found {logs}")
    with open(logs[0]) as f:
        events = [json.loads(line) for line in f if line.strip()]
    end = [e for e in events if e.get("kind") == "run_end"]
    require(bool(end) and end[-1].get("status") == "ok", f"finetune run_end: {end}")
    compiles = [e for e in events if e.get("kind") == "compile"
                and e.get("fn") == "train_step"]
    retraces = [e for e in compiles if e.get("unexpected")]
    require(not retraces, f"train_step recompiled after a bucket's first step: {retraces}")
    buckets = sorted({str(e.get("key")) for e in compiles})
    require(
        len(compiles) == len(sizes.train_buckets),
        f"train_step compiled {len(compiles)}x for buckets {buckets}, "
        f"expected one per bucket of {sorted(sizes.train_buckets)}",
    )
    # the harness's own ledger: traced pallas_call count per bucket, and
    # the compiled program's tpu_custom_calls for the first bucket
    ledgers = glob.glob(os.path.join(os.path.dirname(logs[0]), "*.ledger.json"))
    require(len(ledgers) == 1, f"expected one finetune ledger, found {ledgers}")
    with open(ledgers[0]) as f:
        entries = json.load(f)["entries"]
    entries = entries.values() if isinstance(entries, dict) else entries
    steps = [e for e in entries if e.get("name") == "train_step"]
    require(len(steps) == len(sizes.train_buckets), f"ledger train_step entries: {len(steps)}")
    traced = {str(e.get("key")): e["jaxpr"]["primitives"].get("pallas_call", 0) for e in steps}
    compiled_calls = {str(e.get("key")): e["tpu_custom_calls"] for e in steps
                      if "tpu_custom_calls" in e}
    temp_bytes = {str(e.get("key")): (e.get("memory") or {}).get("temp_bytes") for e in steps
                  if "memory" in e}
    if not sizes.tiny:
        require(all(n > 0 for n in traced.values()),
                f"train step traced without kernels: {traced}")
        require(
            bool(compiled_calls) and all(n for n in compiled_calls.values()),
            f"train step compiled without any tpu_custom_call: {compiled_calls}",
        )
    summary = pd.read_csv(glob.glob(os.path.join(save_dir, "**", "summary.csv"), recursive=True)[0])
    test_loss = float(summary["test_loss"].iloc[0])
    require(math.isfinite(test_loss), f"test loss {test_loss}")
    # the epoch's loss is the mean of every step's loss (one device-side
    # sum): a single non-finite step would make it non-finite
    epoch_losses = re.findall(r"Epoch: \d+, Loss: (\S+), Epoch time", console.text())
    require(len(epoch_losses) == 1, f"epoch loss lines: {epoch_losses}")
    train_loss = float(epoch_losses[0])
    require(math.isfinite(train_loss), f"train loss {train_loss}")
    # what is worth keeping is small (run log, ledger, summary): the data
    # set and the checkpoint are hundreds of MB at full width
    shutil.rmtree(root)
    for ckpt in glob.glob(os.path.join(save_dir, "**", "checkpoint"), recursive=True):
        shutil.rmtree(ckpt)
    return {
        "arch": sizes.slide_arch, "optimizer_steps": n_train,
        "train_tiles": sizes.train_tiles, "buckets": buckets,
        "train_step_compiles": len(compiles),
        "first_call_seconds": {str(e.get("key")): e.get("seconds") for e in compiles},
        "compiles_after_first_step_of_a_bucket": len(retraces),
        "train_loss_epoch_mean": train_loss, "test_loss": test_loss,
        "pallas_calls_traced": traced,
        "tpu_custom_calls": compiled_calls,
        "train_step_temp_bytes": temp_bytes,
    }


class _ConsoleTee(io.TextIOBase):
    """Pass the harness's console through to ``stream`` and keep a copy."""

    def __init__(self, stream):
        self._stream = stream
        self._buf = io.StringIO()

    def write(self, text):
        self._stream.write(text)
        return self._buf.write(text)

    def flush(self):
        self._stream.flush()

    def text(self) -> str:
        return self._buf.getvalue()


# ---------------------------------------------------------------------------
# phase D: serving (serve/service.py)
# ---------------------------------------------------------------------------

def phase_d(args, sizes: Sizes, out_dir: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gigapath_tpu.inference import load_model
    from gigapath_tpu.serve import ServeConfig, SlideService
    from gigapath_tpu.serve.buckets import assemble_batch

    serve_dir = os.path.join(out_dir, "serve")
    artifact_dir = os.path.join(serve_dir, "artifacts")
    model, params = load_model(
        "", input_dim=sizes.feature_dim, latent_dim=sizes.latent_dim,
        feat_layer=sizes.feat_layer, n_classes=6, model_arch=sizes.slide_arch,
    )

    def forward(p, embeds, coords, pad_mask):
        return model.apply({"params": p}, embeds, coords,
                           pad_mask=pad_mask, deterministic=True)

    config = ServeConfig(
        max_batch=2, max_wait_s=float("inf"), artifact_dir=artifact_dir,
        bucket_min=sizes.serve_bucket_min, feature_dim=sizes.feature_dim,
    )
    identity = f"chip_smoke|{sizes.slide_arch}"
    rng = np.random.default_rng(args.seed)
    slides = [
        (f"slide{i}", rng.normal(size=(n, sizes.feature_dim)).astype(np.float32),
         rng.uniform(0, 250000, (n, 2)).astype(np.float32))
        for i, n in enumerate(sizes.serve_lengths)
    ]

    def serve_all(tag: str):
        svc = SlideService(forward, params, config=config, identity=identity,
                           out_dir=os.path.join(serve_dir, tag))
        try:
            futs = [svc.submit(sid, feats, coords) for sid, feats, coords in slides]
            svc.drain()
            answers = [np.asarray(f.result(timeout=0)) for f in futs]
            stats = svc.stats()
            calls = {f"{k[0]}x{k[1]}": custom_calls(exe)
                     for k, exe in svc.aot._executables.items()}
            ladder = svc.ladder
        finally:
            svc.close()
        return answers, stats, calls, ladder

    cold, cold_stats, calls, ladder = serve_all("cold")
    n_buckets = cold_stats["buckets_used"]
    require(n_buckets >= 2, f"requests fell into {n_buckets} bucket(s)")
    require(cold_stats["compiled_executables"] == n_buckets, f"cold stats {cold_stats}")
    require(cold_stats["unexpected_retraces"] == 0, f"cold stats {cold_stats}")
    if not sizes.tiny:
        require(all(n > 0 for n in calls.values()),
                f"serve bucket compiled without any tpu_custom_call: {calls}")

    # each answer against a direct model.apply on the same padded slide
    direct = jax.jit(forward)
    max_err = 0.0
    for (sid, feats, coords), answer in zip(slides, cold):
        bucket_n = ladder.bucket_for(feats.shape[0])
        e, c, m = assemble_batch([(feats, coords)], bucket_n, 1, sizes.feature_dim)
        ref = np.asarray(direct(params, jnp.asarray(e), jnp.asarray(c), jnp.asarray(m)))[0]
        require(bool(np.isfinite(answer).all()), f"non-finite answer for {sid}")
        max_err = max(max_err, float(np.abs(answer.astype(np.float32) - ref.astype(np.float32)).max()))
    atol = 2e-2  # bf16 activations; same program, batch row aside
    require(max_err <= atol, f"serve vs direct apply: max_abs_err {max_err} > {atol}")

    # a second service on the same artifact_dir: reloaded executables only
    warm, warm_stats, _, _ = serve_all("warm")
    require(warm_stats["compiled_executables"] == 0, f"warm stats {warm_stats}")
    require(warm_stats["loaded_executables"] == n_buckets, f"warm stats {warm_stats}")
    reload_err = max(float(np.abs(a - b).max()) for a, b in zip(cold, warm))
    require(reload_err == 0.0, f"reloaded executables answer differently: {reload_err}")
    artifact_bytes = sum(
        os.path.getsize(os.path.join(artifact_dir, f)) for f in os.listdir(artifact_dir)
    )
    shutil.rmtree(artifact_dir)  # tens of MB each; they have answered
    return {
        "arch": sizes.slide_arch, "requests": sizes.serve_lengths,
        "buckets": sorted(cold_stats["per_bucket_dispatches"]),
        "dispatches": cold_stats["dispatches"],
        "cold_compiled_executables": cold_stats["compiled_executables"],
        "cold_compile_seconds": round(cold_stats["compile_seconds_total"], 2),
        "tpu_custom_calls": calls,
        "max_abs_err_vs_direct_apply": max_err, "atol": atol,
        "warm_compiled_executables": warm_stats["compiled_executables"],
        "warm_loaded_executables": warm_stats["loaded_executables"],
        "warm_vs_cold_max_abs_err": reload_err,
        "artifact_dir": artifact_dir, "artifact_bytes": artifact_bytes,
    }


# ---------------------------------------------------------------------------
# the four-chip phase (--chips 4)
# ---------------------------------------------------------------------------

def _collectives(text: str) -> dict:
    names = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    counts = {n: text.count(f" {n}(") + text.count(f" {n}-start(") for n in names}
    return {n: c for n, c in counts.items() if c}


def _per_device_bytes(compiled) -> dict:
    from gigapath_tpu.obs.ledger import memory_analysis_of

    mem = memory_analysis_of(compiled) or {}
    return {k: mem.get(k) for k in ("argument_bytes", "output_bytes", "temp_bytes")}


def phase_seq_parallel(args, sizes: Sizes, devices) -> dict:
    """The documented sequence-parallel recipe on the ``seq`` mesh of every
    chip, against the same op at the same N on one of those chips."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from gigapath_tpu.ops.dilated_attention import dilated_attention

    W = len(devices)
    N, H, Dh = sizes.sp_tokens, sizes.sp_heads, sizes.sp_head_dim
    segs, ratios = list(sizes.sp_segments), list(sizes.sp_ratios)
    mesh = Mesh(np.array(devices), ("seq",))
    rng = np.random.default_rng(args.seed)
    host = [rng.normal(size=(1, N, H, Dh)).astype(np.float32) for _ in range(3)]
    sharded_in = NamedSharding(mesh, P(None, "seq"))
    qs, ks, vs = (jax.device_put(jnp.asarray(x, jnp.bfloat16), sharded_in) for x in host)

    sp = jax.jit(shard_map(
        lambda q, k, v: dilated_attention(
            q, k, v, segs, ratios, seq_axis_name="seq", seq_axis_size=W
        ),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
        # vma checking cannot see through pallas_call
        check_vma=False,
    ))
    compiled = sp.lower(qs, ks, vs).compile()
    text = compiled.as_text()
    calls = custom_calls(compiled)
    if not sizes.tiny:
        require(calls > 0, "sequence-parallel program holds no tpu_custom_call: "
                "the local branches fell back to the generic path")
    out_sharded = compiled(qs, ks, vs)
    shard_devices = sorted(d.id for d in out_sharded.sharding.device_set)
    out_sp = np.asarray(out_sharded, np.float32)

    one = devices[0]
    q1, k1, v1 = (jax.device_put(jnp.asarray(x, jnp.bfloat16), one) for x in host)
    single = jax.jit(lambda q, k, v: dilated_attention(q, k, v, segs, ratios))
    compiled_1 = single.lower(q1, k1, v1).compile()
    out_1 = np.asarray(compiled_1(q1, k1, v1), np.float32)
    require(bool(np.isfinite(out_sp).all()), "non-finite sequence-parallel output")
    err = float(np.abs(out_sp - out_1).max())
    atol = 3e-2  # bf16 outputs of O(1); gathered branches reduce in another order
    require(err <= atol, f"seq-parallel vs one chip: max_abs_err {err} > {atol}")
    return {
        "tokens": N, "tokens_per_chip": N // W, "heads": H, "head_dim": Dh,
        "segments": segs, "ratios": ratios, "mesh": {"seq": W},
        "tpu_custom_calls": calls, "collectives": _collectives(text),
        "per_device_bytes": _per_device_bytes(compiled),
        "output_on_devices": shard_devices,
        "one_chip_tpu_custom_calls": custom_calls(compiled_1),
        "one_chip_bytes": _per_device_bytes(compiled_1),
        "max_abs_err_vs_one_chip": err, "atol": atol,
    }


def phase_spmd_step(args, sizes: Sizes, devices) -> dict:
    """One ``parallel/spmd.make_train_step`` step with ``apply_shardings``
    on the mesh ``factorize(n, (data, seq, model))`` gives, built as
    ``__graft_entry__._dryrun_multichip_impl`` builds it, against the same
    step on one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gigapath_tpu.models.classification_head import ClassificationHead
    from gigapath_tpu.parallel.mesh import factorize, make_mesh
    from gigapath_tpu.parallel.sharding import apply_shardings
    from gigapath_tpu.parallel.spmd import make_train_step

    axes = factorize(len(devices), ("data", "seq", "model"))
    mesh = make_mesh(axis_sizes=axes, devices=devices)
    B, N, C, D = axes["data"], sizes.spmd_tokens, 6, sizes.feature_dim
    slide_kwargs = dict(dropout=0.0, drop_path_rate=0.0)
    if sizes.spmd_segments is not None:
        slide_kwargs.update(
            embed_dim=sizes.latent_dim, depth=sizes.spmd_depth,
            segment_length=sizes.spmd_segments, dilated_ratio=sizes.spmd_ratios,
        )
    model = ClassificationHead(
        input_dim=D, latent_dim=sizes.latent_dim,
        feat_layer=sizes.feat_layer, n_classes=C,
        model_arch=sizes.slide_arch, dtype=None if sizes.tiny else jnp.bfloat16,
        slide_kwargs=slide_kwargs,
    )
    rng = np.random.default_rng(args.seed)
    host_batch = {
        "images": rng.normal(size=(B, N, D)).astype(np.float32),
        "coords": rng.uniform(0, 250000, (B, N, 2)).astype(np.float32),
        "labels": (np.arange(B) % C).astype(np.int32),
    }
    params = jax.jit(model.init)(
        jax.random.PRNGKey(args.seed), jnp.zeros((1, 4, D), jnp.float32),
        jnp.zeros((1, 4, 2), jnp.float32),
    )["params"]
    host_params = jax.device_get(params)
    del params
    optimizer = optax.adamw(1e-4)
    step = make_train_step(model, optimizer)
    key = jax.random.PRNGKey(1)

    def run(place_params, place_batch):
        p = place_params(host_params)
        opt_state = optimizer.init(p)
        batch = place_batch(host_batch)
        compiled = jax.jit(step).lower(p, opt_state, batch, key).compile()
        _, _, loss = compiled(p, opt_state, batch, key)
        return compiled, float(jax.block_until_ready(loss))

    specs = {"images": P("data", "seq", None), "coords": P("data", "seq", None),
             "labels": P("data")}
    with mesh:
        compiled, loss = run(
            lambda hp: apply_shardings(hp, mesh),
            lambda hb: {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
                        for k, v in hb.items()},
        )
    text = compiled.as_text()
    one = devices[0]
    compiled_1, loss_1 = run(
        lambda hp: jax.device_put(hp, one),
        lambda hb: {k: jax.device_put(v, one) for k, v in hb.items()},
    )
    require(np.isfinite(loss) and np.isfinite(loss_1), f"loss {loss} / {loss_1}")
    atol = 5e-2  # bf16 activations, dropout off, another reduction order
    require(abs(loss - loss_1) <= atol, f"sharded loss {loss} vs one chip {loss_1}")
    return {
        "arch": sizes.slide_arch, "mesh": dict(axes), "batch": B, "tokens": N,
        "loss": loss, "one_chip_loss": loss_1, "atol": atol,
        "tpu_custom_calls": custom_calls(compiled),
        "one_chip_tpu_custom_calls": custom_calls(compiled_1),
        "collectives": _collectives(text),
        "per_device_bytes": _per_device_bytes(compiled),
        "one_chip_bytes": _per_device_bytes(compiled_1),
    }


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the sharded phase and its one-chip comparison")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy size, Pallas in interpret mode; "
                    "never ends in ok:true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out", "chip_smoke"),
                    help="output directory (data set, run logs, .aot artifacts)")
    args = ap.parse_args(argv)
    global _OUT
    _OUT = sys.stdout

    import jax

    from gigapath_tpu.utils.compile_cache import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.tiny:
        print(
            f"chip_smoke: JAX found platform {platform!r} "
            f"({devices[0].device_kind} x{len(devices)}), not a TPU. Nothing "
            "was run and nothing is reported. The CPU rehearsal is "
            "`JAX_PLATFORMS=cpu python chip_smoke.py --tiny`.",
            file=sys.stderr,
        )
        return EXIT_NO_CHIP
    n_devices = len(devices)
    if n_devices < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return EXIT_NO_CHIP
    devices = devices[: args.chips]

    cache_dir = enable_compile_cache()
    if args.tiny:
        # XLA:CPU cannot serialize an executable it loaded from the
        # persistent cache (phase D's .aot persist then fails NOT_FOUND on
        # a second rehearsal): the rehearsal resolves the directory, as every
        # driver does, and compiles afresh
        jax.config.update("jax_enable_compilation_cache", False)
    # this mode's own subdirectory, made anew: a previous run's .aot
    # artifacts, checkpoints and run logs must not answer for this one
    out_dir = os.path.join(args.out, "tiny" if args.tiny else f"chips{args.chips}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    sizes = Sizes(args.tiny)
    meter = CompileMeter()
    emit(env_line(args, devices, cache_dir, out_dir))

    def kernels_phase():
        # the rehearsal runs the kernels through Pallas' interpreter, asked
        # for here and nowhere else; the models of phases B-D then take the
        # branch the CPU's device gate gives them (the jnp tier)
        if not args.tiny:
            return phase_a(args, sizes, out_dir)
        from jax.experimental.pallas import tpu as pltpu

        with pltpu.force_tpu_interpret_mode():
            return phase_a(args, sizes, out_dir)

    t0 = time.perf_counter()
    if args.chips == 4:
        phases = [
            ("seq_parallel", lambda: phase_seq_parallel(args, sizes, devices)),
            ("spmd_train_step", lambda: phase_spmd_step(args, sizes, devices)),
        ]
    else:
        phases = [
            ("A_kernels", kernels_phase),
            ("B_tiles_to_slide", lambda: phase_b(args, sizes, out_dir)),
            ("C_finetune", lambda: phase_c(args, sizes, out_dir)),
            ("D_serving", lambda: phase_d(args, sizes, out_dir)),
        ]
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": n_devices}
    for name, fn in phases:
        try:
            run_phase(name, fn, meter, devices[0])
        except Exception as e:
            # a failed phase ends the run: its error, the verdict, exit 1
            traceback.print_exc()
            emit({"phase": name, "ok": False,
                  "error": f"{type(e).__name__}: {e}"})
            emit({"ok": False, "device": device})
            return EXIT_PHASE_FAILED
    emit({"phase": "total", "seconds": round(time.perf_counter() - t0, 2),
          "compile_seconds": round(meter.seconds, 2), "compiles": meter.compiles,
          "compile_cache_hits": meter.cache_hits,
          "compile_cache_misses": meter.cache_misses})

    if args.tiny or platform != "tpu":
        emit({"ok": False, "device": device})
        return EXIT_REHEARSAL
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
