"""Headline benchmark: PANDA-scale slide embedding + ViT-G tile encoding.

Two workloads, one JSON line:

1. **Slide encoder** (gigapath_slide_enc12l768d, 86M params, 5-branch
   dilated attention) forward + train step over N=10240 tile embeddings —
   the "PANDA slide-embed wallclock" north star from BASELINE.md — in bf16
   under jit, reported as tokens/sec.
2. **Tile encoder** (ViT-G/14, 1.13B params) batch-128 bf16 jitted forward
   — the literal tiles/sec/chip north-star metric, mirroring the
   reference's inference recipe (``gigapath/pipeline.py:141-161``: batches
   of 128 tiles under fp16 autocast).

Timing: iterations are chained inside one jitted fori_loop with a forced
data dependency and two loop counts are differenced (see
gigapath_tpu/utils/timing.py).

vs_baseline: the reference publishes no numbers (BASELINE.md), so the
denominator is an analytic estimate of the reference stack on its stated
hardware (1x A100, fp16 autocast, flash-attn) running the *same workload*,
with the FLOP count computed exactly from the flagship config below
(12 layers x [qkv/out + FFN GEMMs] + the 5-branch dilated-attention
schedule + patch embed ~= 3.0 TFLOP per 10240-token slide). Per branch,
head group p attends only its own dilation phase's tokens, so each of the
H heads runs m = ceil(g/r) queries x m keys per segment: branch cost =
4*E*L*m/r FLOPs, NOT 4*E*L*m (each token is queried by H/r heads, not H).
A100 fp16 at a generous 35% end-to-end MFU => ~109 TFLOPS =>
~27.6 ms/slide => ~3.7e5 tokens/s. Generous because the reference's
dilated gather/scatter/recombination runs in eager torch between
flash-attn calls. The baseline value + version ride in the JSON line so
rounds computed under different denominators stay comparable
(``baseline_version`` history: v1 = per-branch cost 4*E*L*m, v2 = the
corrected 4*E*L*m/r used since round 2).

``mfu`` / ``tile_mfu`` ground the numbers in hardware terms: measured
FLOP/s over the chip's peak bf16 FLOP/s. Denominator bases differ by
design: ``mfu`` always uses the analytic slide workload count (the same
count the baseline is computed from, so the two stay comparable);
``tile_mfu`` prefers compiled-HLO cost analysis and falls back to the
analytic ViT count.

A run measures on a TPU or fails: JAX is initialised once, in this process
(no child interpreter — a chip belongs to one process at a time), the JSON
line names the device it ran on (``platform`` / ``device_kind`` /
``device_count``), and any failure — no ``tpu`` platform, a ``device_kind``
missing from the peaks table, an exception in any phase — exits nonzero
with no JSON line and no ``value``.

Prints exactly one JSON line on stdout on success. An obs telemetry stream
(run_start/step/run_end events, gigapath_tpu.obs schema) rides stderr and
appends to BENCH_OBS.jsonl.
"""

import json
import os
import sys
import time

import jax.numpy as jnp
import numpy as np

# Append-only telemetry stream (gigapath_tpu.obs schema): every bench run
# emits run_start/step/run_end events here, queryable long after the
# one-line stdout contract scrolled away.
OBS_STREAM = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_OBS.jsonl")

# Per-run perf ledger (gigapath_tpu.obs.ledger): the compiled artifact's
# cost/memory analysis + jaxpr fingerprints for the bench workloads,
# diffable across commits with scripts/ledger_diff.py. The path rides the
# JSON line ("ledger") so every published number carries a pointer to its
# compiled-artifact profile.
BENCH_LEDGER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_LEDGER.json")

N = 10240
TILE_BATCH = 128  # reference pipeline.py:141

# flagship gigapath_slide_enc12l768d geometry, from the single source of
# truth (reference slide_encoder.py:137-154)
from gigapath_tpu.models.longnet_config import flagship_geometry

_G = flagship_geometry()
DEPTH, E, FFN, IN_CHANS = _G["depth"], _G["embed_dim"], _G["ffn_dim"], _G["in_chans"]
SEGS, RATIOS = _G["segment_lengths"], _G["dilated_ratios"]
A100_FP16_FLOPS = 312e12
A100_MFU = 0.35
BASELINE_VERSION = "analytic-a100-v2-perbranch"

# peak dense bf16 FLOP/s by TPU generation (public spec sheets), matched
# as a substring of ``device_kind``; a kind that matches no key is an error
_PEAK_BY_KIND = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6": 918e12,
}


def chip_peak_flops(device_kind: str) -> float:
    kind = device_kind.lower()
    for key, val in _PEAK_BY_KIND.items():
        if key in kind:
            return val
    raise KeyError(
        f"no peak FLOP/s on record for device_kind {device_kind!r}: add it "
        "to bench._PEAK_BY_KIND with its source"
    )


def workload_flops(n_tokens: int) -> float:
    """Analytic forward FLOPs of one slide at n_tokens (+cls) tokens."""
    L = n_tokens + 1  # cls token
    gemms = DEPTH * (4 * 2 * L * E * E + 2 * 2 * L * E * FFN)
    # per branch: every head attends m x m per segment on 1/r of the tokens
    # => 4 * E * L * m / r (see module docstring)
    windows = sum(
        -(-min(sl, L) // r) / r for sl, r in zip(SEGS, RATIOS)
    )
    attn = DEPTH * 4 * L * E * windows
    patch = 2 * L * IN_CHANS * E
    return float(gemms + attn + patch)


A100_REF_TOKENS_PER_SEC = N / (workload_flops(N) / (A100_FP16_FLOPS * A100_MFU))


def tile_workload_flops(model) -> float:
    """Analytic forward FLOPs of ONE tile through the ViT-G/14 encoder.

    SwiGLU MLP: packed fc1 is [d -> hidden] where hidden already counts
    both gate+value mats, and fc2 is [hidden/2 -> d]: per token
    2*d*hidden + 2*d*hidden/2 = 3*d*hidden FLOPs. Used both as the
    compiled-HLO fallback for tile_mfu and as the workload count behind
    the analytic A100 tile baseline (same treatment the slide encoder's
    baseline got): BASELINE.md's north star is tiles/sec vs 1xA100
    running the reference recipe (``gigapath/pipeline.py:141-161``)."""
    L = model.num_patches + 1
    hidden = model.mlp_hidden_dim
    d = model.embed_dim
    p = model.patch_size
    per_layer = 4 * 2 * L * d * d + 3 * L * d * hidden + 4 * L * L * d
    return float(model.depth * per_layer + 2 * L * 3 * p * p * d)


def bench_tile_encoder(peak_flops: float, ledger=None):
    """Batch-128 bf16 ViT-G/14 forward: (tiles/sec, mfu)."""
    import jax

    from gigapath_tpu.models.tile_encoder import gigapath_tile_enc
    from gigapath_tpu.obs.ledger import NullLedger
    from gigapath_tpu.utils.timing import chained_seconds_per_iter

    ledger = ledger if ledger is not None else NullLedger()

    model = gigapath_tile_enc(dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    x0 = jnp.zeros((1, 224, 224, 3), jnp.float32)
    # init on-device under jit: a host-side 4.5 GB fp32 init + transfer is
    # both slow and needless for a throughput measurement
    params = jax.jit(lambda r: model.init(r, x0)["params"])(rng)
    imgs = jnp.asarray(
        np.random.default_rng(0).normal(size=(TILE_BATCH, 224, 224, 3)),
        jnp.bfloat16,
    )

    def step(x, params):
        out = model.apply({"params": params}, x)  # [B, 1536]
        return x + (out.sum() * 1e-30).astype(x.dtype)

    sec_per_iter, _ = chained_seconds_per_iter(
        step, imgs, args=(params,), iters_low=2, iters_high=8
    )
    tiles_per_sec = TILE_BATCH / sec_per_iter

    # params as an ARG: closed-over params become 4.5 GB of inline constants
    # in the lowered HLO
    entry = ledger.capture_full(
        "tile_forward", lambda x, p: model.apply({"params": p}, x), imgs, params
    )
    flops = ((entry or {}).get("cost") or {}).get("flops")
    mfu_source = "compiled_hlo"
    if not flops or not np.isfinite(flops):
        print(
            "bench: tile_mfu falling back to analytic FLOP count "
            f"(compiled_flops returned {flops!r})",
            file=sys.stderr,
        )
        flops = TILE_BATCH * tile_workload_flops(model)
        mfu_source = "analytic"
    mfu = (flops / sec_per_iter) / peak_flops
    # analytic A100 denominator for the tiles/sec north star, mirroring
    # the slide encoder's baseline treatment (same MFU assumption)
    baseline_tiles_per_sec = (A100_FP16_FLOPS * A100_MFU) / tile_workload_flops(model)
    return tiles_per_sec, mfu, baseline_tiles_per_sec, mfu_source


def run_bench(runlog=None, ledger=None) -> dict:
    import jax

    from gigapath_tpu.models import slide_encoder
    from gigapath_tpu.obs import NullRunLog, span
    from gigapath_tpu.obs.ledger import NullLedger
    from gigapath_tpu.utils.timing import chained_seconds_per_iter

    runlog = runlog if runlog is not None else NullRunLog(driver="bench")
    ledger = ledger if ledger is not None else NullLedger()

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"bench.py measures on a TPU; JAX found platform {platform!r} "
            f"({devices[0].device_kind}). A number from another backend is "
            "not a result."
        )
    peak = chip_peak_flops(devices[0].device_kind)
    runlog.event(
        "heartbeat", phase="backend_up", device_kind=devices[0].device_kind,
        device_count=len(devices), peak_flops=peak,
    )

    model, params = slide_encoder.create_model(
        "", "gigapath_slide_enc12l768d", in_chans=1536, dtype=jnp.bfloat16
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, N, 1536)), jnp.bfloat16)
    coords = jnp.asarray(rng.uniform(0, 250000, (1, N, 2)), jnp.float32)

    def step(x, params, coords):
        out = model.apply({"params": params}, x, coords)[0]  # [1, 768]
        # feed a (numerically negligible) function of the output back into
        # the input so the loop body cannot be hoisted out of fori_loop
        return x + (out.sum() * 1e-30).astype(x.dtype)

    with span("slide_forward", runlog):
        sec_per_iter, overhead = chained_seconds_per_iter(step, x, args=(params, coords))
    tokens_per_sec = N / sec_per_iter
    mfu = (workload_flops(N) / sec_per_iter) / peak
    runlog.step(0, wall_s=sec_per_iter, synced=True, workload="slide_forward",
                tokens_per_sec=tokens_per_sec, mfu=mfu)

    # compiled-artifact profile of the headline workload: cost analysis
    # (FLOPs) + memory analysis (peak HBM) + jaxpr fingerprint, ledgered
    # under "slide_forward" and surfaced as headline JSON fields
    entry = ledger.capture_full(
        "slide_forward", lambda x, p: model.apply({"params": p}, x, coords)[0],
        x, params,
    )
    mem = (entry or {}).get("memory")
    # the ledger already sanitizes non-finite analysis values to None, so
    # nothing here can leak a NaN into the contractual JSON line
    slide_flops = ((entry or {}).get("cost") or {}).get("flops")
    peak_hbm_gb = None
    if mem and mem.get("temp_bytes") is not None and mem.get("argument_bytes") is not None:
        peak_hbm_gb = round((mem["temp_bytes"] + mem["argument_bytes"]) / 2**30, 2)

    # train-step variant (fwd+bwd, the reference's actual hot loop —
    # finetune/training.py:223-282): grad of a scalar readout wrt params
    def train_step(x, params, coords):
        def loss_fn(p):
            return model.apply({"params": p}, x, coords)[0].astype(jnp.float32).var()

        grads = jax.grad(loss_fn)(params)
        # depend on EVERY grad leaf — depending on one would let XLA DCE all
        # other weight-gradient matmuls and overstate the throughput
        total = sum(g.sum().astype(jnp.float32) for g in jax.tree.leaves(grads))
        return x + (total * 1e-30).astype(x.dtype)

    with span("slide_train", runlog):
        sec_train, _ = chained_seconds_per_iter(
            train_step, x, args=(params, coords), iters_low=2, iters_high=8
        )
    train_tokens_per_sec = N / sec_train
    runlog.step(1, wall_s=sec_train, synced=True, workload="slide_train",
                tokens_per_sec=train_tokens_per_sec)

    with span("tile_forward", runlog):
        tile_tiles_per_sec, tile_mfu, tile_baseline, tile_mfu_source = (
            bench_tile_encoder(peak, ledger=ledger)
        )
    tile_vs_baseline = round(tile_tiles_per_sec / tile_baseline, 3)
    runlog.step(2, wall_s=TILE_BATCH / tile_tiles_per_sec, synced=True,
                workload="tile_forward", tiles_per_sec=tile_tiles_per_sec,
                mfu=tile_mfu)
    tile_tiles_per_sec = round(tile_tiles_per_sec, 1)
    tile_mfu = round(tile_mfu, 3)
    tile_baseline = round(tile_baseline, 1)

    return {
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "metric": "slide_embed_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tokens_per_sec / A100_REF_TOKENS_PER_SEC, 3),
        "train_tokens_per_sec": round(train_tokens_per_sec, 1),
        "mfu": round(mfu, 3),
        "peak_hbm_gb": peak_hbm_gb,
        "compiled_flops": slide_flops,
        "ledger": ledger.path,
        "tile_tiles_per_sec": tile_tiles_per_sec,
        "tile_mfu": tile_mfu,
        "tile_mfu_source": tile_mfu_source,
        "tile_vs_baseline": tile_vs_baseline,
        "tile_baseline_tiles_per_sec": tile_baseline,
        "baseline_tokens_per_sec": round(A100_REF_TOKENS_PER_SEC, 1),
        "baseline_version": BASELINE_VERSION,
    }


def main() -> int:
    """Print exactly one JSON line and return 0, or fail.

    Any failure — no TPU, an unknown ``device_kind``, an exception in any
    workload — is logged to the obs stream and re-raised: the process exits
    nonzero and prints no JSON line, so an unmeasured run can never be
    recorded as a number.
    """
    from gigapath_tpu.obs import get_run_log
    from gigapath_tpu.obs.ledger import PerfLedger
    from gigapath_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # telemetry stream rides stderr + BENCH_OBS.jsonl: stdout stays the
    # one contractual JSON line. probe_devices=False — run_bench names the
    # device itself (and refuses any platform but tpu).
    runlog = get_run_log(
        "bench", path=OBS_STREAM, echo_stream=sys.stderr, probe_devices=False,
        config={"n_tokens": N, "tile_batch": TILE_BATCH,
                "baseline_version": BASELINE_VERSION},
    )
    # the ledger always CAPTURES (compiled_flops/peak_hbm_gb are bench
    # measurements, not telemetry); GIGAPATH_OBS=0 only suppresses the
    # artifact file + events ("ledger" stays null in the JSON line).
    # autowrite=False: the file lands only on SUCCESS, so a failed run
    # cannot overwrite the last good run's ledger with a partial one.
    recording = getattr(runlog, "path", None) is not None
    ledger = PerfLedger(runlog, path=BENCH_LEDGER if recording else None,
                        autowrite=False)
    try:
        payload = run_bench(runlog, ledger=ledger)
    except Exception as e:
        runlog.error("bench.run_bench", e)
        runlog.run_end(status="error", error=f"{type(e).__name__}: {e}")
        raise
    payload["snapshot_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    ledger.write()  # success: publish the run's compiled-artifact ledger
    runlog.run_end(status="ok", **payload)
    print(json.dumps(payload))
    return 0


if __name__ == "__main__":
    sys.exit(main())
