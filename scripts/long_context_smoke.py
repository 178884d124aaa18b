"""Long-context smoke: flagship slide-encoder forward at PANDA-scale N.

The reference fine-tunes with ``max_tiles: 1000000`` (panda.yaml) on an
80 GB A100 via fp16 + flash + batch 1; the single-chip TPU counterpart
(SURVEY §7.3) leans on bf16 + the Pallas dilated kernels + XLA remat. This
script drives the full 12-layer model at a caller-chosen N and reports
wall-clock and achieved token throughput, one JSON line per N — the
machine-checkable evidence that the long-context path holds up beyond the
bench default of 10k tokens.

Usage: python scripts/long_context_smoke.py [N ...]   (default: 65536 131072)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def run(n: int) -> dict:
    from gigapath_tpu.models import slide_encoder

    model, params = slide_encoder.create_model(
        "", "gigapath_slide_enc12l768d", in_chans=1536, dtype=jnp.bfloat16
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, n, 1536)), jnp.bfloat16)
    coords = jnp.asarray(rng.uniform(0, 250000, (1, n, 2)), jnp.float32)

    fn = jax.jit(lambda p, x, c: model.apply({"params": p}, x, c)[0])
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(params, x, coords))
    compile_s = time.perf_counter() - t0
    assert np.isfinite(np.asarray(out, np.float32)).all()

    # per-iter time via the chained-fori_loop recipe (utils/timing.py)
    from gigapath_tpu.utils.timing import chained_seconds_per_iter

    def step(x, params, coords):
        out = model.apply({"params": params}, x, coords)[0]
        return x + (out.sum() * 1e-30).astype(x.dtype)

    step_s, _ = chained_seconds_per_iter(
        step, x, args=(params, coords), iters_low=2, iters_high=6
    )
    from gigapath_tpu.utils.profiling import compiled_memory

    mem = compiled_memory(
        lambda p, x, c: model.apply({"params": p}, x, c)[0], params, x, coords
    )
    peak_hbm_gb = None
    # compiled_memory sanitizes unavailable fields to None (obs.ledger)
    if mem and mem.get("temp_bytes") is not None and mem.get("argument_bytes") is not None:
        peak_hbm_gb = round(
            (mem["temp_bytes"] + mem["argument_bytes"]) / 2**30, 2
        )
    return {
        "metric": "long_context_forward",
        "n_tokens": n,
        "step_seconds": round(step_s, 3),
        "tokens_per_sec": round(n / step_s, 1),
        "compile_seconds": round(compile_s, 1),
        "peak_hbm_gb": peak_hbm_gb,
    }


def run_sharded(n: int, n_devices: int = 8) -> dict:
    """The documented beyond-single-chip recipe: dilated attention sharded
    over a ``seq`` mesh axis via shard_map, with K/V gathered per oversized
    branch (``_gather_kv_seq_parallel``, reference ``gather_kv:55-74``).

    Runs on the virtual CPU mesh (XLA_FLAGS=--xla_force_host_platform_device_count=8)
    at a reduced width — the sharding structure is what a v5e-8 would run;
    single-chip HBM tops out between 256k and 512k tokens (measured:
    512k = 16.6 GB vs 15.75 GB available, OOM).
    """
    jax.config.update("jax_platforms", "cpu")
    assert jax.device_count() >= n_devices, (jax.device_count(), n_devices)
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from gigapath_tpu.ops.dilated_attention import dilated_attention

    H, Dh = 4, 16  # reduced width: the *sequence* scale is what's under test
    local = n // n_devices
    # power-of-2 schedule: oversized segments must divide into whole shards
    sls = [1024, 32768, local * 2, n]
    drs = [1, 2, 4, 8]
    mesh = Mesh(np.array(jax.devices()[:n_devices]), ("seq",))
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, n, H, Dh)), jnp.float32) for _ in range(3)
    )
    fn = shard_map(
        lambda q, k, v: dilated_attention(
            q, k, v, sls, drs, seq_axis_name="seq", seq_axis_size=n_devices
        ),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
        # required whenever the Pallas tier runs inside this region (TPU):
        # vma checking cannot see through pallas_call
        check_vma=False,
    )
    t0 = time.perf_counter()
    out = jax.block_until_ready(jax.jit(fn)(q, k, v))
    wall = time.perf_counter() - t0
    assert np.isfinite(np.asarray(out, np.float32)).all()
    return {
        "metric": "long_context_seq_sharded",
        "n_tokens": n,
        "n_devices": n_devices,
        "branches": list(zip(sls, drs)),
        "compile_plus_step_seconds": round(wall, 1),
        "finite": True,
    }


def run_stream(n: int, chunk: int = 2048) -> dict:
    """Streaming-chunked-prefill vs dense-assemble A/B at the attention
    level (reduced width, like ``run_sharded`` — the SEQUENCE scale is
    what's under test): the ``adopt_chunked_prefill`` decision table.

    Memory rows come from XLA memory analysis of the COMPILED programs
    (AOT, nothing executed — the same ledger numbers the tier-1 pins
    check): the dense variant is the whole ``dilated_attention`` forward
    at ``[1, n, H, D]``; the streaming variant is the largest per-chunk
    fold executable (``fold_pair`` at the widest branch), whose arg/temp
    bytes are O(chunk) by construction. Walltime runs both variants at
    ``n`` on a chip and at ``min(n, 4096)`` elsewhere (a laptop cannot
    execute the 16k dense logits tensor just to time it); parity is
    checked at the walltime geometry. ``perf_history.py ingest
    --prefill`` folds the JSON under ``prefill|stream`` (non-chip runs
    land stale, provenance only)."""
    import functools

    import jax.numpy as jnp

    from gigapath_tpu.ops.dilated_attention import dilated_attention
    from gigapath_tpu.ops.streaming_prefill import (
        assemble_dense_fallback,
        chunk_bounds,
        fold_pair,
        streaming_dilated_attention,
    )
    from gigapath_tpu.utils.profiling import compiled_memory

    H, Dh = 4, 16
    drs = [1, 2, 4]
    sls = [min(1024, n), min(4096, n), n]
    backend = jax.default_backend()
    on_chip = backend in ("tpu", "gpu")

    def make_qkv(m):
        rng = np.random.default_rng(0)
        return tuple(
            jnp.asarray(rng.normal(size=(1, m, H, Dh)), jnp.float32)
            for _ in range(3)
        )

    def mb(x):
        return None if x is None else round(x / 2**20, 3)

    # --- memory: AOT analysis at the full geometry, nothing executed ---
    q, k, v = make_qkv(n)
    dense_fn = lambda q, k, v: dilated_attention(q, k, v, sls, drs)  # noqa: E731
    dense_mem = compiled_memory(dense_fn, q, k, v) or {}
    cq = min(chunk, n)
    qb, kb, vb = (x[:, :cq] for x in (q, k, v))
    acc_out = jnp.zeros((1, cq, H, Dh), jnp.float32)
    acc_lse = jnp.zeros((1, H, cq), jnp.float32)
    widest = functools.partial(fold_pair, segment_len=min(sls[-1], n),
                               ratio=drs[-1])
    stream_mem = compiled_memory(
        widest, acc_out, acc_lse, qb, kb, vb,
        jnp.int32(0), jnp.int32(0), jnp.int32(n),
    ) or {}

    def peak(mem):
        vals = [mem.get("argument_bytes"), mem.get("temp_bytes"),
                mem.get("output_bytes")]
        return None if any(v is None for v in vals) else sum(vals)

    # --- walltime + parity at an executable geometry ---
    wall_n = n if on_chip else min(n, 4096)
    wall_sls = [min(s, wall_n) for s in sls]
    qw, kw, vw = make_qkv(wall_n)
    wall_bounds = chunk_bounds(wall_n, min(chunk, wall_n))
    dense_jit = jax.jit(
        lambda q, k, v: dilated_attention(q, k, v, wall_sls, drs)
    )
    dense_out = jax.block_until_ready(dense_jit(qw, kw, vw))  # compile
    t0 = time.perf_counter()
    dense_out = jax.block_until_ready(dense_jit(qw, kw, vw))
    dense_wall = time.perf_counter() - t0

    def stream_once():
        blocks = streaming_dilated_attention(
            [qw[:, a:b] for a, b in wall_bounds],
            [kw[:, a:b] for a, b in wall_bounds],
            [vw[:, a:b] for a, b in wall_bounds],
            wall_bounds, wall_sls, drs,
        )
        jax.block_until_ready(blocks)
        return blocks
    blocks = stream_once()  # compile the stage executables
    t0 = time.perf_counter()
    blocks = stream_once()
    stream_wall = time.perf_counter() - t0
    parity = float(jnp.abs(
        assemble_dense_fallback(blocks) - dense_out.astype(jnp.float32)
    ).max())

    dense_peak, stream_peak = peak(dense_mem), peak(stream_mem)
    temp_ratio = peak_ratio = None
    if dense_mem.get("temp_bytes") and stream_mem.get("temp_bytes") is not None:
        temp_ratio = round(stream_mem["temp_bytes"] / dense_mem["temp_bytes"], 4)
    if dense_peak and stream_peak is not None:
        peak_ratio = round(stream_peak / dense_peak, 4)
    payload = {
        "metric": "prefill_stream",
        "backend": backend,
        "n_tokens": n,
        "chunk": chunk,
        "branches": list(zip(sls, drs)),
        "wall_n_tokens": wall_n,
        "dense_arg_mb": mb(dense_mem.get("argument_bytes")),
        "dense_temp_mb": mb(dense_mem.get("temp_bytes")),
        "dense_peak_mb": mb(dense_peak),
        "stream_arg_mb": mb(stream_mem.get("argument_bytes")),
        "stream_temp_mb": mb(stream_mem.get("temp_bytes")),
        "stream_peak_mb": mb(stream_peak),
        "temp_ratio": temp_ratio,
        "peak_ratio": peak_ratio,
        "dense_wall_s": round(dense_wall, 4),
        "stream_wall_s": round(stream_wall, 4),
        "parity_max_err": parity,
        "decision": {
            # adopt when the per-chunk fold's peak comes in under 0.6x
            # the dense program AND the math matches the oracle — the
            # acceptance thresholds, machine-checkable like
            # adopt_stream_fusion / adopt_ring_attn
            "adopt_chunked_prefill": bool(
                peak_ratio is not None and peak_ratio < 0.6
                and parity < 1e-5
            ),
            "peak_ratio": peak_ratio,
            "parity_max_err": parity,
        },
    }
    return payload


def main():
    args = [a for a in sys.argv[1:]]
    json_out = None
    if "--json" in args:
        i = args.index("--json")
        json_out = args[i + 1]
        del args[i:i + 2]
    def emit(payload, n, many):
        # one payload per file (perf_history ingest json.load's it):
        # with several token counts, suffix each path so no row is
        # silently overwritten
        line = json.dumps(payload)
        print(line)
        if json_out:
            path = json_out
            if many:
                root, ext = os.path.splitext(json_out)
                path = f"{root}.n{n}{ext or '.json'}"
            with open(path, "w", encoding="utf-8") as fh:
                fh.write(line + "\n")

    if "--stream" in args:
        args.remove("--stream")
        chunk = 2048
        if "--chunk" in args:
            i = args.index("--chunk")
            chunk = int(args[i + 1])
            del args[i:i + 2]
        ns = [int(a) for a in args] or [16384]
        for n in ns:
            emit(run_stream(n, chunk), n, len(ns) > 1)
        return
    if "--sharded" in args:
        args.remove("--sharded")
        ns = [int(a) for a in args] or [1048576]
        for n in ns:
            emit(run_sharded(n), n, len(ns) > 1)
        return
    ns = [int(a) for a in args] or [65536, 131072]
    for n in ns:
        emit(run(n), n, len(ns) > 1)


if __name__ == "__main__":
    main()
