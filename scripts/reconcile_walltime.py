#!/usr/bin/env python
"""Reconcile XLA-op-time attribution with wall-clock, once, in one process.

Every round-3/4 perf delta was decided on XLA-op-time attribution
(scripts/profile_op.py), which is contention-independent but DMA-stall
blind; the round-3 task of reconciling it against wall-clock never ran.
This script runs BOTH disciplines on the headline op (5-branch fused
dilated attention at N=10241, bf16) interleaved in a single process:

  - wall: the chained-fori differencing recipe (utils/timing.py), three
    interleaved repetitions, min taken (host noise only ever adds
    time);
  - op-time: jax.profiler trace over the same jitted step, this process's
    device ops only, divided by iteration count.

Prints one JSON line and (with --out) writes RECONCILE.json. A wall/op
ratio near 1 validates the op-time discipline; a large residual means
DMA stalls or dispatch gaps that op-time cannot see — either way the
number is finally on record with contention conditions stated.
"""

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10241)
    ap.add_argument("--iters", type=int, default=24)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument(
        "--variant", default="fused", choices=["fused", "bhld", "pipe"],
    )
    args = ap.parse_args()

    from gigapath_tpu.models.longnet_config import flagship_geometry
    from gigapath_tpu.ops import dilated_attention as da
    from gigapath_tpu.utils.profiling import xla_op_totals
    from gigapath_tpu.utils.timing import chained_seconds_per_iter

    G = flagship_geometry()
    H, Dh = G["heads"], G["head_dim"]
    SEGS, RATIOS = list(G["segment_lengths"]), list(G["dilated_ratios"])
    L = args.n
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, L, H, Dh)), jnp.bfloat16) for _ in range(3)
    )

    if args.variant == "pipe":
        os.environ["GIGAPATH_PIPELINED_ATTN"] = "1"
    op = da.dilated_attention_bhld if args.variant == "bhld" else da.dilated_attention_fused

    def step(x, k, v):
        out = op(x, k, v, SEGS, RATIOS)
        return x + (out.astype(jnp.float32).sum() * 1e-30).astype(x.dtype)

    # ---- wall-clock: interleaved reps of the chained-fori recipe ----
    walls = []
    for _ in range(args.reps):
        sec, _ = chained_seconds_per_iter(
            step, q, args=(k, v), iters_low=2, iters_high=2 + args.iters
        )
        walls.append(sec)

    # ---- op-time: profiler trace over the same jitted step ----
    jstep = jax.jit(step)
    x = jax.block_until_ready(jstep(q, k, v))
    iters = args.iters
    tmp = tempfile.mkdtemp(prefix="reconcile_")
    with jax.profiler.trace(tmp):
        for _ in range(iters):
            x = jstep(x, k, v)
        jax.block_until_ready(x)
    totals = xla_op_totals(tmp)["ops"]
    op_ms = sum(totals.values()) / iters / 1e3

    wall_ms = min(walls) * 1e3
    result = {
        "metric": "walltime_op_time_reconciliation",
        "variant": args.variant,
        "n_tokens": L,
        "wall_ms_per_op": round(wall_ms, 3),
        "wall_ms_all_reps": [round(w * 1e3, 3) for w in walls],
        "op_time_ms_per_op": round(op_ms, 3),
        "wall_over_op_ratio": round(wall_ms / op_ms, 3) if op_ms else None,
        "conditions": "reps interleaved in one process; "
        "min-of-reps wall vs per-process XLA op totals",
        "device_kind": jax.devices()[0].device_kind,
    }
    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
