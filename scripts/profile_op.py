#!/usr/bin/env python
"""XLA-op-time attribution for the full 5-branch dilated op + summary.

Host wall-clock includes whatever else the host was doing; the 'XLA Ops'
line sums only the device ops of this process, a host-independent (if
DMA-stall-blind) cost measure.
"""

import glob
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    import argparse

    from gigapath_tpu.models.longnet_config import flagship_geometry
    from gigapath_tpu.ops import dilated_attention as da

    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default="bhld", choices=["bhld", "fused"])
    ap.add_argument(
        "--flags", default="",
        help="comma list of GIGAPATH_* env flags set for the trace, e.g. "
        "PIPELINED_ATTN,PACK_DIRECT,STREAM_FUSION,PIPELINED_BWD",
    )
    ap.add_argument("--n", type=int, default=10241)
    ap.add_argument(
        "--json", default="",
        help="write the kernel/glue decomposition JSON here (also emitted "
        "as a run_end obs event, stream AB_DILATED_OBS.jsonl) — the "
        "before/after glue table of the epilogue decision is two "
        "invocations of this flag",
    )
    args = ap.parse_args()
    for flag in args.flags.split(","):
        if flag:
            os.environ[f"GIGAPATH_{flag.strip()}"] = "1"

    G = flagship_geometry()
    H, Dh = G["heads"], G["head_dim"]
    SEGS, RATIOS = G["segment_lengths"], G["dilated_ratios"]
    L = args.n
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, L, H, Dh)), jnp.bfloat16) for _ in range(3)
    )
    op = (
        da.dilated_attention_fused
        if args.variant == "fused"
        else da.dilated_attention_bhld
    )

    @jax.jit
    def step(x, k, v):
        out = op(x, k, v, SEGS, RATIOS)
        return x + (out.astype(jnp.float32).sum() * 1e-30).astype(x.dtype)

    x = step(q, k, v)
    x.block_until_ready()
    iters = 10
    tmp = tempfile.mkdtemp(prefix="opprof_")
    with jax.profiler.trace(tmp):
        for _ in range(iters):
            x = step(x, k, v)
        x.block_until_ready()

    from gigapath_tpu.utils.profiling import xla_op_totals

    totals = xla_op_totals(tmp)["ops"]
    kernels = sum(
        us for name, us in totals.items()
        if "custom" in name or "step." in name.split(" = ")[0]
    )
    glue = sum(totals.values()) - kernels
    total = sum(totals.values())
    print(f"total XLA-op time: {total / iters / 1e3:.3f} ms/op over {iters} iters")
    print(f"  pallas kernels:  {kernels / iters / 1e3:.3f} ms/op")
    print(f"  XLA glue:        {glue / iters / 1e3:.3f} ms/op")
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:12]
    for name, us in top:
        print(f"  {us / iters:9.1f} us  {100 * us / total:5.1f}%  {name[:100]}")

    if args.json:
        import json

        payload = {
            "metric": "profile_op",
            "variant": args.variant,
            "flags": sorted(f for f in args.flags.split(",") if f),
            "n": args.n,
            "iters": iters,
            "total_ms_per_op": round(total / iters / 1e3, 3),
            "kernels_ms_per_op": round(kernels / iters / 1e3, 3),
            "glue_ms_per_op": round(glue / iters / 1e3, 3),
            "top_ops_us_per_op": {
                name[:160]: round(us / iters, 1) for name, us in top
            },
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        from gigapath_tpu.obs import get_run_log

        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        log = get_run_log(
            "profile_op", config={"argv": sys.argv[1:]},
            path=os.path.join(repo_root, "AB_DILATED_OBS.jsonl"), echo=False,
        )
        log.run_end(status="ok", **payload)  # run_end closes the log
        print(json.dumps(payload))


if __name__ == "__main__":
    main()
