#!/usr/bin/env python
"""A/B microbench for the dilated-attention op on the real chip.

Interleaves variants in ONE process (the chip is shared; cross-process
numbers are incomparable) and prints ms per 5-branch op plus effective
TFLOPS on the intrinsic branch FLOPs. Variants via --variants, e.g.::

    python scripts/ab_dilated.py --variants bhld,fused
    python scripts/ab_dilated.py --variants bhld,fused --grad
    python scripts/ab_dilated.py --variants bhld --branches 0,1,2,3,4

``--json PATH`` additionally writes a machine-checkable DECISION TABLE
(per-variant ms/TFLOPS + a decision row where an A/B has one) and emits
the same payload as a ``run_end`` event through the obs runlog (stream
``AB_DILATED_OBS.jsonl`` next to the repo's bench stream). ``fused`` is
the default call: its branches are merged by the packed epilogue wherever
the shapes admit a plan (PR 33; the adoption A/B is in ``PERF.md`` §6).

``gather``/``ring`` A/B the sequence-parallel K/V exchange for oversized
branches on a multi-device slice (a ``seq`` mesh over every visible
device): ``gather`` is the all-gather path, ``ring`` the
GIGAPATH_RING_ATTN ppermute schedule. With both present the JSON gains
the ``adopt_ring_attn`` decision row::

    python scripts/ab_dilated.py --variants gather,ring --n 16384 --json AB_RING.json
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variants", default="bhld,fused")
    ap.add_argument("--branches", default="", help="comma indices; empty = all 5")
    ap.add_argument("--n", type=int, default=10241)
    ap.add_argument("--iters", type=int, default=24)
    ap.add_argument(
        "--grad", action="store_true",
        help="measure the grad step (fwd+bwd wrt q/k/v) instead of forward",
    )
    ap.add_argument(
        "--pipebwd", action="store_true",
        help="with --grad: also run a GIGAPATH_PIPELINED_BWD twin of each "
        "fused variant",
    )
    ap.add_argument(
        "--json", default="",
        help="write the decision-table JSON here (also emitted as a "
        "run_end obs event)",
    )
    args = ap.parse_args()

    from gigapath_tpu.models.longnet_config import flagship_geometry
    from gigapath_tpu.ops import dilated_attention as da
    from gigapath_tpu.utils.timing import chained_seconds_per_iter

    G = flagship_geometry()
    H, Dh = G["heads"], G["head_dim"]
    SEGS, RATIOS = list(G["segment_lengths"]), list(G["dilated_ratios"])
    if args.branches:
        idx = [int(i) for i in args.branches.split(",")]
        SEGS = [SEGS[i] for i in idx]
        RATIOS = [RATIOS[i] for i in idx]
    L = args.n
    print(f"L={L} H={H} Dh={Dh} branches={list(zip(SEGS, RATIOS))}")

    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, L, H, Dh)), jnp.bfloat16) for _ in range(3)
    )

    # intrinsic branch FLOPs: per branch 4 * E * L * m / r (bench.py docstring)
    E = H * Dh
    flops = sum(4 * E * L * (-(-min(sl, L) // r)) / r for sl, r in zip(SEGS, RATIOS))
    if args.grad:
        # grad step = fwd (2 logits-tile matmuls: s, pv) + bwd (7: dq's
        # s/dp/dq + dkv's s/dp/dv/dk) => 4.5x the forward matmul work
        flops *= 4.5

    def with_env(fn, **env):
        """Scope env flags to one variant's TRACE (flags are read at trace
        time); prior values restored afterward."""

        def wrapped(q, k, v):
            prior = {key: os.environ.get(key) for key in env}
            os.environ.update({k_: str(v_) for k_, v_ in env.items()})
            try:
                return fn(q, k, v)
            finally:
                for key, val in prior.items():
                    if val is None:
                        os.environ.pop(key, None)
                    else:
                        os.environ[key] = val

        return wrapped

    seq_requested = [n for n in ("gather", "ring") if n in args.variants]
    if seq_requested:
        # seq-parallel A/B: shard the token axis over EVERY visible
        # device. L trims to a shard multiple; gathered branches must
        # divide into whole shards (the shard_map path's contract), so
        # incompatible segments are dropped with a note.
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        ndev = len(jax.devices())
        if ndev < 2:
            sys.exit("--variants gather/ring need >= 2 devices")
        Lp = L - (L % ndev)
        lloc = Lp // ndev
        kept = [
            (sl, r) for sl, r in zip(SEGS, RATIOS)
            if sl <= Lp and (sl <= lloc or sl % lloc == 0)
        ]
        dropped = [b for b in zip(SEGS, RATIOS) if b not in kept]
        if dropped:
            print(f"seq A/B: dropping branches {dropped} "
                  f"(segment not local and not a multiple of the "
                  f"{lloc}-token shard)")
        if L != Lp:
            print(f"seq A/B: trimming L {L} -> {Lp} ({ndev} shards)")
            q, k, v = (x[:, :Lp] for x in (q, k, v))
            L = Lp
        SEGS = [sl for sl, _ in kept]
        RATIOS = [r for _, r in kept]
        if not SEGS:
            sys.exit(
                "seq A/B: NO branch survives the shard filter at this "
                f"geometry (Lp={Lp}, {ndev} shards) — raise --n (e.g. "
                "--n 1048576, the 1M operating point) or pick compatible "
                "--branches"
            )
        if not any(sl > lloc for sl in SEGS):
            print(
                "seq A/B: WARNING — no branch exceeds the shard length, so "
                "ring and gather are byte-identical here; pass a "
                "power-of-two --n (e.g. --n 1048576, the 1M operating "
                "point) so an oversized branch survives the filter"
            )
        flops = sum(
            4 * E * L * (-(-min(sl, L) // r)) / r for sl, r in kept
        ) * (4.5 if args.grad else 1.0)
        mesh = Mesh(np.array(jax.devices()), ("seq",))

        def seq_fn(q, k, v):
            return shard_map(
                lambda q, k, v: da.dilated_attention(
                    q, k, v, SEGS, RATIOS,
                    seq_axis_name="seq", seq_axis_size=ndev,
                ),
                mesh=mesh, in_specs=(P(None, "seq"),) * 3,
                out_specs=P(None, "seq"), check_vma=False,
            )(q, k, v)

    fused = lambda q, k, v: da.dilated_attention_fused(q, k, v, SEGS, RATIOS)
    variants = {}
    if "gather" in args.variants:
        variants["gather"] = with_env(seq_fn, GIGAPATH_RING_ATTN=0)
    if "ring" in args.variants:
        # ring-scheduled K/V exchange: ppermute rotation + stored-LSE
        # combine, per-shard memory O(local chunk)
        variants["ring"] = with_env(seq_fn, GIGAPATH_RING_ATTN=1)
    if "bhld" in args.variants:
        variants["bhld"] = lambda q, k, v: da.dilated_attention_bhld(
            q, k, v, SEGS, RATIOS
        )
    if "fused" in args.variants:
        variants["fused"] = fused
    if args.grad and args.pipebwd:
        for name, fn in list(variants.items()):
            if name != "bhld":
                variants[f"{name}_pbwd"] = with_env(
                    fn, GIGAPATH_PIPELINED_BWD=1
                )

    def make_step(fn):
        if args.grad:

            def step(x, k, v):
                def loss(q_, k_, v_):
                    return fn(q_, k_, v_).astype(jnp.float32).sum()

                gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(x, k, v)
                tot = (
                    gq.astype(jnp.float32).sum()
                    + gk.astype(jnp.float32).sum()
                    + gv.astype(jnp.float32).sum()
                )
                return x + (tot * 1e-30).astype(x.dtype)

            return step

        def step(x, k, v):
            out = fn(x, k, v)
            return x + (out.astype(jnp.float32).sum() * 1e-30).astype(x.dtype)

        return step

    # two interleaved rounds per variant to defeat chip drift
    results = {name: [] for name in variants}
    for _round in range(2):
        for name, fn in variants.items():
            sec, _ = chained_seconds_per_iter(
                make_step(fn), q, args=(k, v), iters_low=2, iters_high=2 + args.iters
            )
            results[name].append(sec)
    table = {}
    for name, secs in results.items():
        best = min(secs)
        table[name] = {
            "ms_per_op": round(best * 1e3, 3),
            "tflops": round(flops / best / 1e12, 1),
            "rounds_ms": [round(s * 1e3, 3) for s in secs],
        }
        print(
            f"{name:8s} {best * 1e3:8.3f} ms/op   {flops / best / 1e12:6.1f} TFLOPS"
            f"   (rounds: {', '.join(f'{s * 1e3:.3f}' for s in secs)})"
        )

    if args.json:
        payload = {
            "metric": "ab_dilated_grad" if args.grad else "ab_dilated_fwd",
            "n": L, "heads": H, "head_dim": Dh,
            "branches": [[int(s), int(r)] for s, r in zip(SEGS, RATIOS)],
            "variants": table,
        }
        # the decision rows the A/Bs exist for: adopt a variant when it
        # beats its baseline by more than measurement noise (>= 3%)
        if "gather" in table and "ring" in table:
            g_ms = table["gather"]["ms_per_op"]
            r_ms = table["ring"]["ms_per_op"]
            payload.setdefault("decision", {}).update({
                "gather_ms": g_ms,
                "ring_ms": r_ms,
                "ring_over_gather": round(r_ms / g_ms, 4),
                "adopt_ring_attn": bool(r_ms <= g_ms * 0.97),
            })
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        # decision provenance rides the obs stream like bench.py's
        # snapshots: one run_end event per A/B invocation
        from gigapath_tpu.obs import get_run_log

        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        log = get_run_log(
            "ab_dilated", config={"argv": sys.argv[1:]},
            path=os.path.join(repo_root, "AB_DILATED_OBS.jsonl"),
            echo=False,
        )
        log.run_end(status="ok", **payload)  # run_end closes the log
        print(json.dumps(payload))


if __name__ == "__main__":
    main()
