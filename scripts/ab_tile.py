#!/usr/bin/env python
"""A/B microbench + parity harness for the quantized tile-encoder tier.

Interleaves variants in ONE process (chip drift discipline of
ab_dilated.py) and reports tiles/s per variant plus the drift-vs-oracle
parity numbers from the committed fixture weights. Variants::

    python scripts/ab_tile.py --variants bf16,int8
    python scripts/ab_tile.py --variants bf16,int8,fp8_e4m3,int8+attn
    python scripts/ab_tile.py --variants bf16,int8 --pallas   # Pallas tier

``--json PATH`` writes the machine-checkable DECISION TABLE — the
``adopt_quant_tile`` row (parity gates: cosine >= 0.999 vs the f32
oracle and |PCam-recipe probe delta| <= 0.5 pt; speed gate: int8 >= 3%
faster than bf16) — and emits the same payload as a ``run_end`` obs
event (stream ``AB_TILE_OBS.jsonl``), so the adoption decision is one
command the moment a chip answers::

    python scripts/ab_tile.py --variants bf16,int8 --json AB_TILE.json
    python scripts/perf_history.py ingest --label rNN --tile AB_TILE.json

On CPU the payload carries ``backend: "cpu"`` so the perf-history fold
lands it STALE (keys recorded, trend untouched) and the decision row
reports ``parity_ok`` with ``adopt_quant_tile`` false — CPU walltime
never flips a kernel default.

``--arch``/``--batch`` scale the measured forward (the parity numbers
always come from the committed fixture weights, whatever is measured):
the default fixture arch makes the whole A/B a CPU-runnable smoke; on a
chip, ``--arch gigapath_tile_enc --batch 128`` measures the flagship.
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
    ap.add_argument("--variants", default="bf16,int8",
                    help="comma list: bf16, int8, fp8_e4m3, +attn riders")
    ap.add_argument("--arch", default="",
                    help="measured arch (default: the fixture arch; "
                    "'gigapath_tile_enc' for the flagship on a chip)")
    ap.add_argument("--batch", type=int, default=0,
                    help="measured batch of tiles (default: the fixture)")
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--pallas", action="store_true",
                    help="route the quant variants through the Pallas "
                    "tier (GIGAPATH_QUANT_PALLAS semantics, passed as "
                    "the snapshot value — no env mutation)")
    ap.add_argument("--json", default="",
                    help="write the decision-table JSON here (also "
                    "emitted as a run_end obs event)")
    args = ap.parse_args()

    from gigapath_tpu.models.tile_encoder import init_params
    from gigapath_tpu.quant import parity
    from gigapath_tpu.utils.timing import chained_seconds_per_iter

    variants = [v.strip() for v in args.variants.split(",") if v.strip()]
    params, images, labels = parity.load_fixture()

    # ---- parity: always on the committed fixture weights ----
    report = parity.parity_report(
        params, images, labels,
        variants=tuple(v for v in variants),
        quant_pallas=args.pallas,
    )

    # ---- walltime: fixture by default, --arch/--batch for the chip ----
    if args.arch:
        measured_arch = args.arch
        model_f32 = parity.build_variant(measured_arch, dtype_name="float32")
        m_params = init_params(model_f32)
        batch = args.batch or 8
        rng = np.random.default_rng(0)
        m_images = rng.standard_normal(
            (batch, model_f32.img_size, model_f32.img_size, 3)
        ).astype(np.float32)
    else:
        measured_arch = parity.FIXTURE_ARCH
        m_params = params
        batch = args.batch or len(images)
        m_images = images[:batch]
    x = jnp.asarray(m_images, jnp.bfloat16)

    def make_step(name):
        quant = "" if name == "bf16" else name
        model = parity.build_variant(
            measured_arch, quant=quant, quant_pallas=args.pallas,
            dtype_name="bfloat16",
        )

        # params ride as an ARGUMENT (chained_seconds_per_iter's
        # contract: closed-over constants get serialized into the
        # size-limited remote-compile request — fatal at the 1.13 B
        # flagship); each variant's step is its own function identity,
        # built ONCE so round 2 hits round 1's jit cache entry
        def step(x, params):
            out = model.apply({"params": params}, x)
            return x + (out.astype(jnp.float32).sum() * 1e-30).astype(x.dtype)

        return step

    steps = {name: make_step(name) for name in variants}
    results = {name: [] for name in variants}
    for _round in range(2):  # interleaved rounds defeat chip drift
        for name in variants:
            sec, _ = chained_seconds_per_iter(
                steps[name], x, args=(m_params,),
                iters_low=1, iters_high=1 + args.iters,
            )
            results[name].append(sec)

    timings = {}
    table = {}
    for name, secs in results.items():
        best = min(secs)
        timings[name] = best
        table[name] = {
            "ms_per_batch": round(best * 1e3, 3),
            "tiles_per_sec": round(batch / best, 1),
            "rounds_ms": [round(s * 1e3, 3) for s in secs],
            **report["variants"].get(name, {}),
        }
        print(f"{name:10s} {best * 1e3:9.3f} ms/batch "
              f"{batch / best:10.1f} tiles/s  "
              f"cosine={report['variants'].get(name, {}).get('cosine')}")

    backend = jax.default_backend()
    # the decision row only sees walltime measured ON A CHIP: a CPU
    # timing fluke must never emit adopt_quant_tile=true (the "CPU rows
    # never flip defaults" contract) — CPU runs still report the
    # per-variant ms/tiles_per_sec above as provenance
    decision = parity.decision_table(
        report, timings if backend == "tpu" else None
    )
    payload = {
        "metric": "ab_tile",
        "backend": backend,
        "arch": measured_arch,
        "batch": batch,
        "oracle_probe_acc": report["oracle"]["probe_acc"],
        "variants": table,
        "decision": decision,
    }
    # flat keys for the perf-history tile|quant fold
    for name in variants:
        if name in table:
            key = name.replace("+", "_")
            payload[f"{key}_tiles_per_sec"] = table[name]["tiles_per_sec"]
    payload["cosine_drift"] = decision["cosine_drift"]
    payload["probe_delta_pt"] = decision["probe_delta_pt"]
    if "int8_over_bf16" in decision:
        payload["int8_over_bf16"] = decision["int8_over_bf16"]
    print(f"adopt_quant_tile: {decision['adopt_quant_tile']} "
          f"(parity_ok={decision['parity_ok']}, backend={backend})")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        # decision provenance rides the obs stream (the ab_dilated
        # convention): one run_end event per A/B invocation
        from gigapath_tpu.obs import get_run_log

        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        log = get_run_log(
            "ab_tile", config={"argv": sys.argv[1:]},
            path=os.path.join(repo_root, "AB_TILE_OBS.jsonl"),
            echo=False,
        )
        log.run_end(status="ok", **payload)  # run_end closes the log
        print(json.dumps(payload))


if __name__ == "__main__":
    main()
