#!/usr/bin/env python
"""Build the flagship perf ledger, and keep a local golden of it.

No golden is committed: one that pins jaxpr primitive counts is broken by
every PR on the dilated path and so guards nothing (ROADMAP D5, D14).
``build_golden_ledger`` is what ``tests/test_ledger.py`` reads the
round-6 / ring / fold signals from; ``regenerate`` writes, checks or
refuses to overwrite a golden a builder keeps for themselves
(``tests/goldens/`` is ignored by git).

    JAX_PLATFORMS=cpu python scripts/refresh_ledger.py            # refuse on regressions
    JAX_PLATFORMS=cpu python scripts/refresh_ledger.py --force    # overwrite anyway
    JAX_PLATFORMS=cpu python scripts/refresh_ledger.py --check    # diff only, write nothing
    bash scripts/refresh_ledger.sh [--force|--check]              # the one-command wrapper

The golden ledger is the machine-checkable successor of
PERFORMANCE.md's hand-tabulated round-6 jaxpr op-count table: it pins,
for the flagship workload shapes, the compiled/traced artifact metrics
the perf subsystem captures (``gigapath_tpu.obs.ledger``) —

- the flagship 5-branch dilated-attention schedule (segment lengths
  ``[1024, 5792, 32768, 185363, 1048576]``, ratios ``[1,2,4,8,16]``) at
  B=1, L=512, H=16: jaxpr fingerprints (eqn counts by primitive, the
  transpose/slice/broadcast/reshape/pallas_call columns) for the dense
  fused path and the streaming-fusion epilogue, forward and grad;
- the slide encoder (``gigapath_slide_enc_tiny`` — the flagship
  ``LongNetViT`` topology at smoke scale, CPU-compilable in seconds) at
  N=256: full profile including XLA cost/memory analysis.

Everything is captured deterministically on CPU (``JAX_PLATFORMS=cpu``,
same virtual-device flags as tests/conftest.py), so
``tests/test_ledger.py`` can build it, and ``scripts/ledger_diff.py``
diff two of them, on any machine without a chip.

Refusal contract: if regenerating would REGRESS any golden metric
(``ledger_diff`` verdict not ok), the script refuses to overwrite and
exits 1 — pass ``--force`` to accept the regression knowingly (and say
why in the commit message).
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

# Mirror tests/conftest.py exactly: the ledger must be regenerable from
# the test environment byte-for-byte.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# where a builder's own golden goes; none is committed
GOLDEN_PATH = os.path.join(REPO_ROOT, "tests", "goldens", "LEDGER_flagship.json")

# flagship LongNet schedule (models/longnet_config.py flagship_geometry)
FLAGSHIP_SEGMENTS = [1024, 5792, 32768, 185363, 1048576]
FLAGSHIP_RATIOS = [1, 2, 4, 8, 16]
DILATED_SHAPE = dict(B=1, L=512, H=16, Dh=4)
SLIDE_N, SLIDE_IN_CHANS = 256, 16
# ring-vs-gather seq-parallel fingerprint geometry: a 4-rank seq mesh
# (of the 8 virtual CPU devices), one fused-local branch and one
# gathered branch spanning the whole sub-ring
RING_SHAPE = dict(B=1, L=32, H=4, Dh=8, ndev=4)
RING_SEGMENTS = [8, 32]
RING_RATIOS = [1, 2]
# streaming-fold A/B geometry: one fold step (chunk pair) of the 16k
# smoke — C=2048 token chunks, g=2048, r=2, valid horizon 16384. The
# jnp control materializes dense [H, C, C] masks (jaxpr.mask > 0, fat
# temp bytes); the Pallas tier computes them in-kernel (jaxpr.mask == 0,
# leaner temps) — both sides pinned by tests/test_pallas_streaming.py.
FOLD_SHAPE = dict(B=1, C=2048, H=4, Dh=16)
FOLD_SEGMENT = 2048
FOLD_RATIO = 2
FOLD_VALID = 16384


def build_golden_ledger():
    """-> (PerfLedger, meta dict). Deterministic: fixed shapes, constant
    inputs (profiles depend on shapes/dtypes, never on values)."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")

    from gigapath_tpu.models import slide_encoder
    from gigapath_tpu.obs.ledger import PerfLedger
    from gigapath_tpu.ops.dilated_attention import dilated_attention_fused
    from gigapath_tpu.ops.pallas_dilated import PipelineFlags

    ledger = PerfLedger()

    # -- dilated attention, flagship schedule (fingerprint-only: the
    # interpret-mode pallas kernels trace fast but compile slowly on CPU,
    # and the eqn counts are the round-6 table's signal) ------------------
    B, L, H, Dh = (DILATED_SHAPE[k] for k in ("B", "L", "H", "Dh"))
    q = jnp.ones((B, L, H, Dh), jnp.float32)

    def dilated_fn(flags, grad):
        def f(q, k, v):
            out = dilated_attention_fused(
                q, k, v, FLAGSHIP_SEGMENTS, FLAGSHIP_RATIOS,
                interpret=True, flags=flags,
            )
            return (out.astype(jnp.float32) ** 2).sum()

        return jax.grad(f) if grad else f

    for variant, flags in (
        ("fused", PipelineFlags()),
        ("stream", PipelineFlags(stream_fusion=True)),
    ):
        for pass_name, grad in (("fwd", False), ("grad", True)):
            ledger.capture_fingerprint(
                f"dilated_{variant}_{pass_name}", dilated_fn(flags, grad),
                q, q, q,
            )

    # -- ring vs gather seq parallelism (fingerprint-only): the ring
    # path's jaxpr must carry ZERO full-segment all_gather of K/V — only
    # ppermute (and, when ragged, the one hoisted counts gather) — while
    # the gather path still materializes the K/V all_gathers. Pinned by
    # tests/test_ledger.py::test_golden_covers_the_ring_signal. ----------
    import numpy as onp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from gigapath_tpu.ops.dilated_attention import dilated_attention
    from gigapath_tpu.ops.pallas_dilated import PipelineFlags as PF

    rB, rL, rH, rDh, ndev = (
        RING_SHAPE[k] for k in ("B", "L", "H", "Dh", "ndev")
    )
    rq = jnp.ones((rB, rL, rH, rDh), jnp.float32)
    mesh = Mesh(onp.array(jax.devices()[:ndev]), ("seq",))

    def ring_fn(ring: bool, grad: bool):
        flags = PF(ring_attn=ring)
        sp = shard_map(
            lambda q, k, v: dilated_attention(
                q, k, v, RING_SEGMENTS, RING_RATIOS,
                seq_axis_name="seq", seq_axis_size=ndev, flags=flags,
            ),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"), check_vma=False,
        )

        def f(q, k, v):
            return (sp(q, k, v).astype(jnp.float32) ** 2).sum()

        return jax.grad(f, argnums=(0, 1, 2)) if grad else f

    for variant, ring in (("ring", True), ("ring_gather", False)):
        for pass_name, grad in (("fwd", False), ("grad", True)):
            ledger.capture_fingerprint(
                f"dilated_{variant}_{pass_name}", ring_fn(ring, grad),
                rq, rq, rq,
            )

    # -- streaming fold step, jnp vs Pallas (full profile: the temp-bytes
    # A/B is half the signal; the jaxpr.mask column is the other) --------
    from gigapath_tpu.ops.attention import NEG_INF
    from gigapath_tpu.ops.streaming_prefill import fold_pair

    fB, fC, fH, fDh = (FOLD_SHAPE[k] for k in ("B", "C", "H", "Dh"))
    fq = jnp.ones((fB, fC, fH, fDh), jnp.float32)
    facc_o = jnp.zeros((fB, fC, fH, fDh), jnp.float32)
    facc_l = jnp.full((fB, fH, fC), NEG_INF, jnp.float32)

    def fold_fn(flags, grad):
        def step(acc_o, acc_l, q, k, v):
            return fold_pair(
                acc_o, acc_l, q, k, v,
                jnp.int32(0), jnp.int32(0), jnp.int32(FOLD_VALID),
                segment_len=FOLD_SEGMENT, ratio=FOLD_RATIO, flags=flags,
            )

        if not grad:
            return step

        def loss(acc_o, acc_l, q, k, v):
            out, _ = step(acc_o, acc_l, q, k, v)
            return (out.astype(jnp.float32) ** 2).sum()

        return jax.grad(loss, argnums=(2, 3, 4))

    from jax.experimental.pallas import tpu as pltpu

    # the golden is a CPU artifact: the fold seam passes no `interpret`
    # of its own, so interpret mode is asked for here, explicitly
    with pltpu.force_tpu_interpret_mode():
        for variant, fold_flags in (
            ("jnp", None),
            ("pallas", PipelineFlags(fold_pallas=True)),
        ):
            ledger.capture_full(
                f"stream_fold_{variant}", fold_fn(fold_flags, grad=False),
                facc_o, facc_l, fq, fq, fq,
            )
            ledger.capture_fingerprint(
                f"stream_fold_{variant}_grad", fold_fn(fold_flags, grad=True),
                facc_o, facc_l, fq, fq, fq,
            )

    # -- slide encoder (flagship topology at smoke scale): full profile
    # with XLA cost/memory analysis --------------------------------------
    model, params = slide_encoder.create_model(
        "", "gigapath_slide_enc_tiny", in_chans=SLIDE_IN_CHANS
    )
    x = jnp.ones((1, SLIDE_N, SLIDE_IN_CHANS), jnp.float32)
    coords = (
        jnp.stack(
            jnp.meshgrid(jnp.arange(16.0), jnp.arange(16.0), indexing="ij"),
            axis=-1,
        ).reshape(1, SLIDE_N, 2)
        * 256.0
    )

    def slide_fwd(x, params, coords):
        return model.apply({"params": params}, x, coords)[0]

    ledger.capture_full("slide_enc_tiny_fwd", slide_fwd, x, params, coords)

    meta = {
        "workload": "flagship-cpu-golden",
        "segments": FLAGSHIP_SEGMENTS,
        "ratios": FLAGSHIP_RATIOS,
        "dilated_shape": DILATED_SHAPE,
        "ring": {**RING_SHAPE, "segments": RING_SEGMENTS,
                 "ratios": RING_RATIOS},
        "fold": {**FOLD_SHAPE, "segment": FOLD_SEGMENT,
                 "ratio": FOLD_RATIO, "valid": FOLD_VALID},
        "slide": {"n_tokens": SLIDE_N, "in_chans": SLIDE_IN_CHANS,
                  "arch": "gigapath_slide_enc_tiny"},
        "jax_version": jax.__version__,
    }
    return ledger, meta


def regenerate(golden_path: str = GOLDEN_PATH, *, force: bool = False,
               check: bool = False) -> int:
    from gigapath_tpu.obs.ledger import LEDGER_SCHEMA_VERSION, write_ledger

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import ledger_diff

    ledger, meta = build_golden_ledger()
    fresh = {"v": LEDGER_SCHEMA_VERSION, **meta,
             "entries": {k: ledger.entries[k] for k in sorted(ledger.entries)}}

    if os.path.exists(golden_path):
        golden = ledger_diff.load_ledger(golden_path)
        verdict = ledger_diff.compare(golden, fresh)
        ledger_diff.render(verdict)
        if check:
            return 0 if verdict["decision"]["ok"] else 1
        if not verdict["decision"]["ok"] and not force:
            print(
                "refresh_ledger: REFUSING to overwrite the golden with a "
                "regressed ledger (rerun with --force to accept knowingly)",
                file=sys.stderr,
            )
            return 1
    elif check:
        print(f"error: no golden at {golden_path} to check against",
              file=sys.stderr)
        return 2

    write_ledger(fresh, golden_path)
    print(f"wrote {golden_path} ({len(fresh['entries'])} entries)")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python scripts/refresh_ledger.py",
        description="Write or check a local golden of the flagship ledger "
                    "(none is committed)",
    )
    ap.add_argument("--force", action="store_true",
                    help="overwrite even when metrics regressed")
    ap.add_argument("--check", action="store_true",
                    help="diff against the golden, write nothing")
    ap.add_argument("--out", default=GOLDEN_PATH,
                    help="golden path (default: tests/goldens/"
                    "LEDGER_flagship.json, ignored by git)")
    args = ap.parse_args(argv)
    return regenerate(args.out, force=args.force, check=args.check)


if __name__ == "__main__":
    sys.exit(main())
