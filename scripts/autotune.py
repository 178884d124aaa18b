#!/usr/bin/env python
"""Geometry autotuner: sweep dispatch variants x Pallas block sizes for
one dilated-attention geometry, gate every candidate on the ledger's
CPU-checkable metrics, and bless the winner into the plan registry.

    python scripts/autotune.py                                  # tiny demo sweep (CPU)
    python scripts/autotune.py --n 10241 --json AUTOTUNE.json   # flagship sweep (chip)
    python scripts/autotune.py --n 10241 --bless                # ... and write the winner
    python scripts/autotune.py --surface fold --bless           # streaming-fold tier sweep
    python scripts/autotune.py --selftest                       # seeded end-to-end check

``--surface fold`` sweeps the OTHER hot path: the streaming-fold tier
(``ops/pallas_streaming.py`` vs the jnp oracle, x fold block sizes) at
one chunk geometry, blessing the winner under the ``stream_fold`` key
the :class:`StreamingEncoderSession` resolves once per construction.
The decision table additionally carries the ``mask_eqns`` column (the
golden ledger's dense-mask-materialization pin: 0 for the Pallas tier).

Inner loop = the ledger/ledger_diff machinery (the ``ab_dilated``
discipline):

- every candidate gets a FULL compile profile
  (``obs.ledger.capture_profile``): jaxpr eqn counts + XLA cost/memory
  analysis — the **eqn / temp-bytes / peak-bytes gates run ALWAYS**,
  on CPU and chip alike, via ``ledger_diff.compare`` against the
  default-dispatch baseline (a candidate that blows the traced program
  or the memory envelope up is refused no matter how it times);
- the **walltime gate runs only on measured on-chip rows** (backend
  tpu/gpu): interleaved timing, adopt at >= 3% over the default — a
  CPU sweep emits ``adopt_plan: false`` on walltime grounds BY DESIGN
  (CPU interpret-mode timings are not evidence) but may still adopt a
  candidate on a >= 3% peak-bytes win, the memory-motivated CPU
  adoption the chunked-prefill decision table established.

``--bless`` writes the winner into the registry
(``GIGAPATH_PLAN_REGISTRY`` / ``PLAN_REGISTRY.json``) keyed by the
geometry's ``name|shape-sig``; ``--json`` emits the full
``adopt_plan`` decision table (also folded into PERF_HISTORY's
``plan|autotune`` trend entry by ``perf_history.py ingest --plan``,
round7_measure.sh step 11).

``--selftest``: seeded sweep on a tiny geometry + tmp registry, then —
with ZERO kernel env flags set — proves a blessed plan changes
dispatch: distinct jit cache entries and a distinct ledger fingerprint
vs the default, env-flag precedence over the plan, and corrupt-registry
refusal falling back to default dispatch.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# plan-resolution infrastructure vars (not measured variants; the
# selftest clears these too, the sweep leaves them alone)
_PLAN_ENV = ("GIGAPATH_PLAN", "GIGAPATH_PLAN_REGISTRY")

ADOPT_GATE = 0.97  # >= 3% win over default, the ab_dilated discipline


def _sweep_env():
    """The kernel dispatch flags the sweep must be blind to — derived
    from the ONE FLAG_ENV mapping (pallas_dilated) so a future flag
    cannot drift out of the hermetic-sweep contract."""
    from gigapath_tpu.ops.pallas_dilated import FLAG_ENV

    return tuple(FLAG_ENV.values())


def _build_fn(segs, ratios, flags, interpret):
    from gigapath_tpu.ops.dilated_attention import dilated_attention_fused

    def fn(q, k, v):
        return dilated_attention_fused(
            q, k, v, segs, ratios, interpret=interpret, flags=flags,
        )

    return fn


def fold_candidate_plans(classes, blocks) -> List[Tuple[str, Any]]:
    """The fold-surface (``--surface fold``) candidates: the jnp default
    (the parity oracle and gate baseline), the Pallas fold tier at its
    default blocks, and one per-branch-class block table per requested
    block size."""
    from gigapath_tpu.plan import ExecutionPlan

    cands: List[Tuple[str, Any]] = [
        ("default", ExecutionPlan()),
        ("fold", ExecutionPlan(fold_pallas=True)),
    ]
    for block in blocks:
        branches = tuple(
            (int(sl), int(r), int(block), int(block))
            for sl, r in classes
        )
        cands.append((
            f"fold_b{block}",
            ExecutionPlan(fold_pallas=True, fold_branches=branches),
        ))
    return cands


def _build_fold_fn(classes, valid, flags):
    """One streaming fold step over every branch class of the schedule —
    the per-chunk workload the fold tier exists to speed up (each class
    folds the same resident pair into the running accumulator)."""
    import jax.numpy as jnp

    from gigapath_tpu.ops.streaming_prefill import fold_pair

    def fn(acc_o, acc_l, q, k, v):
        o, l = acc_o, acc_l
        for g, r in classes:
            o, l = fold_pair(
                o, l, q, k, v,
                jnp.int32(0), jnp.int32(0), jnp.int32(valid),
                segment_len=g, ratio=r, flags=flags,
            )
        return o, l

    return fn


def evaluate_fold(name, plan, classes, valid, acc_o, acc_l, q, k, v, *,
                  on_chip, iters) -> Dict[str, Any]:
    """One fold-surface candidate row — same discipline as
    :func:`evaluate`: full compile profile always, walltime only on
    chip."""
    from gigapath_tpu.obs.ledger import capture_profile
    from gigapath_tpu.ops.pallas_dilated import PipelineFlags
    from gigapath_tpu.plan import apply_plan

    flags = apply_plan(plan, PipelineFlags())
    fn = _build_fold_fn(classes, valid, flags)
    try:
        profile = capture_profile(fn, acc_o, acc_l, q, k, v, full=True)
    except Exception as e:  # an untraceable candidate is a refused row
        return {"name": name, "plan": plan.as_dict(),
                "error": f"{type(e).__name__}: {e}"}
    row: Dict[str, Any] = {
        "name": name,
        "plan": plan.as_dict(),
        "entry": {"name": name, **profile},
    }
    mem = profile.get("memory") or {}
    jaxpr = profile.get("jaxpr") or {}
    row["eqns_total"] = jaxpr.get("eqns_total")
    row["mask_eqns"] = jaxpr.get("mask")
    for field in ("peak_bytes", "temp_bytes"):
        value = mem.get(field)
        row[field.replace("bytes", "mb")] = (
            round(value / 2**20, 3) if value is not None else None
        )
    if on_chip:
        from gigapath_tpu.utils.timing import chained_seconds_per_iter

        def step(x, acc_l_, q_, k_, v_):
            o, _ = fn(x, acc_l_, q_, k_, v_)
            return o

        sec, _ = chained_seconds_per_iter(
            step, acc_o, args=(acc_l, q, k, v),
            iters_low=2, iters_high=2 + iters,
        )
        row["wall_s"] = sec
    return row


def candidate_plans(segs, ratios, L, E, H, blocks) -> List[Tuple[str, Any]]:
    """The sweep's (name, ExecutionPlan) candidates: the default (empty
    plan — the baseline every gate compares against), the fusion
    classes, the pipelined forward family, and one branch-block table
    per legal block size."""
    from gigapath_tpu.plan import ExecutionPlan
    from gigapath_tpu.ops.pallas_dilated import plan_stream_fusion

    cands: List[Tuple[str, Any]] = [("default", ExecutionPlan())]
    if len(segs) > 1 and plan_stream_fusion(L, E, H, segs, ratios) is not None:
        cands.append(("stream", ExecutionPlan(fusion="stream")))
    cands.append(("pipelined", ExecutionPlan(pipelined_fwd=True)))
    for block in blocks:
        branches = tuple(
            (int(sl), int(r), "", int(block))
            for sl, r in zip(segs, ratios)
            if H % int(r) == 0 and E % int(r) == 0
        )
        if branches:
            cands.append((f"block{block}", ExecutionPlan(branches=branches)))
    return cands


def evaluate(name, plan, segs, ratios, q, k, v, key, *, interpret,
             on_chip, iters) -> Dict[str, Any]:
    """One candidate row: full compile profile always; walltime only on
    chip (interleaving happens at the caller via repeated rounds)."""
    from gigapath_tpu.obs.ledger import capture_profile
    from gigapath_tpu.ops.pallas_dilated import PipelineFlags
    from gigapath_tpu.plan import apply_plan

    flags = apply_plan(plan, PipelineFlags())
    fn = _build_fn(segs, ratios, flags, interpret)
    try:
        profile = capture_profile(fn, q, k, v, full=True)
    except Exception as e:  # an untraceable candidate is a refused row
        return {"name": name, "plan": plan.as_dict(),
                "error": f"{type(e).__name__}: {e}"}
    row: Dict[str, Any] = {
        "name": name,
        "plan": plan.as_dict(),
        "entry": {"name": name, **profile},
    }
    mem = profile.get("memory") or {}
    jaxpr = profile.get("jaxpr") or {}
    row["eqns_total"] = jaxpr.get("eqns_total")
    for field in ("peak_bytes", "temp_bytes"):
        value = mem.get(field)
        row[field.replace("bytes", "mb")] = (
            round(value / 2**20, 3) if value is not None else None
        )
    if on_chip:
        from gigapath_tpu.utils.timing import chained_seconds_per_iter

        import jax.numpy as jnp

        def step(x, k_, v_):
            out = fn(x, k_, v_)
            return x + (out.astype(jnp.float32).sum() * 1e-30).astype(x.dtype)

        sec, _ = chained_seconds_per_iter(
            step, q, args=(k, v), iters_low=2, iters_high=2 + iters,
        )
        row["wall_s"] = sec
    return row


def _gate_entry(entry: Dict[str, Any]) -> Dict[str, Any]:
    """The gated metric subset: TOTAL eqn count + cost/memory analysis.
    Per-primitive counts are deliberately excluded — a different
    VARIANT legitimately shifts the primitive mix (the stream epilogue
    is one more custom_vjp, the pipelined kernels one more scratch);
    the gates exist to refuse blowups, which eqns_total and the byte
    metrics catch, not to pin program structure (the golden ledger does
    that for the DEFAULT dispatch)."""
    jaxpr = entry.get("jaxpr") or {}
    return {
        "name": entry.get("name"),
        "jaxpr": {"eqns_total": jaxpr.get("eqns_total", 0)},
        "cost": entry.get("cost"),
        "memory": entry.get("memory"),
    }


def gate(default_row, row, *, rel_tol, eqn_tol) -> Tuple[bool, dict]:
    """The always-on CPU-checkable gates: total eqn count and
    temp/peak bytes of the candidate's compiled artifact vs the
    default's, judged by ledger_diff with its usual per-metric
    directions."""
    import ledger_diff

    if "entry" not in row or "entry" not in default_row:
        return False, {"error": "no profile"}
    key = "autotune"
    verdict = ledger_diff.compare(
        {"entries": {key: _gate_entry(default_row["entry"])}},
        {"entries": {key: _gate_entry(row["entry"])}},
        rel_tol=rel_tol, eqn_tol=eqn_tol,
    )
    return verdict["decision"]["ok"], verdict["decision"]


def sweep(args) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gigapath_tpu.plan import bless_plan, geometry_key, plan_stats

    if args.segments == "flagship" or args.heads is None \
            or args.head_dim is None:
        # default to the REAL flagship geometry (heads=16, head_dim=48
        # — models/longnet_config.flagship_geometry), like ab_dilated:
        # a sweep blessed at the wrong E would land under a key the
        # production dispatcher never resolves
        from gigapath_tpu.models.longnet_config import flagship_geometry

        G = flagship_geometry()
        if args.heads is None:
            args.heads = G["heads"]
        if args.head_dim is None:
            args.head_dim = G["head_dim"]
        if args.segments == "flagship":
            args.segments = ",".join(str(s) for s in G["segment_lengths"])
            args.ratios = ",".join(str(r) for r in G["dilated_ratios"])
    segs = [int(s) for s in args.segments.split(",")]
    ratios = [int(r) for r in args.ratios.split(",")]
    blocks = [int(b) for b in args.blocks.split(",") if b]
    B, L, H, Dh = args.batch, args.n, args.heads, args.head_dim
    E = H * Dh

    # the sweep must be BLIND to the kernel env flags: candidates pin
    # dispatch through explicit PipelineFlags, and a present env flag
    # would veto exactly the plan opinions under measurement
    # (apply_plan's precedence) — clear them for the sweep's duration.
    # GIGAPATH_PLAN(_REGISTRY) stay: they are resolution infrastructure,
    # not measured variants.
    cleared = {name: os.environ.pop(name, None) for name in _sweep_env()}
    if any(v for v in cleared.values()):
        print(f"autotune: cleared kernel env flags for the sweep: "
              f"{sorted(k for k, v in cleared.items() if v)}")
    try:
        if getattr(args, "surface", "dilated") == "fold":
            if args.name == "dilated_attention":
                # the fold surface's dispatch site is the streaming
                # session's once-per-construction resolve
                args.name = "stream_fold"
            return _fold_sweep_body(args, segs, ratios, blocks, B, H, Dh)
        return _sweep_body(args, segs, ratios, blocks, B, L, H, Dh, E)
    finally:
        for name, value in cleared.items():
            if value is not None:
                os.environ[name] = value


def _sweep_body(args, segs, ratios, blocks, B, L, H, Dh, E) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gigapath_tpu.plan import bless_plan, geometry_key, plan_stats
    backend = jax.default_backend()
    on_chip = backend in ("tpu", "gpu")
    interpret = not on_chip
    dtype = jnp.bfloat16 if on_chip else jnp.float32

    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, L, H, Dh)), dtype) for _ in range(3)
    )
    key = geometry_key(args.name, (q, k, v))
    print(f"autotune: {key} backend={backend} "
          f"(walltime gate {'ON' if on_chip else 'OFF — CPU rows are '}"
          f"{'' if on_chip else 'memory/eqn-gated only'})")

    cands = candidate_plans(segs, ratios, L, E, H, blocks)
    rows: Dict[str, Dict[str, Any]] = {}
    for name, plan in cands:
        rows[name] = evaluate(
            name, plan, segs, ratios, q, k, v, key,
            interpret=interpret, on_chip=on_chip, iters=args.iters,
        )
        r = rows[name]
        print(f"  {name:12s} eqns={r.get('eqns_total')} "
              f"peak_mb={r.get('peak_mb')} temp_mb={r.get('temp_mb')} "
              f"wall_s={r.get('wall_s')} "
              f"{'ERROR ' + r['error'] if 'error' in r else ''}")

    default_row = rows["default"]
    passing: List[str] = []
    for name, row in rows.items():
        if name == "default":
            row["gates_ok"] = "error" not in row  # the baseline itself
            continue
        if "error" in row:
            row["gates_ok"] = False
            continue
        ok, decision = gate(default_row, row, rel_tol=args.gate_rel_tol,
                            eqn_tol=args.eqn_tol)
        row["gates_ok"] = ok
        if not ok:
            row["gate_regressions"] = decision.get("regressed", [])
        else:
            passing.append(name)

    # winner: on chip by walltime; on CPU by (peak bytes, eqns) — the
    # CPU-checkable objective the memory-motivated decision tables use
    def cpu_key(name):
        r = rows[name]
        return (r.get("peak_mb") or float("inf"),
                r.get("eqns_total") or float("inf"))

    best = None
    if passing:
        if on_chip:
            timed = [n for n in passing if rows[n].get("wall_s") is not None]
            best = min(timed, key=lambda n: rows[n]["wall_s"]) if timed else None
        else:
            best = min(passing, key=cpu_key)

    adopt = False
    reason = "no gate-passing candidate"
    if best is not None:
        if on_chip:
            d_wall = default_row.get("wall_s")
            b_wall = rows[best].get("wall_s")
            adopt = bool(d_wall and b_wall and b_wall <= d_wall * ADOPT_GATE)
            reason = (f"walltime {b_wall:.4f}s vs default {d_wall:.4f}s"
                      if d_wall and b_wall else "no walltime")
        else:
            d_peak = default_row.get("peak_mb")
            b_peak = rows[best].get("peak_mb")
            adopt = bool(d_peak and b_peak and b_peak <= d_peak * ADOPT_GATE)
            reason = (f"CPU memory-only row: peak {b_peak} MB vs default "
                      f"{d_peak} MB (walltime needs a chip)"
                      if d_peak and b_peak else "no memory analysis")

    blessed = False
    force = bool(args.force_bless)
    if force:
        if args.force_bless not in rows or "error" in rows[args.force_bless]:
            print(f"autotune: cannot --force-bless unknown/errored "
                  f"candidate '{args.force_bless}'", file=sys.stderr)
            force = False
        else:
            best = args.force_bless
    if (args.bless and adopt and best) or (force and best):
        registry = args.registry or None
        bless_plan(
            key, rows[best]["plan"], path=registry,
            provenance={
                "label": args.label, "backend": backend,
                "candidate": best, "reason": reason,
                "source": "scripts/autotune.py",
            },
        )
        blessed = True
        print(f"autotune: blessed '{best}' into "
              f"{registry or 'the default registry'} under {key}")

    # verification resolve: does THIS geometry now resolve to a
    # registry entry? (plan_hit_rate = registry coverage of the swept
    # key — the sweep itself pins dispatch via explicit flags and never
    # consults the registry, so without this probe the stat would be
    # vacuously 0)
    from gigapath_tpu.plan import reset_plan_state, resolve_plan

    prior = os.environ.get("GIGAPATH_PLAN_REGISTRY")
    try:
        if args.registry:
            os.environ["GIGAPATH_PLAN_REGISTRY"] = args.registry
        reset_plan_state()
        resolve_plan(args.name, (q, k, v))
        stats = plan_stats()
    finally:
        if args.registry:
            if prior is None:
                os.environ.pop("GIGAPATH_PLAN_REGISTRY", None)
            else:
                os.environ["GIGAPATH_PLAN_REGISTRY"] = prior
        reset_plan_state()
    payload: Dict[str, Any] = {
        "metric": "autotune",
        "key": key,
        "backend": backend,
        "label": args.label,
        "n": L, "heads": H, "head_dim": Dh,
        "branches": [[int(s), int(r)] for s, r in zip(segs, ratios)],
        "candidates": len(cands),
        "gates_passed": len(passing),
        "rows": {
            name: {kk: vv for kk, vv in row.items() if kk != "entry"}
            for name, row in rows.items()
        },
        "plan_hit_rate": stats["plan_hit_rate"],
        "best_wall_s": rows[best].get("wall_s") if best else None,
        "default_wall_s": default_row.get("wall_s"),
        "decision": {
            "best": best,
            "adopt_plan": adopt,
            "reason": reason,
            "blessed": blessed,
        },
        "blessed": 1.0 if blessed else 0.0,
    }
    return payload


def _fold_sweep_body(args, segs, ratios, blocks, B, H, Dh) -> Dict[str, Any]:
    """``--surface fold``: sweep the streaming-fold tier at one chunk
    geometry. Same gates/adoption/bless discipline as the dilated
    sweep; the workload is one per-chunk fold step over every branch
    class; the key is the streaming session's ``stream_fold`` resolve."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gigapath_tpu.ops.attention import NEG_INF
    from gigapath_tpu.plan import bless_plan, geometry_key, plan_stats

    backend = jax.default_backend()
    on_chip = backend in ("tpu", "gpu")
    dtype = jnp.bfloat16 if on_chip else jnp.float32
    C, valid = int(args.chunk), int(args.valid)
    # branch class per schedule entry, with the streaming state's
    # g = min(sl, L) clamp applied at the sweep's valid horizon
    classes = sorted({(min(int(sl), valid), int(r))
                      for sl, r in zip(segs, ratios)})

    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, C, H, Dh)), dtype) for _ in range(3)
    )
    acc_o = jnp.zeros((B, C, H, Dh), jnp.float32)
    acc_l = jnp.full((B, H, C), NEG_INF, jnp.float32)
    key = geometry_key(args.name, (q, k, v))
    print(f"autotune[fold]: {key} chunk={C} valid={valid} "
          f"classes={classes} backend={backend} "
          f"(walltime gate {'ON' if on_chip else 'OFF — CPU rows are '}"
          f"{'' if on_chip else 'memory/eqn-gated only'})")

    cands = fold_candidate_plans(classes, blocks)
    rows: Dict[str, Dict[str, Any]] = {}
    for name, plan in cands:
        rows[name] = evaluate_fold(
            name, plan, classes, valid, acc_o, acc_l, q, k, v,
            on_chip=on_chip, iters=args.iters,
        )
        r = rows[name]
        print(f"  {name:12s} eqns={r.get('eqns_total')} "
              f"mask={r.get('mask_eqns')} "
              f"peak_mb={r.get('peak_mb')} temp_mb={r.get('temp_mb')} "
              f"wall_s={r.get('wall_s')} "
              f"{'ERROR ' + r['error'] if 'error' in r else ''}")

    default_row = rows["default"]
    passing: List[str] = []
    for name, row in rows.items():
        if name == "default":
            row["gates_ok"] = "error" not in row  # the baseline itself
            continue
        if "error" in row:
            row["gates_ok"] = False
            continue
        ok, decision = gate(default_row, row, rel_tol=args.gate_rel_tol,
                            eqn_tol=args.eqn_tol)
        row["gates_ok"] = ok
        if not ok:
            row["gate_regressions"] = decision.get("regressed", [])
        else:
            passing.append(name)

    def cpu_key(name):
        r = rows[name]
        return (r.get("peak_mb") or float("inf"),
                r.get("eqns_total") or float("inf"))

    best = None
    if passing:
        if on_chip:
            timed = [n for n in passing if rows[n].get("wall_s") is not None]
            best = min(timed, key=lambda n: rows[n]["wall_s"]) if timed else None
        else:
            best = min(passing, key=cpu_key)

    adopt = False
    reason = "no gate-passing candidate"
    if best is not None:
        if on_chip:
            d_wall = default_row.get("wall_s")
            b_wall = rows[best].get("wall_s")
            adopt = bool(d_wall and b_wall and b_wall <= d_wall * ADOPT_GATE)
            reason = (f"fold-step walltime {b_wall:.4f}s vs default "
                      f"{d_wall:.4f}s" if d_wall and b_wall
                      else "no walltime")
        else:
            d_peak = default_row.get("peak_mb")
            b_peak = rows[best].get("peak_mb")
            adopt = bool(d_peak and b_peak and b_peak <= d_peak * ADOPT_GATE)
            reason = (f"CPU memory-only row: peak {b_peak} MB vs default "
                      f"{d_peak} MB (walltime needs a chip)"
                      if d_peak and b_peak else "no memory analysis")

    blessed = False
    force = bool(args.force_bless)
    if force:
        if args.force_bless not in rows or "error" in rows[args.force_bless]:
            print(f"autotune: cannot --force-bless unknown/errored "
                  f"candidate '{args.force_bless}'", file=sys.stderr)
            force = False
        else:
            best = args.force_bless
    if (args.bless and adopt and best) or (force and best):
        registry = args.registry or None
        bless_plan(
            key, rows[best]["plan"], path=registry,
            provenance={
                "label": args.label, "backend": backend,
                "candidate": best, "reason": reason,
                "source": "scripts/autotune.py --surface fold",
            },
        )
        blessed = True
        print(f"autotune: blessed '{best}' into "
              f"{registry or 'the default registry'} under {key}")

    # verification resolve: same probe as the dilated sweep — does the
    # stream_fold key now resolve to a registry entry?
    from gigapath_tpu.plan import reset_plan_state, resolve_plan

    prior = os.environ.get("GIGAPATH_PLAN_REGISTRY")
    try:
        if args.registry:
            os.environ["GIGAPATH_PLAN_REGISTRY"] = args.registry
        reset_plan_state()
        resolve_plan(args.name, (q, k, v))
        stats = plan_stats()
    finally:
        if args.registry:
            if prior is None:
                os.environ.pop("GIGAPATH_PLAN_REGISTRY", None)
            else:
                os.environ["GIGAPATH_PLAN_REGISTRY"] = prior
        reset_plan_state()
    payload: Dict[str, Any] = {
        "metric": "fold_autotune",
        "key": key,
        "backend": backend,
        "label": args.label,
        "chunk": C, "valid": valid, "heads": H, "head_dim": Dh,
        "classes": [[int(g), int(r)] for g, r in classes],
        "candidates": len(cands),
        "gates_passed": len(passing),
        "rows": {
            name: {kk: vv for kk, vv in row.items() if kk != "entry"}
            for name, row in rows.items()
        },
        "plan_hit_rate": stats["plan_hit_rate"],
        "best_wall_s": rows[best].get("wall_s") if best else None,
        "default_wall_s": default_row.get("wall_s"),
        "decision": {
            "best": best,
            "adopt_plan": adopt,
            "reason": reason,
            "blessed": blessed,
        },
        "blessed": 1.0 if blessed else 0.0,
    }
    return payload


# ---------------------------------------------------------------------------
# selftest
# ---------------------------------------------------------------------------

def selftest() -> int:
    """Seeded end-to-end check on a tiny geometry (CPU, interpret):
    sweep -> force-bless -> prove the blessed plan changes dispatch with
    ZERO env flags set (distinct jit cache entries + distinct ledger
    fingerprint), env precedence over the plan, corrupt-registry
    refusal."""
    import functools
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    saved = {
        name: os.environ.pop(name, None)
        for name in _sweep_env() + _PLAN_ENV
    }
    try:
        with tempfile.TemporaryDirectory() as tmp:
            registry = os.path.join(tmp, "PLAN_REGISTRY.json")
            os.environ["GIGAPATH_PLAN_REGISTRY"] = registry

            from gigapath_tpu.obs.ledger import jaxpr_fingerprint
            from gigapath_tpu.ops.dilated_attention import (
                dilated_attention_fused,
            )
            from gigapath_tpu.ops.pallas_dilated import (
                PipelineFlags,
                snapshot_flags,
            )
            from gigapath_tpu.plan import (
                CorruptPlanRegistry,
                load_registry,
                reset_plan_state,
                resolve_plan,
            )

            reset_plan_state()
            segs, ratios = [16, 32], [1, 2]
            rng = np.random.default_rng(0)
            q = jnp.asarray(rng.normal(size=(1, 64, 4, 8)), jnp.float32)

            ns = argparse.Namespace(
                segments="16,32", ratios="1,2", n=64, batch=1, heads=4,
                head_dim=8, blocks="256", iters=2, name="dilated_fused",
                label="selftest", registry=registry, bless=False,
                force_bless="stream", gate_rel_tol=0.5, eqn_tol=8,
                json="", surface="dilated", chunk=64, valid=256,
            )
            payload = sweep(ns)
            if not payload["decision"]["blessed"]:
                print("autotune selftest FAILED: force-bless did not land",
                      file=sys.stderr)
                return 1
            doc = load_registry(registry)  # strict: digest must verify
            key = payload["key"]
            if key not in doc["entries"]:
                print("autotune selftest FAILED: blessed key missing",
                      file=sys.stderr)
                return 1

            # -- the acceptance demonstration: zero env flags set, the
            # blessed plan alone changes dispatch -----------------------
            reset_plan_state()
            resolved = resolve_plan("dilated_fused", (q, q, q))
            default = PipelineFlags()
            if not resolved.stream_fusion or resolved == default:
                print(f"autotune selftest FAILED: blessed plan did not "
                      f"resolve ({resolved})", file=sys.stderr)
                return 1
            if snapshot_flags() != default:
                print("autotune selftest FAILED: env not clean",
                      file=sys.stderr)
                return 1

            @functools.partial(jax.jit, static_argnums=(3,))
            def run(q_, k_, v_, flags):
                return dilated_attention_fused(
                    q_, k_, v_, segs, ratios, interpret=True, flags=flags,
                )

            run(q, q, q, default).block_until_ready()
            if run._cache_size() != 1:
                print("autotune selftest FAILED: baseline cache size != 1",
                      file=sys.stderr)
                return 1
            out_plan = run(q, q, q, resolved)
            if run._cache_size() != 2:  # the DISTINCT jit key
                print("autotune selftest FAILED: blessed plan did not "
                      "produce a distinct jit cache entry", file=sys.stderr)
                return 1
            fp_def = jaxpr_fingerprint(
                _build_fn(segs, ratios, default, True), q, q, q)
            fp_plan = jaxpr_fingerprint(
                _build_fn(segs, ratios, resolved, True), q, q, q)
            if fp_def == fp_plan:  # the DISTINCT ledger fingerprint
                print("autotune selftest FAILED: plan fingerprint == "
                      "default fingerprint", file=sys.stderr)
                return 1
            out_def = run(q, q, q, default)
            if not np.allclose(np.asarray(out_def), np.asarray(out_plan),
                               atol=2e-5):
                print("autotune selftest FAILED: plan dispatch is not "
                      "numerically parity with default", file=sys.stderr)
                return 1

            # -- env flags win over the plan where set ------------------
            os.environ["GIGAPATH_STREAM_FUSION"] = "0"
            reset_plan_state()
            pinned = resolve_plan("dilated_fused", (q, q, q))
            os.environ.pop("GIGAPATH_STREAM_FUSION")
            if pinned.stream_fusion:
                print("autotune selftest FAILED: explicit env off did not "
                      "beat the plan", file=sys.stderr)
                return 1

            # -- corrupt registry = refused load, default dispatch ------
            body = open(registry, encoding="utf-8").read()
            with open(registry, "w", encoding="utf-8") as fh:
                fh.write(body.replace('"entries"', '"entries" ', 1))
            reset_plan_state()
            try:
                load_registry(registry)
            except CorruptPlanRegistry:
                pass
            else:
                # a pure-whitespace edit may keep json equal; force it
                with open(registry, "a", encoding="utf-8") as fh:
                    fh.write("garbage")
                try:
                    load_registry(registry)
                except CorruptPlanRegistry:
                    pass
                else:
                    print("autotune selftest FAILED: corrupt registry "
                          "loaded", file=sys.stderr)
                    return 1
            reset_plan_state()
            import warnings

            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                fallback = resolve_plan("dilated_fused", (q, q, q))
            if fallback != default:
                print("autotune selftest FAILED: corrupt registry did not "
                      "fall back to default dispatch", file=sys.stderr)
                return 1

            # -- fold surface (--surface fold): tiny CPU sweep — every
            # candidate ranked in the decision table, the mask-eqn A/B
            # visible, bless round-trips through the registry, and a
            # SECOND resolve hits the blessed entry ---------------------
            registry_fold = os.path.join(tmp, "PLAN_REGISTRY_FOLD.json")
            os.environ["GIGAPATH_PLAN_REGISTRY"] = registry_fold
            reset_plan_state()
            ns_fold = argparse.Namespace(
                segments="16,32", ratios="1,2", n=64, batch=1, heads=4,
                head_dim=8, blocks="128", iters=2, name="stream_fold",
                label="selftest", registry=registry_fold, bless=True,
                # at C=64 the interpret-mode emulation buffers dominate
                # peak bytes; the selftest checks the machinery, so the
                # byte gate gets generous slack here (real sweeps run at
                # real chunk shapes where the Pallas tier is leaner)
                force_bless="fold_b128", gate_rel_tol=10.0, eqn_tol=64,
                json="", surface="fold", chunk=64, valid=256,
            )
            fold_payload = sweep(ns_fold)
            fold_rows = fold_payload["rows"]
            if not ({"default", "fold", "fold_b128"} <= set(fold_rows)
                    and fold_payload["gates_passed"] >= 1
                    and all("eqns_total" in r for r in fold_rows.values())):
                print("autotune selftest FAILED: fold sweep candidates "
                      "not ranked/gated", file=sys.stderr)
                return 1
            if not fold_payload["decision"]["blessed"] \
                    or "adopt_plan" not in fold_payload["decision"]:
                print("autotune selftest FAILED: fold bless did not land",
                      file=sys.stderr)
                return 1
            if not (fold_rows["default"].get("mask_eqns", 0) > 0
                    and fold_rows["fold"].get("mask_eqns") == 0):
                print("autotune selftest FAILED: fold mask-eqn A/B wrong "
                      f"(default={fold_rows['default'].get('mask_eqns')}, "
                      f"fold={fold_rows['fold'].get('mask_eqns')})",
                      file=sys.stderr)
                return 1
            doc = load_registry(registry_fold)  # digest must verify
            if fold_payload["key"] not in doc["entries"]:
                print("autotune selftest FAILED: fold key missing from "
                      "registry", file=sys.stderr)
                return 1
            from gigapath_tpu.plan import plan_stats

            reset_plan_state()
            qb = jnp.zeros((1, 64, 4, 8), jnp.float32)
            hit = resolve_plan("stream_fold", (qb, qb, qb))
            stats = plan_stats()
            if not getattr(hit, "fold_pallas", False) \
                    or not getattr(hit, "fold_branches", ()) \
                    or stats["hits"] != 1:
                print(f"autotune selftest FAILED: second resolve did not "
                      f"hit the blessed fold entry (stats={stats}, "
                      f"flags={hit})", file=sys.stderr)
                return 1
    finally:
        os.environ.pop("GIGAPATH_PLAN_REGISTRY", None)
        for name, value in saved.items():
            if value is not None:
                os.environ[name] = value
    print("autotune selftest OK")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python scripts/autotune.py",
        description="Sweep dispatch variants x block sizes per geometry; "
        "bless the winner into the plan registry",
    )
    ap.add_argument("--name", default="dilated_attention",
                    help="geometry-key name prefix — must match the "
                    "dispatch site that will RESOLVE the plan. The "
                    "production model path enters through "
                    "ops/dilated_attention.py::dilated_attention, which "
                    "resolves 'dilated_attention' over the 4-D q/k/v "
                    "shapes (the default here); 'dilated_fused' is the "
                    "direct-fused-entry key, 'serve.forward' the bucket "
                    "geometries")
    ap.add_argument("--segments", default="flagship",
                    help="comma segment lengths, or 'flagship' (the "
                    "default): the real 5-branch schedule from "
                    "models/longnet_config.flagship_geometry")
    ap.add_argument("--ratios", default="1,2,4,8,16")
    ap.add_argument("--n", type=int, default=512, help="sequence length L")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=None,
                    help="default: the flagship geometry's head count")
    ap.add_argument("--head-dim", type=int, default=None,
                    help="default: the flagship head_dim (48) — sweeping "
                    "at the wrong E blesses a key production never "
                    "resolves")
    ap.add_argument("--surface", choices=("dilated", "fold"),
                    default="dilated",
                    help="what to sweep: 'dilated' (default) = dense "
                    "dilated-attention dispatch variants; 'fold' = the "
                    "streaming-fold tier (jnp vs Pallas x fold block "
                    "sizes) keyed under the session's 'stream_fold' "
                    "resolve")
    ap.add_argument("--chunk", type=int, default=2048,
                    help="[fold] streaming chunk rows per block "
                    "(default 2048 — the 16k smoke's chunk shape)")
    ap.add_argument("--valid", type=int, default=16384,
                    help="[fold] valid-token horizon for the ragged "
                    "mask and the g=min(sl,L) clamp (default 16384)")
    ap.add_argument("--blocks", default="512,768,1024",
                    help="comma list of per-branch block candidates "
                    "(128-multiples in [128, 1024])")
    ap.add_argument("--iters", type=int, default=12,
                    help="walltime iterations per candidate (chip only)")
    ap.add_argument("--gate-rel-tol", type=float, default=0.25,
                    help="relative tolerance for the always-on "
                    "temp/peak-bytes gates (default 0.25)")
    ap.add_argument("--eqn-tol", type=int, default=0,
                    help="absolute slack for the eqn-count gate")
    ap.add_argument("--registry", default="",
                    help="registry path (default: GIGAPATH_PLAN_REGISTRY "
                    "or PLAN_REGISTRY.json at the repo root)")
    ap.add_argument("--label", default="local",
                    help="provenance label for blessed plans / the trend")
    ap.add_argument("--bless", action="store_true",
                    help="write the winner into the registry when the "
                    "adopt gate passes")
    ap.add_argument("--force-bless", default="",
                    help="bless THIS candidate regardless of the adopt "
                    "gate (selftest / manual override)")
    ap.add_argument("--json", default="",
                    help="write the adopt_plan decision-table JSON here")
    ap.add_argument("--selftest", action="store_true")
    args = ap.parse_args(argv)

    if args.selftest:
        # the selftest is a CPU check: the fold seam passes no `interpret`
        # of its own, so interpret mode is asked for here, explicitly
        from jax.experimental.pallas import tpu as pltpu

        with pltpu.force_tpu_interpret_mode():
            return selftest()

    payload = sweep(args)
    print(json.dumps(payload["decision"]))
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
