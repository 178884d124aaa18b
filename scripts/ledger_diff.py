#!/usr/bin/env python
"""Diff two perf ledgers (gigapath_tpu.obs.ledger JSON) with per-metric
thresholds and emit a machine-checkable regression verdict.

    python scripts/ledger_diff.py BASELINE.json CANDIDATE.json
    python scripts/ledger_diff.py A.json B.json --json verdict.json
    python scripts/ledger_diff.py --selftest

Entries are keyed ``name|shape-signature``; per entry the compared
metrics and their regression directions:

- ``jaxpr.eqns_total`` and every ``jaxpr.primitives`` count: an INCREASE
  beyond ``--eqn-tol`` (default 0 — exact) is a regression. This is the
  machine-checkable successor of PERFORMANCE.md's hand-tabulated
  transpose/slice/broadcast/reshape/pallas_call columns: glue ops
  silently reappearing in a traced program fail the diff.
- ``cost.flops`` / ``cost.bytes_accessed``: relative increase beyond
  ``--rel-tol`` (default 2%) is a regression.
- ``memory.peak_bytes`` / ``temp`` / ``argument`` / ``output``: same
  relative threshold.
- ``memory.donated_bytes``: a DECREASE is the regression (a lost buffer
  donation means a silently fatter memory high-water mark).
- an entry present in the baseline but missing from the candidate (or a
  metric section lost, e.g. cost analysis no longer captured) is a
  regression; new candidate entries are reported as notes.

Improvements (the opposite direction) are listed but never fail the
diff. The verdict JSON has the same decision-table shape as
``scripts/ab_dilated.py --json``: a ``decision`` object with the one
boolean consumers should read (``ok``).

Pure stdlib — no jax import — so it runs anywhere the ledgers land.
Exit 0 when ok, 1 on regressions, 2 on unreadable input / usage errors.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

DEFAULT_REL_TOL = 0.02
DEFAULT_EQN_TOL = 0

# (section, field, direction): "up" = increase is the regression,
# "down" = decrease is the regression. rel=True -> --rel-tol applies,
# else exact (eqn-tol applies to jaxpr counts only).
_SCALAR_METRICS: List[Tuple[str, str, str, bool]] = [
    ("cost", "flops", "up", True),
    ("cost", "bytes_accessed", "up", True),
    ("memory", "peak_bytes", "up", True),
    ("memory", "temp_bytes", "up", True),
    ("memory", "argument_bytes", "up", True),
    ("memory", "output_bytes", "up", True),
    ("memory", "donated_bytes", "down", True),
]


def _is_finite(value) -> bool:
    import math

    return isinstance(value, (int, float)) and math.isfinite(value)


def load_ledger(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    if not isinstance(doc, dict) or "entries" not in doc:
        raise ValueError(f"{path}: not a ledger (no 'entries' object)")
    return doc


def _row(metric: str, base, cand, verdict: str) -> dict:
    row = {"metric": metric, "baseline": base, "candidate": cand,
           "verdict": verdict}
    if isinstance(base, (int, float)) and isinstance(cand, (int, float)) and base:
        row["ratio"] = round(cand / base, 4)
    return row


def _judge(base: float, cand: float, *, direction: str, rel: bool,
           rel_tol: float, eqn_tol: int) -> str:
    """'ok' | 'regression' | 'improvement' for one metric pair."""
    delta = cand - base
    if direction == "down":
        delta = -delta
    # delta > 0 now always means "moved in the regression direction"
    if rel:
        tol = rel_tol * abs(base) if base else 0.0
    else:
        tol = eqn_tol
    if delta > tol:
        return "regression"
    if delta < -tol:
        return "improvement"
    return "ok"


def compare(base_doc: dict, cand_doc: dict, *,
            rel_tol: float = DEFAULT_REL_TOL,
            eqn_tol: int = DEFAULT_EQN_TOL) -> dict:
    """Diff two ledger documents -> verdict payload (see module doc)."""
    base_entries: Dict[str, dict] = base_doc.get("entries", {})
    cand_entries: Dict[str, dict] = cand_doc.get("entries", {})
    entries: Dict[str, List[dict]] = {}
    regressions: List[str] = []
    improvements: List[str] = []
    notes: List[str] = []

    for key in sorted(set(base_entries) | set(cand_entries)):
        rows: List[dict] = []
        base = base_entries.get(key)
        cand = cand_entries.get(key)
        if base is None:
            notes.append(f"{key}: new entry (not in baseline)")
            continue
        if cand is None:
            rows.append(_row("entry", "present", "MISSING", "regression"))
            regressions.append(f"{key}: entry missing from candidate")
            entries[key] = rows
            continue

        # -- jaxpr fingerprint (exact counts, eqn_tol slack) -------------
        bj, cj = base.get("jaxpr") or {}, cand.get("jaxpr") or {}
        if bj and not cj:
            rows.append(_row("jaxpr", "present", None, "regression"))
            regressions.append(f"{key}: jaxpr fingerprint lost")
        elif bj and cj:
            pairs = [("jaxpr.eqns_total",
                      bj.get("eqns_total", 0), cj.get("eqns_total", 0))]
            if "quant" in bj:
                # the quantized-tier op-mix pin (obs/ledger.py): an
                # INCREASE in low-precision eqns on a key whose tier
                # did not change is a mix shift, gated like any other
                # eqn count (legacy ledgers without the column are not
                # held to it)
                pairs.append(("jaxpr.quant",
                              bj.get("quant", 0), cj.get("quant", 0)))
            if "mask" in bj:
                # the mask-materialization pin (obs/ledger.py): a
                # square-bool mask eqn creeping into a path pinned at 0
                # (the Pallas fold tier) means dense [C,C] masks are
                # being materialized again — the exact regression the
                # fold kernels exist to remove (legacy ledgers without
                # the column are not held to it)
                pairs.append(("jaxpr.mask",
                              bj.get("mask", 0), cj.get("mask", 0)))
            bp = bj.get("primitives") or {}
            cp = cj.get("primitives") or {}
            for prim in sorted(set(bp) | set(cp)):
                pairs.append((f"jaxpr.primitives.{prim}",
                              bp.get(prim, 0), cp.get(prim, 0)))
            for metric, b, c in pairs:
                verdict = _judge(b, c, direction="up", rel=False,
                                 rel_tol=rel_tol, eqn_tol=eqn_tol)
                if verdict != "ok":
                    rows.append(_row(metric, b, c, verdict))
                    target = (regressions if verdict == "regression"
                              else improvements)
                    target.append(f"{key}: {metric} {b} -> {c}")

        # -- cost / memory analysis --------------------------------------
        # non-finite values (hand-edited or legacy ledgers; the writer
        # sanitizes to None) are treated exactly like missing ones — a
        # NaN delta would compare as in-tolerance and silently blind the
        # gate
        for section, field, direction, rel in _SCALAR_METRICS:
            bs, cs = base.get(section), cand.get(section)
            if not isinstance(bs, dict) or not _is_finite(bs.get(field)):
                continue  # baseline never had it: nothing to hold
            b = bs[field]
            if not isinstance(cs, dict) or not _is_finite(cs.get(field)):
                rows.append(_row(f"{section}.{field}", b, None, "regression"))
                regressions.append(f"{key}: {section}.{field} lost "
                                   "(no longer captured)")
                continue
            c = cs[field]
            verdict = _judge(float(b), float(c), direction=direction,
                             rel=rel, rel_tol=rel_tol, eqn_tol=eqn_tol)
            if verdict != "ok":
                rows.append(_row(f"{section}.{field}", b, c, verdict))
                target = (regressions if verdict == "regression"
                          else improvements)
                target.append(f"{key}: {section}.{field} {b} -> {c}")
        if rows:
            entries[key] = rows

    return {
        "metric": "ledger_diff",
        "thresholds": {"rel_tol": rel_tol, "eqn_tol": eqn_tol},
        "baseline_entries": len(base_entries),
        "candidate_entries": len(cand_entries),
        "entries": entries,
        "notes": notes,
        "decision": {
            "regressions": len(regressions),
            "improvements": len(improvements),
            "regressed": regressions,
            "improved": improvements,
            "ok": not regressions,
        },
    }


def render(verdict: dict, out=None) -> None:
    out = out or sys.stdout
    w = out.write
    dec = verdict["decision"]
    w(f"ledger_diff: {verdict['baseline_entries']} baseline / "
      f"{verdict['candidate_entries']} candidate entries, "
      f"{dec['regressions']} regression(s), "
      f"{dec['improvements']} improvement(s)\n")
    for line in dec["regressed"]:
        w(f"  REGRESSION {line}\n")
    for line in dec["improved"]:
        w(f"  improvement {line}\n")
    for note in verdict.get("notes", []):
        w(f"  note {note}\n")
    w("verdict: " + ("OK\n" if dec["ok"] else "REGRESSED\n"))


def selftest() -> int:
    """Synthesize a ledger, diff against itself (must be clean), then
    inject the canonical regressions (doubled eqn count, inflated flops,
    lost donation, missing entry) and assert the verdict flips — the
    ledger half of scripts/lint.sh."""
    import copy

    base = {
        "v": 1,
        "entries": {
            "slide_fwd|f32[1,256,16]": {
                "name": "slide_fwd",
                "jaxpr": {"eqns_total": 121, "mask": 0,
                          "primitives": {"transpose": 0, "reshape": 31,
                                         "pallas_call": 22, "slice": 0}},
                "cost": {"flops": 2.1e7, "bytes_accessed": 1.6e7},
                "memory": {"argument_bytes": 9e4, "output_bytes": 128.0,
                           "temp_bytes": 1e6, "donated_bytes": 4096.0,
                           "peak_bytes": 1.1e6},
            },
            "train_step|f32[1,256,16];tree{2}": {
                "name": "train_step",
                "jaxpr": {"eqns_total": 357, "primitives": {"reshape": 60}},
            },
        },
    }
    clean = compare(base, copy.deepcopy(base))
    if not clean["decision"]["ok"] or clean["decision"]["regressions"]:
        print("ledger_diff selftest FAILED: self-diff not clean",
              file=sys.stderr)
        return 1

    bad = copy.deepcopy(base)
    entry = bad["entries"]["slide_fwd|f32[1,256,16]"]
    entry["jaxpr"]["primitives"]["transpose"] = 10     # glue reappeared
    entry["jaxpr"]["eqns_total"] += 10
    entry["jaxpr"]["mask"] = 4                         # dense masks back
    entry["cost"]["flops"] *= 1.5                      # >2% flop growth
    entry["memory"]["donated_bytes"] = 0.0             # donation lost
    del bad["entries"]["train_step|f32[1,256,16];tree{2}"]
    verdict = compare(base, bad)
    dec = verdict["decision"]
    expect_regressed = [
        "jaxpr.primitives.transpose", "jaxpr.eqns_total", "jaxpr.mask",
        "cost.flops", "memory.donated_bytes", "entry missing",
    ]
    missing = [m for m in expect_regressed
               if not any(m in line for line in dec["regressed"])]
    if dec["ok"] or missing:
        print(f"ledger_diff selftest FAILED: ok={dec['ok']}, "
              f"undetected: {missing}", file=sys.stderr)
        render(verdict, out=sys.stderr)
        return 1

    # NaN in a candidate (hand-edited/legacy ledger) must read as a LOST
    # metric, never as in-tolerance
    nanbad = copy.deepcopy(base)
    nanbad["entries"]["slide_fwd|f32[1,256,16]"]["cost"]["flops"] = float("nan")
    v = compare(base, nanbad)
    if v["decision"]["ok"] or not any(
        "cost.flops lost" in line for line in v["decision"]["regressed"]
    ):
        print("ledger_diff selftest FAILED: NaN candidate not flagged",
              file=sys.stderr)
        return 1

    # improvements must not fail the diff
    better = copy.deepcopy(base)
    better["entries"]["slide_fwd|f32[1,256,16]"]["jaxpr"]["eqns_total"] = 100
    improved = compare(base, better)
    if not improved["decision"]["ok"] or not improved["decision"]["improved"]:
        print("ledger_diff selftest FAILED: improvement misjudged",
              file=sys.stderr)
        return 1
    print("ledger_diff selftest OK")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python scripts/ledger_diff.py",
        description="Diff two gigapath perf ledgers, verdict on regressions",
    )
    ap.add_argument("baseline", nargs="?", help="baseline ledger JSON")
    ap.add_argument("candidate", nargs="?", help="candidate ledger JSON")
    ap.add_argument("--rel-tol", type=float, default=DEFAULT_REL_TOL,
                    help="relative tolerance for cost/memory metrics "
                    f"(default {DEFAULT_REL_TOL})")
    ap.add_argument("--eqn-tol", type=int, default=DEFAULT_EQN_TOL,
                    help="absolute slack for jaxpr eqn counts (default 0)")
    ap.add_argument("--json", default="",
                    help="also write the verdict JSON here")
    ap.add_argument("--selftest", action="store_true",
                    help="verify the diff logic on a synthetic ledger pair")
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest()
    if not args.baseline or not args.candidate:
        ap.error("provide BASELINE and CANDIDATE ledgers (or --selftest)")
    try:
        base = load_ledger(args.baseline)
        cand = load_ledger(args.candidate)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    verdict = compare(base, cand, rel_tol=args.rel_tol, eqn_tol=args.eqn_tol)
    verdict["baseline"] = os.path.abspath(args.baseline)
    verdict["candidate"] = os.path.abspath(args.candidate)
    render(verdict)
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(verdict, f, indent=1)
            f.write("\n")
    return 0 if verdict["decision"]["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
