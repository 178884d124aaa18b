"""Geometry-keyed execution plans: ONE dispatch decision per public call.

Kernel choice used to be 9+ trace-time ``GIGAPATH_*`` flags snapshotted
into :class:`~gigapath_tpu.ops.pallas_dilated.PipelineFlags` plus a
hand-rolled 3-tier dispatch — every new variant multiplied the A/B
matrix by hand, and the Pallas block sizes that dominate walltime were
fixed per-flag even though every (segment, dilation) pair has its own
best shape. This module collapses that to an :class:`ExecutionPlan`
resolved ONCE per public call from a geometry key — the ledger's
existing ``name|shape-signature`` — against a persistent registry of
blessed plans (:mod:`gigapath_tpu.plan.registry`, written by
``scripts/autotune.py``).

Resolution order (pinned by tests/test_plan.py):

1. **env flags win where set** — a ``GIGAPATH_*`` dispatch flag that is
   present (non-empty) in the environment keeps exactly its
   ``snapshot_flags`` value, including an explicit ``=0`` off;
2. **the blessed plan fills the rest** — fields the registry entry has
   an opinion on and the environment does not;
3. **built-in defaults** cover everything else — with an EMPTY registry
   and no env flags the resolved snapshot is bit-identical to
   ``snapshot_flags()``, so every traced program is byte-identical to
   the pre-plan dispatch (the golden-ledger parity contract).

``GIGAPATH_PLAN=off`` (or ``0``/``false``/``no``) disables plan lookup
entirely — dispatch degrades to the flag/default behavior. A corrupt
registry is a REFUSED load (warned once) and degrades the same way; it
can never silently mis-dispatch.

This module and :mod:`~gigapath_tpu.plan.registry` are the sanctioned
plan-resolution env-read points (gigalint GL017 keeps kernel-dispatch
``GIGAPATH_*`` reads out of all other library code; ``snapshot_flags``
remains the one sanctioned flag-VALUE read).
"""

from __future__ import annotations

import os
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

from gigapath_tpu.plan.registry import (
    CorruptPlanRegistry,
    load_registry,
    registry_path,
)

# Plan-eligible branch variants: "" = no opinion (the global
# pipelined_fwd flag stands), "serial"/"pipelined" pin the branch's
# forward kernel family regardless of the global field (more specific
# wins INSIDE a plan; env presence strips variants at resolve time so
# the env flag still wins overall).
BRANCH_VARIANTS = ("", "serial", "pipelined")
FUSION_CLASSES = ("", "dense", "stream", "streaming")


class ExecutionPlan(NamedTuple):
    """One geometry's blessed dispatch decision. Every field's zero
    value ("" / None / ()) means "no opinion" — the env flag or the
    built-in default stands. Fields mirror ``PipelineFlags`` where a
    flag twin exists; ``branches`` and ``fusion`` are plan-only.

    ``branches``: per branch class ``(segment_length, ratio, variant,
    block)`` — ``variant`` in :data:`BRANCH_VARIANTS`, ``block`` the
    phase-major Pallas q/k block (0 = the geometry auto choice; legal
    values are 128-multiples in [128, 1024]).
    ``fusion``: cross-branch combine class — ``"stream"`` = the packed
    streaming epilogue, ``"streaming"`` = the online dense branch fold,
    ``"dense"`` = explicitly pin the stacked dense fusion.
    ``fold_branches``: per streaming-fold branch class
    ``(segment_length, ratio, block_q, block_k)`` — Pallas block sizes
    for the chunk-pair fold kernel (0 = the auto choice); plan-only,
    like ``branches``.
    """

    branches: Tuple[Tuple[int, int, str, int], ...] = ()
    fusion: str = ""
    pipelined_fwd: Optional[bool] = None
    pipelined_bwd: Optional[bool] = None
    pipe_block_k: Optional[int] = None
    pipe_bwd_block_k: Optional[int] = None
    ring_attn: Optional[bool] = None
    chunked_prefill: Optional[bool] = None
    quant_tile: Optional[str] = None
    quant_pallas: Optional[bool] = None
    fold_pallas: Optional[bool] = None
    fold_block_q: Optional[int] = None
    fold_block_k: Optional[int] = None
    fold_branches: Tuple[Tuple[int, int, int, int], ...] = ()

    def as_dict(self) -> Dict[str, Any]:
        """Registry serialization: only fields with an opinion."""
        doc: Dict[str, Any] = {}
        if self.branches:
            doc["branches"] = [
                [int(sl), int(r), str(v), int(b)]
                for sl, r, v, b in self.branches
            ]
        if self.fold_branches:
            doc["fold_branches"] = [
                [int(sl), int(r), int(bq), int(bk)]
                for sl, r, bq, bk in self.fold_branches
            ]
        if self.fusion:
            doc["fusion"] = str(self.fusion)
        for field in _SCALAR_PLAN_FIELDS:
            value = getattr(self, field)
            if value is not None:
                doc[field] = value
        return doc

    @classmethod
    def from_dict(cls, doc: Dict[str, Any]) -> "ExecutionPlan":
        """Inverse of :meth:`as_dict`; unknown keys are ignored (forward
        compatibility), malformed known fields raise ValueError (the
        registry loader treats that as corruption)."""
        branches = []
        for row in doc.get("branches", ()) or ():
            sl, r, variant, block = row
            variant = str(variant)
            if variant not in BRANCH_VARIANTS:
                raise ValueError(f"unknown branch variant {variant!r}")
            branches.append((int(sl), int(r), variant, int(block)))
        fold_branches = tuple(
            (int(sl), int(r), int(bq), int(bk))
            for sl, r, bq, bk in doc.get("fold_branches", ()) or ()
        )
        fusion = str(doc.get("fusion", "") or "")
        if fusion not in FUSION_CLASSES:
            raise ValueError(f"unknown fusion class {fusion!r}")
        kwargs: Dict[str, Any] = {}
        for field in _SCALAR_PLAN_FIELDS:
            if field in doc and doc[field] is not None:
                if field in ("pipe_block_k", "pipe_bwd_block_k",
                             "fold_block_q", "fold_block_k"):
                    kwargs[field] = int(doc[field])
                elif field == "quant_tile":
                    # validate the tier spelling HERE so a digest-valid
                    # entry with an unknown mode is refused by
                    # lookup_plan's guard (warn once, default dispatch)
                    # instead of raising from apply_plan on every
                    # resolve — the never-mis-dispatch contract
                    from gigapath_tpu.quant.qtensor import normalize_mode

                    kwargs[field] = normalize_mode(str(doc[field]))
                else:
                    kwargs[field] = bool(doc[field])
        return cls(branches=tuple(branches), fusion=fusion,
                   fold_branches=fold_branches, **kwargs)


_SCALAR_PLAN_FIELDS = (
    "pipelined_fwd", "pipelined_bwd", "pipe_block_k", "pipe_bwd_block_k",
    "ring_attn", "chunked_prefill", "quant_tile", "quant_pallas",
    "fold_pallas", "fold_block_q", "fold_block_k",
)


# ---------------------------------------------------------------------------
# geometry keys
# ---------------------------------------------------------------------------

def geometry_key(name: str, shapes: Sequence[Any]) -> str:
    """The plan registry key: the ledger's ``name|shape-signature`` over
    the call's array-like arguments (real arrays or ShapeDtypeStructs —
    only .shape/.dtype are read, never values)."""
    from gigapath_tpu.obs.ledger import shape_signature

    if not isinstance(shapes, (tuple, list)):
        shapes = (shapes,)
    return f"{name}|{shape_signature(tuple(shapes), {})}"


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------

_WARNED: set = set()
# registry cache: one parsed doc per (path, mtime_ns, size) — a registry
# edit mid-process is seen on the next resolve (the aot.py stale-plan
# guarantee rides this), an unchanged file costs one os.stat per resolve
_CACHE: Dict[str, Any] = {"stamp": None, "doc": None}
_STATS: Dict[str, int] = {"lookups": 0, "hits": 0}


def _warn_once(msg: str) -> None:
    if msg not in _WARNED:
        _WARNED.add(msg)
        import warnings

        warnings.warn(msg, stacklevel=3)


def plan_enabled() -> bool:
    """``GIGAPATH_PLAN`` gate: unset/anything-else = on; ``off``/``0``/
    ``false``/``no`` = plan lookup disabled (flag/default dispatch)."""
    raw = os.environ.get("GIGAPATH_PLAN", "").strip().lower()
    return raw not in ("off", "0", "false", "no")


def _env_present(name: str) -> bool:
    """Is a dispatch flag explicitly set? Non-empty value = present
    (``=0`` is an explicit off and WINS over a plan); empty/unset = the
    plan may fill it."""
    return bool(os.environ.get(name, "").strip())


def _registry_doc() -> dict:
    """Cached verified registry load; corrupt = warn once + empty
    (defaults) — degraded dispatch, never wrong dispatch."""
    path = registry_path()
    try:
        st = os.stat(path)
        stamp = (path, st.st_mtime_ns, st.st_size)
    except OSError:
        stamp = (path, None, None)
    if _CACHE["stamp"] == stamp:
        return _CACHE["doc"]
    try:
        doc = load_registry(path)
    except CorruptPlanRegistry as e:
        _warn_once(
            f"plan registry refused: {e} — dispatch falls back to "
            "env-flag/default behavior"
        )
        doc = {"v": 1, "entries": {}}
    _CACHE["stamp"] = stamp
    _CACHE["doc"] = doc
    return doc


def reset_plan_state() -> None:
    """Drop the registry cache, hit statistics and warn-once memory
    (tests and the autotuner selftest re-point the registry mid-process)."""
    _CACHE["stamp"] = None
    _CACHE["doc"] = None
    _STATS["lookups"] = 0
    _STATS["hits"] = 0
    _WARNED.clear()


def plan_stats() -> Dict[str, float]:
    """Lookup/hit counters since process start (or the last reset) plus
    the derived hit rate — the ``plan_hit_rate`` trend metric."""
    lookups = _STATS["lookups"]
    return {
        "lookups": lookups,
        "hits": _STATS["hits"],
        "plan_hit_rate": (_STATS["hits"] / lookups) if lookups else 0.0,
    }


def plan_registry_signature() -> str:
    """Identity of the ACTIVE plan state, for artifact fingerprints
    (serve/aot.py): the verified registry's entries digest when plan
    dispatch can consult a non-empty registry, else the one constant
    ``"plan-none"`` — off, missing, empty and corrupt-refused all
    resolve every call to flag/default dispatch, i.e. the same traced
    programs, so they intentionally share an identity. A compiled
    executable bakes in the plans of EVERY geometry key its trace
    resolved (not just the caller's own key), which no caller can
    enumerate — so artifact identity must cover the whole registry
    state: any edit to the blessed entries re-fingerprints, and
    over-invalidation costs a recompile where staleness would cost
    wrong dispatch."""
    if not plan_enabled():
        return "plan-none"
    entries = _registry_doc().get("entries") or {}
    if not entries:
        return "plan-none"
    from gigapath_tpu.plan.registry import _digest

    return _digest(entries)


def lookup_plan(key: str) -> Optional[ExecutionPlan]:
    """The registry entry for one geometry key, or None. Counts into
    :func:`plan_stats`. Malformed entries are refused (warned once) —
    the digest catches file corruption, this catches schema drift."""
    _STATS["lookups"] += 1
    entry = (_registry_doc().get("entries") or {}).get(key)
    if entry is None:
        return None
    try:
        plan = ExecutionPlan.from_dict(entry)
    except (ValueError, TypeError, KeyError) as e:
        _warn_once(
            f"plan registry entry for {key!r} refused "
            f"({type(e).__name__}: {e}); using flag/default dispatch"
        )
        return None
    _STATS["hits"] += 1
    return plan


def apply_plan(plan: ExecutionPlan, snap) -> Any:
    """Overlay a plan onto one ``snapshot_flags()`` result, honoring the
    precedence contract: a field whose env twin is PRESENT keeps the
    snapshot value; everything else takes the plan's opinion."""
    from gigapath_tpu.ops.pallas_dilated import FLAG_ENV

    updates: Dict[str, Any] = {}
    for field in _SCALAR_PLAN_FIELDS:
        opinion = getattr(plan, field)
        if opinion is None or _env_present(FLAG_ENV[field]):
            continue
        # quant_tile arrives already normalize_mode-validated: from_dict
        # refuses unknown spellings at lookup time (never mid-resolve)
        updates[field] = opinion
    if plan.fusion == "stream":
        if not _env_present(FLAG_ENV["stream_fusion"]):
            updates["stream_fusion"] = True
    elif plan.fusion == "streaming":
        if not _env_present(FLAG_ENV["streaming_fusion"]):
            updates["streaming_fusion"] = True
    elif plan.fusion == "dense":
        if not _env_present(FLAG_ENV["stream_fusion"]):
            updates["stream_fusion"] = False
        if not _env_present(FLAG_ENV["streaming_fusion"]):
            updates["streaming_fusion"] = False
    if plan.branches:
        # an explicitly-set global pipelined flag beats per-branch
        # variants (env > plan); blocks have no env twin and always apply
        strip = _env_present(FLAG_ENV["pipelined_fwd"])
        updates["branch_plans"] = tuple(
            (int(sl), int(r), "" if strip else str(v), int(b))
            for sl, r, v, b in plan.branches
        )
    if plan.fold_branches:
        # per-fold-branch blocks: an explicitly-set global fold block
        # env twin beats the plan's per-branch value IN THAT FIELD (the
        # same env > plan contract the branch variants honor)
        strip_q = _env_present(FLAG_ENV["fold_block_q"])
        strip_k = _env_present(FLAG_ENV["fold_block_k"])
        updates["fold_branches"] = tuple(
            (int(sl), int(r), 0 if strip_q else int(bq),
             0 if strip_k else int(bk))
            for sl, r, bq, bk in plan.fold_branches
        )
    return snap._replace(**updates) if updates else snap


def resolve_plan(name: str, shapes: Sequence[Any], flags=None):
    """THE dispatch seam: one resolved ``PipelineFlags`` per public
    call.

    ``flags`` not None = the caller already holds a snapshot (an outer
    dispatcher resolved once, or a test pinned dispatch explicitly) —
    returned unchanged, so resolution happens exactly once per public
    call. ``flags`` None = snapshot the environment, look the geometry
    key up in the blessed-plan registry, and overlay the plan where the
    environment is silent. With plan dispatch off (``GIGAPATH_PLAN=off``)
    or no registry entry this IS ``snapshot_flags()`` — bit-identical
    dispatch, byte-identical traced programs.
    """
    if flags is not None:
        return flags
    from gigapath_tpu.ops.pallas_dilated import snapshot_flags

    snap = snapshot_flags()
    if not plan_enabled():
        return snap
    plan = lookup_plan(geometry_key(name, shapes))
    if plan is None:
        return snap
    return apply_plan(plan, snap)
