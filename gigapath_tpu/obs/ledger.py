"""Compiled-artifact perf ledger: machine-checkable performance
observability that needs no chip.

Kernel work lands between chip runs; the signals that ARE deterministic
without a device are the compiled artifact's own numbers: XLA ``cost_analysis()`` FLOPs / bytes
accessed, ``memory_analysis()`` argument/output/temp/donated bytes, and
the traced program's shape — eqn counts by primitive (the same
transpose/slice/broadcast/reshape/pallas_call columns PERFORMANCE.md's
round-6 table tabulates by hand). This module captures those as
``compile_profile`` obs events and folds every profile of a run into one
canonical per-run ledger JSON, keyed by ``name|shape-signature``, that
``scripts/ledger_diff.py`` can diff across commits with per-metric
thresholds. The flagship shapes' ledger is built by
``scripts/refresh_ledger.py`` (no golden of it is committed);
``tests/test_ledger.py`` reads its signals.

Capture paths:

- hooked through :class:`~gigapath_tpu.obs.watchdog.CompileWatchdog`
  (``ledger=`` arg): ``wrap()`` captures automatically on each new key,
  loops driving the ``is_new``/``record`` surface call
  ``watchdog.profile(key, fn, *args, **kwargs)``;
- standalone: :func:`capture_profile` / :meth:`PerfLedger.capture`.

Cost model: a FULL profile (cost+memory analysis) lowers AND compiles
the function once more through the AOT path — that does not touch the
jit call cache (no retrace is visible to ``fn._cache_size()``, pinned by
tests/test_obs.py) but it is one extra XLA compile. The ledger therefore
takes the full profile only for the FIRST signature seen per name (the
hot/flagship shape); later signatures get a fingerprint-only profile
(one extra trace, no compile). ``full=True`` on capture overrides.

``GIGAPATH_OBS=0``: :func:`get_ledger` returns a :class:`NullLedger`
(no events, no trace/lower/compile work, no file).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

LEDGER_SCHEMA_VERSION = 1

# Primitive columns every fingerprint reports explicitly (0 when absent):
# the glue-op classes PERFORMANCE.md's round-6 table tracks, the kernel
# count, and the sequence-parallel collectives (the ring-vs-gather
# signal: the ring path must show ppermute > 0 and zero full-segment
# all_gather of K/V — pinned by the golden ledger's dilated_ring_*
# entries). Other primitives appear under their own names as seen.
FINGERPRINT_COLUMNS = (
    "transpose", "slice", "broadcast_in_dim", "reshape", "pallas_call",
    "ppermute", "all_gather",
)


# ---------------------------------------------------------------------------
# jaxpr fingerprint
# ---------------------------------------------------------------------------

def _eqn_is_quant(eqn) -> bool:
    """Does an equation touch a low-precision (int8 / float8_*) aval?
    The ``quant`` fingerprint column: when the quantized kernel tier
    (gigapath_tpu/quant/, GIGAPATH_QUANT_TILE) is on, the traced
    program must SHOW low-precision operands — a tier flag that
    compiles the f32 program silently is exactly the regression this
    column pins, the same way ppermute/all_gather pin the ring tier."""
    for var in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(var, "aval", None)
        dtype = str(getattr(aval, "dtype", ""))
        if dtype == "int8" or dtype.startswith("float8"):
            return True
    return False


def _eqn_is_mask(eqn) -> bool:
    """Does an equation PRODUCE a dense square boolean mask — a bool
    aval whose two trailing dims are equal and > 1? The ``mask``
    fingerprint column: the jnp streaming fold materializes per-pair
    ``[.., C, C]`` segment/phase/validity masks (pure O(C^2) traffic),
    while the Pallas fold tier computes the same predicates in-kernel
    from iota comparisons and must show ZERO such eqns — the golden
    ledger pins both sides of that A/B, and a mask count creeping back
    into a kernel path is exactly the regression this column flags."""
    for var in eqn.outvars:
        aval = getattr(var, "aval", None)
        if str(getattr(aval, "dtype", "")) != "bool":
            continue
        shape = tuple(getattr(aval, "shape", ()) or ())
        if len(shape) >= 2 and shape[-1] == shape[-2] and shape[-1] > 1:
            return True
    return False


def _count_eqns(jaxpr, counts: Dict[str, int],
                qbox: Optional[List[int]] = None,
                mbox: Optional[List[int]] = None) -> None:
    """Recursive primitive histogram over a jaxpr and every sub-jaxpr
    (pjit bodies, custom_vjp calls, scan/cond branches, pallas_call).
    ``qbox``/``mbox`` (1-element lists) additionally accumulate the
    low-precision and square-bool-mask eqn counts for the ``quant`` /
    ``mask`` columns."""
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        if qbox is not None and _eqn_is_quant(eqn):
            qbox[0] += 1
        if mbox is not None and _eqn_is_mask(eqn):
            mbox[0] += 1
        for val in eqn.params.values():
            for item in val if isinstance(val, (list, tuple)) else (val,):
                sub = getattr(item, "jaxpr", None)
                if sub is not None:
                    # ClosedJaxpr has .jaxpr.eqns; Jaxpr has .eqns
                    _count_eqns(getattr(sub, "jaxpr", sub), counts, qbox,
                                mbox)
                elif hasattr(item, "eqns") and eqn.primitive.name != "pallas_call":
                    # a RAW Jaxpr param (shard_map bodies ride as one):
                    # without this arm the whole sharded program would
                    # fingerprint as a single opaque eqn. pallas_call
                    # kernel bodies stay opaque on purpose — the KERNEL
                    # COUNT is the round-6 column's signal; Mosaic
                    # kernel-internal ops are not XLA glue
                    _count_eqns(item, counts, qbox, mbox)


def jaxpr_fingerprint(fn, *args, **kwargs) -> Dict[str, Any]:
    """Eqn counts by primitive for ``fn(*args, **kwargs)``'s traced
    program: ``{"eqns_total": N, "quant": Q, "mask": M, "primitives":
    {name: count}}`` with the :data:`FINGERPRINT_COLUMNS` always
    present, ``quant`` the count of eqns touching int8/float8 avals
    (the quantized-tier op-mix pin) and ``mask`` the count of eqns
    producing dense square boolean masks (the streaming-fold
    mask-materialization pin) — neither is a primitive, so neither
    feeds ``eqns_total``. One extra trace, no compile. ``fn`` may be
    jitted or plain."""
    import jax

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    counts: Dict[str, int] = {}
    qbox = [0]
    mbox = [0]
    _count_eqns(closed.jaxpr, counts, qbox, mbox)
    for col in FINGERPRINT_COLUMNS:
        counts.setdefault(col, 0)
    return {
        "eqns_total": int(sum(counts.values())),
        "quant": int(qbox[0]),
        "mask": int(mbox[0]),
        "primitives": {k: int(v) for k, v in sorted(counts.items())},
    }


# ---------------------------------------------------------------------------
# cost / memory analysis (the utils.profiling backends live HERE now)
# ---------------------------------------------------------------------------

def _compile_aot(fn, *args, **kwargs):
    """AOT lower+compile (jitting if needed). Does not touch the jit call
    cache, so watched functions see no retrace."""
    import jax

    lowered = getattr(fn, "lower", None)
    if lowered is None:
        fn = jax.jit(fn)
    return fn.lower(*args, **kwargs).compile()


def _finite(value) -> Optional[float]:
    """float(value) if finite, else None — NaN must never reach a ledger
    (it serializes as a non-RFC token and blinds ledger_diff's
    comparisons, which treat NaN deltas as in-tolerance)."""
    import math

    try:
        f = float(value)
    except (TypeError, ValueError):
        return None
    return f if math.isfinite(f) else None


def cost_analysis_of(compiled) -> Optional[Dict[str, Optional[float]]]:
    """``{"flops", "bytes_accessed"}`` from a compiled object's XLA cost
    analysis; None when the backend offers none; individual fields None
    when the backend reports them non-finite or not at all."""
    try:
        analysis = compiled.cost_analysis()
        if isinstance(analysis, list):
            analysis = analysis[0]
        return {
            "flops": _finite(analysis.get("flops")),
            "bytes_accessed": _finite(analysis.get("bytes accessed")),
        }
    except Exception:
        return None


def memory_analysis_of(compiled) -> Optional[Dict[str, Optional[float]]]:
    """Argument/output/temp/donated bytes plus a derived ``peak_bytes``
    (arguments + temporaries + non-aliased outputs — donated inputs alias
    their outputs, so ``donated_bytes`` is subtracted once). Fields the
    backend cannot report finitely are None, and so is the derived peak."""
    try:
        mem = compiled.memory_analysis()
        arg = _finite(getattr(mem, "argument_size_in_bytes", None))
        out = _finite(getattr(mem, "output_size_in_bytes", None))
        tmp = _finite(getattr(mem, "temp_size_in_bytes", None))
        donated = _finite(getattr(mem, "alias_size_in_bytes", 0.0))
        peak = None
        if None not in (arg, out, tmp):
            peak = arg + tmp + max(out - (donated or 0.0), 0.0)
        return {
            "argument_bytes": arg,
            "output_bytes": out,
            "temp_bytes": tmp,
            "donated_bytes": donated,
            "peak_bytes": peak,
        }
    except Exception:
        return None


def custom_calls_of(compiled) -> Optional[int]:
    """How many ``tpu_custom_call``s (Pallas/Mosaic kernels) the compiled
    program holds; 0 on any backend that lowers none, None when the
    program's text cannot be read. The evidence that a program meant to
    run the kernels does: a dispatch gate that quietly chose the jnp tier
    compiles to zero."""
    try:
        return int(compiled.as_text().count('custom_call_target="tpu_custom_call"'))
    except Exception:
        return None


def compiled_flops(fn, *args) -> Optional[float]:
    """FLOPs of the jitted computation, from XLA cost analysis."""
    try:
        cost = cost_analysis_of(_compile_aot(fn, *args))
    except Exception:
        return None
    return None if cost is None else cost["flops"]


def compiled_memory(fn, *args) -> Optional[Dict[str, float]]:
    """Peak/argument/output memory of the compiled computation (bytes).
    Field names kept compatible with the original utils.profiling shim
    consumers (bench.py): temp/argument/output``_bytes``."""
    try:
        mem = memory_analysis_of(_compile_aot(fn, *args))
    except Exception:
        return None
    return None if mem is None else {
        "temp_bytes": mem["temp_bytes"],
        "argument_bytes": mem["argument_bytes"],
        "output_bytes": mem["output_bytes"],
    }


# ---------------------------------------------------------------------------
# profiles
# ---------------------------------------------------------------------------

def _tree_size(value: Any) -> int:
    """Leaf count of a nested dict/list/tuple pytree (no jax import)."""
    if isinstance(value, dict):
        return sum(_tree_size(v) for v in value.values())
    if isinstance(value, (list, tuple)):
        return sum(_tree_size(v) for v in value)
    return 1


def shape_signature(args: tuple, kwargs: dict) -> str:
    """Static shape/dtype signature over array-like arguments — the facts
    the jit cache keys on for them (non-arrays are skipped, mirroring the
    watchdog's default key)."""
    parts: List[str] = []

    def leaf_sig(prefix: str, value: Any) -> None:
        shape = getattr(value, "shape", None)
        if shape is not None and hasattr(value, "dtype"):
            parts.append(f"{prefix}{str(value.dtype)}[{','.join(map(str, shape))}]")
            return
        # pytrees (param dicts): summarize as LEAF count so two models of
        # equal batch shapes but different depths do not collide silently
        if isinstance(value, dict):
            parts.append(f"{prefix}tree{{{_tree_size(value)}}}")

    for a in args:
        leaf_sig("", a)
    for name in sorted(kwargs):
        leaf_sig(f"{name}=", kwargs[name])
    return ";".join(parts)


def capture_profile(fn, *args, full: bool = True, **kwargs) -> Dict[str, Any]:
    """One compile profile of ``fn(*args, **kwargs)``: jaxpr fingerprint
    always; cost/memory analysis when ``full`` (one extra AOT compile).
    Every section is best-effort — a profile must never take a run down —
    but a totally untraceable function raises (callers decide)."""
    profile: Dict[str, Any] = {
        "sig": shape_signature(args, kwargs),
        "jaxpr": jaxpr_fingerprint(fn, *args, **kwargs),
    }
    if full:
        try:
            compiled = _compile_aot(fn, *args, **kwargs)
        except Exception as e:
            profile["compile_error"] = f"{type(e).__name__}: {e}"
            return profile
        profile["cost"] = cost_analysis_of(compiled)
        profile["memory"] = memory_analysis_of(compiled)
        profile["tpu_custom_calls"] = custom_calls_of(compiled)
    return profile


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

class NullLedger:
    """``GIGAPATH_OBS=0`` twin: absorbs every call, creates nothing."""

    path: Optional[str] = None

    def capture(self, name: str, fn, *args, **kwargs):
        return None

    def capture_for_key(self, name: str, key, fn, *args, **kwargs):
        return None

    capture_full = capture_fingerprint = capture

    def adopt_compiled(self, name: str, key, compiled, fn, *args, **kwargs):
        return None

    def write(self, path: Optional[str] = None):
        return None

    @property
    def entries(self) -> Dict[str, dict]:
        return {}


class PerfLedger(NullLedger):
    """Folds a run's compile profiles into one canonical ledger JSON.

    Entries are keyed ``name|shape-signature`` and written sorted with a
    fixed field order, so two ledgers of the same code + shapes are
    byte-comparable. The file is (re)written after every capture — like
    the run JSONL, the artifact exists the moment the run dies.
    """

    def __init__(self, runlog=None, path: Optional[str] = None,
                 meta: Optional[dict] = None, autowrite: bool = True):
        self.runlog = runlog
        if path is None and runlog is not None and getattr(runlog, "path", None):
            base = os.path.dirname(os.path.abspath(runlog.path))
            path = os.path.join(base, f"{runlog.run_id}.ledger.json")
        self.path = path
        # autowrite=False defers the file to an explicit write() — bench
        # uses it so a failed run cannot overwrite the last good ledger
        # with a partial one (its failure JSON points at the old file)
        self.autowrite = autowrite
        self._entries: Dict[str, dict] = {}
        self._full_named: set = set()  # names that already got a full profile
        self.meta = dict(meta or {})

    @property
    def entries(self) -> Dict[str, dict]:
        return self._entries

    def capture(self, name: str, fn, *args, **kwargs) -> Optional[dict]:
        """Profile ``fn`` under ``name`` unless this (name, signature) is
        already ledgered. Full (cost+memory) for the first signature per
        name, fingerprint-only afterwards; force with ``self.capture_full``.
        Returns the entry (or the existing one), None on capture failure."""
        return self._capture(name, fn, args, kwargs,
                             full=name not in self._full_named)

    def capture_full(self, name: str, fn, *args, **kwargs) -> Optional[dict]:
        return self._capture(name, fn, args, kwargs, full=True)

    def capture_fingerprint(self, name: str, fn, *args, **kwargs) -> Optional[dict]:
        """Jaxpr fingerprint only — one extra trace, never a compile
        (golden generation uses this for interpret-mode pallas programs
        whose CPU compile is slow but whose eqn counts are the signal)."""
        return self._capture(name, fn, args, kwargs, full=False)

    def capture_for_key(self, name: str, key, fn, *args, **kwargs) -> Optional[dict]:
        """Like :meth:`capture`, tagging the entry/event with the
        watchdog's bucket key so compile events and compile_profile
        events join without re-deriving the key<->signature mapping."""
        from gigapath_tpu.obs.runlog import _key_str

        return self._capture(name, fn, args, kwargs,
                             full=name not in self._full_named,
                             extra={"key": _key_str(key)})

    def adopt_compiled(self, name: str, key, compiled, fn,
                       *args, **kwargs) -> Optional[dict]:
        """Ledger an ALREADY-compiled AOT executable.

        The documented cost model of :meth:`capture` pays one extra AOT
        compile per full profile; callers that hold the compiled object
        already (the serving stack's per-bucket executables,
        :mod:`gigapath_tpu.serve.aot`) get cost/memory analysis straight
        off it for free — the only added work is the fingerprint's one
        extra trace. ``args``/``kwargs`` may be ``jax.ShapeDtypeStruct``s
        (they only feed the trace and the shape signature). Every
        (name, signature) is a FULL profile here, since full costs
        nothing. Failures are contained like every other capture."""
        from gigapath_tpu.obs.runlog import _key_str

        sig = shape_signature(args, kwargs)
        entry_key = f"{name}|{sig}"
        existing = self._entries.get(entry_key)
        if existing is not None and "cost" in existing:
            return existing
        try:
            profile: Dict[str, Any] = {
                "sig": sig,
                "jaxpr": jaxpr_fingerprint(fn, *args, **kwargs),
                "cost": cost_analysis_of(compiled),
                "memory": memory_analysis_of(compiled),
                "tpu_custom_calls": custom_calls_of(compiled),
            }
        except Exception as e:
            if self.runlog is not None:
                self.runlog.event(
                    "compile_profile", name=name, sig=sig,
                    error=f"{type(e).__name__}: {e}",
                )
            return None
        self._full_named.add(name)
        extra = {"key": _key_str(key)}
        entry = {"name": name, **extra, **profile}
        self._entries[entry_key] = entry
        if self.runlog is not None:
            self.runlog.event("compile_profile", name=name, **extra, **profile)
        if self.autowrite:
            try:
                self.write()
            except Exception as e:  # the artifact must never take a run down
                if self.runlog is not None:
                    self.runlog.error("ledger.write", e)
        return entry

    def _capture(self, name, fn, args, kwargs, *, full,
                 extra: Optional[dict] = None) -> Optional[dict]:
        sig = shape_signature(args, kwargs)
        key = f"{name}|{sig}"
        existing = self._entries.get(key)
        if existing is not None:
            # a full request upgrades a fingerprint-only entry (the
            # documented capture_full override); anything else dedups
            if not full or "cost" in existing or "compile_error" in existing:
                return existing
        try:
            profile = capture_profile(fn, *args, full=full, **kwargs)
        except Exception as e:
            if self.runlog is not None:
                self.runlog.event(
                    "compile_profile", name=name, sig=sig,
                    error=f"{type(e).__name__}: {e}",
                )
            return None
        if full and "compile_error" not in profile:
            self._full_named.add(name)
        entry = {"name": name, **(extra or {}), **profile}
        self._entries[key] = entry
        if self.runlog is not None:
            self.runlog.event("compile_profile", name=name, **(extra or {}),
                              **profile)
        if self.autowrite:
            try:
                self.write()
            except Exception as e:  # the artifact must never take a run down
                if self.runlog is not None:
                    self.runlog.error("ledger.write", e)
        return entry

    def as_dict(self) -> dict:
        doc = {"v": LEDGER_SCHEMA_VERSION}
        doc.update(self.meta)
        if self.runlog is not None and getattr(self.runlog, "run_id", None):
            doc.setdefault("run", self.runlog.run_id)
        doc["entries"] = {k: self._entries[k] for k in sorted(self._entries)}
        return doc

    def write(self, path: Optional[str] = None) -> Optional[str]:
        path = path or self.path
        if path is None:
            return None
        write_ledger(self.as_dict(), path)
        return path


def write_ledger(doc: dict, path: str) -> str:
    """Canonical serialization shared by PerfLedger and the golden
    regenerator: sorted keys, indent 1, trailing newline. allow_nan=False
    enforces the no-NaN invariant loudly — a NaN would serialize as a
    non-RFC token and blind ledger_diff."""
    parent = os.path.dirname(os.path.abspath(path))
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True, allow_nan=False)
        f.write("\n")
    return path


def get_ledger(runlog, path: Optional[str] = None,
               meta: Optional[dict] = None):
    """Ledger for a run: a real :class:`PerfLedger` when the runlog
    records to a file, a :class:`NullLedger` under ``GIGAPATH_OBS=0``
    (NullRunLog). Mirrors how every other obs component keys off the
    runlog, so the one ``get_run_log`` env read stays the only gate."""
    if runlog is None or getattr(runlog, "path", None) is None:
        return NullLedger()
    return PerfLedger(runlog, path=path, meta=meta)
