"""Nestable host-side spans: where the host was, and for how long.

A ``span`` brackets a region of driver code. When it closes it goes, by one
exit path and with one interval, to whatever is listening:

    with span("epoch", runlog, epoch=3):
        with span("step", runlog, fence=True) as sp:
            out = step_fn(params, batch)
            sp.fence(out)          # block_until_ready(out) at span exit

    with record() as rec:          # in memory; nothing is written
        run_inference_with_slide_encoder(...)
    rec.spans                      # [SpanRecord(name, start_ns, end_ns, ...)]

The interval is one read of ``time.perf_counter_ns()`` at entry and one at
exit: the clock ``benchmarks/lib/trace.py`` ties to the device's timeline, so
a recorded span can be laid over a profiler trace. Sinks:

- a :class:`Recorder` installed by :func:`record` keeps a
  :class:`SpanRecord` (``name``, ``start_ns``, ``end_ns`` and the span that
  caused it as ``parent``: a span with none is a root, a request of
  ``pipeline.py``'s entries). While it is installed it also listens to
  ``jax.monitoring`` and files every trace / lowering / backend compile as a
  ``trace`` / ``lower`` / ``compile`` span under the span that paid for it;
- a recording ``runlog`` gets one ``span`` event (schema v1): ``name``,
  ``path`` (dotted nesting, e.g. ``epoch/step``), ``depth``, ``dur_s``
  (``end_ns - start_ns``), ``fenced``, ``rank`` (``jax.process_index()`` for
  multi-host skew analysis — ``scripts/obs_report.py`` folds per-rank spans
  into a straggler table), plus any free-form keyword fields;
- ``trace=`` mirrors the region into a fleet causal tree
  (:mod:`gigapath_tpu.obs.reqtrace`).

Why ``fence``: under async dispatch a wall-clock delta around a jitted
call measures *dispatch*, not execution (gigalint GL008 flags exactly
that). ``fence=True`` makes the span call ``jax.block_until_ready`` on
every value registered via :meth:`Span.fence` (or passed directly as
``fence=value``) before reading the clock, so the span ends when the values
are ready.

Zero-overhead contract: with no recorder installed and against a
:class:`~gigapath_tpu.obs.runlog.NullRunLog` (``GIGAPATH_OBS=0``) or no
runlog at all, a span is a true no-op — no event, no clock reads and no
fence sync (there is no timing consumer, and an opt-out run must behave
byte-identically minus obs artifacts). Spans never touch the traced program
either way, so they can add no retraces (pinned by tests/test_obs.py).

This module is also the home of the ``jax.profiler`` passthroughs that
``gigapath_tpu.utils.profiling`` used to own (thin shims remain there):
:func:`trace` captures a full XLA device trace and :func:`annotate` names a
host region inside one.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Any, Dict, List, Optional


@contextlib.contextmanager
def trace(log_dir: str, *, create_perfetto_link: bool = False):
    """Capture a device trace for the enclosed block:

    >>> with trace("/tmp/profile"):
    ...     step(params, batch)  # compiled work is recorded
    """
    start_trace(log_dir, create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        stop_trace()


def start_trace(log_dir: str, *, create_perfetto_link: bool = False) -> None:
    """The sanctioned open-ended trace start (gigalint GL010: library
    code reaches ``jax.profiler.start_trace``/``stop_trace`` only
    through here). Prefer :func:`trace` when the region is a lexical
    block; the anomaly engine's triggered capture is the open-ended
    case — it starts on a firing detector and stops K step events later,
    two different call sites."""
    import jax

    jax.profiler.start_trace(log_dir, create_perfetto_link=create_perfetto_link)  # gigalint: waive GL010 -- the one sanctioned passthrough


def stop_trace() -> None:
    """Close the trace opened by :func:`start_trace` (see GL010 note)."""
    import jax

    jax.profiler.stop_trace()  # gigalint: waive GL010 -- the one sanctioned passthrough


def annotate(name: str):
    """Named host region inside a trace (``with annotate("collate"): ...``)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def ring_step(step: int, total: int, comm_bytes: int):
    """IN-GRAPH annotation for one step of a ring collective schedule.

    Unlike :func:`span` (host wall-time) and :func:`annotate` (host
    region inside a profiler trace), a ring step is not a host region at
    all — it is a slice of one traced program, so the right annotation
    is a ``jax.named_scope``: the step name (with its per-step comm
    byte count baked in, ``comm_bytes`` = the K/V chunk bytes the step's
    ``ppermute`` moves per shard) lands on the HLO metadata of every op
    the step emits, which is what XLA profiles and the ledger's jaxpr
    render group by. Zero runtime cost, no obs event — the schedule's
    host-level record is the ledger fingerprint (``ppermute`` /
    ``all_gather`` columns, :data:`~gigapath_tpu.obs.ledger.FINGERPRINT_COLUMNS`).
    """
    import jax

    return jax.named_scope(
        f"ring_step_{step + 1}of{total}_comm{comm_bytes}B"
    )


_RANK: Optional[int] = None

# span-event schema keys; caller fields colliding with these are emitted
# under a "field_" prefix instead of crashing the emitting finally block
_RESERVED_SPAN_KEYS = (
    "name", "path", "depth", "dur_s", "fenced", "rank", "status",
    "fence_error",
)


def process_index() -> int:
    """``jax.process_index()`` with a cautious cache; 0 when jax/backends
    are unavailable (spans must never be the thing that takes a run down
    on a flaky backend). The value is cached only once
    ``jax.process_count() > 1`` — before ``jax.distributed.initialize``
    both calls SUCCEED and answer 0/1 on every rank, so caching that
    premature answer would freeze every later rank tag at 0. Single-host
    runs simply re-read the (cheap, post-init) value each time."""
    global _RANK
    if _RANK is not None:
        return _RANK
    try:
        import jax

        idx = int(jax.process_index())
        if int(jax.process_count()) > 1:
            _RANK = idx  # definitely post-distributed-init: safe to pin
        return idx
    except Exception:
        return 0


class _SpanStack(threading.local):
    def __init__(self):
        self.open: List["Span"] = []
        self.cache: Optional[str] = None  # the persistent cache's last answer


_STACK = _SpanStack()


@dataclasses.dataclass(frozen=True)
class SpanRecord:
    """One closed span as a :class:`Recorder` keeps it. ``parent`` is the
    ``id`` of the span that was open around it on its thread (None for a
    root, and for a compile phase paid for under no span at all)."""

    id: int
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]
    thread: int
    fields: Dict[str, Any]


# jax.monitoring's duration events (jax/_src/dispatch.py) -> the span filed
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


class Recorder:
    """Closed spans, kept in memory in the order they closed (a child before
    its parent). Installed by :func:`record`; nothing is written anywhere
    until the caller does so."""

    def __init__(self):
        self.spans: List[SpanRecord] = []
        self._ids = itertools.count()

    def _file(self, name: str, start_ns: int, end_ns: int, *, span_id: int,
              parent: Optional[int], fields: dict) -> None:
        self.spans.append(SpanRecord(
            span_id, name, start_ns, end_ns, parent, threading.get_ident(), fields,
        ))

    def _on_duration(self, event: str, duration: float, fun_name: str = "", **_) -> None:
        """A compile phase ended on this thread just now: a span of its
        duration that ends at this instant, under the span open here (on a
        first call, the entry's ``dispatch``). Nested jitted functions report
        one by one, the inner inside the outer's interval."""
        phase = _COMPILE_PHASES.get(event)
        if phase is None:
            return
        end_ns = time.perf_counter_ns()
        fields = {"fun_name": fun_name}
        if phase == "compile" and _STACK.cache is not None:
            fields["cache"], _STACK.cache = _STACK.cache, None
        # a span opened before this recorder was installed has no id: no parent
        parent = _STACK.open[-1] if _STACK.open else _NULL_SPAN
        # JAX times the phase by time.time(): keep it inside the span that paid
        start_ns = max(end_ns - int(duration * 1e9), parent.start_ns or 0)
        self._file(phase, start_ns, end_ns, span_id=next(self._ids),
                   parent=parent.id, fields=fields)

    def _on_event(self, event: str, **_) -> None:
        # the persistent cache answers inside the backend compile's interval
        if event.endswith("/cache_hits"):
            _STACK.cache = "hit"
        elif event.endswith("/cache_misses"):
            _STACK.cache = "miss"


_RECORDER: Optional[Recorder] = None


@contextlib.contextmanager
def record():
    """Install a :class:`Recorder` for the enclosed block: every ``span`` of
    the process lands in it, with or without a runlog, and so does every
    trace / lowering / compile JAX reports. One at a time."""
    global _RECORDER
    from jax import monitoring

    if _RECORDER is not None:
        raise RuntimeError("a span recorder is already installed")
    rec = Recorder()
    monitoring.register_event_duration_secs_listener(rec._on_duration)
    monitoring.register_event_listener(rec._on_event)
    _RECORDER = rec
    try:
        yield rec
    finally:
        _RECORDER = None
        monitoring.unregister_event_duration_listener(rec._on_duration)
        monitoring.unregister_event_listener(rec._on_event)


class Span:
    """Live span handle yielded by :func:`span`.

    ``start_ns`` / ``end_ns`` (``time.perf_counter_ns``) and ``dur_s``, their
    difference, are populated at exit (None until then, and always None for
    the no-op span), so drivers can reuse the span's measurement::

        with span("step", runlog, fence=True) as sp:
            out = step_fn(...)
            sp.fence(out)
        runlog.step(i, wall_s=sp.dur_s, synced=True)
    """

    __slots__ = ("name", "fenced", "start_ns", "end_ns", "dur_s", "id",
                 "_fence_values", "_fields")

    def __init__(self, name: str, fenced: bool):
        self.name = name
        self.fenced = fenced
        self.start_ns: Optional[int] = None
        self.end_ns: Optional[int] = None
        self.dur_s: Optional[float] = None
        self.id: Optional[int] = None        # drawn from the recorder, if one
        self._fence_values: List[Any] = []
        self._fields: dict = {}

    def fence(self, value: Any) -> Any:
        """Register a value to ``block_until_ready`` at span exit (only
        honored when the span was opened with ``fence=...``); returns the
        value so it can be used inline."""
        self._fence_values.append(value)
        return value

    def note(self, **fields) -> None:
        """Attach free-form fields to the span event."""
        self._fields.update(fields)


class _NullSpan(Span):
    """Absorbs fence()/note() without recording anything."""

    def fence(self, value: Any) -> Any:
        return value

    def note(self, **fields) -> None:
        return None


_NULL_SPAN = _NullSpan("null", fenced=False)


def _is_recording(runlog) -> bool:
    # RunLog always has a file path; NullRunLog (and None) does not.
    return runlog is not None and getattr(runlog, "path", None) is not None


@contextlib.contextmanager
def span(name: str, runlog=None, *, fence: Any = None,
         rank: Optional[int] = None, trace=None, **fields):
    """Nestable timed region; at exit it goes to the installed recorder, to
    a recording ``runlog`` as one ``span`` event, and to ``trace``.

    ``fence``: falsy -> no sync (the span ends when the host leaves it,
    marked ``fenced: false``); ``True`` -> block on values registered via
    ``Span.fence``; any other value -> block on it (plus registered
    values).

    ``rank`` overrides the event's rank tag (default:
    ``jax.process_index()``). The dist dryrun's worker processes use it
    — two process groups on ONE machine all answer jax process index 0,
    but the per-rank straggler table needs the WORKER index; an explicit
    rank also keeps a numpy-only worker from importing jax just to be
    told ``0``.

    ``trace`` threads a fleet :class:`~gigapath_tpu.obs.reqtrace.TraceContext`:
    at exit the region is MIRRORED into the context's causal tree (same
    name, same interval in seconds, structural span id). ``dist/`` library
    code must pass it (gigalint GL022) so no per-slide region is orphaned
    from the cross-process timeline; a ``chunk=`` field keys the mirrored
    span per chunk. The tree's own intervals are ``time.monotonic`` values,
    which on Linux is this clock (CLOCK_MONOTONIC; tests/test_obs.py holds
    the two together).

    With no recorder installed and a ``NullRunLog`` (``GIGAPATH_OBS=0``) or
    no runlog, the whole thing is a no-op: the yielded span absorbs
    ``fence``/``note`` calls and nothing is timed, synced or written.
    """
    rec = _RECORDER
    to_runlog = _is_recording(runlog)
    if rec is None and not to_runlog:
        yield _NULL_SPAN
        return

    # NOTE: no bool() on fence — it may be a device array (forcing a sync
    # here would defeat the point of deferring it to span exit)
    fenced = fence is not None and fence is not False
    sp = Span(name, fenced=fenced)
    if fence is not None and fence is not True and fence is not False:
        sp._fence_values.append(fence)
    parent = _STACK.open[-1] if _STACK.open else _NULL_SPAN
    if rec is not None:
        sp.id = next(rec._ids)
    _STACK.open.append(sp)
    path = "/".join(s.name for s in _STACK.open)
    depth = len(_STACK.open)
    sp.start_ns = time.perf_counter_ns()
    status = "ok"
    try:
        yield sp
    except BaseException:
        status = "error"
        raise
    finally:
        try:
            fence_error = None
            # fence only on the clean path: if the body raised (incl.
            # KeyboardInterrupt during a device stall — the exact hang
            # this obs layer exists to diagnose), blocking on the stuck
            # computation here would turn an interruptible stall into a
            # hard hang. The span is emitted unfenced instead.
            if sp.fenced and sp._fence_values and status == "ok":
                # a failing fence (device error surfacing at the sync
                # point) must still leave a span event — the obs layer
                # exists precisely for the failure moment — and must not
                # replace an exception already in flight from the body
                try:
                    import jax

                    jax.block_until_ready(sp._fence_values)
                except Exception as e:
                    fence_error = f"{type(e).__name__}: {e}"
                    status = "error"
            sp.end_ns = time.perf_counter_ns()
            sp.dur_s = round((sp.end_ns - sp.start_ns) / 1e9, 6)
            merged = dict(fields)
            merged.update(sp._fields)
            failed = {} if fence_error is None else {"fence_error": fence_error}
            if rec is not None:
                rec._file(name, sp.start_ns, sp.end_ns, span_id=sp.id,
                          parent=parent.id,
                          fields={**merged, "status": status, **failed})
            if to_runlog:
                # caller fields must not shadow the span schema (a collision
                # would TypeError inside this finally and crash the driver)
                for reserved in _RESERVED_SPAN_KEYS:
                    if reserved in merged:
                        merged[f"field_{reserved}"] = merged.pop(reserved)
                # a swallowed fence error is recorded, not raised: without the
                # span there would be no sync here at all, so surfacing it
                # would introduce a new failure site the bare driver lacks
                runlog.event(
                    "span", name=name, path=path, depth=depth, dur_s=sp.dur_s,
                    fenced=sp.fenced,
                    rank=process_index() if rank is None else int(rank),
                    status=status,
                    **merged, **failed,
                )
            if trace is not None:
                # mirror the region into the fleet causal tree; the
                # context dedups on its structural id, so a retried
                # region re-announcing itself cannot fork the tree
                trace.add_span(name, sp.start_ns / 1e9, sp.end_ns / 1e9,
                               chunk=merged.get("chunk"), status=status)
        finally:
            _STACK.open.pop()
