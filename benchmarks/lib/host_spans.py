"""Reduction of the program's own host spans (``gigapath_tpu/obs/spans.py``'s
recorder: ``id``, ``name``, ``start_ns``, ``end_ns``, ``parent``, ``thread``,
``fields``) to what the host readers report.

One rule: every instant that some span covers belongs to the innermost span
covering it on its thread, the deepest by its chain of parents and, among
spans of one depth (JAX reports a nested function's trace inside the outer
function's, both under the one ``dispatch``), the one that started last. What
a span owns is its self time: its duration less what its children cover. So
the self times of a root and of everything beneath it add up to the root's
duration, and the owned intervals of a thread never overlap: they are the
``(name, start_ns, end_ns)`` spans ``lib/trace.reduce_xplane`` takes, and its
idle-gap attribution runs over them as it runs over the driver's.
"""

from __future__ import annotations

from benchmarks.lib.trace import WINDOW_SPAN

REQUEST = "request"
REQUEST_SPANS = ("prepare", "h2d", "dispatch", "device_wait", "d2h")
COMPILE_PHASES = ("trace", "lower", "compile")


def _depths(spans) -> dict:
    by_id = {s.id: s for s in spans}
    depths = {}

    def depth(s):
        if s.id not in depths:
            parent = by_id.get(s.parent)
            depths[s.id] = 0 if parent is None else depth(parent) + 1
        return depths[s.id]

    for s in spans:
        depth(s)
    return depths


def owned(spans) -> list:
    """``[(span, start_ns, end_ns)]``: each stretch of time with the span that
    owns it (the module's rule), in order of time within a thread."""
    depths = _depths(spans)
    out = []
    for thread in sorted({s.thread for s in spans}):
        mine = [s for s in spans if s.thread == thread and s.end_ns > s.start_ns]
        edges = sorted({t for s in mine for t in (s.start_ns, s.end_ns)})
        starting = sorted(mine, key=lambda s: s.start_ns)
        active, nxt = [], 0
        for a, b in zip(edges, edges[1:]):
            while nxt < len(starting) and starting[nxt].start_ns <= a:
                active.append(starting[nxt])
                nxt += 1
            active = [s for s in active if s.end_ns > a]
            if not active:
                continue
            owner = max(active, key=lambda s: (depths[s.id], s.start_ns, -s.end_ns))
            if out and out[-1][0] is owner and out[-1][2] == a:
                out[-1] = (owner, out[-1][1], b)
            else:
                out.append((owner, a, b))
    return out


def leaf_intervals(spans) -> list:
    """The owned stretches as ``(name, start_ns, end_ns)``."""
    return [(s.name, a, b) for s, a, b in owned(spans)]


def self_seconds(spans, lo: int = None, hi: int = None, key=lambda s: s.name) -> dict:
    """Self time in seconds by ``key(span)`` (its name), clipped to ``[lo,
    hi]`` where given."""
    out = {}
    for s, a, b in owned(spans):
        a = a if lo is None else max(a, lo)
        b = b if hi is None else min(b, hi)
        if b > a:
            out[key(s)] = out.get(key(s), 0.0) + (b - a) / 1e9
    return out


def requests(spans, lo: int = None, hi: int = None) -> list:
    """The ``request`` spans that lie whole inside ``[lo, hi]``."""
    return [s for s in spans if s.name == REQUEST
            and (lo is None or s.start_ns >= lo) and (hi is None or s.end_ns <= hi)]


def phase_seconds(spans, lo: int = None, hi: int = None) -> dict:
    """``{phase: {function: seconds}}`` of the compile phases' self time: a
    function's own tracing, lowering or backend compile, less that of the
    jitted functions nested in it, so that a phase's functions add up to the
    time the host spent in it."""
    phases = [s for s in spans if s.name in COMPILE_PHASES]
    by = self_seconds(phases, lo, hi, key=lambda s: (s.name, s.fields.get("fun_name", "")))
    out = {}
    for (phase, fun), seconds in by.items():
        out.setdefault(phase, {})[fun] = seconds
    return out


def window_interval(ctx):
    """The harness's ``window`` span, ``(start_ns, end_ns)``, or None."""
    for name, start, end in ctx.spans.spans:
        if name == WINDOW_SPAN:
            return start, end
    return None
