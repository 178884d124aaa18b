"""A second reduction of a ``--trace 1`` run's xplane, by the names the program
gives its own work (``jax.named_scope``, ``pallas_call(name=...)``, the jitted
step's name; PERF.md §3 lists them), beside ``lib/trace.py``, which reduces by
an operation's HLO text.

Where the names are (looked at by hand in one traced run of each cell, my
chip run, PR 25; host tracer off): the ``op_name`` path of an operation stands
in the stat ``tf_op`` of its *event metadata* on the device plane's "XLA Ops"
line (``jit(tile_encode)/VisionTransformer/.../blocks_3/attn/attn_core/reduce_sum:``),
and ``program_id`` beside it names the jitted program it belongs to, the
number in brackets of that program's events on the "XLA Modules" line
(``jit_tile_encode(6713804780998900468)``). ``jax.profiler.ProfileData`` shows
an event's own stats and not its metadata's, so this module reads the file
with ``google.protobuf`` and the seven messages of ``xplane.proto`` written
out below.

The rule: every operation's self time goes to exactly one group of the cell's
table (``benchmarks/scopes/<cell kind>.json``), the first whose pattern is
found in its path, and ``other`` takes what no pattern matches; a fusion
carries one path, its root's, and goes to that group whole; an operation the
compiler left without a path (a relayout copy it put in) takes the path of
the operation of its own program that started last before it.

Clipped to the ``window`` span on the same clock as ``lib/trace.py`` (the
``bench_clock_sync`` marker). Parsed once per run and kept on the run's
context (``for_run``).
"""

from __future__ import annotations

import dataclasses
import functools
import re
import time

from benchmarks.lib import tables
from benchmarks.lib import trace as trace_lib

OTHER = "other"

# xplane.proto (tsl/profiler/protobuf), only the fields this reduction reads
# (a parser skips the rest): message -> [(field, number, type, label, message type)]
_I64, _U64, _STR, _MSG = 3, 4, 9, 11
_SCHEMA = {
    "XSpace": [("planes", 1, _MSG, 3, "XPlane")],
    "XPlane": [("name", 2, _STR, 1, None), ("lines", 3, _MSG, 3, "XLine"),
               ("event_metadata", 4, _MSG, 3, "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, _MSG, 3, "XPlane.StatMetadataEntry")],
    "XLine": [("name", 2, _STR, 1, None), ("timestamp_ns", 3, _I64, 1, None),
              ("events", 4, _MSG, 3, "XEvent")],
    "XEvent": [("metadata_id", 1, _I64, 1, None), ("offset_ps", 2, _I64, 1, None),
               ("duration_ps", 3, _I64, 1, None)],
    "XStat": [("metadata_id", 1, _I64, 1, None), ("uint64_value", 3, _U64, 1, None),
              ("int64_value", 4, _I64, 1, None), ("str_value", 5, _STR, 1, None)],
    "XEventMetadata": [("name", 2, _STR, 1, None), ("stats", 5, _MSG, 3, "XStat")],
    "XStatMetadata": [("name", 2, _STR, 1, None)],
}
_MAPS = {"EventMetadataEntry": "XEventMetadata", "StatMetadataEntry": "XStatMetadata"}


@functools.lru_cache(maxsize=1)
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    package = "benchmarks.xplane"
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmarks_xplane.proto", package=package, syntax="proto3")

    def fill(message, fields):
        for name, number, kind, label, of in fields:
            f = message.field.add(name=name, number=number, type=kind, label=label)
            if of:
                f.type_name = f".{package}.{of}"

    for name, fields in _SCHEMA.items():
        message = fd.message_type.add(name=name)
        fill(message, fields)
        if name == "XPlane":
            for entry, value in _MAPS.items():
                nested = message.nested_type.add(name=entry)
                nested.options.map_entry = True
                fill(nested, [("key", 1, _I64, 1, None), ("value", 2, _MSG, 1, value)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName(package + ".XSpace"))


def scope_path(op_name: str) -> str:
    """``jit(f)/transpose(jvp(a/b))/c/mul:`` -> ``f/a/b/c/mul``: the path with
    the marks of JAX's transformations taken off, so that a pattern finds a
    scope in a forward, a backward and a vmapped program alike."""
    return re.sub(r"\w+\(|\)", "", op_name.rstrip(":"))


def collapse(path: str) -> str:
    """``.../blocks_17/...`` -> ``.../blocks_*/...``: one line for a layer's
    instances (``branch_r2`` stays: its number is a ratio, not an index)."""
    return re.sub(r"(?<=[a-z])_\d+(?=/|$)", "_*", path)


@dataclasses.dataclass
class ScopeReduction:
    window_s: float
    busy_s: float        # union of device-busy intervals, mean over devices
    n_devices: int
    op_self_s: dict      # (scope path or "", kind of operation) -> self seconds, mean over devices
    inherited_s: float   # self time of operations that took their predecessor's path
    no_path_s: float     # self time left with no path at all
    modules: dict        # module name, brackets off -> durations (s) of its runs that began in the window
    parse_s: float       # what this second parse of the xplane took

    def seconds(self, pattern: str) -> float:
        """Self seconds of the operations whose path the pattern is found in."""
        rx = re.compile(pattern)
        return sum(s for (path, _), s in self.op_self_s.items() if rx.search(f"/{path}/"))

    def groups(self, table: dict):
        """``({group: self seconds}, {group: {collapsed path: seconds}})`` by the
        cell's table, every operation in exactly one group; None where the
        trace holds none of the scopes the table requires (a program from
        before the names: its shares would be of other things)."""
        requires = [re.compile(p) for p in table.get("requires", ())]
        if requires and not any(rx.search(f"/{path}/") for rx in requires
                                for path, _ in self.op_self_s):
            return None
        patterns = [(g["name"], [re.compile(p) for p in g.get("match", ())])
                    for g in table["groups"]]
        seconds = {name: 0.0 for name, _ in patterns}
        paths = {name: {} for name, _ in patterns}
        for (path, _), s in self.op_self_s.items():
            padded = f"/{path}/"
            group = next((name for name, rxs in patterns
                          if any(rx.search(padded) for rx in rxs)), OTHER)
            seconds[group] += s
            key = collapse(path) or "(no path)"
            paths[group][key] = paths[group].get(key, 0.0) + s
        return seconds, paths


def _describe(plane):
    """``metadata id -> (scope path or "", kind of operation, program id)``
    for the plane's operations, each made on first use."""
    stat_ids = {m.name: k for k, m in plane.stat_metadata.items()}
    tf_op, program = stat_ids.get("tf_op"), stat_ids.get("program_id")

    @functools.lru_cache(maxsize=None)
    def describe(metadata_id):
        md = plane.event_metadata[metadata_id]
        stats = {stat.metadata_id: stat for stat in md.stats}
        own, prog = stats.get(tf_op), stats.get(program)
        return (scope_path(own.str_value) if own is not None else "",
                trace_lib.op_kind(md.name),
                (prog.uint64_value or prog.int64_value) if prog is not None else None)

    return describe


def reduce_scopes(path: str, host_spans, sync_host_ns: int):
    """Arguments as ``lib.trace.reduce_xplane``. Returns None where the trace
    holds no device timeline (a CPU rehearsal)."""
    t0 = time.perf_counter()
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices = [p for p in space.planes if p.name.startswith("/device:")
               and any(line.name == trace_lib.OPS_LINE for line in p.lines)]
    sync_device_ns = None
    for plane in devices[:1]:
        for line in plane.lines:
            if line.name != trace_lib.MODULES_LINE:
                continue
            for ev in line.events:
                if trace_lib.SYNC_NAME in plane.event_metadata[ev.metadata_id].name:
                    sync_device_ns = line.timestamp_ns + (ev.offset_ps + ev.duration_ps) / 1e3
                    break
    if sync_device_ns is None:
        return None
    shift = sync_device_ns - sync_host_ns  # host clock -> trace clock
    windows = [(a + shift, b + shift) for name, a, b in host_spans
               if name == trace_lib.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace_lib.WINDOW_SPAN!r} span, found {len(windows)}")
    lo, hi = windows[0]

    busy_ns = inherited_ns = no_path_ns = 0.0
    selfs, modules = {}, {}
    for plane in devices:
        describe = _describe(plane)
        events = []
        for line in plane.lines:
            if line.name == trace_lib.MODULES_LINE:
                for ev in line.events:
                    start = line.timestamp_ns + ev.offset_ps / 1e3
                    if lo <= start < hi:
                        name = plane.event_metadata[ev.metadata_id].name.split("(")[0]
                        modules.setdefault(name, []).append(ev.duration_ps / 1e12)
            if line.name != trace_lib.OPS_LINE:
                continue
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps / 1e3
                a, b = max(start, lo), min(start + ev.duration_ps / 1e3, hi)
                if b > a:
                    events.append((a, b, ev.metadata_id))
        # self time as lib/trace.py takes it: a span less what is nested inside
        busy_ns += sum(b - a for a, b in trace_lib._union((a, b) for a, b, _ in events))
        events.sort(key=lambda e: (e[0], -(e[1] - e[0])))
        own_ns, stack = [b - a for a, b, _ in events], []
        for i, (a, b, _) in enumerate(events):
            while stack and events[stack[-1]][1] <= a:
                stack.pop()
            if stack:
                own_ns[stack[-1]] -= min(b, events[stack[-1]][1]) - a
            stack.append(i)
        last_path = {}  # program -> the path of its operation that started last
        for (_, _, metadata_id), ns in zip(events, own_ns):
            path, kind, prog = describe(metadata_id)
            if path:
                last_path[prog] = path
            elif prog in last_path:
                path = last_path[prog]
                inherited_ns += ns
            else:
                no_path_ns += ns
            selfs[(path, kind)] = selfs.get((path, kind), 0.0) + ns
    n = max(len(devices), 1)
    return ScopeReduction(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_ns / n / 1e9,
        n_devices=len(devices),
        op_self_s={k: v / n / 1e9 for k, v in selfs.items()},
        inherited_s=inherited_ns / n / 1e9,
        no_path_s=no_path_ns / n / 1e9,
        modules=modules,
        parse_s=time.perf_counter() - t0,
    )


@functools.lru_cache(maxsize=None)
def table(kind: str) -> dict:
    """``benchmarks/scopes/<kind>.json``: the ordered groups of a cell kind."""
    return tables.load("scopes", kind)


def for_run(ctx):
    """The run's reduction, parsed on the first reader's call and kept on the
    context; None where the run has no device trace. ``ctx.trace_dir`` still
    holds the xplane while the readers run."""
    if not hasattr(ctx, "scope_reduction"):
        try:
            xplane = trace_lib.newest_xplane(ctx.trace_dir)
        except FileNotFoundError:
            ctx.scope_reduction = None
        else:
            ctx.scope_reduction = reduce_scopes(xplane, ctx.spans.spans, ctx.sync_host_ns)
            if ctx.scope_reduction is not None:
                ctx.notes.append(
                    f"scopes: second parse of the xplane took {ctx.scope_reduction.parse_s:.3f} s")
    return ctx.scope_reduction
