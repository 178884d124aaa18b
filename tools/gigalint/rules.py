"""Rule registry and the AST-level rules (GL001, GL002, GL004).

GL003 (sharding coverage) and GL005 (pytest hygiene) live in their own
modules — they are cross-file audits, not per-function AST walks — but
register here so the CLI sees one registry.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Callable, Dict, List, Optional, Set, Tuple

from tools.gigalint.astutils import (
    dotted_name,
    is_mutable_default,
    names_in,
)
from tools.gigalint.graph import Project, env_reader_functions
from tools.gigalint.walker import FunctionInfo


@dataclasses.dataclass
class Finding:
    rule: str
    path: str
    lineno: int
    symbol: str  # function qualname or harvested parameter name
    message: str
    waived_by: Optional[str] = None  # reason string once waived

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if self.waived_by is None:
            d.pop("waived_by")
        return d

    def text(self) -> str:
        return f"{self.path}:{self.lineno}: {self.rule} [{self.symbol}] {self.message}"


RULES: Dict[str, "Rule"] = {}


@dataclasses.dataclass
class Rule:
    rule_id: str
    summary: str
    check: Callable[[Project], List[Finding]]


def register(rule_id: str, summary: str):
    def deco(fn):
        RULES[rule_id] = Rule(rule_id, summary, fn)
        return fn

    return deco


# ---------------------------------------------------------------------------
# GL001 — trace-time environment reads
# ---------------------------------------------------------------------------

@register(
    "GL001",
    "environment read reachable from traced code: the value is baked in at "
    "trace time and the jit cache can serve kernels traced under stale flags",
)
def check_trace_env(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    reached = project.trace_reachable()
    readers = env_reader_functions(project)
    for fn, why in reached.items():
        for lineno, desc in fn.env_reads:
            findings.append(Finding(
                rule="GL001", path=fn.module.path, lineno=lineno,
                symbol=fn.qualname,
                message=f"direct env read ({desc}) in trace context: {why}. "
                "Hoist the read to the un-traced dispatch layer and pass the "
                "value in as a static argument.",
            ))
        for site in fn.calls:
            callee = project.resolve(fn.module, fn, site.callee)
            if callee in readers and callee is not fn:
                findings.append(Finding(
                    rule="GL001", path=fn.module.path, lineno=site.lineno,
                    symbol=fn.qualname,
                    message=f"call to env-reading helper "
                    f"{callee.module.path}::{callee.qualname} in trace "
                    f"context: {why}. Pass the flag value in instead.",
                ))
    return findings


# ---------------------------------------------------------------------------
# GL002 — tracer leaks
# ---------------------------------------------------------------------------

_NONDET_CALLS = (
    "time.time", "time.perf_counter", "time.monotonic", "time.time_ns",
    "datetime.now", "datetime.datetime.now", "uuid.uuid4",
)
_NP_ALIASES = ("np", "numpy", "onp")
_HOST_CASTS = ("bool", "int", "float")


def _derived_names(fn: FunctionInfo) -> Set[str]:
    """Traced params plus names assigned from expressions mentioning them
    (single forward pass — good enough for straight-line dispatch code)."""
    derived: Set[str] = set(fn.traced_params or [])
    if not derived:
        return derived
    for node in ast.walk(fn.node):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.AST):
            used = {n.id for n in names_in(node.value)}
            if used & derived:
                for tgt in node.targets:
                    for n in names_in(tgt):
                        derived.add(n.id)
    return derived


def _non_is_names(test: ast.AST) -> Set[str]:
    """Bare names in a condition, excluding operands of ``is (not) None``
    comparisons — ``if x is None`` on a traced argument is legitimate
    Python-level structure dispatch, not a tracer leak.

    The exemption is per NODE, not per name: in
    ``if x is not None and x > 0`` the ``x`` inside ``x > 0`` is a
    different Name node and still leaks the tracer, so it must be
    reported even though the same name also appears null-checked."""
    exempt: Set[ast.AST] = set()
    for node in ast.walk(test):
        if isinstance(node, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
        ):
            exempt.add(node.left)
            exempt.update(node.comparators)
    return {
        node.id
        for node in ast.walk(test)
        if isinstance(node, ast.Name) and node not in exempt
    }


@register(
    "GL002",
    "tracer leak: host-side value inspection or nondeterminism inside "
    "traced code (forces trace-time concretization or bakes in stale values)",
)
def check_tracer_leaks(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    reached = project.trace_reachable()
    roots = project.trace_roots()
    for fn in reached:
        # --- hazards valid in ANY trace context ---
        for node in ast.walk(fn.node):
            if isinstance(node, ast.Call):
                callee = dotted_name(node.func)
                if not callee:
                    continue
                if callee.endswith(".item") and not node.args:
                    findings.append(Finding(
                        "GL002", fn.module.path, node.lineno, fn.qualname,
                        ".item() in traced code forces a host sync at trace "
                        "time (and fails on abstract tracers under jit)",
                    ))
                elif callee in _NONDET_CALLS or any(
                    callee.startswith(f"{a}.random.") for a in _NP_ALIASES
                ) or callee.startswith("random."):
                    findings.append(Finding(
                        "GL002", fn.module.path, node.lineno, fn.qualname,
                        f"nondeterministic host call {callee}() in traced "
                        "code: the value is frozen at trace time and silently "
                        "reused from the jit cache",
                    ))
        # --- hazards needing known traced params: only functions whose
        # own decorator declares the traced/static split (jit/custom_vjp).
        # Pallas-containing helpers and defvjp pieces pass static geometry
        # ints positionally — flagging those would be all noise.
        if fn not in roots or not fn.is_trace_decorated or fn.traced_params is None:
            continue
        derived = _derived_names(fn)
        if not derived:
            continue
        for node in ast.walk(fn.node):
            if isinstance(node, ast.Call):
                callee = dotted_name(node.func)
                if not callee:
                    continue
                arg0 = node.args[0] if node.args else None
                arg_is_traced = isinstance(arg0, ast.Name) and arg0.id in derived
                if callee in _HOST_CASTS and arg_is_traced:
                    findings.append(Finding(
                        "GL002", fn.module.path, node.lineno, fn.qualname,
                        f"{callee}() on traced argument '{arg0.id}' "
                        "concretizes a tracer (TracerBoolConversionError at "
                        "best, silently stale constant at worst)",
                    ))
                elif arg_is_traced and any(
                    callee in (f"{a}.asarray", f"{a}.array") for a in _NP_ALIASES
                ):
                    findings.append(Finding(
                        "GL002", fn.module.path, node.lineno, fn.qualname,
                        f"{callee}() on traced argument '{arg0.id}' pulls the "
                        "value to the host inside jitted code",
                    ))
            elif isinstance(node, (ast.If, ast.While)):
                leak = _non_is_names(node.test) & derived
                if leak:
                    findings.append(Finding(
                        "GL002", fn.module.path, node.lineno, fn.qualname,
                        f"Python branch on traced argument(s) {sorted(leak)}: "
                        "branching must use lax.cond/jnp.where, or the "
                        "argument belongs in static_argnums",
                    ))
    return findings


# ---------------------------------------------------------------------------
# GL006 — bare print in library code
# ---------------------------------------------------------------------------

# Path segments that mark host-side tooling, not library code: drivers
# under scripts/, the test tree, demos. Test files are exempt wherever
# they live (the selftest fixture's tests/ subtree included).
_GL006_EXEMPT_SEGMENTS = frozenset({"scripts", "tests", "demo"})
_GL006_MSG = (
    "bare print() in library code: route console output through the obs "
    "layer (RunLog.echo for run-scoped drivers, gigapath_tpu.obs.console "
    "for one-off notices) so every run stays a machine-readable artifact"
)


@register(
    "GL006",
    "bare print() in library code — console output must flow through the "
    "obs layer (RunLog.echo / console); scripts, tests and demos exempt",
)
def check_library_prints(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules.values():
        segments = mod.path.split("/")[:-1]
        if mod.is_test_file or any(
            s in _GL006_EXEMPT_SEGMENTS for s in segments
        ):
            continue
        for fn in mod.functions.values():
            for site in fn.calls:
                if site.callee == "print":
                    findings.append(Finding(
                        "GL006", mod.path, site.lineno, fn.qualname, _GL006_MSG,
                    ))
        for site in mod.module_calls:
            if site.callee == "print":
                findings.append(Finding(
                    "GL006", mod.path, site.lineno, "<module>", _GL006_MSG,
                ))
    return findings


# ---------------------------------------------------------------------------
# GL007 — undocumented GIGAPATH_* flags
# ---------------------------------------------------------------------------

# Exact-match flag-name string literals only: docstrings and log messages
# mentioning a flag inline are prose, not a reference that creates a knob.
_GL007_FLAG = re.compile(r"\AGIGAPATH_[A-Z0-9_]+\Z")
_GL007_EXEMPT_SEGMENTS = _GL006_EXEMPT_SEGMENTS  # same host-tooling carve-out


def _gl007_readme_flags(readme_path: str) -> Optional[Set[str]]:
    """Flags documented in a README's flag table(s): GIGAPATH_* tokens on
    markdown table rows that also note the read-at semantics ("trace" or
    "host" in the row). None when the file does not exist."""
    if not os.path.isfile(readme_path):
        return None
    flags: Set[str] = set()
    with open(readme_path, "r", encoding="utf-8") as f:
        for line in f:
            stripped = line.strip()
            if not stripped.startswith("|"):
                continue
            low = stripped.lower()
            if "trace" not in low and "host" not in low:
                continue
            flags.update(re.findall(r"GIGAPATH_[A-Z0-9_]+", stripped))
    return flags


def _gl007_nearest_readme(project: Project, mod_path: str) -> Optional[str]:
    """Nearest ancestor README.md of a module (fixture trees carry their
    own), falling back to the project root's."""
    parts = mod_path.split("/")[:-1]
    for depth in range(len(parts), -1, -1):
        cand = os.path.join(project.root, *parts[:depth], "README.md")
        if os.path.isfile(cand):
            return cand
    return None


@register(
    "GL007",
    "GIGAPATH_* flag referenced in library code but absent from the README "
    "flag table — every flag must document its read-at (trace/host) "
    "semantics where users will look for it",
)
def check_flag_documentation(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    readme_cache: Dict[str, Optional[Set[str]]] = {}
    for mod in project.modules.values():
        segments = mod.path.split("/")[:-1]
        if mod.is_test_file or any(
            s in _GL007_EXEMPT_SEGMENTS for s in segments
        ):
            continue
        refs: List[tuple] = []  # (lineno, flag)
        for node in ast.walk(mod.tree):
            if (
                isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and _GL007_FLAG.match(node.value)
            ):
                refs.append((node.lineno, node.value))
        if not refs:
            continue
        readme = _gl007_nearest_readme(project, mod.path)
        key = readme or ""
        if key not in readme_cache:
            readme_cache[key] = (
                _gl007_readme_flags(readme) if readme else None
            )
        documented = readme_cache[key]
        # innermost enclosing function for the finding symbol
        spans = sorted(
            (
                (fn.lineno, getattr(fn.node, "end_lineno", fn.lineno), fn)
                for fn in mod.functions.values()
            ),
            key=lambda t: t[1] - t[0],
        )
        for lineno, flag in refs:
            if documented is not None and flag in documented:
                continue
            symbol = "<module>"
            for lo, hi, fn in spans:
                if lo <= lineno <= hi:
                    symbol = fn.qualname
                    break
            where = (
                f"the flag table in {os.path.relpath(readme, project.root)}"
                if readme
                else "any README.md flag table (none found above this file)"
            )
            findings.append(Finding(
                "GL007", mod.path, lineno, symbol,
                f"flag {flag} referenced in library code is missing from "
                f"{where}: add a table row noting its trace-time (or "
                "host-side) read semantics",
            ))
    return findings


# ---------------------------------------------------------------------------
# GL008 — timing hygiene
# ---------------------------------------------------------------------------

# Wall-clock sources whose deltas are meaningless around async-dispatched
# device work (resolved through the module's import aliases first).
_GL008_TIME_CALLS = frozenset({
    "time.time", "time.monotonic", "time.perf_counter",
    "time.time_ns", "time.monotonic_ns", "time.perf_counter_ns",
})
# Sanctioned fences: any of these anywhere in the timing function means
# the author thought about dispatch-vs-execution (function granularity —
# per-statement regions would be all noise in loop-shaped drivers).
_GL008_FENCE_SUFFIXES = ("block_until_ready", "chained_seconds_per_iter")
# tests and demos are exempt; scripts/ and library code are NOT — the
# measurement scripts are exactly where a dispatch-time number quietly
# becomes a published benchmark.
_GL008_EXEMPT_SEGMENTS = frozenset({"demo"})


def _gl008_resolved_callee(mod, callee: str) -> str:
    """Expand a leading import alias (``from time import monotonic`` ->
    ``time.monotonic``; ``import time as t`` -> ``time.*``)."""
    head, sep, rest = callee.partition(".")
    target = mod.imports.get(head)
    if target:
        return f"{target}.{rest}" if sep else target
    return callee


def _gl008_scan_function(project, mod, fn, reached) -> Optional[Finding]:
    """One GL008 verdict for a function: a wall-clock delta + a
    jit-reachable (or jit/wrap-bound) call + no fence -> finding."""
    timer_names: Set[str] = set()
    wrapped_names: Set[str] = set()
    delta_lineno: Optional[int] = None
    device_call: Optional[str] = None
    fenced = False

    def is_time_call(node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        name = dotted_name(node.func)
        return bool(name) and _gl008_resolved_callee(mod, name) in _GL008_TIME_CALLS

    from tools.gigalint.walker import TRACING_WRAPPERS

    for node in ast.walk(fn.node):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            callee = dotted_name(node.value.func) or ""
            if is_time_call(node.value):
                for tgt in node.targets:
                    for n in names_in(tgt):
                        timer_names.add(n.id)
            elif callee in TRACING_WRAPPERS or callee.endswith(".wrap"):
                # x = jax.jit(f) / x = watchdog.wrap(step): calls through
                # x dispatch compiled device work
                for tgt in node.targets:
                    for n in names_in(tgt):
                        wrapped_names.add(n.id)
        elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
            for side in (node.left, node.right):
                if is_time_call(side) or (
                    isinstance(side, ast.Name) and side.id in timer_names
                ):
                    delta_lineno = delta_lineno or node.lineno
        elif isinstance(node, ast.Call):
            callee = dotted_name(node.func)
            if not callee:
                continue
            if callee.endswith(_GL008_FENCE_SUFFIXES):
                fenced = True
            elif (callee == "span" or callee.endswith(".span")) and any(
                kw.arg == "fence"
                and not (
                    isinstance(kw.value, ast.Constant)
                    and kw.value.value in (None, False)
                )
                for kw in node.keywords
            ):
                # span(..., fence=None/False) is explicitly unfenced and
                # earns no credit; any other fence value counts
                fenced = True
            elif callee in wrapped_names:
                device_call = device_call or callee
            else:
                target = project.resolve(mod, fn, callee)
                if target is not None and target in reached:
                    device_call = device_call or callee

    if delta_lineno is None or device_call is None or fenced:
        return None
    return Finding(
        "GL008", mod.path, delta_lineno, fn.qualname,
        f"wall-clock delta around jit-reachable call '{device_call}()' "
        "without a device fence: under async dispatch this measures "
        "dispatch, not execution. Fence with block_until_ready, use "
        "chained_seconds_per_iter, or wrap the region in "
        "span(..., fence=True) (gigapath_tpu.obs.spans)",
    )


@register(
    "GL008",
    "timing hygiene: wall-clock delta around jit-reachable work without a "
    "device fence (block_until_ready / chained_seconds_per_iter / "
    "span(fence=True)) measures async dispatch, not execution",
)
def check_timing_hygiene(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    reached = project.trace_reachable()
    for mod in project.modules.values():
        segments = mod.path.split("/")[:-1]
        if mod.is_test_file or any(
            s in _GL008_EXEMPT_SEGMENTS for s in segments
        ) or "tests" in segments:
            continue
        for fn in mod.functions.values():
            finding = _gl008_scan_function(project, mod, fn, reached)
            if finding is not None:
                findings.append(finding)
    return findings


# ---------------------------------------------------------------------------
# GL012 — ad-hoc latency aggregation
# ---------------------------------------------------------------------------

# The pattern: wall-clock deltas appended to a bare list, then
# sorted/indexed for a percentile by hand. Three copies of that had
# grown by PR 9 (obs_report, serve_smoke, and the serving stats) with
# three subtly different nearest-rank conventions — and a list of every
# request's latency is unbounded memory on a serving path. Library code
# must aggregate through gigapath_tpu/obs/metrics.py (Histogram /
# percentile): one bounded, thread-exact, snapshot-able implementation.
_GL012_EXEMPT_SEGMENTS = frozenset({"scripts", "tests", "demo"})
# the sanctioned aggregation layer itself, matched by path segment so
# fixture trees can carry their own obs/ twin as a negative control
_GL012_SANCTIONED_SEGMENT = "obs"


def _gl012_scan_function(mod, fn) -> Optional[Finding]:
    """One GL012 verdict per function: a time-derived value appended to
    a list that the SAME function then sorts (``sorted(x)`` /
    ``x.sort()``) is a hand-rolled latency aggregation."""

    def resolved(callee: str) -> str:
        return _gl008_resolved_callee(mod, callee)

    def is_time_call(node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        name = dotted_name(node.func)
        return bool(name) and resolved(name) in _GL008_TIME_CALLS

    def time_derived(node: ast.AST) -> bool:
        """Expression mentions a timer/delta name or calls the clock."""
        for sub in ast.walk(node):
            if is_time_call(sub):
                return True
            if isinstance(sub, ast.Name) and sub.id in tainted:
                return True
        return False

    tainted: Set[str] = set()      # timer values and deltas of them
    latency_lists: Set[str] = set()  # lists holding time-derived appends
    append_lineno: Dict[str, int] = {}

    # pass 1: taint timer names and their deltas (two sweeps so a delta
    # assigned above its timer's textual position still taints)
    for _ in range(2):
        for node in ast.walk(fn.node):
            if isinstance(node, ast.Assign):
                value_tainted = is_time_call(node.value) or (
                    isinstance(node.value, ast.BinOp)
                    and isinstance(node.value.op, ast.Sub)
                    and time_derived(node.value)
                )
                if value_tainted:
                    for tgt in node.targets:
                        for n in names_in(tgt):
                            tainted.add(n.id)

    if not tainted:
        return None

    # pass 2: appends of time-derived values, and sorts of those lists
    for node in ast.walk(fn.node):
        if not isinstance(node, ast.Call):
            continue
        callee = dotted_name(node.func)
        if not callee:
            continue
        if callee.endswith(".append") and node.args and time_derived(
            node.args[0]
        ):
            owner = callee.rsplit(".", 1)[0]
            latency_lists.add(owner)
            append_lineno.setdefault(owner, node.lineno)
    if not latency_lists:
        return None
    for node in ast.walk(fn.node):
        if not isinstance(node, ast.Call):
            continue
        callee = dotted_name(node.func)
        if not callee:
            continue
        # the append pass tracks dotted owners ('self._walls'), so the
        # sorted() arm must resolve dotted names too — not just bare
        # ast.Name (sorted(self._walls) is the same aggregation)
        sorted_owner = (
            dotted_name(node.args[0])
            if callee == "sorted" and node.args else None
        )
        sorted_arg = sorted_owner is not None and \
            sorted_owner in latency_lists
        sort_method = (
            callee.endswith(".sort")
            and callee.rsplit(".", 1)[0] in latency_lists
        )
        if sorted_arg or sort_method:
            which = (
                sorted_owner if sorted_arg
                else callee.rsplit(".", 1)[0]
            )
            return Finding(
                "GL012", mod.path, node.lineno, fn.qualname,
                f"hand-rolled latency aggregation: wall-clock deltas "
                f"appended to '{which}' (line {append_lineno.get(which)}) "
                "and then sorted for percentiles. Library code must "
                "aggregate through gigapath_tpu.obs.metrics — a "
                "Histogram (bounded memory, exact concurrent counts, "
                "atomic snapshots) or the one shared percentile()",
            )
    return None


@register(
    "GL012",
    "ad-hoc latency aggregation in library code: wall-clock deltas "
    "appended to a list and sorted for percentiles by hand — use the typed "
    "metrics registry (gigapath_tpu.obs.metrics Histogram / the shared "
    "percentile) instead; scripts, tests, demos and obs/ itself exempt",
)
def check_latency_aggregation(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules.values():
        segments = mod.path.split("/")[:-1]
        if mod.is_test_file or any(
            s in _GL012_EXEMPT_SEGMENTS for s in segments
        ):
            continue
        if _GL012_SANCTIONED_SEGMENT in segments:
            continue  # the aggregation layer may aggregate
        for fn in mod.functions.values():
            finding = _gl012_scan_function(mod, fn)
            if finding is not None:
                findings.append(finding)
    return findings


# ---------------------------------------------------------------------------
# GL013 — unbounded hand-rolled queues
# ---------------------------------------------------------------------------

# An unbounded queue.Queue() (or deque used as an inter-thread buffer)
# between a producer and a consumer is backpressure deferred to the OOM
# killer: when the consumer falls behind, the channel grows without
# limit and nothing upstream ever learns. The serving queue
# (serve/queue.py: token-budgeted lanes + load shedding) and the
# cross-stage boundary (dist/boundary.py: credit-based flow control +
# schema'd ``backpressure`` events) are the two sanctioned channel
# implementations — everything else in library code must either bound
# its buffer (Queue(maxsize=...), deque(maxlen=...)) or go through
# them.
_GL013_QUEUE_CLASSES = frozenset({
    "queue.Queue", "queue.LifoQueue", "queue.PriorityQueue",
    "queue.SimpleQueue",  # SimpleQueue has no maxsize at all
})
_GL013_DEQUE = "collections.deque"
# sanctioned channel modules, matched by path suffix so fixture trees
# can carry their own twins as negative controls (the GL010/011 pattern)
_GL013_SANCTIONED_SUFFIXES = ("dist/boundary.py", "serve/queue.py")
_GL013_EXEMPT_SEGMENTS = frozenset({"scripts", "tests", "demo"})


def _gl013_positive_bound(node: ast.Call, *, kwarg: str,
                          positional_index: int) -> bool:
    """True when the construction carries a bound: a POSITIVE constant,
    or ANY non-constant expression (a computed bound is a bound the
    author thought about). ``maxsize=-1`` is Python's idiomatic
    *explicitly infinite* queue — the exact pattern this rule exists to
    catch — so non-positive constants (None/0/negatives) never count."""
    candidates = [kw.value for kw in node.keywords if kw.arg == kwarg]
    if len(node.args) > positional_index:
        candidates.append(node.args[positional_index])
    for value in candidates:
        if isinstance(value, ast.Constant):
            if isinstance(value.value, (int, float)) and not isinstance(
                value.value, bool
            ) and value.value > 0:
                return True
        elif isinstance(value, ast.UnaryOp) and isinstance(
            value.op, ast.USub
        ) and isinstance(value.operand, ast.Constant):
            continue  # -N parses as USub(Constant): explicitly unbounded
        else:
            return True  # computed bound
    return False


def _gl013_module_threads(mod) -> bool:
    """Does the module deal in threads (import threading/queue)? The
    inter-thread signal that turns a bare deque() from a scratch list
    into a channel candidate."""
    return any(
        target == "threading" or target.startswith("threading.")
        for target in mod.imports.values()
    )


@register(
    "GL013",
    "unbounded hand-rolled queue in library code: queue.Queue()/deque() used "
    "as an inter-thread channel without a maxsize/maxlen bound — bound it, or "
    "route through the sanctioned channels (serve/queue.py's token-budgeted "
    "lanes, dist/boundary.py's credit-based boundary)",
)
def check_unbounded_queues(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules.values():
        segments = mod.path.split("/")[:-1]
        if mod.is_test_file or any(
            s in _GL013_EXEMPT_SEGMENTS for s in segments
        ):
            continue
        if any(
            mod.path == s or mod.path == s.split("/")[-1]
            or mod.path.endswith("/" + s)
            for s in _GL013_SANCTIONED_SUFFIXES
        ):
            continue
        module_threaded = _gl013_module_threads(mod)
        spans = sorted(
            (
                (fn.lineno, getattr(fn.node, "end_lineno", fn.lineno), fn)
                for fn in mod.functions.values()
            ),
            key=lambda t: t[1] - t[0],
        )
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if not name:
                continue
            head, sep, rest = name.partition(".")
            target = mod.imports.get(head)
            resolved = (f"{target}.{rest}" if sep else target) if target else name
            if resolved in _GL013_QUEUE_CLASSES:
                if resolved != "queue.SimpleQueue" and _gl013_positive_bound(
                    node, kwarg="maxsize", positional_index=0
                ):
                    continue
                what = (
                    f"{resolved}() has no size bound at all"
                    if resolved == "queue.SimpleQueue"
                    else f"unbounded {resolved}() (no positive maxsize)"
                )
            elif resolved == _GL013_DEQUE and module_threaded:
                # deque(maxlen=...) is bounded; deque(iterable, maxlen)
                # passes it positionally
                if _gl013_positive_bound(node, kwarg="maxlen",
                                         positional_index=1):
                    continue
                what = (
                    "unbounded deque() in a threading module (an "
                    "inter-thread buffer without a maxlen)"
                )
            else:
                continue
            symbol = "<module>"
            for lo, hi, fn in spans:
                if lo <= node.lineno <= hi:
                    symbol = fn.qualname
                    break
            findings.append(Finding(
                "GL013", mod.path, node.lineno, symbol,
                f"{what}: a producer that outruns its consumer grows this "
                "buffer until the OOM killer is the backpressure. Bound it "
                "(maxsize/maxlen), or route the flow through the sanctioned "
                "channels — serve/queue.py (token-budgeted lanes + load "
                "shedding) or dist/boundary.py (credit-based flow control "
                "with backpressure events)",
            ))
    return findings


# ---------------------------------------------------------------------------
# GL010 — profiler trace hygiene
# ---------------------------------------------------------------------------

# jax.profiler's open-ended trace pair. The contextmanager form
# (jax.profiler.trace) is lexically scoped and self-closing; the
# start/stop pair is the dangerous one: a start without a guaranteed
# stop leaks an open trace across the rest of the run (every later op
# recorded, trace files growing unbounded), and scattered call sites
# defeat the anomaly engine's per-run capture budget. Library code must
# go through gigapath_tpu/obs/spans.py (trace()/start_trace()/
# stop_trace()), the one place with the stop-on-close and budget
# bookkeeping.
_GL010_TRACE_SUFFIXES = ("profiler.start_trace", "profiler.stop_trace")
_GL010_FULL_NAMES = frozenset({
    "jax.profiler.start_trace", "jax.profiler.stop_trace",
})
# the sanctioned passthrough module, matched by path suffix so fixture
# trees can carry their own obs/spans.py twin as a negative control
_GL010_SANCTIONED_SUFFIX = "obs/spans.py"
_GL010_EXEMPT_SEGMENTS = frozenset({"scripts", "tests", "demo"})


@register(
    "GL010",
    "jax.profiler.start_trace/stop_trace called directly in library code — "
    "open-ended trace capture must go through the sanctioned "
    "gigapath_tpu/obs/spans.py entry points (trace/start_trace/stop_trace), "
    "which own the stop-on-close and capture-budget bookkeeping",
)
def check_profiler_hygiene(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules.values():
        segments = mod.path.split("/")[:-1]
        if mod.is_test_file or any(
            s in _GL010_EXEMPT_SEGMENTS for s in segments
        ):
            continue
        if (
            mod.path == _GL010_SANCTIONED_SUFFIX.split("/")[-1]
            or mod.path.endswith("/" + _GL010_SANCTIONED_SUFFIX)
            or mod.path == _GL010_SANCTIONED_SUFFIX
        ):
            continue
        # innermost enclosing function for the finding symbol (the same
        # resolution GL007/GL009 use)
        spans = sorted(
            (
                (fn.lineno, getattr(fn.node, "end_lineno", fn.lineno), fn)
                for fn in mod.functions.values()
            ),
            key=lambda t: t[1] - t[0],
        )
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if not name:
                continue
            # expand a leading import alias (``from jax.profiler import
            # start_trace``; ``import jax.profiler as prof``)
            head, sep, rest = name.partition(".")
            target = mod.imports.get(head)
            resolved = (f"{target}.{rest}" if sep else target) if target else name
            if not (
                resolved in _GL010_FULL_NAMES
                or resolved.endswith(_GL010_TRACE_SUFFIXES)
            ):
                continue
            symbol = "<module>"
            for lo, hi, fn in spans:
                if lo <= node.lineno <= hi:
                    symbol = fn.qualname
                    break
            findings.append(Finding(
                "GL010", mod.path, node.lineno, symbol,
                f"direct {resolved}() in library code: route profiler "
                "capture through gigapath_tpu.obs.spans "
                "(trace()/start_trace()/stop_trace()) so every open trace "
                "has an owner that stops it and a capture budget",
            ))
    return findings


# ---------------------------------------------------------------------------
# GL011 — signal-handler hygiene
# ---------------------------------------------------------------------------

# A second signal.signal(SIGTERM, ...) call silently REPLACES the first:
# whichever library module installs its handler last wins, and the
# flight recorder's final dump (plus every chained recovery callback —
# emergency checkpoints, serving drains) silently stops running. Library
# code must register through gigapath_tpu/obs/flight.py's single
# chaining handler (register_signal_dump / register_signal_callback) —
# the one sanctioned signal.signal site.
_GL011_SIGNAL_SUFFIXES = ("signal.signal",)
_GL011_FULL_NAMES = frozenset({"signal.signal"})
# matched by path suffix so fixture trees can carry their own
# obs/flight.py twin as a negative control (the GL010 pattern)
_GL011_SANCTIONED_SUFFIX = "obs/flight.py"
_GL011_EXEMPT_SEGMENTS = frozenset({"scripts", "tests", "demo"})


@register(
    "GL011",
    "signal.signal() called directly in library code — a handler installed "
    "outside gigapath_tpu/obs/flight.py silently clobbers the chained "
    "SIGTERM handler (flight dump, emergency checkpoint, serving drain); "
    "register via flight.register_signal_dump/register_signal_callback",
)
def check_signal_hygiene(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules.values():
        segments = mod.path.split("/")[:-1]
        if mod.is_test_file or any(
            s in _GL011_EXEMPT_SEGMENTS for s in segments
        ):
            continue
        if (
            mod.path == _GL011_SANCTIONED_SUFFIX.split("/")[-1]
            or mod.path.endswith("/" + _GL011_SANCTIONED_SUFFIX)
            or mod.path == _GL011_SANCTIONED_SUFFIX
        ):
            continue
        spans = sorted(
            (
                (fn.lineno, getattr(fn.node, "end_lineno", fn.lineno), fn)
                for fn in mod.functions.values()
            ),
            key=lambda t: t[1] - t[0],
        )
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if not name:
                continue
            # expand a leading import alias (``from signal import
            # signal``; ``import signal as sig``)
            head, sep, rest = name.partition(".")
            target = mod.imports.get(head)
            resolved = (f"{target}.{rest}" if sep else target) if target else name
            # suffix match only at a dotted boundary: a bare endswith
            # would flag e.g. ``shutdown_signal.signal(...)`` (the name
            # 'shutdown_signal.signal' ends with 'signal.signal' without
            # ever touching the signal module)
            if not (
                resolved in _GL011_FULL_NAMES
                or any(resolved.endswith("." + s)
                       for s in _GL011_SIGNAL_SUFFIXES)
            ):
                continue
            symbol = "<module>"
            for lo, hi, fn in spans:
                if lo <= node.lineno <= hi:
                    symbol = fn.qualname
                    break
            findings.append(Finding(
                "GL011", mod.path, node.lineno, symbol,
                f"direct {resolved}() in library code: the last installer "
                "wins and the chained SIGTERM handler (flight dump + "
                "recovery callbacks) is silently clobbered — register via "
                "gigapath_tpu.obs.flight.register_signal_callback/"
                "register_signal_dump instead",
            ))
    return findings


# ---------------------------------------------------------------------------
# GL015 — raw socket hygiene
# ---------------------------------------------------------------------------

# Raw socket plumbing in library code means a second, unaudited
# transport: no credits, no backpressure events, no frame digests, no
# reconnect discipline — everything dist/transport.py exists to own in
# ONE place. And a blocking recv/accept/connect without a configured
# deadline is the classic distributed-systems hang: a silent peer parks
# the process forever with no stall event and no recovery path. Two
# checks:
#   1. socket/socketserver CONNECTION primitives (socket.socket,
#      create_connection/server, socketpair, any socketserver.*) in
#      library code only inside the path-sanctioned dist/transport.py;
#   2. EVEN THERE, every function that calls .recv/.accept/.connect
#      (or create_connection) must configure a deadline in that same
#      function: settimeout(non-None), setblocking(False), a
#      select(timeout=...), or create_connection(..., timeout=...).
_GL015_SOCKET_CALLS = frozenset({
    "socket.socket", "socket.create_connection", "socket.create_server",
    "socket.socketpair", "socket.fromfd",
})
_GL015_BLOCKING_SUFFIXES = (".recv", ".recvfrom", ".recv_into",
                            ".accept", ".connect")
# matched by path suffix so fixture trees can carry their own
# dist/transport.py twin (the GL010/GL011/GL013 pattern)
_GL015_SANCTIONED_SUFFIX = "dist/transport.py"
_GL015_EXEMPT_SEGMENTS = frozenset({"scripts", "tests", "demo"})


def _gl015_resolved(mod, name: str) -> str:
    head, sep, rest = name.partition(".")
    target = mod.imports.get(head)
    if target:
        return f"{target}.{rest}" if sep else target
    return name


def _gl015_module_sockets(mod) -> bool:
    """Does the module deal in sockets (import socket/socketserver at
    any level)? The scoping signal for the deadline discipline —
    ``.connect()`` on a database handle in a socket-free module is not
    this rule's business."""
    return any(
        target in ("socket", "socketserver")
        or target.startswith("socket.")
        or target.startswith("socketserver.")
        for target in mod.imports.values()
    )


def _gl015_conn_timeout(node: ast.Call) -> bool:
    """create_connection carries its deadline inline: a second
    positional or a non-None ``timeout`` kwarg."""
    if len(node.args) >= 2:
        return True
    for kw in node.keywords:
        if kw.arg == "timeout" and not (
            isinstance(kw.value, ast.Constant) and kw.value.value is None
        ):
            return True
    return False


def _gl015_fn_has_deadline(mod, fn) -> bool:
    """Any deadline-configuring call inside the function body."""
    for node in ast.walk(fn.node):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func)
        if not name:
            continue
        if name.endswith(".settimeout") and node.args:
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant) and arg.value is None):
                return True
        elif name.endswith(".setblocking") and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and arg.value is False:
                return True
        elif name.endswith(".select"):
            # the timeout operand's position depends on the API:
            # selectors' select(timeout) is the ONLY positional; stdlib
            # select.select(r, w, x, timeout) puts it fourth — a
            # 3-positional select.select(r, w, x) blocks forever and
            # must earn NO credit (its rlist is not a deadline)
            operands = [kw.value for kw in node.keywords
                        if kw.arg == "timeout"]
            if len(node.args) >= 4:
                operands.append(node.args[3])
            elif len(node.args) == 1:
                operands.append(node.args[0])
            if any(
                not (isinstance(op, ast.Constant) and op.value is None)
                for op in operands
            ):
                return True
    return False


@register(
    "GL015",
    "raw socket use in library code outside the sanctioned "
    "dist/transport.py, or a blocking recv/accept/connect without a "
    "configured timeout (flagged even inside the sanctioned transport) — "
    "sockets get credits/digests/reconnect discipline in ONE place, and "
    "no read blocks without a deadline",
)
def check_socket_hygiene(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules.values():
        segments = mod.path.split("/")[:-1]
        if mod.is_test_file or any(
            s in _GL015_EXEMPT_SEGMENTS for s in segments
        ):
            continue
        sanctioned = (
            mod.path == _GL015_SANCTIONED_SUFFIX
            or mod.path == _GL015_SANCTIONED_SUFFIX.split("/")[-1]
            or mod.path.endswith("/" + _GL015_SANCTIONED_SUFFIX)
        )
        module_sockets = _gl015_module_sockets(mod)
        spans = sorted(
            (
                (fn.lineno, getattr(fn.node, "end_lineno", fn.lineno), fn)
                for fn in mod.functions.values()
            ),
            key=lambda t: t[1] - t[0],
        )

        def symbol_at(lineno: int) -> str:
            for lo, hi, fn in spans:
                if lo <= lineno <= hi:
                    return fn.qualname
            return "<module>"

        # check 1: connection primitives outside the sanctioned module
        if not sanctioned:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func)
                if not name:
                    continue
                resolved = _gl015_resolved(mod, name)
                if resolved in _GL015_SOCKET_CALLS or resolved.startswith(
                    "socketserver."
                ):
                    findings.append(Finding(
                        "GL015", mod.path, node.lineno,
                        symbol_at(node.lineno),
                        f"raw {resolved}() in library code: a second "
                        "unaudited transport with no credits, digests or "
                        "reconnect discipline — route the flow through "
                        "gigapath_tpu/dist/transport.py (or the boundary "
                        "channels behind it)",
                    ))
        # check 2: deadline discipline, sanctioned module INCLUDED
        if not module_sockets:
            continue
        for fn in mod.functions.values():
            has_deadline = _gl015_fn_has_deadline(mod, fn)
            for node in ast.walk(fn.node):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func)
                if not name:
                    continue
                resolved = _gl015_resolved(mod, name)
                if resolved.endswith("create_connection"):
                    if not _gl015_conn_timeout(node):
                        findings.append(Finding(
                            "GL015", mod.path, node.lineno, fn.qualname,
                            "create_connection() without a timeout: a "
                            "silent peer parks this call forever — pass "
                            "timeout= (the connect deadline)",
                        ))
                    continue
                if any(name.endswith(s) for s in _GL015_BLOCKING_SUFFIXES) \
                        and "." in name and not has_deadline:
                    findings.append(Finding(
                        "GL015", mod.path, node.lineno, fn.qualname,
                        f"blocking {name.rsplit('.', 1)[1]}() with no "
                        "configured deadline in this function: a silent "
                        "peer hangs the process with no stall event — "
                        "settimeout(...), setblocking(False) + select("
                        "timeout=...), or bound the wait another way",
                    ))
                    break  # one deadline finding per function is enough
    return findings


# ---------------------------------------------------------------------------
# GL016 — raw low-precision casts outside the quant module
# ---------------------------------------------------------------------------

# A raw astype/asarray to int8 or a float8_* dtype in library code is a
# second, unaudited quantization: no scale contract, no per-channel
# calibration, no round-trip guarantee — exactly the drift class the
# quant subsystem's parity harness exists to pin. Low-precision casts
# are sanctioned only inside the ``quant/`` package (matched by path
# SEGMENT so the fixture tree can carry its own quant/ twin as a
# negative control), where qtensor.py's helpers own the scale/clip/
# dequant contract. uint8 is NOT this rule's business (images are
# uint8); neither are bf16/f16 casts (activation dtypes, not storage
# quantization).
_GL016_CAST_CALLS = frozenset({
    "asarray", "array", "full", "zeros", "ones", "empty",
})
_GL016_ARRAY_MODULES = ("numpy", "jax.numpy")
_GL016_SANCTIONED_SEGMENT = "quant"
_GL016_EXEMPT_SEGMENTS = frozenset({"scripts", "tests", "demo"})


def _gl016_lowprec_name(node) -> Optional[str]:
    """Resolve a dtype operand to a low-precision name, or None:
    attribute/name forms (``jnp.int8``, ``np.float8_e4m3fn``, a bare
    ``int8`` after a from-import) and string literals ('int8',
    'float8_e4m3fn')."""
    if isinstance(node, (ast.Attribute, ast.Name)):
        name = dotted_name(node)
        if name:
            tail = name.rsplit(".", 1)[-1]
            if tail == "int8" or tail.startswith("float8"):
                return tail
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        value = node.value.strip().lower()
        if value == "int8" or value.startswith("float8"):
            return value
    return None


@register(
    "GL016",
    "raw low-precision cast (astype/asarray to int8/float8_*) in library "
    "code outside the sanctioned quant/ module — quantization must go "
    "through gigapath_tpu/quant/qtensor.py's helpers, which own the "
    "scale/clip/dequant contract; scripts, tests and demos exempt",
)
def check_lowprec_casts(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules.values():
        segments = mod.path.split("/")[:-1]
        if mod.is_test_file or any(
            s in _GL016_EXEMPT_SEGMENTS for s in segments
        ):
            continue
        if _GL016_SANCTIONED_SEGMENT in segments:
            continue  # the quant package may quantize
        spans = sorted(
            (
                (fn.lineno, getattr(fn.node, "end_lineno", fn.lineno), fn)
                for fn in mod.functions.values()
            ),
            key=lambda t: t[1] - t[0],
        )

        def symbol_at(lineno: int) -> str:
            for lo, hi, fn in spans:
                if lo <= lineno <= hi:
                    return fn.qualname
            return "<module>"

        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            lowprec = None
            how = ""
            # .astype on ANY receiver (a dotted name resolves for the
            # message; an expression receiver — (w / s).astype(int8) —
            # is the same cast and must not slip through)
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "astype"
                and node.args
            ):
                lowprec = _gl016_lowprec_name(node.args[0])
                how = f"{dotted_name(node.func) or '<expr>.astype'}()"
            name = dotted_name(node.func)
            if lowprec is None and not name:
                continue
            if lowprec is None:
                head, sep, rest = name.partition(".")
                target = mod.imports.get(head)
                resolved = (
                    (f"{target}.{rest}" if sep else target)
                    if target else name
                )
                mod_name, _, func = resolved.rpartition(".")
                if (
                    func in _GL016_CAST_CALLS
                    and mod_name in _GL016_ARRAY_MODULES
                ):
                    candidates = [
                        kw.value for kw in node.keywords if kw.arg == "dtype"
                    ]
                    if len(node.args) >= 2:
                        candidates.append(node.args[1])
                    for cand in candidates:
                        lowprec = _gl016_lowprec_name(cand)
                        if lowprec:
                            how = f"{resolved}(dtype={lowprec})"
                            break
            if lowprec is None:
                continue
            findings.append(Finding(
                "GL016", mod.path, node.lineno, symbol_at(node.lineno),
                f"raw low-precision cast {how or lowprec} in library "
                "code: an unaudited quantization with no scale contract "
                "— route it through gigapath_tpu/quant/qtensor.py "
                "(quantize_per_channel / dequantize / QTensor), the ONE "
                "sanctioned quantize/dequantize helper set",
            ))
    return findings


# ---------------------------------------------------------------------------
# GL017 — kernel-dispatch env reads outside snapshot_flags
# ---------------------------------------------------------------------------

# A read of one of the attention kernels' dispatch switches anywhere
# else in library code is a second, unaudited dispatch decision: the
# forward and backward of one call, or two branches of one op, could
# see different values, and an explicit ``flags=`` argument would
# silently lose to it. Reads are sanctioned only inside
# ``snapshot_flags`` (the one read, threaded everywhere as a
# PipelineFlags snapshot). Host-side flags (GIGAPATH_OBS,
# GIGAPATH_SERVE_*, the tile encoder's GIGAPATH_QUANT_TILE, a driver's
# GIGAPATH_CHUNKED_PREFILL, ...) are not this rule's business — only
# the set below, the environment twins of PipelineFlags' fields
# (ops/pallas_dilated.FLAG_ENV; tests/test_layering.py holds the two
# equal).
_GL017_FLAGS = frozenset({
    "GIGAPATH_PIPELINED_BWD", "GIGAPATH_PIPE_BWD_BLOCK_K",
    "GIGAPATH_STREAMING_FUSION", "GIGAPATH_RING_ATTN",
    "GIGAPATH_FOLD_PALLAS", "GIGAPATH_FOLD_BLOCK_Q",
    "GIGAPATH_FOLD_BLOCK_K",
})
_GL017_SANCTIONED_FUNC = "snapshot_flags"
_GL017_EXEMPT_SEGMENTS = frozenset({"scripts", "tests", "demo"})


def _gl017_read_flag(node: ast.Call) -> Optional[str]:
    """The dispatch-flag name a call reads, or None: os.environ.get /
    os.getenv / environ.setdefault under any alias, and the shared
    env_flag helper (any alias ending in env_flag), with a literal
    first argument from the dispatch set."""
    fn = dotted_name(node.func)
    if not fn:
        return None
    reader = (
        "environ" in fn and fn.rsplit(".", 1)[-1] in ("get", "setdefault")
    ) or fn.endswith("getenv") or fn.endswith("env_flag")
    if not reader or not node.args:
        return None
    arg = node.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str) \
            and arg.value in _GL017_FLAGS:
        return arg.value
    return None


@register(
    "GL017",
    "kernel-dispatch GIGAPATH_* variant/block flag read in library code "
    "outside snapshot_flags — dispatch is an explicit flags= argument or "
    "ONE snapshot of the environment per public call; a stray read lets "
    "two halves of one call disagree; scripts, tests and demos exempt",
)
def check_dispatch_env_reads(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules.values():
        segments = mod.path.split("/")[:-1]
        if mod.is_test_file or any(
            s in _GL017_EXEMPT_SEGMENTS for s in segments
        ):
            continue
        spans = sorted(
            (
                (fn.lineno, getattr(fn.node, "end_lineno", fn.lineno), fn)
                for fn in mod.functions.values()
            ),
            key=lambda t: t[1] - t[0],
        )

        def symbol_at(lineno: int) -> str:
            for lo, hi, fn in spans:
                if lo <= lineno <= hi:
                    return fn.qualname
            return "<module>"

        for node in ast.walk(mod.tree):
            flag = None
            how = ""
            if isinstance(node, ast.Call):
                flag = _gl017_read_flag(node)
                how = f"{dotted_name(node.func)}({flag!r})" if flag else ""
            elif isinstance(node, ast.Subscript) and isinstance(
                node.ctx, ast.Load
            ):
                base = dotted_name(node.value)
                sl = node.slice
                if (
                    base and base.endswith("environ")
                    and isinstance(sl, ast.Constant)
                    and isinstance(sl.value, str)
                    and sl.value in _GL017_FLAGS
                ):
                    flag = sl.value
                    how = f"{base}[{flag!r}]"
            if flag is None:
                continue
            symbol = symbol_at(node.lineno)
            if symbol.rsplit(".", 1)[-1] == _GL017_SANCTIONED_FUNC:
                continue  # the one sanctioned flag-VALUE read point
            findings.append(Finding(
                "GL017", mod.path, node.lineno, symbol,
                f"kernel-dispatch env read {how} in library code: this "
                "flag is read ONCE per public call by snapshot_flags — "
                "take the PipelineFlags snapshot from the caller instead "
                "of re-reading the environment",
            ))
    return findings


# ---------------------------------------------------------------------------
# GL004 — forbidden APIs
# ---------------------------------------------------------------------------

@register(
    "GL004",
    "forbidden API: eval/exec, bare except (swallows KeyboardInterrupt and "
    "masks checkpoint-IO corruption), or mutable default argument",
)
def check_forbidden(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules.values():
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                fn = dotted_name(node.func)
                if fn in ("eval", "exec"):
                    findings.append(Finding(
                        "GL004", mod.path, node.lineno, fn,
                        f"{fn}() is forbidden — use ast.literal_eval or an "
                        "explicit registry",
                    ))
            elif isinstance(node, ast.ExceptHandler) and node.type is None:
                findings.append(Finding(
                    "GL004", mod.path, node.lineno, "except",
                    "bare 'except:' — catch a concrete exception type "
                    "(bare except swallows KeyboardInterrupt/SystemExit and "
                    "hides corrupted checkpoint IO)",
                ))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for default in list(node.args.defaults) + [
                    d for d in node.args.kw_defaults if d is not None
                ]:
                    if is_mutable_default(default):
                        findings.append(Finding(
                            "GL004", mod.path, node.lineno, node.name,
                            f"mutable default argument in {node.name}() is "
                            "shared across calls — default to None and "
                            "construct inside",
                        ))
    return findings


# ---------------------------------------------------------------------------
# GL014 — chunk reassembly in streaming-sanctioned modules
# ---------------------------------------------------------------------------

# The streaming-prefill modules exist to fold chunk lists WITHOUT ever
# materializing the dense sequence (ops/streaming_prefill.py,
# models/streaming_encoder.py). A jnp.concatenate/stack over the chunk
# axis inside them silently reintroduces the O(L) buffer the feature
# removes — numerically invisible, exactly the regression a reviewer
# will not catch. The one sanctioned reassembly is the oracle/fallback
# surface, marked by a ``dense_fallback`` function name (matched on the
# enclosing function's qualname, so helpers nested under the fallback
# stay sanctioned too).
_GL014_STREAMING_SUFFIXES = (
    "ops/streaming_prefill.py",
    "models/streaming_encoder.py",
)
_GL014_REASSEMBLY = frozenset({
    "jax.numpy.concatenate", "jax.numpy.stack",
    "jax.numpy.vstack", "jax.numpy.hstack",
    "numpy.concatenate", "numpy.stack",
    "numpy.vstack", "numpy.hstack",
})
_GL014_SANCTION_MARK = "dense_fallback"


@register(
    "GL014",
    "chunk-list reassembly in a streaming-sanctioned module: "
    "concatenate/stack here rebuilds the dense sequence the streaming "
    "prefill exists to never materialize — fold blockwise (partial "
    "attention + combine_partials, per-block reductions), or move the "
    "code into an explicit *dense_fallback* oracle function",
)
def check_streaming_reassembly(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules.values():
        if not any(
            mod.path == s or mod.path == s.split("/")[-1]
            or mod.path.endswith("/" + s)
            for s in _GL014_STREAMING_SUFFIXES
        ):
            continue
        spans = sorted(
            (
                (fn.lineno, getattr(fn.node, "end_lineno", fn.lineno), fn)
                for fn in mod.functions.values()
            ),
            key=lambda t: t[1] - t[0],
        )
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if not name:
                continue
            head, sep, rest = name.partition(".")
            target = mod.imports.get(head)
            resolved = (f"{target}.{rest}" if sep else target) if target else name
            if resolved not in _GL014_REASSEMBLY:
                continue
            symbol = "<module>"
            for lo, hi, fn in spans:
                if lo <= node.lineno <= hi:
                    symbol = fn.qualname
                    break
            if _GL014_SANCTION_MARK in symbol:
                continue  # the sanctioned oracle/fallback surface
            findings.append(Finding(
                "GL014", mod.path, node.lineno, symbol,
                f"{resolved}() in a streaming-sanctioned module "
                "reassembles chunks into a dense sequence: the fold "
                "path must stay O(chunk) — merge partials with "
                "combine_partials / per-block reductions instead, or "
                "rename the enclosing function *dense_fallback* if it "
                "IS the sanctioned oracle path",
            ))
    return findings


# ---------------------------------------------------------------------------
# GL022 — untraced spans in distributed library code
# ---------------------------------------------------------------------------

# The fleet timeline (obs/fleet.py) is assembled from per-process trace
# exports: a span in dist/ library code that does not thread the slide's
# TraceContext (``span(..., trace=ctx)``) records into the local runlog
# but falls OUT of the merged cross-process tree — its seconds silently
# land in the critical path's "idle" bucket and the causality invariants
# go blind to it. That is exactly the kind of gap nobody notices until a
# production straggler hunt comes up empty. Host tooling (scripts/,
# tests/, demos) renders single-process reports and is exempt; manual
# ``ctx.add_span(...)`` calls (the deliver/fold paths that measure
# across ``with`` boundaries) are invisible to this rule by design —
# they already name a context.
_GL022_EXEMPT_SEGMENTS = frozenset({"scripts", "tests", "demo"})
_GL022_PATH_SEGMENT = "dist"


@register(
    "GL022",
    "span() in dist/ library code without a trace= context: the span "
    "lands in the local runlog but not the fleet's merged cross-process "
    "timeline — thread the slide's TraceContext "
    "(span(..., trace=ctx), gigapath_tpu.obs.reqtrace)",
)
def check_untraced_dist_spans(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules.values():
        segments = mod.path.split("/")[:-1]
        if _GL022_PATH_SEGMENT not in segments:
            continue
        if mod.is_test_file or any(
            s in _GL022_EXEMPT_SEGMENTS for s in segments
        ):
            continue
        # innermost-enclosing-function attribution (the GL014 pattern):
        # smallest span containing the call wins
        spans = sorted(
            (
                (fn.lineno, getattr(fn.node, "end_lineno", fn.lineno), fn)
                for fn in mod.functions.values()
            ),
            key=lambda t: t[1] - t[0],
        )
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = dotted_name(node.func)
            if not callee or not (
                callee == "span" or callee.endswith(".span")
            ):
                continue
            if any(
                kw.arg == "trace"
                and not (
                    isinstance(kw.value, ast.Constant)
                    and kw.value.value in (None, False)
                )
                for kw in node.keywords
            ):
                # trace=<ctx> threads the fleet context (the GL008
                # fence-kwarg shape: an explicit None/False earns no
                # credit — it IS the untraced case, spelled out)
                continue
            symbol = "<module>"
            for lo, hi, fn in spans:
                if lo <= node.lineno <= hi:
                    symbol = fn.qualname
                    break
            findings.append(Finding(
                "GL022", mod.path, node.lineno, symbol,
                "span() in dist/ library code without a trace= context: "
                "this span never reaches the fleet's merged timeline — "
                "its wall lands in the critical path's idle bucket and "
                "the cross-process causality checks cannot see it. "
                "Thread the slide's TraceContext: span(..., trace=ctx)",
            ))
    return findings


# ---------------------------------------------------------------------------
# GL023 — hand-rolled running-moment accumulators
# ---------------------------------------------------------------------------

# The pattern: a Welford-style running-moment update written by hand in
# library code — a sample count bumped by one, a mean nudged by
# ``delta / count``, and a squared-delta sum (M2 / variance numerator)
# accumulated in the SAME function. Hand-rolled copies drift on the
# merge rule (Chan's cross term is easy to get wrong), cannot be
# combined across shards, and have no save/load discipline. Time- or
# batch-series moments in library code must go through
# gigapath_tpu/obs — EmbeddingSketch (count/mean/M2 + merge +
# manifest-verified artifacts) or the metrics registry. The obs/
# segment itself is sanctioned (it IS the accumulator layer), matched
# by path segment so fixture trees can carry their own obs/ twin as a
# negative control; scripts, tests and demos render one-shot reports
# and are exempt.
_GL023_EXEMPT_SEGMENTS = frozenset({"scripts", "tests", "demo"})
_GL023_SANCTIONED_SEGMENT = "obs"


def _gl023_scan_function(mod, fn) -> Optional[Finding]:
    """One GL023 verdict per function: the Welford triple — a count
    bumped by one, a mean updated via a division by that count, and a
    product-of-deltas accumulation — co-occurring in one function is a
    hand-rolled running-moment accumulator."""

    def owner(node: ast.AST) -> Optional[str]:
        name = dotted_name(node)
        return name or None

    def self_add(node: ast.AST) -> Optional[Tuple[str, ast.AST]]:
        """``x += expr`` or ``x = x + expr`` -> (owner, added expr)."""
        if isinstance(node, ast.AugAssign) and isinstance(node.op, ast.Add):
            tgt = owner(node.target)
            if tgt:
                return tgt, node.value
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.value, ast.BinOp)
                and isinstance(node.value.op, ast.Add)):
            tgt = owner(node.targets[0])
            if tgt and owner(node.value.left) == tgt:
                return tgt, node.value.right
            if tgt and owner(node.value.right) == tgt:
                return tgt, node.value.left
        return None

    # pass 1: sample counters (n += 1 / self._n = self._n + 1)
    counts: Set[str] = set()
    for node in ast.walk(fn.node):
        bump = self_add(node)
        if (bump is not None and isinstance(bump[1], ast.Constant)
                and bump[1].value == 1):
            counts.add(bump[0])
    if not counts:
        return None

    # pass 2: a mean update — any assignment whose value divides by one
    # of the counters (mean += delta / n, or Chan's merged-mean form)
    mean_line: Optional[int] = None
    for node in ast.walk(fn.node):
        if not isinstance(node, (ast.Assign, ast.AugAssign)):
            continue
        for sub in ast.walk(node.value):
            if (isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Div)
                    and owner(sub.right) in counts):
                mean_line = mean_line or node.lineno
    if mean_line is None:
        return None

    # pass 3: the second-moment accumulation — a self-add (to a target
    # that is not the counter) of a product of two non-constant factors
    # (delta * delta2 / delta**2-shaped cross terms)
    for node in ast.walk(fn.node):
        acc = self_add(node)
        if acc is None or acc[0] in counts:
            continue
        for sub in ast.walk(acc[1]):
            if (isinstance(sub, ast.BinOp)
                    and isinstance(sub.op, (ast.Mult, ast.Pow))
                    and not isinstance(sub.left, ast.Constant)
                    and not isinstance(sub.right, ast.Constant)):
                return Finding(
                    "GL023", mod.path, node.lineno, fn.qualname,
                    f"hand-rolled running-moment accumulator: a sample "
                    f"count, a mean update dividing by it (line "
                    f"{mean_line}), and a squared-delta accumulation "
                    f"into '{acc[0]}' in one function. Library code "
                    "must accumulate moments through gigapath_tpu.obs "
                    "— EmbeddingSketch (mergeable count/mean/M2 with "
                    "manifest-verified save/load) or the metrics "
                    "registry — not a by-hand Welford loop",
                )
    return None


@register(
    "GL023",
    "hand-rolled running-moment accumulator in library code: count bump + "
    "mean-update-by-count + squared-delta sum in one function — use "
    "gigapath_tpu.obs (EmbeddingSketch / metrics registry) instead; "
    "scripts, tests, demos and obs/ itself exempt",
)
def check_running_moments(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules.values():
        segments = mod.path.split("/")[:-1]
        if mod.is_test_file or any(
            s in _GL023_EXEMPT_SEGMENTS for s in segments
        ):
            continue
        if _GL023_SANCTIONED_SEGMENT in segments:
            continue  # the accumulator layer may accumulate
        for fn in mod.functions.values():
            finding = _gl023_scan_function(mod, fn)
            if finding is not None:
                findings.append(finding)
    return findings
