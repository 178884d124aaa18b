"""GL005 — pytest hygiene: slow-only kernel coverage needs fast siblings.

The repo's contract (tests/conftest.py) is that everything in the slow
tier has a faster sibling covering the same code path in the default
tier. The round-5 advisor found the new kernel-flag parity tests broke
that contract silently: every test exercising GIGAPATH_PIPELINED_BWD (and
the forward twin it then had) and the seq-parallel fused routing was
slow-only, so
``pytest -q`` exercised none of the new kernel paths.

This rule makes the contract mechanical, per test file:

- every ``GIGAPATH_*`` env flag set (monkeypatch.setenv) in a slow test
  must also be set in at least one non-slow test in the same file;
- if any slow test uses ``shard_map`` (seq-parallel routing), some
  non-slow test in the same file must too.

"Slow" means ``@pytest.mark.slow`` (function or class) or an exact-name
entry in conftest's ``_SLOW_NODEIDS`` tier list.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.gigalint.astutils import dotted_name, str_tuple_literal
from tools.gigalint.graph import Project
from tools.gigalint.rules import Finding, register
from tools.gigalint.walker import ModuleInfo


def _slow_nodeids(project: Project) -> Set[Tuple[str, str]]:
    """{(test file basename, "Class.name" | "name")} from any scanned
    conftest's _SLOW_NODEIDS tuple."""
    out: Set[Tuple[str, str]] = set()
    for mod in project.modules.values():
        if not mod.path.endswith("conftest.py"):
            continue
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "_SLOW_NODEIDS"
                for t in node.targets
            ):
                vals = str_tuple_literal(node.value) or []
                for nodeid in vals:
                    parts = nodeid.split("::")
                    if len(parts) >= 2:
                        out.add((parts[0], ".".join(parts[1:])))
    return out


def _has_slow_marker(node) -> bool:
    for deco in node.decorator_list:
        name = dotted_name(deco)
        if name and name.endswith("mark.slow"):
            return True
    return False


class _TestScan(ast.NodeVisitor):
    """Collect (qualname, slow?, flags set, uses shard_map?) per test."""

    def __init__(self, mod: ModuleInfo, slow_ids: Set[Tuple[str, str]]):
        self.mod = mod
        self.base = mod.path.rsplit("/", 1)[-1]
        self.slow_ids = slow_ids
        self.tests: List[Tuple[str, bool, Set[str], bool, int]] = []
        self._class: Optional[str] = None
        self._class_slow = False

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        if node.name.startswith("Test"):
            prev, prev_slow = self._class, self._class_slow
            self._class, self._class_slow = node.name, _has_slow_marker(node)
            self.generic_visit(node)
            self._class, self._class_slow = prev, prev_slow

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        if not node.name.startswith("test_"):
            return
        qual = f"{self._class}.{node.name}" if self._class else node.name
        slow = (
            _has_slow_marker(node)
            or self._class_slow
            or (self.base, qual) in self.slow_ids
        )
        flags: Set[str] = set()
        uses_shard_map = False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                fn = dotted_name(sub.func)
                if fn and fn.endswith("setenv") and sub.args:
                    arg0 = sub.args[0]
                    if isinstance(arg0, ast.Constant) and isinstance(
                        arg0.value, str
                    ) and arg0.value.startswith("GIGAPATH_"):
                        flags.add(arg0.value)
            elif isinstance(sub, ast.Attribute) and sub.attr == "shard_map":
                uses_shard_map = True
            elif isinstance(sub, ast.Name) and sub.id == "shard_map":
                uses_shard_map = True
        self.tests.append((qual, slow, flags, uses_shard_map, node.lineno))


@register(
    "GL005",
    "slow-tier-only coverage: a kernel env flag or seq-parallel routing is "
    "exercised only by slow tests, so the default tier never runs that path",
)
def check_pytest_hygiene(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    slow_ids = _slow_nodeids(project)
    for mod in project.modules.values():
        if not mod.is_test_file:
            continue
        scan = _TestScan(mod, slow_ids)
        scan.visit(mod.tree)
        slow_flags: Dict[str, Tuple[str, int]] = {}
        fast_flags: Set[str] = set()
        slow_shard: Optional[Tuple[str, int]] = None
        fast_shard = False
        for qual, slow, flags, uses_shard, lineno in scan.tests:
            if slow:
                for f in flags:
                    slow_flags.setdefault(f, (qual, lineno))
                if uses_shard and slow_shard is None:
                    slow_shard = (qual, lineno)
            else:
                fast_flags |= flags
                fast_shard = fast_shard or uses_shard
        for flag, (qual, lineno) in sorted(slow_flags.items()):
            if flag not in fast_flags:
                findings.append(Finding(
                    "GL005", mod.path, lineno, qual,
                    f"env flag {flag} is exercised only by slow tests in "
                    "this file — add a fast small-geometry sibling so the "
                    "default tier covers the flagged kernel path",
                ))
        if slow_shard is not None and not fast_shard:
            qual, lineno = slow_shard
            findings.append(Finding(
                "GL005", mod.path, lineno, qual,
                "shard_map (seq-parallel routing) is exercised only by slow "
                "tests in this file — add a fast small-mesh sibling",
            ))
    return findings
