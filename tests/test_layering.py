"""Which way the package's import arrows point, read from the source.

``ops/`` is the bottom layer (kernels and the ops built on them): it may not
know the models, the quantised tier, serving, the dist boundary, the
observability bus or a driver. ``models/`` may not know a driver or a serving
stack. Function-level imports count like module-level ones: an arrow hidden
inside a function is still an arrow. An arrow that stands today is named
here and in ROADMAP.md as a debt — not hidden.
"""

import ast
import importlib.util
import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "gigapath_tpu"

# (importing file, imported module): the arrows that stand at HEAD
_OPS_EXCEPTIONS = {
    # the ring schedule files its per-step span on the obs bus
    ("gigapath_tpu/ops/dilated_attention.py", "gigapath_tpu.obs.spans"),
}


def _imported_modules(path):
    """Every ``gigapath_tpu...`` module a file imports, at any depth of
    nesting, relative imports resolved."""
    with open(os.path.join(REPO_ROOT, path)) as f:
        tree = ast.parse(f.read())
    package = os.path.dirname(path).replace("/", ".").split(".")
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            module = node.module or ""
            if node.level:
                base = package[:len(package) - (node.level - 1)]
                module = ".".join(base + ([module] if module else []))
            # ``from gigapath_tpu import plan`` names a subpackage too
            found.update(f"{module}.{a.name}" for a in node.names)
            found.add(module)
    return {m for m in found if m == PACKAGE or m.startswith(PACKAGE + ".")}


def _arrows(layer, forbidden):
    """(file, module) for every import from ``layer`` into ``forbidden``."""
    arrows = set()
    top = os.path.join(REPO_ROOT, PACKAGE, layer)
    for dirpath, _, names in os.walk(top):
        for name in names:
            if not name.endswith(".py"):
                continue
            path = os.path.relpath(os.path.join(dirpath, name), REPO_ROOT)
            for module in _imported_modules(path):
                parts = module.split(".")
                if len(parts) > 1 and parts[1] in forbidden:
                    arrows.add((path, ".".join(parts[:3])))
    return arrows


def test_ops_imports_nothing_above_itself():
    arrows = _arrows("ops", {"quant", "models", "serve", "dist", "obs", "plan",
                             "pipeline", "inference"})
    assert arrows == _OPS_EXCEPTIONS, sorted(arrows ^ _OPS_EXCEPTIONS)


def test_models_import_no_driver_and_no_serving_stack():
    arrows = _arrows("models", {"serve", "dist", "pipeline", "inference"})
    assert arrows == set(), sorted(arrows)


def test_there_is_no_plan_package():
    assert not os.path.exists(os.path.join(REPO_ROOT, PACKAGE, "plan"))
    assert importlib.util.find_spec(PACKAGE + ".plan") is None


def test_the_carrier_holds_the_attention_switches_and_nothing_else():
    """One field per environment twin, in one order, and the lint rule that
    keeps their reads inside ``snapshot_flags`` guards exactly that set."""
    from gigapath_tpu.ops.pallas_dilated import FLAG_ENV, PipelineFlags
    from tools.gigalint.rules import _GL017_FLAGS

    assert PipelineFlags._fields == tuple(FLAG_ENV)
    assert len(FLAG_ENV) == 7
    assert set(FLAG_ENV.values()) == set(_GL017_FLAGS)
