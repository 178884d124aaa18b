"""A later PR adds files and edits none: the harness finds a new cell,
configuration, traffic mix and layer metric by their file names, and every
per-cell test takes a cell of a new kind added that way."""

import filecmp
import json
import os
import re
import shutil
import subprocess
import sys

from benchmarks.lib import tables

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_IGNORED = shutil.ignore_patterns(".jax_cache", ".trace", "__pycache__")


def _list(root):
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), "--list"],
        capture_output=True, text=True, timeout=120, check=True, cwd=root,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_added_files_are_listed_without_an_edit(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=_IGNORED)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    before = _list(str(root))

    bench = root / "benchmarks"
    cell = json.loads((bench / "workloads" / "tile_b128.json").read_text())
    cell.update(name="tile_b64", traffic="closed_b64", config="vit_other")
    (bench / "workloads" / "tile_b64.json").write_text(json.dumps(cell))
    config = json.loads((bench / "configs" / "gigapath_tile_enc.json").read_text())
    config.update(name="vit_other", depth=24)
    (bench / "configs" / "vit_other.json").write_text(json.dumps(config))
    traffic = json.loads((bench / "traffic" / "closed_b128.json").read_text())
    traffic.update(batch=64)
    (bench / "traffic" / "closed_b64.json").write_text(json.dumps(traffic))
    (bench / "layer_metrics" / "gemm_roofline.py").write_text(
        "def read(metric, trace, window, ctx):\n    return None\n")

    after = _list(str(root))
    assert set(after["workloads"]) - set(before["workloads"]) == {"tile_b64"}
    assert set(after["configs"]) - set(before["configs"]) == {"vit_other"}
    assert set(after["traffic"]) - set(before["traffic"]) == {"closed_b64"}
    assert set(after["layer_metrics"]) - set(before["layer_metrics"]) == {"gemm_roofline"}
    for kind in ("drivers", "systems", "kernels"):
        assert after[kind] == before[kind]


# The cell, configuration and kind the test below adds. Its own name must not
# hold the kind's: the copy selects its tests by it and would run this one again.
NEW_CELL, NEW_CONFIG, NEW_KIND = "rehearsed_prefill_b1_16k", "rehearsed_ep2", "rehearsed"
TWIN_CELL = "granite_prefill_b1_16k"


def _appended_only(before, after) -> bool:
    """``after`` is ``before`` with entries appended to its lists, at any depth."""
    if isinstance(before, dict):
        return isinstance(after, dict) and set(after) == set(before) and all(
            _appended_only(before[k], after[k]) for k in before)
    if isinstance(before, list):
        return isinstance(after, list) and len(after) >= len(before) and all(
            _appended_only(b, a) for b, a in zip(before, after))
    return before == after


def _add_a_cell_of_a_new_kind(root):
    """A copy of the Granite cell under a kind no test knows, by new files and
    entries appended to ``BENCHMARK.json``."""
    bench, manifest_path = root / "benchmarks", root / "BENCHMARK.json"
    twin = json.loads((bench / "workloads" / f"{TWIN_CELL}.json").read_text())
    twin_kind = tables.cell_kind(twin)
    renamed = {name: name[: -len(twin_kind)] + NEW_KIND for name in twin["per_layer"]}
    cell = dict(twin, name=NEW_CELL, config=NEW_CONFIG, per_layer=list(renamed.values()))
    (bench / "workloads" / f"{NEW_CELL}.json").write_text(json.dumps(cell, indent=2))
    config = json.loads((bench / "configs" / f"{twin['config']}.json").read_text())
    (bench / "configs" / f"{NEW_CONFIG}.json").write_text(
        json.dumps(dict(config, name=NEW_CONFIG), indent=2))
    shutil.copy(bench / "scopes" / f"{twin_kind}.json", bench / "scopes" / f"{NEW_KIND}.json")

    manifest = json.loads(manifest_path.read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == twin["config"])
    manifest["configs"].append(
        dict(entry, name=NEW_CONFIG, file=f"benchmarks/configs/{NEW_CONFIG}.json"))
    entry = next(w for w in manifest["workloads"] if w["name"] == TWIN_CELL)
    manifest["workloads"].append(dict(entry, name=NEW_CELL, config=NEW_CONFIG))
    for metric in manifest["end_to_end"]:
        if metric["name"] == twin["end_to_end"]["rate"]:
            metric["workloads"].append(NEW_CELL)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    manifest["per_layer"] += [dict(by_name[old], name=new, workloads=[NEW_CELL])
                              for old, new in renamed.items()]
    manifest_path.write_text(json.dumps(manifest, indent=2))


def test_a_new_kind_of_cell_passes_every_per_cell_test_as_additions_only(tmp_path):
    """The repair of ISSUE 38, guarded: a cell, its configuration and its kind
    are new files and appended manifest entries, and every test that runs per
    cell or per kind in ``tests/benchmarks`` (selected by the kind's name) takes
    the cell as it stands; so do the existing cells' tests of their own files
    (``test_the_cell_*``), which read the appended manifest. The program is
    the repo's, the copy's ``benchmarks`` first on the path."""
    root = tmp_path / "checkout"
    for tree in ("benchmarks", os.path.join("tests", "benchmarks")):
        shutil.copytree(os.path.join(ROOT, tree), root / tree, ignore=_IGNORED)
    for name in (os.path.join("tests", "conftest.py"), "pyproject.toml", "BENCHMARK.json"):
        shutil.copy(os.path.join(ROOT, name), root / name)
    _add_a_cell_of_a_new_kind(root)

    new, edited = [], []
    for here, _, files in os.walk(root):
        for name in files:
            rel = os.path.relpath(os.path.join(here, name), root)
            theirs = os.path.join(ROOT, rel)
            if not os.path.exists(theirs):
                new.append(rel)
            elif not filecmp.cmp(os.path.join(here, name), theirs, shallow=False):
                edited.append(rel)
    assert edited == ["BENCHMARK.json"]
    assert sorted(new) == sorted(os.path.join("benchmarks", *p) for p in (
        ("workloads", f"{NEW_CELL}.json"), ("configs", f"{NEW_CONFIG}.json"),
        ("scopes", f"{NEW_KIND}.json")))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert _appended_only(json.load(f), json.loads((root / "BENCHMARK.json").read_text()))

    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(root), ROOT]), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/benchmarks", "-k",
         f"{NEW_KIND} or test_the_cell_", "-p", "no:randomly", "-p", "no:cacheprovider"],
        capture_output=True, text=True, timeout=300, cwd=root, env=env)
    summary = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
    passed = re.search(r"(\d+) passed", summary)
    assert out.returncode == 0 and "failed" not in summary and "error" not in summary, \
        out.stdout[-6000:] + out.stderr[-2000:]
    assert passed and int(passed.group(1)) >= 10, summary


def test_listing_names_every_cell_of_the_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    found = _list(ROOT)
    assert {w["name"] for w in manifest["workloads"]} <= set(found["workloads"])
    assert {c["name"] for c in manifest["configs"]} <= set(found["configs"])
    assert {w["traffic"] for w in manifest["workloads"]} <= set(found["traffic"])


def test_the_yardstick_alone_gives_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's own
    paths there is no system under test: nonzero exit, nothing on stdout."""
    root = tmp_path / "bare"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=_IGNORED)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), "--workload",
         "tile_b128", "--seed", "1", "--seconds", "0.1", "--trace", "0", "--tiny"],
        capture_output=True, text=True, timeout=300, cwd=root, env=env,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "gigapath_tpu" in out.stderr
