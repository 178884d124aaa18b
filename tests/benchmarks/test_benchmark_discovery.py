"""A later PR adds files and edits none: the harness finds a new cell,
configuration, traffic mix and layer metric by their file names."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _list(root):
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), "--list"],
        capture_output=True, text=True, timeout=120, check=True, cwd=root,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_added_files_are_listed_without_an_edit(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace", "__pycache__"))
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
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace", "__pycache__"))
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
