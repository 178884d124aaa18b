"""``chip_smoke.py`` off the chip, and the compile-cache rule.

The rehearsal (``--tiny``) must walk every phase on the CPU in this process
and can never report success; without the rehearsal option a run that finds
no accelerator prints nothing and exits nonzero; a failing phase ends the
run. The compile-cache helper resolves one fixed in-checkout directory
unless ``JAX_COMPILATION_CACHE_DIR`` is set, and nothing else in the tree
sets one.
"""

import json
import os
import subprocess

import jax
import pytest

import chip_smoke
from gigapath_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _restore_cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def _json_lines(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return [json.loads(line) for line in out]


def test_tiny_rehearsal_runs_every_phase_and_never_reports_ok(tmp_path, capsys):
    rc = chip_smoke.main(["--tiny", "--out", str(tmp_path)])
    lines = _json_lines(capsys)
    assert rc == chip_smoke.EXIT_REHEARSAL != 0
    phases = {l["phase"]: l for l in lines if "phase" in l}
    for name in ("A_kernels", "B_tiles_to_slide", "C_finetune", "D_serving"):
        assert phases[name]["ok"] is True, phases[name]
    assert phases["env"]["mode"] == "tiny-rehearsal"
    assert phases["env"]["compile_cache_dir"] == compile_cache.DEFAULT_CACHE_DIR
    assert phases["A_kernels"]["checks"] >= 20
    assert phases["C_finetune"]["train_step_compiles"] == 2
    assert phases["C_finetune"]["compiles_after_first_step_of_a_bucket"] == 0
    assert phases["D_serving"]["warm_compiled_executables"] == 0
    assert phases["D_serving"]["warm_loaded_executables"] == 2
    # the .aot artifacts live under the output directory, not a temp name
    assert phases["D_serving"]["artifact_dir"].startswith(str(tmp_path))
    assert lines[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())},
    }
    assert not any(l.get("ok") is True and "phase" not in l for l in lines)


def test_four_chip_phase_runs_on_four_virtual_devices(tmp_path, capsys):
    rc = chip_smoke.main(["--tiny", "--chips", "4", "--out", str(tmp_path)])
    lines = _json_lines(capsys)
    assert rc == chip_smoke.EXIT_REHEARSAL
    ran = [l["phase"] for l in lines if "phase" in l]
    # the sharded phase and its one-chip comparison, and no other phase
    assert ran == ["env", "seq_parallel", "spmd_train_step", "total"]
    sp, step = lines[1], lines[2]
    assert sp["mesh"] == {"seq": 4} and sp["output_on_devices"] == [0, 1, 2, 3]
    assert sp["max_abs_err_vs_one_chip"] <= sp["atol"]
    assert sp["collectives"].get("all-gather", 0) > 0  # the gathered branches
    assert step["mesh"] == {"data": 2, "seq": 2, "model": 1}
    assert abs(step["loss"] - step["one_chip_loss"]) <= step["atol"]
    assert lines[-1]["ok"] is False


def test_no_accelerator_prints_no_result_and_exits_nonzero(tmp_path, capsys):
    rc = chip_smoke.main(["--out", str(tmp_path)])
    captured = capsys.readouterr()
    assert rc == chip_smoke.EXIT_NO_CHIP != 0
    assert captured.out == ""
    assert "not a TPU" in captured.err
    assert os.listdir(tmp_path) == []


def test_a_failing_phase_ends_the_run(tmp_path, capsys, monkeypatch):
    def broken(args, sizes, out_dir):
        raise chip_smoke.PhaseFailed("kernel checks outside tolerance")

    monkeypatch.setattr(chip_smoke, "phase_a", broken)
    rc = chip_smoke.main(["--tiny", "--out", str(tmp_path)])
    lines = _json_lines(capsys)
    assert rc == chip_smoke.EXIT_PHASE_FAILED != 0
    assert lines[-2]["phase"] == "A_kernels" and lines[-2]["ok"] is False
    assert "outside tolerance" in lines[-2]["error"]
    assert lines[-1]["ok"] is False and "device" in lines[-1]
    # no later phase ran
    assert [l["phase"] for l in lines if "phase" in l] == ["env", "A_kernels"]


def test_cache_helper_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "elsewhere"))

    def refuse(name, value):
        raise AssertionError(f"code set {name} although the environment names a directory")

    monkeypatch.setattr(jax.config, "update", refuse)
    assert compile_cache.enable_compile_cache() == str(tmp_path / "elsewhere")


def test_cache_helper_resolves_the_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == os.path.join(REPO, ".jax_cache") == compile_cache.DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.enable_compile_cache() == first  # no pid, time or temp name


def _tracked_python_files():
    out = subprocess.run(
        ["git", "ls-files", "-co", "--exclude-standard", "*.py"],
        cwd=REPO, capture_output=True, text=True, check=True,
    ).stdout.split()
    return [p for p in out if os.path.exists(os.path.join(REPO, p))]


def test_no_other_code_sets_a_cache_directory():
    if not os.path.isdir(os.path.join(REPO, ".git")):
        pytest.skip("not a git checkout")
    setters = []
    for rel in _tracked_python_files():
        if rel.startswith("tests/"):
            continue
        with open(os.path.join(REPO, rel)) as f:
            text = f.read()
        if "jax_compilation_cache_dir" in text or "set_cache_dir" in text:
            setters.append(rel)
    assert setters == ["gigapath_tpu/utils/compile_cache.py"]


@pytest.mark.parametrize("driver", [
    "chip_smoke.py", "bench.py", "gigapath_tpu/finetune/main.py",
    "gigapath_tpu/inference.py", "gigapath_tpu/train_gigapath.py",
    "gigapath_tpu/linear_probe/main.py", "scripts/serve_smoke.py",
    "scripts/tpu_selfcheck.py", "gigapath_tpu/dist/worker.py",
    "gigapath_tpu/dist/pipeline.py",
])
def test_every_driver_enables_the_cache(driver):
    with open(os.path.join(REPO, driver)) as f:
        assert "enable_compile_cache()" in f.read()


def test_cache_and_output_directories_are_git_ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = f.read().split()
    assert ".jax_cache/" in ignored and "chiprun_out/" in ignored
    assert "BENCH_LOCAL.json" not in ignored
