"""The ``--tiny`` rehearsal of every cell, end to end on the CPU, and the
controls and planted faults that ``correct`` has to refuse.

In-process (one JAX for the whole file): the harness's look for a chip is
what ``--tiny`` skips, everything after it is what a run on the chip drives.
"""

import argparse
import json

import jax.numpy as jnp
import pytest

from benchmarks import run as harness
from benchmarks.lib import tables

MANIFEST = tables.manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
SEED = 3000000019  # past 2**31, as the driver's seeds are


def _run(capsys, *argv):
    rc = harness.main(list(argv))
    captured = capsys.readouterr()
    return rc, captured.out.strip().splitlines(), captured.err.strip().splitlines()


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_prints_the_contract_line(capsys, cell, trace):
    rc, out, err = _run(capsys, "--workload", cell, "--seed", str(SEED),
                        "--seconds", "0.3", "--trace", str(trace), "--tiny")
    assert rc == 0
    line = json.loads(out[-1])
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert "memory_peak_bytes" in line["device"]
    declared = {m["name"]: m for m in MANIFEST["end_to_end" if trace == 0 else "per_layer"]}
    assert line["metrics"]
    for name, metric in line["metrics"].items():
        assert metric["unit"] == declared[name]["unit"]
        assert cell in declared[name].get("workloads", CELLS)
    if trace == 0:
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
        assert all(m["value"] > 0 for m in line["metrics"].values())
    else:  # nothing compiles in the window; no share of a peak from a CPU
        compiles = [v for k, v in line["metrics"].items() if k.startswith("window_compiles")]
        assert compiles and compiles[0]["value"] == 0
        assert not [k for k in line["metrics"] if "mfu" in k or "roofline" in k]
    # each number compared stands beside its limit, last on standard error too
    for name, check in line["checks"].items():
        assert check["value"] <= check["limit"]
        assert any(l.startswith(f"check {name}:") for l in err[-len(line["checks"]):])


@pytest.mark.parametrize("cell", CELLS)
def test_without_a_tpu_and_without_tiny_there_is_no_result(capsys, cell):
    rc, out, _ = _run(capsys, "--workload", cell, "--seed", "1", "--seconds", "0.1",
                      "--trace", "0")
    assert rc != 0
    assert out == []


def _prepared(cell, seed=SEED, seconds=0.2):
    ctx, driver = harness.prepare(argparse.Namespace(
        workload=cell, seed=seed, seconds=seconds, trace=0, tiny=True))
    return ctx, driver


def test_same_seed_same_inputs_and_weights():
    import jax
    import numpy as np

    def state(seed):
        ctx, driver = _prepared(CELLS[0], seed=seed, seconds=0.05)
        params, batches, _, _ = driver.run(ctx)["_state"]
        return [np.asarray(x) for x in jax.tree.leaves(params)] + list(batches)

    one, two, other = state(7), state(7), state(8)
    assert all(np.array_equal(x, y) for x, y in zip(one, two))
    assert not any(np.array_equal(x, y) for x, y in zip(one, other))


@pytest.mark.parametrize("in_flight", [1, 3])
def test_every_request_sent_is_fetched_in_its_order(in_flight):
    """With requests dispatched ahead, the window still counts each request
    sent, waits for its answer, and keeps answer ``i`` beside request ``i``."""
    import numpy as np

    ctx, driver = _prepared("tile_b128", seconds=0.3)
    ctx.traffic = {**ctx.traffic, "in_flight": in_flight}
    window = driver.run(ctx)
    params, batches, served, outputs = window["_state"]
    assert len(outputs) == len(served) == window["attempted"] > in_flight
    assert window["work"] == sum(ctx.system.work(batches[w]) for w in served)
    assert window["seconds"] >= 0.3
    system, fn = ctx.system, ctx.system.make_fn()
    for idx in (0, len(served) - 1):
        again = system.to_host(fn(params, *system.to_device(batches[served[idx]])))
        np.testing.assert_array_equal(outputs[idx], again)


@pytest.mark.parametrize("cell", CELLS)
def test_control_one_precision_down_is_not_correct(cell):
    """The reference in the cell's control precision, put in the program's
    place, fails at least one of the cell's limits; the program passes all."""
    ctx, driver = _prepared(cell)
    window = driver.run(ctx)
    limits = ctx.cell["correct"]["tiny_limits"]
    program = driver.check(ctx, window)
    control = driver.check(ctx, window, stand_in=ctx.cell["correct"]["control"])
    assert all(program[k] <= limits[k] for k in limits)
    assert any(control[k] > limits[k] for k in limits)
    assert all(control[k] > 2 * program[k] for k in limits)


def _misrouted(fn):
    """Every answer goes to its neighbour's row."""
    def broken(params, *inputs):
        import jax

        return jax.tree.map(lambda o: jnp.roll(o, 1, axis=0), fn(params, *inputs))
    return broken


def _nudged(fn):
    """One feature of every answer altered where it is produced."""
    def broken(params, *inputs):
        import jax

        return jax.tree.map(lambda o: o.at[:, 0].add(1.0), fn(params, *inputs))
    return broken


def _poisoned(fn):
    """One answer comes back not a number."""
    def broken(params, *inputs):
        import jax

        return jax.tree.map(lambda o: o.at[0, 0].set(jnp.nan), fn(params, *inputs))
    return broken


@pytest.mark.parametrize("fault", [_misrouted, _nudged, _poisoned],
                         ids=["misrouted", "nudged", "poisoned"])
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_comes_out_not_correct(capsys, monkeypatch, cell, fault):
    """A whole run with the timed path broken underneath it."""
    import importlib

    system = tables.load("configs", tables.load("workloads", cell)["config"])["system"]
    cls = importlib.import_module("benchmarks.systems." + system).System
    make_fn = cls.make_fn
    monkeypatch.setattr(cls, "make_fn", lambda self: fault(make_fn(self)))
    rc, out, err = _run(capsys, "--workload", cell, "--seed", str(SEED),
                        "--seconds", "0.2", "--trace", "0", "--tiny")
    assert rc == 0
    assert "NaN" not in out[-1] and "Infinity" not in out[-1]  # strict JSON
    line = json.loads(out[-1])
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
    assert any("FAIL" in l for l in err)
