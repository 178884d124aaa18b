"""The benchmark's data files, found by name."""

from __future__ import annotations

import functools
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load(kind: str, name: str) -> dict:
    """``benchmarks/<kind>/<name>.json``."""
    path = os.path.join(BENCH_DIR, kind, name + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no {kind[:-1] if kind.endswith('s') else kind} named {name!r}: "
            f"{os.path.relpath(path, ROOT)} is missing; there are {names(kind)}"
        )
    with open(path) as f:
        return json.load(f)


def names(kind: str, ext: str = ".json") -> list:
    d = os.path.join(BENCH_DIR, kind)
    return sorted(
        f[: -len(ext)] for f in os.listdir(d)
        if f.endswith(ext) and not f.startswith("_")
    )


@functools.lru_cache(maxsize=None)
def kernel_table(name: str) -> dict:
    return load("kernels", name)


def cell_kind(cell: dict) -> str:
    """The suffix every layer metric of a cell's file carries, which names its
    scope table and its readers' families: ``step_mfu.tile`` -> ``tile``."""
    return cell["per_layer"][0].rsplit(".", 1)[-1]


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
