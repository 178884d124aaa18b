"""Kept for ``tests/test_axk1.py``, which imports ``System`` from here and
which a benchmark PR may not edit: since PR 38 the `axk1_ep16` configuration
runs on ``systems/lm.py`` (``"system": "lm"``) and this is that adapter under a
second name. A PR outside the benchmark moves the import, and the next
benchmark PR deletes this file (PERF.md §7)."""

from benchmarks.systems.lm import System  # noqa: F401
