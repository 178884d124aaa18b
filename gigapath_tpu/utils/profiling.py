"""Profiling & telemetry hooks (thin shims over the obs layer).

Superset of the reference's instrumentation (SURVEY §5.1): the reference
records CPU wall-clock + CUDA events around each MoE all-to-all
(``xmoe/moe_layer.py:276-307``) and prints sec/it in the train loop; here
the implementations live in the obs subsystem and this module re-exports
the historical names:

- :func:`trace` / :func:`annotate` — ``jax.profiler`` passthroughs, now
  owned by :mod:`gigapath_tpu.obs.spans` (which also provides the
  nestable, event-emitting ``span`` context manager);
- :func:`compiled_flops` / :func:`compiled_memory` — XLA cost/memory
  analysis (the thop replacement), now owned by
  :mod:`gigapath_tpu.obs.ledger`, which additionally folds full
  ``compile_profile`` captures into the per-run perf ledger;
- :func:`collect_moe_metadata` surfaces the gating telemetry MoE layers
  sow (entropy, unused experts, balance fractions —
  ``xmoe/routing.py:53,72-87``) as a flat scalar dict — still defined
  here (it is host-side pytree flattening, not a compiled-artifact
  concern), shared with the in-graph ``gigapath_tpu.obs.telemetry`` twin.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import numpy as np

from gigapath_tpu.obs.ledger import (  # noqa: F401  (re-exported shims)
    compiled_flops,
    compiled_memory,
)
from gigapath_tpu.obs.spans import annotate, trace  # noqa: F401


def iter_moe_metadata(intermediates: Dict[str, Any]):
    """Yield ``("layer_path/metric", leaf)`` for every scalar sown under a
    ``moe_metadata`` collection. The ONE flattening shared by the host
    collector below and the in-graph ``gigapath_tpu.obs.telemetry``
    twin, so their key spaces cannot drift.

    Defensive on the edges (this feeds telemetry, it must never take a
    run down): empty intermediates -> nothing; a non-scalar leaf under
    ``moe_metadata`` (unexpected — gating stats are scalars by design) is
    skipped rather than silently reduced to a made-up number. The size
    check reads only the static shape, so it is trace-safe."""
    flat = jax.tree_util.tree_flatten_with_path(intermediates)[0]
    for path, leaf in flat:
        names = [getattr(p, "key", str(p)) for p in path]
        if "moe_metadata" in names:
            # path: (..., moe_metadata, <tuple idx>, <metric name>)
            metric = names[-1]
            layer = "/".join(n for n in names[: names.index("moe_metadata")])
            if int(np.prod(getattr(leaf, "shape", ()))) != 1:
                continue
            yield f"{layer}/{metric}", leaf


def collect_moe_metadata(intermediates: Dict[str, Any]) -> Dict[str, float]:
    """Flatten every sown ``moe_metadata`` dict into ``layer_path/metric``
    host floats. Collect with ``model.apply(..., mutable=["intermediates"])``."""
    return {
        key: float(np.asarray(leaf).reshape(()))
        for key, leaf in iter_moe_metadata(intermediates)
    }

