"""Models: the share of the causal (query, key) pairs that the indexer's
selection handed latent attention's core, over the window's requests and
their layers: the program's own counter (the ones of each layer's selection,
counted on the device; ``[layers, batch]`` int32 a request from the model's third
output, ``systems/lm.py``: ``kept["selected_pairs"]``) over ``layers x L (L + 1) / 2``.
An exact top-2048 reads 0.2344 at 16,384 tokens; 1.0 would mean a core that
attends densely, and a figure off ``sum_t min(t + 1, index_topk)`` a selection
that lets through more or fewer keys than it may. None where the system keeps
no such counter."""

import numpy as np


def read(metric, trace, window, ctx):
    selected = getattr(ctx.system, "kept", {}).get("selected_pairs")
    if not selected or not window["attempted"] or not window["items"]:
        return None
    served = selected[-window["attempted"]:]   # the window's requests, not the warm-up's
    layers = served[0].shape[0]
    causal = sum(n * (n + 1) // 2 for n in window["items"]) * layers
    if not causal:
        return None
    return float(np.sum([s.sum(dtype=np.int64) for s in served], dtype=np.float64) / causal)
