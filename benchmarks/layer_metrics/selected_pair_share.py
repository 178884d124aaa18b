"""Models: the share of the causal (query, key) pairs that the block
selection handed the sparse layers' core, over the window's requests, their
sparse layers and KV groups: the program's own counter (each layer's pairs
``s <= t`` in its selected blocks, summed over the KV groups and counted on
the device from the selection itself; ``[sparse layers, batch]`` int32 a
request from the model's third output, ``systems/lm.py``:
``kept["selected_pairs"]``) over ``layers x KV groups x L (L + 1) / 2``. An
exact top-64 of 64-token blocks reads 0.1202 at 65,536 tokens; 1.0 would
mean a core that attends densely. None where the system keeps no such
counter."""

import numpy as np


def read(metric, trace, window, ctx):
    selected = getattr(ctx.system, "kept", {}).get("selected_pairs")
    if not selected or not window["attempted"] or not window["items"]:
        return None
    served = selected[-window["attempted"]:]   # the window's requests, not the warm-up's
    groups = int(ctx.sizes["num_key_value_heads"])
    causal = sum(n * (n + 1) // 2 for n in window["items"]) * served[0].shape[0] * groups
    if not causal:
        return None
    return float(np.sum([s.sum(dtype=np.int64) for s in served], dtype=np.float64) / causal)
