"""Models: the key blocks the sparse layers' core visited for each of its
query positions over those the selection named, over the window's requests:
the program's own counters (``kept["kv_blocks_fetched"]``, the blocks each
tile's loop visits times its query positions: the kernel's whole steps of 16
blocks over the tile's list, the last step's entries past the list included,
or the jnp tier's gathered blocks; and ``kept["kv_blocks_selected"]``, the
blocks the selection named; ``[sparse layers, batch]`` int32 a request from
the model's third output). 1.0 means a core that reads no block for a row
that did not choose it; a tile whose rows chose different blocks reads the
union for every row. None where the system keeps no such counters."""

import numpy as np


def read(metric, trace, window, ctx):
    kept = getattr(ctx.system, "kept", {})
    fetched, named = kept.get("kv_blocks_fetched"), kept.get("kv_blocks_selected")
    if not fetched or not named or not window["attempted"]:
        return None
    n = window["attempted"]   # the window's requests, not the warm-up's
    total = np.sum([s.sum(dtype=np.int64) for s in named[-n:]], dtype=np.float64)
    if not total:
        return None
    return float(np.sum([s.sum(dtype=np.int64) for s in fetched[-n:]], dtype=np.float64) / total)
