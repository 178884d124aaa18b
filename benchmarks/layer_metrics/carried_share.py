"""Models: how much of each query's weight came through the state a chunk
of power retention hands the next. The program's own counter (a layer's mean,
over positions and query heads, of the carried part of each query's
denominator over the whole denominator; ``[layers, batch]`` float32 a request
from the model's third output, ``systems/lm.py``: ``kept["carried_share"]``),
averaged over the window's requests, layers and sequences. Near 0 the cell
would measure no scan: every head would forget within its chunk. None where
the system keeps no such counter."""

import numpy as np


def read(metric, trace, window, ctx):
    kept = getattr(ctx.system, "kept", {}).get("carried_share")
    if not kept or not window["attempted"]:
        return None
    served = kept[-window["attempted"]:]   # the window's requests, not the warm-up's
    return float(np.mean([np.mean(s, dtype=np.float64) for s in served]))
