"""Models: how unevenly the router loads the experts held here: the tokens
the fullest held expert received over the mean of the held experts, averaged
over the layers and the window's requests. From the counts the program's
forward returns beside its logits (``[layers, experts_held]`` int32 a request;
``systems/lm.py``: ``kept["received"]``). 1 is an even load; the grouped product's tiles
and a deployment's stragglers follow the fullest. None where the system keeps
no such counter."""

import numpy as np


def read(metric, trace, window, ctx):
    received = getattr(ctx.system, "kept", {}).get("received")
    if not received or not window["attempted"]:
        return None
    counts = np.stack(received[-window["attempted"]:]).astype(np.float64)  # [requests, layers, held]
    return float((counts.max(-1) / counts.mean(-1)).mean())
