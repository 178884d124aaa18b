"""Models: the share of the expert layer's static sorted buffer that belongs
to an expert held here, which is the share the row kernels (``moe_dispatch``,
``moe_combine``) and the grouped products touch: the (token, expert) choices
the program's counter says landed here (``systems/lm.py``: ``kept["received"]``,
``[expert layers, experts_held]`` a request) over every choice the window's tokens made,
``tokens x top_k x expert layers``. With 12 of 192 experts held and an even
router, 0.0625. None where the system keeps no such counter."""

import numpy as np


def read(metric, trace, window, ctx):
    received = getattr(ctx.system, "kept", {}).get("received")
    if not received or not window["attempted"] or not window["work"]:
        return None
    served = received[-window["attempted"]:]
    layers = served[0].shape[0]
    if not layers:
        return None
    choices = window["work"] * int(ctx.sizes["num_experts_per_tok"]) * layers
    return float(np.sum([r.sum() for r in served], dtype=np.float64) / choices)
