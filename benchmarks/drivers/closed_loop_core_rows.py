"""``closed_loop_lm`` for a model whose answer also holds ``core_rows``: an
attention layer's core output at the rows asked for (``[layers, B, P,
width]`` a request, ``kept["core_rows"]``), compared with the reference's
beside the logits. The window and the weights are ``closed_loop_lm``'s.

Where a layer adds little to the residual, a fault in it hardly moves the
logits: MiniCPM-SALA's sparse layer under random weights attends nearly
evenly over ~4,096 keys, and a selection that drops the forced window, or
takes the lowest-scored blocks, moved the logits by 0.0068 at 65,536 tokens
against 0.0055 for a sound program (one v5e). The core's own output is moved
by a block in or out of a selection, whichever of the selection, the tiles'
lists and bits, or the kernel's loop put it there. The configuration names
the reference's ``"<module of lib/>.<function>"`` under
``reference_core_rows``: ``(params, ids [L], positions [P], sizes, mode) ->
(logits [P, vocab], core_rows [layers, P, width])``, one forward for both.
"""

from __future__ import annotations

import importlib

import numpy as np

from benchmarks.drivers import closed_loop, closed_loop_lm

run = closed_loop_lm.run


def _reference(name: str):
    module, function = name.rsplit(".", 1)
    return getattr(importlib.import_module("benchmarks.lib." + module), function)


def _sampled_requests(ctx, window) -> list:
    """The window's indices of ``closed_loop.sampled``'s requests, in its
    order: its first draw from the same stream."""
    served = window["_state"][2]
    last = len(served) - 1
    rng = np.random.default_rng(ctx.seed + 1)
    others = rng.permutation(last)[: int(ctx.cell["correct"]["requests"]) - 1]
    return sorted({last, *map(int, others)})


def check(ctx, window, stand_in=None) -> dict:
    """``closed_loop.check``'s two numbers over the logits, and the same two
    over the core's rows (each layer's row an answer): ``core_gap_max``,
    ``core_gap_mean``. ``stand_in`` as there."""
    params, outputs = window["_state"][0], window["_state"][3]
    system = ctx.system
    forward = _reference(ctx.config["reference_core_rows"])
    kept = system.kept["core_rows"]
    first = len(kept) - len(outputs)  # the warm-up's answers come first
    gaps, core_gaps = [], []
    for idx, (batch, rows, got) in zip(_sampled_requests(ctx, window),
                                       closed_loop.sampled(ctx, window)):
        ids, positions = batch
        p = positions.shape[1]
        core = kept[first + idx]
        core = core.reshape(core.shape[0], -1, core.shape[-1])[:, rows]
        for b in sorted({int(r) // p for r in rows}):  # one forward a sequence
            mine = [i for i, r in enumerate(rows) if int(r) // p == b]
            at = positions[b][[int(rows[i]) % p for i in mine]]
            ref_logits, ref_core = forward(params, ids[b], at, system.sizes, "f32")
            got_logits, got_core = got[mine], core[:, mine]
            if stand_in is not None:
                got_logits, got_core = forward(params, ids[b], at, system.sizes, stand_in)
            gaps.append(closed_loop.row_gaps(got_logits, ref_logits))
            core_gaps.append(closed_loop.row_gaps(got_core, ref_core))
    core = closed_loop.summarise(np.concatenate(core_gaps))
    return {**closed_loop.summarise(np.concatenate(gaps)),
            "core_gap_max": core["embed_gap_max"], "core_gap_mean": core["embed_gap_mean"]}
