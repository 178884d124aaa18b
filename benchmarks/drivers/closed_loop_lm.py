"""``closed_loop`` with the `lm` system's own weights: sets the context's
maker to ``lib/weights_lm.py`` (a matrix's fan-in is ``shape[-2]``, whatever
is stacked in front of it) and hands on to ``closed_loop.run`` / ``check``.
The window, the spans and the comparison are that driver's, unchanged."""

from benchmarks.drivers import closed_loop
from benchmarks.lib import weights_lm

check = closed_loop.check


def run(ctx) -> dict:
    ctx.make_weights = lambda shapes: weights_lm.make_weights(shapes, ctx.seed)
    return closed_loop.run(ctx)
