"""``closed_loop`` with ``lib/weights_retention.py``'s weights: the `lm`
system's (``lib/weights_lm.py``) with every gate's bias drawn so that the
state a chunk hands the next carries something. Hands on to
``closed_loop.run`` / ``check``; the window, the spans and the comparison are
that driver's, unchanged."""

from benchmarks.drivers import closed_loop
from benchmarks.lib import weights_retention

check = closed_loop.check


def run(ctx) -> dict:
    ctx.make_weights = lambda shapes: weights_retention.make_weights(shapes, ctx.seed)
    return closed_loop.run(ctx)
