"""Models, whole step: analytic operations of the work the window completed
(``lib/flops.py``: forward for ``.tile`` / ``.slide``, forward + backward over
valid tokens for ``.train``; recomputation not counted) over window seconds
x chips x the chip's peak, in %."""


def read(metric, trace, window, ctx):
    if ctx.peaks is None or not window["flops"]:
        return None
    peak = ctx.peaks["flops_per_s"] * int(ctx.cell["chips"])
    return 100.0 * window["flops"] / (window["seconds"] * peak)
