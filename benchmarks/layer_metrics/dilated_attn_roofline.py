"""Kernels: the least time the chip could take for the attention the model's
schedule asks for, over the summed duration of the kernels that did it, in %.

Least time per slide: the larger of operations over peak FLOP/s and bytes
over peak bytes/s (``lib/flops.py``: per branch 4 E L m / r forward, twice
that again backward for ``.train``; q, k, v, o once per branch at bfloat16),
from shapes alone, whatever implements it. ``ctx.notes`` gets which of the
two bounds it."""

from benchmarks.lib import flops
from benchmarks.lib.tables import kernel_table


def read(metric, trace, window, ctx):
    if trace is None or ctx.peaks is None:
        return None
    seconds = trace.kernel_seconds(kernel_table("dilated_attn")) * trace.n_devices
    if seconds <= 0:
        return None
    train = metric.endswith(".train")
    ops = bytes_ = 0.0
    for n in window["items"]:
        f = flops.slide_attention_forward_flops(ctx.sizes, n)
        b = flops.slide_attention_bytes(ctx.sizes, n)
        ops += 3.0 * f if train else f
        bytes_ += 3.0 * b if train else b
    by_ops = ops / ctx.peaks["flops_per_s"]
    by_bytes = bytes_ / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes.append(
        f"{metric}: bound by {'compute' if by_ops >= by_bytes else 'memory'} "
        f"(least {by_ops:.6f} s by operations, {by_bytes:.6f} s by bytes; "
        f"kernels took {seconds:.6f} s)"
    )
    return 100.0 * max(by_ops, by_bytes) / seconds
