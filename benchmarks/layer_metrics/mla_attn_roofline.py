"""Kernels: the least time the chip could take for latent attention's causal
core as the window's requests ask for it, over the summed duration of the
``flash_fwd`` kernels that did it (``kernels/flash_fwd_by_name.json``), in %.

Least time per sequence: the larger of operations over peak FLOP/s and bytes
over peak bytes/s (``lib/flops_axk1.py``: QK^T over the keys' 192 and PV over
the values' 128, the lower triangle, 2 x heads x 320 x L^2 / 2 a layer; q and
k once at 192, v and out once at 128, at bfloat16), from shapes alone: a
kernel that padded the values to the keys' width would read five sixths of
what it reads now. None where the run has no device trace, no peaks, or no
such kernel in it. ``ctx.notes`` gets which bound holds."""

from benchmarks.lib import flops_axk1
from benchmarks.lib.tables import kernel_table


def read(metric, trace, window, ctx):
    if trace is None or ctx.peaks is None:
        return None
    seconds = trace.kernel_seconds(kernel_table("flash_fwd_by_name")) * trace.n_devices
    if seconds <= 0:
        return None
    ops = sum(flops_axk1.attention_core_flops(ctx.sizes, n) for n in window["items"])
    bytes_ = sum(flops_axk1.attention_core_bytes(ctx.sizes, n) for n in window["items"])
    by_ops = ops / ctx.peaks["flops_per_s"]
    by_bytes = bytes_ / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes.append(
        f"{metric}: bound by {'compute' if by_ops >= by_bytes else 'memory'} "
        f"(least {by_ops:.6f} s by operations, {by_bytes:.6f} s by bytes; "
        f"kernels took {seconds:.6f} s)")
    return 100.0 * max(by_ops, by_bytes) / seconds
