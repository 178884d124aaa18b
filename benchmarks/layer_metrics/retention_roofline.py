"""Kernels: the least time the chip could take for power retention's
chunked form as the window's requests ask for it, over the summed duration of
the ``power_retention_fwd`` kernels that did it
(``kernels/power_retention_by_name.json``), in %.

Least time per sequence and layer: the larger of operations over peak FLOP/s
and bytes over peak bytes/s (``lib/flops_brumby.py``: the state built over
the KV heads and read over the query heads with the normaliser's column, the
lower triangles of chunks of a fixed 128; q, k, v and the gate read once, y
written once), from shapes alone; every layer of the cell is one. None,
never 0, where the run has no device trace, no peaks, or no such kernel in
it. ``ctx.notes`` gets which bound holds."""

from benchmarks.lib import flops_brumby as flops
from benchmarks.lib.tables import kernel_table


def read(metric, trace, window, ctx):
    if trace is None or ctx.peaks is None:
        return None
    seconds = trace.kernel_seconds(kernel_table("power_retention_by_name")) * trace.n_devices
    if seconds <= 0:
        return None
    layers = int(ctx.sizes["depth"])
    ops = sum(layers * flops.retention_flops(ctx.sizes, n) for n in window["items"])
    bytes_ = sum(layers * flops.retention_bytes(ctx.sizes, n) for n in window["items"])
    by_ops = ops / ctx.peaks["flops_per_s"]
    by_bytes = bytes_ / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes.append(
        f"{metric}: bound by {'compute' if by_ops >= by_bytes else 'memory'} "
        f"(least {by_ops:.6f} s by operations, {by_bytes:.6f} s by bytes; "
        f"kernels took {seconds:.6f} s)")
    return 100.0 * max(by_ops, by_bytes) / seconds
