"""Kernels: the least time the chip could take for the lightning layers'
decayed linear attention in its chunked form as the window's requests ask
for it, over the summed duration of the ``ssd_scan_fwd`` kernels that did it
(``kernels/ssd_scan_by_name.json``: in this cell only the lightning layers
call it), in %.

Least time per sequence and lightning layer: the larger of operations over
peak FLOP/s and bytes over peak bytes/s (``lib/flops_minicpm_sala.py``: the
lower triangles of chunks of a fixed 128 at ``2 (d + d)`` a pair and head,
the state built and read at ``2 d^2`` each a token and head; q, k, v read
once and the output written once), from shapes alone. None, never 0, where
the run has no device trace, no peaks, or no such kernel in it.
``ctx.notes`` gets which bound holds."""

from benchmarks.lib import flops_minicpm_sala as flops
from benchmarks.lib.tables import kernel_table


def read(metric, trace, window, ctx):
    if trace is None or ctx.peaks is None or "lightning_nh" not in ctx.sizes:
        return None
    seconds = trace.kernel_seconds(kernel_table("ssd_scan_by_name")) * trace.n_devices
    if seconds <= 0:
        return None
    layers = flops.lightning_layers(ctx.sizes)
    ops = sum(layers * flops.lightning_flops(ctx.sizes, n) for n in window["items"])
    bytes_ = sum(layers * flops.lightning_bytes(ctx.sizes, n) for n in window["items"])
    by_ops = ops / ctx.peaks["flops_per_s"]
    by_bytes = bytes_ / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes.append(
        f"{metric}: bound by {'compute' if by_ops >= by_bytes else 'memory'} "
        f"(least {by_ops:.6f} s by operations, {by_bytes:.6f} s by bytes; "
        f"kernels took {seconds:.6f} s)")
    return 100.0 * max(by_ops, by_bytes) / seconds
