"""Kernels: the least time the chip could take for the InfLLM-v2 block
scores as the window's requests ask for them, over the summed duration of the
``block_score`` kernels that made them (``kernels/block_score_by_name.json``),
in %.

Least time per sequence and sparse layer: the larger of operations over
peak FLOP/s and bytes over peak bytes/s (``lib/flops_minicpm_sala.py``: ``2
d`` a query head and compressed key it can see; q and the compressed keys
read once, the float32 block scores written once), from shapes alone. None,
never 0, where the run has no device trace, no peaks, or no such kernel in
it. ``ctx.notes`` gets which bound holds."""

from benchmarks.lib import flops_minicpm_sala as flops
from benchmarks.lib.tables import kernel_table


def read(metric, trace, window, ctx):
    if trace is None or ctx.peaks is None or "sparse_config" not in ctx.sizes:
        return None
    seconds = trace.kernel_seconds(kernel_table("block_score_by_name")) * trace.n_devices
    if seconds <= 0:
        return None
    layers = flops.sparse_layers(ctx.sizes)
    ops = sum(layers * flops.block_score_flops(ctx.sizes, n) for n in window["items"])
    bytes_ = sum(layers * flops.block_score_bytes(ctx.sizes, n) for n in window["items"])
    by_ops = ops / ctx.peaks["flops_per_s"]
    by_bytes = bytes_ / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes.append(
        f"{metric}: bound by {'compute' if by_ops >= by_bytes else 'memory'} "
        f"(least {by_ops:.6f} s by operations, {by_bytes:.6f} s by bytes; "
        f"kernels took {seconds:.6f} s)")
    return 100.0 * max(by_ops, by_bytes) / seconds
