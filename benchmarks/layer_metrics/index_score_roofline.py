"""Kernels: the least time the chip could take for the lightning indexer's
scores as the window's requests ask for them, over the summed duration of the
``index_score`` kernels that computed them (``kernels/index_score_by_name.json``),
in %.

Least time per sequence and layer: the larger of operations over peak FLOP/s
and bytes over peak bytes/s (``lib/flops_deepseek_v32.py``: 2 x index heads x
index dim a *causal* pair, L (L + 1) / 2 of them; every query head and the one
key read once, the causal half of the float32 scores written once), from
shapes alone: a kernel that scored the pairs above the diagonal too would
read half of this. None, never 0, where the run has no device trace, no peaks,
or no such kernel in it (a program without the indexer). ``ctx.notes`` gets
which bound holds."""

from benchmarks.lib import flops_deepseek_v32 as flops
from benchmarks.lib.tables import kernel_table


def read(metric, trace, window, ctx):
    if trace is None or ctx.peaks is None or "index_n_heads" not in ctx.sizes:
        return None
    seconds = trace.kernel_seconds(kernel_table("index_score_by_name")) * trace.n_devices
    if seconds <= 0:
        return None
    layers = flops.attention_layers(ctx.sizes)
    ops = sum(layers * flops.index_score_flops_per_layer(ctx.sizes, n) for n in window["items"])
    bytes_ = sum(layers * flops.index_score_bytes_per_layer(ctx.sizes, n) for n in window["items"])
    by_ops = ops / ctx.peaks["flops_per_s"]
    by_bytes = bytes_ / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes.append(
        f"{metric}: bound by {'compute' if by_ops >= by_bytes else 'memory'} "
        f"(least {by_ops:.6f} s by operations, {by_bytes:.6f} s by bytes; "
        f"kernels took {seconds:.6f} s)")
    return 100.0 * max(by_ops, by_bytes) / seconds
