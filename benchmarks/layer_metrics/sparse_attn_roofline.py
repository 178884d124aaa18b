"""Kernels: the least time the chip could take for latent attention's core
over the *selected* keys as the window's requests ask for it, over the summed
duration of the ``sparse_attn`` kernels that did it
(``kernels/sparse_attn_by_name.json``), in %.

Least time per sequence and layer: the larger of operations over peak FLOP/s
and bytes over peak bytes/s (``lib/flops_deepseek_v32.py``: QK^T over the
keys' 192 and PV over the values' 128 for sum_t min(t + 1, index_topk) pairs;
q and k once at 192, v and out once at 128, a byte a causal pair of
selection), from shapes alone. The count is of selected pairs whatever the
kernel visits: a core that runs densely under the causal mask with the
selection as a second mask does 4.27 times the counted work at 16,384 tokens
and can read at most 23.4 % here. None, never 0, where the run has no device
trace, no peaks, or no such kernel in it. ``ctx.notes`` gets which bound
holds."""

from benchmarks.lib import flops_deepseek_v32 as flops
from benchmarks.lib.tables import kernel_table


def read(metric, trace, window, ctx):
    if trace is None or ctx.peaks is None or "index_topk" not in ctx.sizes:
        return None
    seconds = trace.kernel_seconds(kernel_table("sparse_attn_by_name")) * trace.n_devices
    if seconds <= 0:
        return None
    layers = flops.attention_layers(ctx.sizes)
    ops = sum(layers * flops.sparse_core_flops_per_layer(ctx.sizes, n) for n in window["items"])
    bytes_ = sum(layers * flops.sparse_core_bytes_per_layer(ctx.sizes, n) for n in window["items"])
    by_ops = ops / ctx.peaks["flops_per_s"]
    by_bytes = bytes_ / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes.append(
        f"{metric}: bound by {'compute' if by_ops >= by_bytes else 'memory'} "
        f"(least {by_ops:.6f} s by operations, {by_bytes:.6f} s by bytes; "
        f"kernels took {seconds:.6f} s)")
    return 100.0 * max(by_ops, by_bytes) / seconds
