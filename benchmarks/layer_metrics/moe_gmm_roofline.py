"""Kernels: the least time the chip could take for the expert layers' grouped
matrix products, over the summed duration of the ``gmm`` kernels that did
them (``kernels/moe_gmm_by_name.json``), in %.

The rows are those the program's own counter says were routed here (the
tokens each held expert received, a request and layer:
``systems/lm.py``: ``kept["received"]``), not the expected load: operations 2 x 3 x hidden
x width a row; bytes every held expert's two matrices once a layer and
request and every row in and out of both products (``lib/flops_lm.py``). The
sorted buffer is sized for every choice landing here, about twice the rows
routed: a product that visited the empty rows would read near half of this.
None where the run has no device trace, no peaks, no such kernel or no
counter. ``ctx.notes`` gets which bound holds and the rows."""

from benchmarks.lib import flops_lm
from benchmarks.lib.tables import kernel_table


def read(metric, trace, window, ctx):
    received = getattr(ctx.system, "kept", {}).get("received")
    if trace is None or ctx.peaks is None or not received or not window["attempted"]:
        return None
    seconds = trace.kernel_seconds(kernel_table("moe_gmm_by_name")) * trace.n_devices
    if seconds <= 0:
        return None
    served = received[-window["attempted"]:]  # the window's requests, not the warm-up's
    rows = int(sum(int(r.sum()) for r in served))
    layers = sum(r.shape[0] for r in served)
    by_ops = flops_lm.grouped_matmul_flops(ctx.sizes, rows) / ctx.peaks["flops_per_s"]
    by_bytes = flops_lm.grouped_matmul_bytes(ctx.sizes, rows, layers) / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes.append(
        f"{metric}: {rows} rows routed here over {layers} layer passes; bound by "
        f"{'compute' if by_ops >= by_bytes else 'memory'} (least {by_ops:.6f} s by "
        f"operations, {by_bytes:.6f} s by bytes; kernels took {seconds:.6f} s)")
    return 100.0 * max(by_ops, by_bytes) / seconds
