"""Kernels: the least time the chip could take for the expert layers' grouped
matrix products, over the summed duration of the ``gmm`` kernels that did
them (``kernels/expert_gmm_by_name.json``: the custom call's own name, anchored),
in %. As ``moe_gmm_roofline`` reads the Granite cell, from this
configuration's widths (``lib/flops_axk1.py``).

The rows are those the program's own counter says were routed here (the
tokens each held expert received, a request and expert layer:
``systems/lm.py``: ``kept["received"]``): operations 2 x 3 x hidden x expert width a row;
bytes every held expert's two matrices once a layer pass and every row in and
out of both products. With 12 of 192 experts held about a sixteenth of the
static sorted buffer is routed here: a product that visited the rest would
read a sixteenth of this. None where the run has no device trace, no peaks,
no such kernel or no counter. ``ctx.notes`` gets which bound holds and the
rows."""

from benchmarks.lib import flops_axk1
from benchmarks.lib.tables import kernel_table


def read(metric, trace, window, ctx):
    received = getattr(ctx.system, "kept", {}).get("received")
    if trace is None or ctx.peaks is None or not received or not window["attempted"]:
        return None
    seconds = trace.kernel_seconds(kernel_table("expert_gmm_by_name")) * trace.n_devices
    if seconds <= 0:
        return None
    served = received[-window["attempted"]:]  # the window's requests, not the warm-up's
    rows = int(sum(int(r.sum()) for r in served))
    layers = sum(r.shape[0] for r in served)
    by_ops = flops_axk1.grouped_matmul_flops(ctx.sizes, rows) / ctx.peaks["flops_per_s"]
    by_bytes = flops_axk1.grouped_matmul_bytes(ctx.sizes, rows, layers) / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes.append(
        f"{metric}: {rows} rows routed here over {layers} layer passes; bound by "
        f"{'compute' if by_ops >= by_bytes else 'memory'} (least {by_ops:.6f} s by "
        f"operations, {by_bytes:.6f} s by bytes; kernels took {seconds:.6f} s)")
    return 100.0 * max(by_ops, by_bytes) / seconds
