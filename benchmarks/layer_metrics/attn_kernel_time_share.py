"""Models, glue against kernels: summed duration of the dilated-attention
kernels in the device trace (``kernels/dilated_attn.json`` names them) over
the union of device-busy intervals, in %."""

from benchmarks.lib.tables import kernel_table


def read(metric, trace, window, ctx):
    if trace is None or trace.busy_s <= 0:
        return None
    seconds = trace.kernel_seconds(kernel_table("dilated_attn"))
    return 100.0 * seconds / trace.busy_s if seconds > 0 else None
