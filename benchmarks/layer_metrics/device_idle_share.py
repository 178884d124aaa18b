"""Device: 1 - (union of device-busy intervals) / (traced window), in %."""


def read(metric, trace, window, ctx):
    if trace is None or trace.n_devices == 0:
        return None
    return 100.0 * trace.idle_share
