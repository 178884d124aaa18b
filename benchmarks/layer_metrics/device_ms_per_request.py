"""Device: the time the device holds one request, in ms: the mean duration of
the runs of the cell's jitted step (the ``module`` of ``scopes/<tile|slide>.json``,
``jit_tile_encode`` / ``jit_slide_forward`` on the trace's "XLA Modules" line)
that began inside the window. It is what tells a change on the device from
noise on the host: the rate moves with both, this with the device alone. None
where the run has no device trace or no run of that name in it."""

from benchmarks.lib import scopes


def read(metric, trace, window, ctx):
    kind = metric.split(".")[-1]
    reduction = scopes.for_run(ctx) if trace is not None else None
    if reduction is None:
        return None
    module = scopes.table(kind)["module"]
    runs = reduction.modules.get(module)
    if not runs:
        return None
    ctx.notes.append(
        f"{metric}: {len(runs)} runs of {module}, {min(runs) * 1e3:.3f} to {max(runs) * 1e3:.3f} ms; "
        f"all modules in the window: "
        + ", ".join(f"{name} x{len(d)} {sum(d):.6f} s" for name, d in sorted(reduction.modules.items())))
    return 1e3 * sum(runs) / len(runs)
