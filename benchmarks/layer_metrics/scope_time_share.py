"""Models and kernels, by the program's own names: the self time of the
device's operations that fall to one group of the cell's scope table
(``scopes/<tile|slide>.json``; ``lib/scopes.py`` has the rule) over the union
of device-busy time, in %. ``scope_time_share.<group>.<cell kind>``: a cell's
groups add up to 100. None where the run has no device trace, where the
program opens none of the scopes the table requires, or where nothing fell to
the group. ``ctx.notes`` gets the group's ten heaviest scope paths, and for
``other`` how much of the busy time carried no path at all."""

from benchmarks.lib import scopes


def read(metric, trace, window, ctx):
    _, group, kind = metric.split(".")
    reduction = scopes.for_run(ctx) if trace is not None else None
    if reduction is None or reduction.busy_s <= 0:
        return None
    grouped = reduction.groups(scopes.table(kind))
    if grouped is None or grouped[0].get(group, 0.0) <= 0:
        return None
    seconds, paths = grouped
    heaviest = sorted(paths[group].items(), key=lambda kv: -kv[1])[:10]
    note = f"{metric}: {seconds[group]:.6f} s of {reduction.busy_s:.6f} s busy; " + "; ".join(
        f"{100.0 * s / reduction.busy_s:.2f}% {path}" for path, s in heaviest)
    if group == scopes.OTHER:
        note += (f"; no path at all {100.0 * reduction.no_path_s / reduction.busy_s:.3f}% of busy, "
                 f"a predecessor's path taken {100.0 * reduction.inherited_s / reduction.busy_s:.3f}%")
    ctx.notes.append(note)
    return 100.0 * seconds[group] / reduction.busy_s
