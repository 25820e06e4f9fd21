"""How far the busiest device plane's busy time lies above the mean over
the planes, less one, in percent: the slowest shard's lead over an even
split. Says nothing on fewer than two planes."""

from benchmark import program_trace, trace_reduce


def read(ctx):
    xplane = trace_reduce.newest_xplane(program_trace.trace_dir(ctx.cell.wl["name"]))
    if xplane is None:
        return None
    busy = [
        sum(e - s for s, e in trace_reduce.merge(
            [(s, s + d) for _, s, d in events if d > 0]))
        for events in trace_reduce.load(xplane)["devices"].values()
    ]
    if len(busy) < 2 or min(busy) <= 0:
        return None
    return 100.0 * (max(busy) * len(busy) / sum(busy) - 1.0)
