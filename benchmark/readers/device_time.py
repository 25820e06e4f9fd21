"""Device time per unit of work of the operations whose name matches
``match`` (or, with ``invert``, of all the others), from the trace."""

from benchmark import trace_reduce


def read(ctx, match, scale=1.0, invert=False):
    if ctx.trace is None or ctx.traced_units <= 0:
        return None
    seconds = trace_reduce.matching_seconds(ctx.trace["ops"], match)
    if seconds <= 0:
        return None  # nothing by that name ran: say nothing
    if invert:
        seconds = ctx.trace["busy_s"] - seconds
    return scale * seconds / ctx.traced_units
