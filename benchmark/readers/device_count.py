"""Launches per unit of work of the operations whose name matches
``match``, from the trace (``scale`` turns launches into what they count:
a value+gradient is two kernel launches)."""

from benchmark import trace_reduce


def read(ctx, match, scale=1.0):
    if ctx.trace is None or ctx.traced_units <= 0:
        return None
    launches = trace_reduce.matching_seconds(ctx.trace["counts"], match)
    if launches <= 0:
        return None
    return scale * launches / ctx.traced_units
