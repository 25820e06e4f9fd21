"""Device time of one launch of the operations whose name matches
``match`` (a named kernel), from the trace."""

from benchmark import trace_reduce


def read(ctx, match, scale=1.0):
    if ctx.trace is None:
        return None
    seconds = trace_reduce.matching_seconds(ctx.trace["ops"], match)
    launches = trace_reduce.matching_seconds(ctx.trace["counts"], match)
    if seconds <= 0 or launches <= 0:
        return None
    return scale * seconds / launches
