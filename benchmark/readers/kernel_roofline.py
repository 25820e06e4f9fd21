"""A kernel's share of its roofline: the least time the chip could take
for the work its launches need (from shapes; ``launches_per_work`` of
them make one unit of the cell's ``work_per_unit``) over their device time in the
trace."""

from benchmark import trace_reduce, work


def read(ctx, match, launches_per_work=1):
    if ctx.trace is None:
        return None
    seconds = trace_reduce.matching_seconds(ctx.trace["ops"], match)
    launches = trace_reduce.matching_seconds(ctx.trace["counts"], match)
    if seconds <= 0 or launches <= 0:
        return None
    need = work.least_seconds(ctx.cell.work_per_unit(), ctx.peaks)
    return 100.0 * need["seconds"] * (launches / launches_per_work) / seconds
