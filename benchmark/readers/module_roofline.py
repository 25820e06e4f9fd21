"""The share of their roofline that the program runs whose XLA module
name matches ``match`` reach: the least time the chip could take for the
work one step of them needs (``ctx.cell.<work>()``: operations and bytes
from shapes, ``benchmark/work*.py``) over their device seconds a step in
the traced window. ``kernel_roofline`` reads one kernel's launches on the
operations line; this one whole programs, the gathers inside them
included."""

from benchmark import work
from benchmark.readers import module_time


def read(ctx, match, work_of):
    seconds = module_time.read(ctx, match)
    needed = getattr(ctx.cell, work_of, None)
    if seconds is None or needed is None:
        return None  # no such program ran, or the cell tells no such work
    return 100.0 * work.least_seconds(needed(), ctx.peaks)["seconds"] / seconds
