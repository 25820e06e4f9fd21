"""The whole step's share of the chip's peak: the least time the chip
could take for the work the algorithm needs (``benchmark/work.py``, from
shapes) over the measured wall of the whole window, per unit of work."""

from benchmark import work


def read(ctx):
    if ctx.units <= 0 or ctx.wall <= 0:
        return None
    need = work.least_seconds(ctx.cell.work_per_unit(), ctx.peaks)
    return 100.0 * need["seconds"] / (ctx.wall / ctx.units)
