"""One of the program's own counters over another
(``obs.registry.default_registry()``, cumulative over the process: every
step of a cell does the same work), for one label set."""

from photon_ml_tpu.obs.registry import default_registry


def read(ctx, numerator, denominator, labels=None, scale=1.0):
    registry = default_registry()
    below = registry.counter(denominator).value(**(labels or {}))
    if below <= 0:
        return None  # the program does not count this: say nothing
    return scale * registry.counter(numerator).value(**(labels or {})) / below
