"""One label set of a counter of the program's
(``obs.registry.default_registry()``, cumulative over the process) over
the total of ``denominator``, all its label sets together: a share, in
percent."""

from photon_ml_tpu.obs.registry import default_registry


def read(ctx, numerator, denominator, labels=None, scale=100.0):
    registry = default_registry()
    below = registry.counter(denominator).total()
    if below <= 0:
        return None  # the program does not count this: say nothing
    return scale * registry.counter(numerator).value(**(labels or {})) / below
