"""Device seconds of the matching program runs, a step, over what one
step counted (``numerator`` over ``denominator`` of the program's own
counters): the time of one unit of the work the program counts."""

from benchmark.readers import module_time, registry_ratio


def read(ctx, match, numerator, denominator, labels=None, scale=1.0):
    seconds = module_time.read(ctx, match)
    per_step = registry_ratio.read(ctx, numerator, denominator, labels)
    if seconds is None or not per_step:
        return None
    return scale * seconds / per_step
