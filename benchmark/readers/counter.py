"""One of the harness's own counters."""


def read(ctx, counter):
    return ctx.counters.get(counter)
