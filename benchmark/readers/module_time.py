"""Device-busy seconds a step of the program runs whose XLA module name
matches ``match`` (the function handed to ``jax.jit`` names the module),
from the traced window; needs no host sync, so it stays true where steps
overlap."""

from benchmark import program_trace


def read(ctx, match, scale=1.0):
    trace = program_trace.of(ctx)
    if trace is None or ctx.traced_steps <= 0:
        return None
    seconds = program_trace.module_seconds(trace, match)
    if seconds <= 0:
        return None  # no program by that name ran: say nothing
    return scale * seconds / ctx.traced_steps
