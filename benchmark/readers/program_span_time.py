"""Host seconds a step inside the PROGRAM's own host spans named
``span`` (``photon.<name>``, written into the profiler's trace by
``obs.trace.span()``), from the traced window. ``span_time`` reads the
benchmark's spans; this one the program's."""

from benchmark import program_trace


def read(ctx, span, scale=1.0):
    trace = program_trace.of(ctx)
    if trace is None or ctx.traced_steps <= 0:
        return None
    durations = [dur for name, _, dur in trace["spans"] if name == span]
    if not durations:
        return None  # no such span in the trace: say nothing
    return scale * sum(durations) / 1e9 / ctx.traced_steps
