"""Seconds a step in which no program ran on the device, inside the
program's own host spans named ``inside``; the split by the innermost
span that covers each idle piece goes to standard error."""

import sys

from benchmark import program_trace


def read(ctx, inside):
    trace = program_trace.of(ctx)
    if trace is None or ctx.traced_steps <= 0:
        return None
    idle = program_trace.idle_inside(trace, inside)
    if not idle:
        return None  # no such span in the trace: say nothing
    for name, seconds in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"host gap {name}: {seconds / ctx.traced_steps:.6g} s a step",
              file=sys.stderr)
    return sum(idle.values()) / ctx.traced_steps
