"""The late step: over ALL steps of the window (``ctx.steps``, traced or
not), the longest step's wall less the median step's. Every step of a cell
does the same work from the same state, so the difference is what the
machine, the host or the program added to one of them.

To standard error: the longest step's wall split by the innermost span
open on the main thread (the program's ring, ``readers/setup_split``)
beside the median step's, with their difference, and every ``jax.trace`` /
``jax.lower`` / ``jax.compile`` span (any thread) that closed inside the
longest step. A program that files no span still reads the number; its
split is all ``dark``.
"""

from __future__ import annotations

import statistics
import sys
import threading
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.readers import setup_split


def late(walls: Sequence[float]) -> Optional[Tuple[float, int, int]]:
    """(longest less median, index of the longest, index of the step
    nearest the median); ``None`` on fewer than two steps."""
    if len(walls) < 2:
        return None
    median = statistics.median(walls)
    longest = max(range(len(walls)), key=walls.__getitem__)
    typical = min(range(len(walls)), key=lambda i: (abs(walls[i] - median), i))
    return walls[longest] - median, longest, typical


def step_splits(
    ring: Sequence[setup_split.Span], steps: Sequence[Tuple[float, float]],
    main_tid: int, which: Sequence[int],
) -> List[Dict[str, float]]:
    """The main thread's innermost-span split of each named step."""
    intervals = setup_split.main_thread_intervals(ring, (), main_tid)
    return [
        setup_split.totals(setup_split.partition(intervals, *steps[i]))
        for i in which
    ]


def compiles_inside(
    ring: Sequence[setup_split.Span], t0: float, t1: float
) -> List[Tuple[str, str, float]]:
    return [
        (name, str(attrs.get("program", "")), b - a)
        for name, a, b, _, attrs in ring
        if name in setup_split.STAGES and t0 <= b <= t1
    ]


def read(ctx):
    steps = [(t0, t1) for t0, t1, *_ in ctx.steps]
    found = late([t1 - t0 for t0, t1 in steps])
    if found is None:
        return None
    seconds, longest, typical = found
    ring, _, _ = setup_split.ring_of_process()
    slow, usual = step_splits(
        ring, steps, threading.main_thread().ident, (longest, typical))
    print(f"late step: step {longest} of {len(steps)} took "
          f"{steps[longest][1] - steps[longest][0]:.6g} s, {seconds:.6g} over "
          f"the median; step {typical} stands for the median",
          file=sys.stderr)
    for name in sorted(set(slow) | set(usual),
                       key=lambda n: -abs(slow.get(n, 0.0) - usual.get(n, 0.0))):
        a, b = slow.get(name, 0.0), usual.get(name, 0.0)
        print(f"late step {name}: {a:.6g} s against {b:.6g} ({a - b:+.6g})",
              file=sys.stderr)
    for name, program, s in compiles_inside(ring, *steps[longest]):
        print(f"late step held {name} of {program}: {s:.6g} s", file=sys.stderr)
    return seconds
