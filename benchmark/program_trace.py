"""From a profiler trace to the numbers the PROGRAM's own names carry:
device seconds by XLA module (the function handed to ``jax.jit`` names
it: ``glm_fit``, ``bank_fused``, ``re_score``, ...) and the device's idle
time inside the program's own host spans (``photon.<span>``, written by
``obs.trace.span()`` as profiler annotations), named by the innermost
span that covers it.

Two stages, like ``trace_reduce``: :func:`load` reads an ``.xplane.pb``
into plain lists, the rest is arithmetic on those lists, checked on a
small recorded trace without the profiler. A trace of a program that
names nothing (the parent of the PR that added the names) loads to
empty lists, and every function then returns nothing to read.

Events are ``[name, start_ns, duration_ns]``. (Which ``jax.named_scope``
a device operation lies in is not in this chip's trace at all:
``dev-scripts/trace_scopes.py`` joins it in from an XLA text dump.)
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Sequence

from benchmark import trace_reduce

MODULES_LINE = "XLA Modules"  # one event a program run, named jit_<function>(<id>)
SPAN_PREFIX = "photon."
UNATTRIBUTED = "unattributed"

HERE = os.path.dirname(os.path.abspath(__file__))


def trace_dir(cell_name: str) -> str:
    """Where ``run.py`` writes the traced window of a cell."""
    return os.path.join(os.path.dirname(HERE), ".bench_work", cell_name, "trace")


def load(xplane_path: str) -> Dict[str, object]:
    """{"modules": {plane: [event...]}, "spans": [event...]}: the module
    line of every device plane and the program's host annotations (any
    thread)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    modules: Dict[str, List] = {}
    spans: List = []
    for plane in data.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for ln in plane.lines:
                if ln.name == MODULES_LINE:
                    modules[plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in ln.events
                    ]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in ln.events if e.name.startswith(SPAN_PREFIX)
                )
    return {"modules": modules, "spans": spans}


def of(ctx) -> Optional[Dict[str, object]]:
    """The traced window of the run ``ctx`` (``run.MetricContext``)
    belongs to, loaded once; None where there is no trace."""
    if "_program_trace" not in ctx.__dict__:
        xplane = trace_reduce.newest_xplane(trace_dir(ctx.cell.wl["name"]))
        ctx.__dict__["_program_trace"] = load(xplane) if xplane else None
    return ctx.__dict__["_program_trace"]


def module_seconds(trace: Dict[str, object], pattern: str) -> float:
    """Device seconds of the program runs whose module name ``pattern``
    matches (mean over device planes). Runs of one device do not nest."""
    rx = re.compile(pattern)
    planes = trace["modules"]
    if not planes:
        return 0.0
    total = sum(
        dur for events in planes.values() for name, _, dur in events
        if rx.search(name)
    )
    return total / len(planes) / 1e9


def innermost(spans: Sequence, t: float) -> str:
    """The shortest program span that covers time ``t``."""
    best, best_dur = UNATTRIBUTED, float("inf")
    for name, start, dur in spans:
        if start <= t < start + dur and dur < best_dur:
            best, best_dur = name, dur
    return best


def idle_inside(trace: Dict[str, object], inside: str) -> Dict[str, float]:
    """Seconds in which no program ran on the (first) device, inside the
    host spans named ``inside``, by the innermost span covering each
    piece. Empty where the trace holds no such span or no program run."""
    spans = trace["spans"]
    windows = [(s, s + d) for name, s, d in spans if name == inside]
    if not windows or not trace["modules"]:
        return {}
    runs = next(iter(trace["modules"].values()))
    busy = trace_reduce.merge([(s, s + d) for _, s, d in runs if d > 0])
    out: Dict[str, float] = {}
    for w0, w1 in windows:
        at = w0
        for b0, b1 in busy + [(w1, w1)]:
            if b0 > at and at < w1:
                end = min(b0, w1)
                name = innermost(spans, 0.5 * (at + end))
                out[name] = out.get(name, 0.0) + (end - at) / 1e9
            at = max(at, b1)
    return out
