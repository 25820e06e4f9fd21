"""From a profiler trace to numbers: device busy time, per-operation time,
idle gaps named by what the host was doing.

Two stages, so that the arithmetic can be checked on a small recorded
trace without the profiler: :func:`load` reads an ``.xplane.pb`` into
plain lists, :func:`reduce` does the arithmetic on those lists.

Events are ``[name, start_ns, duration_ns]``. A device plane's operation
line nests (a ``while`` holds its body's operations), so busy time is the
UNION of the intervals, and an operation's own time is its duration less
its children's.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target=\\?"([\w.\-]+)')


def short_name(hlo: str) -> str:
    """``%name opcode[:custom-call target]`` from an operation's HLO text
    (the device line names an event by its whole instruction)."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:120]
    opcode = _OPCODE.search(" " + rest)
    short = name + (" " + opcode.group(1) if opcode else "")
    target = _TARGET.search(rest)
    return short + (":" + target.group(1) if target else "")


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return max(found, key=os.path.getmtime) if found else None


def load(xplane_path: str, *, device_plane=DEVICE_PLANE, ops_line: str = OPS_LINE
         ) -> Dict[str, object]:
    """{"devices": {plane: [event...]}, "host": [event...]}: the operation
    line of every device plane (``/device:TPU:<n>``, line ``XLA Ops``) and
    the benchmark's own host annotations (``/host:CPU``, any thread)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if device_plane.match(plane.name):
            for ln in plane.lines:
                if ln.name == ops_line:
                    devices[plane.name] = [
                        (short_name(e.name), float(e.start_ns), float(e.duration_ns))
                        for e in ln.events
                    ]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in ln.events if e.name.startswith(HOST_PREFIX)
                )
    return {"devices": devices, "host": host}


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Own nanoseconds by operation name on one nesting line."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, own]

    def close(upto: float):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def _covering(host: Sequence[Event], t: float) -> str:
    """The innermost benchmark annotation that covers time ``t``."""
    best, best_dur = "unattributed", float("inf")
    for name, start, dur in host:
        if start <= t < start + dur and dur < best_dur:
            best, best_dur = name, dur
    return best


def reduce(trace: Dict[str, object], *, top: int = 10) -> Dict[str, object]:
    """busy_s (mean over device planes of the union of operation
    intervals), ops and counts (own seconds and launches by name, summed
    over planes and divided by their number), matching by regex left to
    the readers, and the idle
    gaps of the first device plane by covering host annotation."""
    devices: Dict[str, List[Event]] = trace["devices"]
    host: List[Event] = trace["host"]
    if not devices:
        return {"planes": 0, "busy_s": 0.0, "ops": {}, "counts": {},
                "idle_gaps": [], "span_s": 0.0}
    busy, ops, counts = [], {}, {}
    for events in devices.values():
        merged = merge([(s, s + d) for _, s, d in events if d > 0])
        busy.append(sum(e - s for s, e in merged))
        for name, ns in self_times(events).items():
            ops[name] = ops.get(name, 0.0) + ns
        for name, _, _ in events:
            counts[name] = counts.get(name, 0) + 1
    n = len(devices)
    first = next(iter(devices.values()))
    merged = merge([(s, s + d) for _, s, d in first if d > 0])
    gaps: Dict[str, float] = {}
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        name = _covering(host, 0.5 * (e0 + s1))
        gaps[name] = gaps.get(name, 0.0) + (s1 - e0)
    span = (merged[-1][1] - merged[0][0]) if merged else 0.0
    return {
        "planes": n,
        "busy_s": sum(busy) / n / 1e9,
        "span_s": span / 1e9,
        "ops": {k: v / n / 1e9 for k, v in ops.items()},
        "counts": {k: v / n for k, v in counts.items()},
        "idle_gaps": sorted(
            ([k, v / 1e9] for k, v in gaps.items()), key=lambda kv: -kv[1]
        )[:top],
    }


def matching_seconds(ops: Dict[str, float], pattern: str) -> float:
    """Sum over the names ``pattern`` matches (seconds or launches)."""
    rx = re.compile(pattern)
    return sum(v for k, v in ops.items() if rx.search(k))


def top_ops(ops: Dict[str, float], top: int = 10) -> List[List[object]]:
    return [[k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]]
