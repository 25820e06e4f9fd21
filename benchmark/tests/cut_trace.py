#!/usr/bin/env python3
"""Cut a recorded profiler trace down to a file a test can hold.

    python3 benchmark/tests/cut_trace.py <trace_dir> <out.json> [events]

Keeps the first ``events`` device events of the first device plane (ended
on a top-level boundary, so no operation is cut from its parent) and the
benchmark's host annotations, and writes beside them what
``trace_reduce.reduce`` makes of the cut, to be checked by hand once and
then held by ``test_trace_reduction_on_a_recorded_trace``.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import trace_reduce  # noqa: E402


def main(trace_dir: str, out: str, events: int = 300) -> None:
    loaded = trace_reduce.load(trace_reduce.newest_xplane(trace_dir))
    plane, all_events = next(iter(loaded["devices"].items()))
    cut = sorted(all_events, key=lambda e: (e[1], -e[2]))[:events]
    end = max(s + d for _, s, d in cut)
    while cut and cut[-1][1] + cut[-1][2] < end:
        cut.pop()  # back to the event that closes last
    t0 = cut[0][1]
    trace = {
        "devices": {plane: [[n, s - t0, d] for n, s, d in cut]},
        "host": [[n, s - t0, d] for n, s, d in loaded["host"]],
    }
    reduced = trace_reduce.reduce(trace)
    with open(out, "w") as f:
        json.dump({"trace": trace, "expect": {
            "busy_s": reduced["busy_s"], "ops": reduced["ops"],
        }}, f)
    print(len(cut), "events;", reduced["busy_s"], "s busy")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *(int(a) for a in sys.argv[3:]))
