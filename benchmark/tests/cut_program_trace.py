#!/usr/bin/env python3
"""Cut a recorded profiler trace down to what ``program_trace``'s test holds.

    python3 benchmark/tests/cut_program_trace.py <trace_dir> <out.json>

Keeps, of the first device plane, the program runs (``XLA Modules``) that
reach into the first ``photon.cd.iteration`` span, cut to it, and the
program's spans inside it. Beside them it writes what ``program_trace``
makes of the cut, to be checked by hand once and then held by
``test_the_arithmetic_on_a_recorded_trace``.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import program_trace, trace_reduce  # noqa: E402

INSIDE = "photon.cd.iteration"
PATTERNS = [
    r"^jit_glm_fit\(", r"^jit_bank_",
    r"^jit_(re_score|fe_score|cd_objective|cd_residual|cd_total)\(",
    r"^jit_(?!(glm_fit|re_score|fe_score|cd_objective|cd_residual|cd_total)\(|bank_)",
]


def main(trace_dir: str, out: str) -> None:
    loaded = program_trace.load(trace_reduce.newest_xplane(trace_dir))
    plane, runs = next(iter(loaded["modules"].items()))
    t0, dur = next((s, d) for name, s, d in loaded["spans"] if name == INSIDE)
    t1 = t0 + dur
    cut = [(n, max(s, t0), min(s + d, t1)) for n, s, d in runs if s < t1 and s + d > t0]
    trace = {
        "modules": {plane: [[n, s - t0, e - s] for n, s, e in cut]},
        "spans": [[n, s - t0, d] for n, s, d in loaded["spans"] if s >= t0 and s + d <= t1],
    }
    expect = {
        "module_seconds": {p: program_trace.module_seconds(trace, p) for p in PATTERNS},
        "idle_inside": program_trace.idle_inside(trace, INSIDE),
    }
    with open(out, "w") as f:
        json.dump({"trace": trace, "expect": expect}, f)
    print(len(trace["modules"][plane]), "program runs,", len(trace["spans"]), "spans;", expect)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
