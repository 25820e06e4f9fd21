#!/usr/bin/env python3
"""Which layer the device operations of a profiler trace belong to.

This chip's profiler writes the programs' module names (line ``XLA
Modules``: ``jit_glm_fit``, ``jit_bank_fused``, ...) and the kernels'
names into the trace, but no ``jax.named_scope`` (``objective.*``,
``lbfgs.*``, ``tron.*``, ``bank.*``, ``cd.*``): those live in the
compiled programs' text, in each instruction's ``metadata={op_name=...}``.
Have XLA dump that text beside the trace, then join the two:

    XLA_FLAGS="--xla_dump_to=<dump> --xla_dump_hlo_as_text" \\
        python3 -m photon_ml_tpu.cli.game_training_driver ... --profile-dir <profile>
    python3 dev-scripts/trace_scopes.py <profile> <dump>

(a benchmark cell's traced window is ``.bench_work/<cell>/trace``). Prints
the ten operations of the first device by own time, each with the module
it ran in and its scope.
"""

import glob
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import program_trace, trace_reduce  # noqa: E402

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = ")


def scope_of(hlo):
    """The ``op_name`` an instruction's text carries (module and name
    scopes, ``jit(glm_fit)/.../lbfgs.line_search/objective.margins/...``),
    or the empty string."""
    found = _OP_NAME.search(hlo)
    return found.group(1) if found else ""


def hlo_scopes(dump_dir):
    """{module: {instruction: scope}} from the optimized programs XLA
    dumped as text (``module_<n>.<module>.<...>after_optimizations.txt``)."""
    out = {}
    for path in glob.glob(os.path.join(dump_dir, "*after_optimizations.txt")):
        scopes = out.setdefault(os.path.basename(path).split(".")[1], {})
        with open(path) as f:
            for line in f:
                named = _INSTRUCTION.match(line)
                if named and scope_of(line):
                    scopes.setdefault(named.group(1), scope_of(line))
    return out


def module_of(runs, t):
    """The program run that covers time ``t`` on one device."""
    for name, start, dur in runs:
        if start <= t < start + dur:
            return name
    return ""


def top_ops(ops, runs, scopes, top=10):
    """The ``top`` operations by own time as (seconds, operation, module,
    scope). ``ops`` and ``runs`` are one device's operation and module
    events; an instruction's name is its own only inside its module."""
    placed = [
        (module_of(runs, start + 0.5 * dur) + "\t" + name, start, dur)
        for name, start, dur in ops
    ]
    out = []
    own = trace_reduce.self_times(placed)
    for key, ns in sorted(own.items(), key=lambda kv: -kv[1])[:top]:
        module, name = key.split("\t")
        dumped = scopes.get(module.split("(")[0], {})
        out.append((ns / 1e9, name, module, dumped.get(name.split(" ")[0], "")))
    return out


def main(profile_dir, dump_dir):
    xplane = trace_reduce.newest_xplane(profile_dir)
    plane, ops = next(iter(trace_reduce.load(xplane)["devices"].items()))
    runs = program_trace.load(xplane)["modules"].get(plane, [])
    for seconds, op, module, scope in top_ops(ops, runs, hlo_scopes(dump_dir)):
        print(f"{seconds:10.6f} s  {op}  [{module}]  {scope}")


if __name__ == "__main__":
    main(*sys.argv[1:3])
