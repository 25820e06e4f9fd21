#!/usr/bin/env python3
"""The readings the limits in ``workloads/<cell>.json`` are set from.

    python3 benchmark/proof.py --workload <name> --seeds 1,2,3 --controls 3

Not part of a benchmark run. For each seed, in one process: a short run
of the cell as ``run.py`` makes it (the program's readings), and for the
first ``--controls`` seeds the same comparison with, in the program's
place, the reference in bfloat16 (the control), the reference on half of
the batch, and the program's own outputs with each planted fault. Every
one goes through ``run.judge`` against the cell's limits, and ``correct``
has to come out false for each. ``--matmul-precision default`` runs the
PROGRAM below the precision its cell states (the program's own
lower-precision path as a control; a process of its own, because the
program keeps its compiled solvers for the life of a process), and
``--shape-seed`` reads another data set than the configuration's. One JSON
line a reading, to
``chiprun_out/proof.<cell>.jsonl`` and standard output.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--shape-seed", type=int, default=None)
    ap.add_argument("--matmul-precision", default=None)
    args = ap.parse_args(argv)

    import jax

    from benchmark import faults, run

    if jax.devices()[0].platform != "tpu":
        print("proof: needs the TPU", file=sys.stderr)
        return 2
    run.enable_cache()
    bench = run.load_json(ROOT, "BENCHMARK.json")
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, f"proof.{args.workload}.jsonl"), "a")

    def emit(**record):
        line = json.dumps(record)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    config_override = (
        {} if args.shape_seed is None else {"shape_seed": args.shape_seed}
    )

    workload_override = (
        {} if args.matmul_precision is None
        else {"matmul_precision": args.matmul_precision}
    )
    kind = "program" if not workload_override else (
        "control_program_at_" + args.matmul_precision)

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        kept = []
        out = run.run_cell(
            bench, args.workload, seed, args.seconds, False,
            config_override=config_override, workload_override=workload_override,
            wrap_cell=lambda cell: kept.append(cell) or cell, keep_outputs=kept,
        )
        emit(seed=seed, kind=kind, shape_seed=args.shape_seed, correct=out["correct"],
             failed_checks=[k for k, c in out["checks"].items() if not c["ok"]],
             readings=out["extra"]["readings"], attempted=out["attempted"],
             check_s=out["extra"]["check_s"], metrics=out["metrics"],
             counted_per_step=out["extra"]["counted_per_step"],
             setup_spans_s=out["extra"]["setup_spans_s"])
        if i >= args.controls:
            continue
        cell, outputs = kept
        entry = cell.wl["entry"]
        n = int(cell.data.labels.shape[0])
        cases = [
            ("control_bf16", lambda: cell.reference_outputs("bf16")),
            ("fault_half_batch",
             lambda: cell.reference_outputs("f32", faults.half_batch(n))),
        ] + [
            ("fault_" + name, lambda f=f: f(outputs))
            for name, f in importlib.import_module(
                "benchmark.entries." + entry
            ).FAULTS.items()
        ]
        for case, make in cases:
            t0 = time.perf_counter()
            readings = cell.check(make())
            checks = run.judge(readings, cell.wl["limits"])
            emit(seed=seed, kind=case, shape_seed=args.shape_seed,
                 correct=all(c["ok"] for c in checks.values()),
                 failed_checks=[k for k, c in checks.items() if not c["ok"]],
                 readings={k: float(v) for k, v in readings.items()},
                 seconds=time.perf_counter() - t0)
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
