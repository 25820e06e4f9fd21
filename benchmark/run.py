#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. It needs a TPU with as many chips as the cell asks for and
exits non-zero, printing no result, without one. Everything that belongs
to one configuration, one cell or one per-layer metric is a file found by
its name: ``configs/<config>.json``, ``workloads/<cell>.json`` (which
names its entry module under ``entries/``), ``metrics/<metric>.json``
(which names its reader under ``readers/`` and the reader's arguments).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: every number compared with its limit.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import math
import os
import shutil
import sys
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

_T_IMPORT = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK_DIR = os.path.join(ROOT, ".bench_work")


def _seconds_since_process_start() -> float:
    """Age of this process by the kernel's clock; falls back to the time
    since this module was imported."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Spans:
    """Host spans of the benchmark's own: kept in memory, and written into
    the profiler's trace as annotations while one is being taken."""

    def __init__(self):
        self.closed: List[tuple] = []  # (name, t0, t1)

    @contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.closed.append((name, t0, time.perf_counter()))

    def total(self, name: str, since: float = -math.inf) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.closed if n == name and t0 >= since)


class CompileCounter:
    """Programs compiled (or fetched from the persistent cache) and
    persistent-cache misses, through ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


class SetupContext:
    def __init__(self, config, workload, seed, work_dir, traced, spans):
        self.config, self.workload = config, workload
        self.seed, self.work_dir, self.traced = seed, work_dir, traced
        self.spans = spans

    def span(self, name: str):
        return self.spans.span(name)


class MetricContext:
    """What the readers read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def enable_cache() -> str:
    """The program's own persistent compile cache
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), with
    its one-second floor taken away: a run is a new process, and every
    program it finds in the cache is set-up it does not pay."""
    import jax

    from photon_ml_tpu.utils.backend import enable_compilation_cache

    path = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _start_trace(trace_dir: str) -> None:
    """Device operations and the benchmark's own annotations; no Python
    call tracing, which would swamp the trace and slow the host."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> Dict[str, Dict]:
    """Each number compared, beside its limit: a reading holds when it is
    a number and at most its limit."""
    checks = {}
    for name, limit in limits.items():
        value = float(readings[name])
        checks[name] = {
            "value": value, "limit": float(limit),
            "ok": math.isfinite(value) and value <= float(limit),
        }
    return checks


def _cells_of(metric: Dict, all_cells: List[str]) -> List[str]:
    return metric.get("workloads", all_cells)


def _replayed_count(cell, spec: Dict, trace_dir: str) -> Optional[float]:
    """What one step does, counted where the program itself reports no
    count: ONE more step, after the window has closed, under the profiler;
    the launches of the operations ``spec["match"]`` names, over
    ``launches_per_count``. Every step of a cell starts from the same state
    on the same rows (``repeat_gap`` holds them to the same answer, the
    replayed one too), so the count is every timed step's."""
    import jax

    from benchmark import trace_reduce

    _start_trace(trace_dir)
    try:
        cell.step()
    except Exception as e:  # told, not raised: the metric is then left out
        print(f"benchmark: replayed step raised {type(e).__name__}: {e}", file=sys.stderr)
        return None
    finally:
        jax.profiler.stop_trace()
    xplane = trace_reduce.newest_xplane(trace_dir)
    if xplane is None:
        return None
    counts = trace_reduce.reduce(trace_reduce.load(xplane))["counts"]
    launches = trace_reduce.matching_seconds(counts, spec["match"])
    return launches / float(spec.get("launches_per_count", 1)) if launches > 0 else None


def run_cell(
    bench: Dict, workload_name: str, seed: int, seconds: float, trace: bool,
    *, config_override: Optional[Dict] = None, workload_override: Optional[Dict] = None,
    wrap_cell=None, keep_outputs: Optional[list] = None,
) -> Dict:
    """Set up, warm up, measure, check; returns the result object.
    ``config_override`` and ``wrap_cell`` exist for ``benchmark/tests`` (a tiny size
    on the CPU, a fault planted under the timed path) and, with
    ``workload_override`` (the program's own lower precision switched on) and
    ``keep_outputs``, for ``benchmark/proof.py``; :func:`main` passes none."""
    import jax

    cell_entry = next(w for w in bench["workloads"] if w["name"] == workload_name)
    config_entry = next(c for c in bench["configs"] if c["name"] == cell_entry["config"])
    config = dict(load_json(ROOT, config_entry["file"]))
    config.update(config_override or {})
    workload = dict(load_json(HERE, "workloads", workload_name + ".json"))
    workload.update(workload_override or {})

    if workload.get("matmul_precision"):
        # the precision the configuration states, for every matmul of the
        # program that names none of its own (the TPU's default is bfloat16)
        jax.config.update("jax_default_matmul_precision", workload["matmul_precision"])
    work_dir = os.path.join(WORK_DIR, workload_name)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    spans = Spans()
    counter = CompileCounter()
    ctx = SetupContext(config, workload, seed, work_dir, trace, spans)
    entry = importlib.import_module("benchmark.entries." + workload["entry"])
    cell = entry.setup(ctx)
    if wrap_cell is not None:
        cell = wrap_cell(cell)
    with spans.span("bench.setup.warmup"):
        for _ in range(int(workload.get("warmup_steps", 1))):
            cell.step()
    setup_compiles, setup_misses = counter.compiles, counter.cache_misses

    # ---- the measured window --------------------------------------------
    trace_dir = os.path.join(work_dir, "trace")
    trace_steps = int(workload.get("trace_steps", 2)) if trace else 0
    steps = []  # (t0, t1, units, ok, traced)
    gc.collect()  # set-up's garbage goes before the window, not inside it
    setup_s = _seconds_since_process_start()
    t_window = time.perf_counter()
    if trace_steps:
        _start_trace(trace_dir)
    tracing = bool(trace_steps)
    while True:
        t0 = time.perf_counter()
        try:
            with spans.span("bench.step"):
                r = cell.step()
        except Exception as e:  # a step that raises has failed; keep going
            print(f"benchmark: step raised {type(e).__name__}: {e}", file=sys.stderr)
            r = {"units": 0, "ok": False}
        t1 = time.perf_counter()
        steps.append((t0, t1, int(r["units"]), bool(r["ok"]), tracing))
        if tracing and len(steps) >= trace_steps:
            jax.profiler.stop_trace()
            tracing = False
        if time.perf_counter() - t_window >= seconds:
            break
    if tracing:  # the window closed before ``trace_steps`` steps had run
        jax.profiler.stop_trace()
    window_compiles = counter.compiles - setup_compiles
    peak = memory_peak_bytes()
    array_shapes = cell.array_shapes()
    counted = None  # per step, for the metrics taken ``per: counted``
    if not trace and "replay_count" in workload:
        counted = _replayed_count(
            cell, workload["replay_count"], os.path.join(work_dir, "replay"))

    # ---- after the window: the comparison with the plain reference -------
    t_check = time.perf_counter()
    outputs = cell.take_outputs()
    if keep_outputs is not None:
        keep_outputs.append(outputs)
    gc.collect()
    in_use_before_check = max(
        int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in jax.devices()
    )
    readings = cell.check(outputs)
    check_s = time.perf_counter() - t_check
    checks = judge(readings, workload["limits"])
    failed = sum(1 for s in steps if not s[3])
    correct = failed == 0 and all(c["ok"] for c in checks.values())

    # ---- metrics ----------------------------------------------------------
    devices = jax.devices()
    wall = sum(t1 - t0 for t0, t1, *_ in steps)
    units = sum(s[2] for s in steps)
    all_cells = [w["name"] for w in bench["workloads"]]
    metrics: Dict[str, Dict] = {}
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": peak,
    }
    result: Dict[str, object] = {}
    if not trace:
        for m in bench["end_to_end"]:
            if workload_name not in _cells_of(m, all_cells):
                continue
            if m["name"] == "setup_s":
                value = setup_s
            else:
                spec = workload["end_to_end"][m["name"]]
                if spec["per"] == "counted":
                    if counted is None:
                        continue  # nothing to count by: say nothing
                    per = counted * len(steps)
                else:
                    per = units if spec["per"] == "unit" else len(steps)
                value = float(spec["scale"]) * wall / max(per, 1)
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        from benchmark import trace_reduce, work

        traced = [s for s in steps if s[4]]
        summary = None
        xplane = trace_reduce.newest_xplane(trace_dir)
        if xplane is not None:
            summary = trace_reduce.reduce(trace_reduce.load(xplane))
        traced_wall = traced[-1][1] - traced[0][0] if traced else 0.0
        mctx = MetricContext(
            steps=steps, wall=wall, units=units, spans=spans, t_window=t_window,
            traced_wall=traced_wall, traced_units=sum(s[2] for s in traced),
            traced_steps=len(traced), trace=summary, peak_bytes=peak,
            counters={"window_compiles": window_compiles,
                      "setup_compiles": setup_compiles,
                      "setup_cache_misses": setup_misses},
            cell=cell, peaks=work.peaks_for(devices[0].device_kind),
        )
        for m in bench["per_layer"]:
            if workload_name not in _cells_of(m, all_cells):
                continue
            spec = load_json(HERE, "metrics", m["name"] + ".json")
            reader = importlib.import_module("benchmark.readers." + spec["reader"])
            value = reader.read(mctx, **spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = traced_wall
            result["breakdown"] = {
                "device_ops": trace_reduce.top_ops(summary["ops"]),
                "idle_gaps": summary["idle_gaps"],
            }
    out = {
        "correct": bool(correct), "attempted": len(steps), "failed": failed,
        "metrics": metrics, "device": device,
    }
    out.update(result)
    out["extra"] = {
        "workload": workload_name, "seed": seed, "units": units,
        "window_wall_s": wall, "check_s": check_s, "counted_per_step": counted,
        "step_walls_s": [t1 - t0 for t0, t1, *_ in steps],
        "bytes_in_use_before_check": in_use_before_check,
        "window_compiles": window_compiles, "setup_compiles": setup_compiles,
        "setup_cache_misses": setup_misses,
        "setup_spans_s": {
            n: spans.total(n) for n in sorted({s[0] for s in spans.closed})
            if n.startswith("bench.setup.")
        },
        "schedule_shapes": getattr(cell, "schedule_shapes", {}),
        "array_shapes": array_shapes,
        "readings": {k: float(v) for k, v in readings.items()},
    }
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell_entry = next(
        (w for w in bench["workloads"] if w["name"] == args.workload), None
    )
    if cell_entry is None:
        print(f"benchmark: no workload {args.workload!r}", file=sys.stderr)
        return 2
    try:
        import photon_ml_tpu  # noqa: F401  the system under test
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout: {e}", file=sys.stderr)
        return 3
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(cell_entry["chips"]):
        print(
            f"benchmark: {args.workload} needs {cell_entry['chips']} TPU chip(s); "
            f"jax.devices() = {devices}", file=sys.stderr,
        )
        return 2
    enable_cache()
    out = run_cell(bench, args.workload, args.seed, args.seconds, bool(args.trace))
    sys.stdout.flush()
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})", file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
