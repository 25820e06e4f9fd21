"""Where set-up went: every instant from process start (the kernel's
clock, as ``setup_s`` takes it) to the window, filed under the innermost
span open on the MAIN thread at that instant. The spans are the program's
own (``photon_ml_tpu.obs.trace.tracer()``'s ring, which ``span()`` files
into with nobody asking) and the benchmark's ``bench.setup.generate`` (the
benchmark's rows, not the program's: kept so that the rest sums); an
instant no span covers is ``dark``. One span is followed into the thread
it waits for: inside ``overlap.prep_wait`` (the main thread waiting for a
prefetched ``prepare`` on the prep worker, which is where a fixed effect's
schedules are built and a bank's solvers warmed) an instant goes to the
span that opened last on any OTHER thread, and to the wait itself where
none is open. So the shares PARTITION the traced run's set-up wall: over
all names, dark included, they sum to it.

``read(ctx, spans, but)`` returns the seconds under the names ``spans``
lists (a name, or a prefix ending in ``*``), less those ``but`` lists;
``spans: null`` is dark. The whole split, the largest dark stretches, what
the other threads did while the main thread waited for them (``WAITS``),
and the programs that cost most to trace, lower and compile (all threads)
go to standard error, once a run. A ring with no span before the window (a
program that files none: the parent of the PR that made ``span()`` always
file) reads ``None``: say nothing.

Spans here are plain tuples ``(name, t0, t1, tid, attrs)`` on
``time.perf_counter()``, so the arithmetic is checked on hand-made lists.
"""

from __future__ import annotations

import heapq
import os
import sys
import threading
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DARK = "dark"
GENERATE = "bench.setup.generate"
BENCH_PREFIX = "bench."
# where the main thread waits for another: the solver pool's compiles, a
# prefetched ``prepare`` on the prep worker
WAITS = ("bank.warm_solvers", "overlap.prep_wait")
FOLLOWED = "overlap.prep_wait"  # filed under what the waited-for threads do
STAGES = ("jax.trace", "jax.lower", "jax.compile")
CACHE_READ = "jax.cache_read"
OUTCOMES = ("hit", "miss", "unsaved", "none")  # ``cache=`` of a jax.compile

Span = Tuple[str, float, float, int, Dict]


def matches(name: str, patterns: Optional[Iterable[str]]) -> bool:
    return any(
        name.startswith(p[:-1]) if p.endswith("*") else name == p
        for p in patterns or ()
    )


def partition(
    intervals: Sequence[Tuple[str, float, float]], t0: float, t1: float
) -> List[Tuple[str, float, float]]:
    """[t0, t1) cut into pieces ``(name, start, end)``, each under the
    innermost interval that covers it: of those open, the one that opened
    last (of two that opened together, the one that closes first); ``DARK``
    where none is open. Adjacent pieces of one name are merged."""
    clipped = sorted(
        (max(a, t0), min(b, t1), name)
        for name, a, b in intervals if min(b, t1) > max(a, t0)
    )
    bounds = sorted({t0, t1} | {x for a, b, _ in clipped for x in (a, b)})
    pieces: List[Tuple[str, float, float]] = []
    open_: List[Tuple[float, float, str]] = []  # (-start, end, name)
    at = 0
    for left, right in zip(bounds, bounds[1:]):
        while at < len(clipped) and clipped[at][0] <= left:
            a, b, name = clipped[at]
            heapq.heappush(open_, (-a, b, name))
            at += 1
        while open_ and open_[0][1] <= left:
            heapq.heappop(open_)
        name = open_[0][2] if open_ else DARK
        if pieces and pieces[-1][0] == name and pieces[-1][2] == left:
            pieces[-1] = (name, pieces[-1][1], right)
        else:
            pieces.append((name, left, right))
    return pieces


def totals(pieces: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, a, b in pieces:
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def main_thread_intervals(
    ring: Sequence[Span], bench: Sequence[Tuple[str, float, float]],
    main_tid: int, bench_names: Sequence[str] = (GENERATE,),
) -> List[Tuple[str, float, float]]:
    """The main thread's program spans and those of the benchmark's own
    that count as spans."""
    return [
        (name, a, b) for name, a, b, tid, _ in ring if tid == main_tid
    ] + [(n, a, b) for n, a, b in bench if n in bench_names]


def split(
    ring: Sequence[Span], bench: Sequence[Tuple[str, float, float]],
    t_start: float, t_window: float, main_tid: int,
) -> Optional[Dict[str, object]]:
    """The partition of [t_start, t_window) and what standard error tells
    beside it; ``None`` where the ring holds no span that began before the
    window."""
    ring = [s for s in ring if s[1] < t_window]
    if not ring:
        return None
    elsewhere = [(n, a, b) for n, a, b, tid, _ in ring if tid != main_tid]
    pieces: List[Tuple[str, float, float]] = []
    for piece in partition(
        main_thread_intervals(ring, bench, main_tid), t_start, t_window
    ):
        if piece[0] == FOLLOWED:
            pieces += [
                (FOLLOWED if n == DARK else n, a, b)
                for n, a, b in partition(elsewhere, piece[1], piece[2])
            ]
        else:
            pieces.append(piece)
    # dark, by the benchmark's own span around it and by its neighbours
    frames = partition(
        [(n, a, b) for n, a, b in bench if n.startswith(BENCH_PREFIX)],
        t_start, t_window)
    dark_under: Dict[str, float] = {}
    for name, a, b in pieces:
        if name != DARK:
            continue
        for frame, fa, fb in frames:
            overlap = min(b, fb) - max(a, fa)
            if overlap > 0:
                key = "no bench span" if frame == DARK else frame
                dark_under[key] = dark_under.get(key, 0.0) + overlap
    stretches = sorted((
        (b - a, pieces[i - 1][0] if i else "process start",
         pieces[i + 1][0] if i + 1 < len(pieces) else "the window")
        for i, (name, a, b) in enumerate(pieces) if name == DARK
    ), reverse=True)[:8]
    # the compile stages, every thread
    programs: Dict[str, Dict[str, float]] = {}
    misses: List[Tuple[str, float]] = []
    for name, a, b, _, attrs in ring:
        if name in STAGES or name == CACHE_READ:
            row = programs.setdefault(str(attrs.get("program", "")), {})
            row[name] = row.get(name, 0.0) + (b - a)
            if name == "jax.compile":
                outcome = str(attrs.get("cache", "none"))
                row[outcome] = row.get(outcome, 0) + 1
                if outcome == "miss":
                    misses.append((str(attrs.get("program", "")), b - a))
                elif outcome == "unsaved":
                    row["unsaved_s"] = row.get("unsaved_s", 0.0) + (b - a)
    # what the other threads did while the main thread waited for them:
    # each other thread's own innermost spans inside the main thread's waits
    waits: Dict[str, Dict[str, object]] = {}
    others = {tid for _, _, _, tid, _ in ring if tid != main_tid}
    for wait in WAITS:
        windows = [(a, b) for name, a, b, tid, _ in ring
                   if name == wait and tid == main_tid]
        if not windows:
            continue
        did: Dict[str, float] = {}
        for tid in others:
            mine = [(n, a, b) for n, a, b, t, _ in ring if t == tid]
            for wa, wb in windows:
                for name, s in totals(partition(mine, wa, wb)).items():
                    if name != DARK:
                        did[name] = did.get(name, 0.0) + s
        waits[wait] = {"waits": len(windows), "threads": len(others),
                       "seconds": sum(b - a for a, b in windows), "did": did}
    return {
        "wall": t_window - t_start, "totals": totals(pieces),
        "dark_under": dark_under, "dark_stretches": stretches,
        "programs": programs, "misses": misses,
        "waits": waits,
    }


def tell(result: Dict[str, object], filed: int, dropped: int, file=None):
    out = file or sys.stderr
    print(f"setup split: process start to window {result['wall']:.6g} s; "
          f"ring {filed} spans, dropped {dropped}", file=out)
    for name, s in sorted(result["totals"].items(), key=lambda kv: -kv[1]):
        print(f"setup split {name}: {s:.6g} s", file=out)
    for frame, s in sorted(result["dark_under"].items(), key=lambda kv: -kv[1]):
        print(f"setup dark under {frame}: {s:.6g} s", file=out)
    for s, before, after in result["dark_stretches"]:
        print(f"setup dark stretch {s:.6g} s after {before}, before {after}",
              file=out)
    for wait, w in result["waits"].items():
        did = ", ".join(f"{n} {x:.6g}" for n, x in sorted(
            w["did"].items(), key=lambda kv: -kv[1])[:8])
        print(f"setup wait {wait}: {w['waits']} waits, {w['seconds']:.6g} s "
              f"of the main thread; meanwhile the other {w['threads']} "
              f"threads' innermost spans (thread-seconds): {did or 'none'}",
              file=out)

    def cost(row):
        return sum(row.get(n, 0.0) for n in STAGES)

    ranked = sorted(result["programs"].items(), key=lambda kv: -cost(kv[1]))
    for program, row in ranked[:10]:
        print(
            f"setup program {program}: trace {row.get('jax.trace', 0.0):.6g}"
            f" + lower {row.get('jax.lower', 0.0):.6g} + compile "
            f"{row.get('jax.compile', 0.0):.6g} s (cache read "
            f"{row.get(CACHE_READ, 0.0):.6g}); compiles: "
            + ", ".join(f"{int(row[o])} {o}" for o in OUTCOMES if o in row),
            file=out)
    rows = list(result["programs"].values())
    print("setup programs, all threads: " + ", ".join(
        f"{n} {sum(r.get(n, 0.0) for r in rows):.6g} s"
        for n in STAGES + (CACHE_READ,)) + "; compiles: " + ", ".join(
        f"{int(sum(r.get(o, 0) for r in rows))} {o}" for o in OUTCOMES),
        file=out)
    print(f"setup compiled and not written back (cache=unsaved, compiled "
          f"again by every run): {sum(r.get('unsaved_s', 0.0) for r in rows):.6g} s",
          file=out)
    for program, s in result["misses"]:
        print(f"setup cache miss {program}: compiled in {s:.6g} s", file=out)


def process_start() -> float:
    """Process start on ``time.perf_counter()``'s axis, by the kernel's
    clock: as ``run.py`` takes ``setup_s``."""
    from benchmark.run import _seconds_since_process_start

    return time.perf_counter() - _seconds_since_process_start()


def ring_of_process() -> Tuple[List[Span], int, int]:
    """(spans closed so far as tuples, spans held, spans dropped)."""
    from photon_ml_tpu.obs.trace import tracer

    t = tracer()
    spans = [
        (s.name, s.t0, s.t1, s.tid, s.attrs)
        for s in t.snapshot() if s.t1 is not None
    ]
    return spans, len(t), t.dropped


def keep_ring(ring: Sequence[Span], ctx) -> None:
    """The run's spans, set-up and window, beside its trace
    (``.bench_work/<cell>/spans.json``: ``[name, t0, t1, tid, attrs]``, the
    benchmark's own ``[name, t0, t1]`` and the window's start), for whoever
    wants another cut of them."""
    import json

    from benchmark import program_trace

    path = os.path.join(
        os.path.dirname(program_trace.trace_dir(ctx.cell.wl["name"])),
        "spans.json")
    try:
        with open(path, "w") as f:
            json.dump({"t_start": process_start(), "t_window": ctx.t_window,
                       "main_tid": threading.main_thread().ident,
                       "bench": [list(s) for s in ctx.spans.closed],
                       "spans": [list(s) for s in ring]}, f, default=str)
    except OSError as e:  # told, not raised: the metrics do not need it
        print(f"setup split: spans not kept: {e}", file=sys.stderr)


def of(ctx) -> Optional[Dict[str, object]]:
    """The split of the run ``ctx`` (``run.MetricContext``) belongs to,
    made and told once."""
    if "_setup_split" not in ctx.__dict__:
        ring, filed, dropped = ring_of_process()
        result = split(
            ring, ctx.spans.closed, process_start(), ctx.t_window,
            threading.main_thread().ident,
        )
        if result is not None:
            tell(result, filed, dropped)
            keep_ring(ring, ctx)
        ctx.__dict__["_setup_split"] = result
    return ctx.__dict__["_setup_split"]


def read(ctx, spans=None, but=None):
    result = of(ctx)
    if result is None:
        return None  # the program files no span: say nothing
    shares = result["totals"]
    if spans is None:
        return shares.get(DARK, 0.0)
    return sum(
        s for name, s in shares.items()
        if name != DARK and matches(name, spans) and not matches(name, but)
    )
