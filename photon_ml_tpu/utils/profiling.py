"""Profiler hooks: ``jax.profiler`` traces behind the drivers'
``--profile-dir`` flag (SURVEY §7.11 — the deliberate upgrade over the
reference's Timer-only observability: XLA/TPU timelines instead of wall
-clock buckets). Traces land in the given directory (conventionally
``<output-dir>/profile``, next to optimization-log.txt) and open in
TensorBoard / Perfetto.

Importing this module also gives ``obs/trace.py`` its way to the
profiler (obs/ itself imports no jax): every ``span()`` / ``traced()``
then opens a ``jax.profiler.TraceAnnotation`` named ``photon.<span>``, so
a trace taken under ``--profile-dir`` shows the program's own spans
beside the device lines, on one clock; and every stage of a compile that
``jax.monitoring`` reports becomes a span naming its program:
``jax.trace``, ``jax.lower``, ``jax.compile`` (``cache=hit|miss|unsaved|none``)
and, under a compile the persistent cache served, ``jax.cache_read``."""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Optional

import jax
import jax.monitoring

from photon_ml_tpu.obs import trace as _obs_trace

# jax.monitoring duration event -> span name: a retrace, a lowering or a
# compile inside a timed window stops being invisible, and set-up can
# tell the three apart. Each carries ``program``: the ``fun_name`` jax
# hands the listener (the function given to ``jax.jit``).
_JAX_DURATION_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
_JAX_COMPILE = "jax.compile"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# ``backend_compile_duration`` covers ``compile_or_get_cached``: a compile
# OR a read of the persistent cache. The cache's own events fire inside
# it, on the thread that compiles (a solver pool's worker as well as the
# main thread), before it ends: kept here until the stage's span is filed.
# ``hit``: the cache served it. ``miss``: compiled and written back (the
# event a benchmark counts as a cache miss). ``unsaved``: the cache was
# asked, the program compiled and was NOT written back (under the
# ``jax_persistent_cache_min_compile_time_secs`` floor, mostly), so the
# next process compiles it again. ``none``: no cache was asked.
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "unsaved",
    "/jax/compilation_cache/cache_misses": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
_PENDING = threading.local()


def _on_jax_event(event: str, **_kw) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome is not None:
        _PENDING.cache = outcome


def _on_jax_duration(event: str, seconds: float, **kw) -> None:
    # already elapsed when jax reports it: the ring gets the true window
    # under the span that caused it; the profiler's API opens no event
    # after the fact, so the trace gets a marker carrying the seconds
    t1 = time.perf_counter()  # photon: entropy(span timestamp; telemetry only, never feeds a result)
    if event == _CACHE_READ_EVENT:
        _PENDING.read = (t1 - seconds, t1)
        return
    name = _JAX_DURATION_SPANS.get(event)
    if name is None:
        return
    # (tracing names the function, lowering and compiling its module,
    # ``jit(<function>)``: one name for all three)
    program = str(kw.get("fun_name", ""))
    if program.endswith(")") and "(" in program:
        program = program[program.index("(") + 1:-1]
    attrs = {"program": program}
    read = None
    if name == _JAX_COMPILE:
        attrs["cache"] = _PENDING.__dict__.pop("cache", "none")
        read = _PENDING.__dict__.pop("read", None)
    filed = _obs_trace.record_elapsed(name, t1 - seconds, t1, **attrs)
    if read is not None:
        _obs_trace.record_elapsed(
            "jax.cache_read", *read, parent=filed,
            program=attrs["program"],
        )
    with jax.profiler.TraceAnnotation(
        _obs_trace.ANNOTATION_PREFIX + name, seconds=float(seconds), **attrs
    ):
        pass


_obs_trace.set_annotation_factory(jax.profiler.TraceAnnotation)
jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
jax.monitoring.register_event_listener(_on_jax_event)


@contextmanager
def _trace(profile_dir: str):
    os.makedirs(profile_dir, exist_ok=True)
    with jax.profiler.trace(profile_dir):
        yield


def profile_trace(profile_dir: Optional[str]):
    """Context manager: a ``jax.profiler`` trace into ``profile_dir``,
    or a no-op when the flag is unset."""
    if not profile_dir:
        return nullcontext()
    return _trace(profile_dir)


def peak_rss_bytes() -> int:
    """Host peak-RSS high-water of this process in BYTES (ru_maxrss is
    KiB on Linux, bytes on macOS) — the out-of-core layer's reported
    memory ceiling (``streaming.peak_rss_bytes`` in metrics.json)."""
    import resource
    import sys

    ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return int(ru if sys.platform == "darwin" else ru * 1024)
