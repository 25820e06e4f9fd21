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
beside the device lines, on one clock; and every jaxpr trace and backend
compile that ``jax.monitoring`` reports becomes a ``jax.trace`` /
``jax.compile`` span."""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Dict, Optional

import jax
import jax.monitoring

from photon_ml_tpu.obs import trace as _obs_trace

# jax.monitoring duration event -> span name: a retrace or a compile
# inside a timed window stops being invisible
_JAX_DURATION_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}


def _on_jax_duration(event: str, seconds: float, **_kw) -> None:
    name = _JAX_DURATION_SPANS.get(event)
    if name is None:
        return
    # already elapsed when jax reports it: the ring gets the true window
    # under the span that caused it; the profiler's API opens no event
    # after the fact, so the trace gets a marker carrying the seconds
    t1 = time.perf_counter()  # photon: entropy(span timestamp; telemetry only, never feeds a result)
    parent = _obs_trace.current_span()
    _obs_trace.record_span(
        name, t1 - seconds, t1,
        trace_id=parent.trace_id if parent is not None else None,
        parent_id=parent.span_id if parent is not None else None,
    )
    with jax.profiler.TraceAnnotation(
        _obs_trace.ANNOTATION_PREFIX + name, seconds=float(seconds)
    ):
        pass


_obs_trace.set_annotation_factory(jax.profiler.TraceAnnotation)
jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)



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


# -- host-side timing registry ----------------------------------------------
#
# jax.profiler covers device timelines; HOST-side one-off costs (schedule
# builds, cache loads/stores) need their own accumulation so drivers can
# report them without wrapping every call site in a Timer. Named buckets
# accumulate across the process; drivers snapshot into metrics.json.

_HOST_TIMINGS: Dict[str, float] = {}
_HOST_TIMINGS_LOCK = threading.Lock()


def record_host_timing(name: str, seconds: float) -> None:
    """Accumulate ``seconds`` into the named host-timing bucket
    (thread-safe — schedule builds run on worker threads)."""
    with _HOST_TIMINGS_LOCK:
        _HOST_TIMINGS[name] = _HOST_TIMINGS.get(name, 0.0) + seconds


def host_timings() -> Dict[str, float]:
    """Snapshot of all accumulated host-timing buckets."""
    with _HOST_TIMINGS_LOCK:
        return dict(_HOST_TIMINGS)


def reset_host_timings() -> None:
    with _HOST_TIMINGS_LOCK:
        _HOST_TIMINGS.clear()


def peak_rss_bytes() -> int:
    """Host peak-RSS high-water of this process in BYTES (ru_maxrss is
    KiB on Linux, bytes on macOS) — the out-of-core layer's reported
    memory ceiling (``streaming.peak_rss_bytes`` in metrics.json)."""
    import resource
    import sys

    ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return int(ru if sys.platform == "darwin" else ru * 1024)
