"""Backend/platform introspection that respects ``jax.default_device``.

``jax.default_backend()`` initializes and reports the process-default
platform (TPU when a plugin is pinned) even inside a
``jax.default_device(cpu)`` scope. Hermetic CPU-mesh paths (the driver's
``dryrun_multichip``) must never touch the TPU runtime, so library code
that branches on "what device will my arrays land on" uses
:func:`effective_platform` instead.
"""

from __future__ import annotations

import os


REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent XLA compilation cache; returns its
    directory. Every driver calls this once at start-up.

    Compiling is most of a cold run (seconds per program against
    milliseconds per warm step), and the cache carries compiled programs
    across processes. Where ``JAX_COMPILATION_CACHE_DIR`` is set the
    operator has placed the cache: JAX reads the variable itself and no
    directory is set in code. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (git-ignored) — the directory is part of
    the cache key, so it is never a temp name, pid or time. Safe to call
    multiple times.

    A Pallas kernel's serialized body, which the key covers, carries its
    operations' source locations, by default with ten frames of the stack
    of whoever traced the kernel first. The bank's programs are traced
    from several places (``prewarm``, a prefetched ``prepare``,
    ``update_bank``, each through the solver pool's threads), so such a
    program's key could change from run to run and the program compile
    again. With ONE frame, the operation's own line in the kernel's file,
    the bytes are the same for every caller; the kernels keep their
    names on the device (``photon_*``), which come with the frame."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    # cache anything that took meaningful compile time (the 1 s floor
    # skips the many tiny programs)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    return path


def effective_platform() -> str:
    """Platform new unannotated arrays land on under the CURRENT context.

    Honors ``jax.default_device`` scopes (returns "cpu" inside one even
    when a TPU plugin is installed) and only initializes the backend the
    caller is about to use anyway. Safe under a trace (a batching rule
    that picks a kernel asks from inside ``jit``): the probe array is
    made eagerly.
    """
    import jax
    import jax.numpy as jnp

    with jax.ensure_compile_time_eval():
        return next(iter(jnp.zeros(()).devices())).platform
