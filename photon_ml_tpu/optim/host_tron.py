"""Host-driven TRON for objectives that cannot be traced into jit.

The in-jit optimizer (optim.tron.minimize_tron) compiles the whole
trust-region while_loop — impossible when each (value, gradient) or
Hessian-vector evaluation performs host IO (the streaming >RAM input
path, io/streaming.py). This variant drives the SAME math from Python:
LIBLINEAR eta/sigma trust-region rules, Steihaug truncated CG (<=20
iterations, one streamed Hv pass per step — exactly the reference's
one-cluster-aggregate-per-CG-step loop,
HessianVectorAggregator.scala:137-152 + TRON.scala:259-341), and the
shared convergence rules (Optimizer.scala:156-170).

Readback discipline (round 10): control scalars come back
BATCHED through the counted ``overlap.device_get`` seam — per CG step
one residual-norm check plus one (d·Hd, d·d, s·d, s·s) batch (the
boundary norm ‖s+αd‖ derives from those on host, so the old separate
norm pull is gone), and per outer iteration ONE batch carrying the
step/model scalars (g·s, s·r, f_new, ‖s‖, ‖g_new‖, the projection flag
and the device-computed convergence reason)."""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import numpy as np
import jax.numpy as jnp

from photon_ml_tpu.optim.common import (
    BoxConstraints,
    GRADIENT_WITHIN_TOLERANCE,
    MAX_ITERATIONS,
    NOT_CONVERGED,
    OptResult,
    Tracker,
    check_convergence,
)
from photon_ml_tpu.parallel import overlap

Array = jnp.ndarray
ValueAndGrad = Callable[[Array], Tuple[Array, Array]]

# LIBLINEAR trust-region constants (TRON.scala / tron.cpp) — identical to
# optim.tron so the two drivers walk the same iterate sequence.
_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0


def _truncated_cg_host(hvp, g, delta, *, max_cg: int, cg_tol_factor=0.1,
                       g_norm: Optional[float] = None):
    """Steihaug truncated CG, host-driven: each iteration costs ONE hvp
    call (= one streamed pass) plus two batched scalar fetches. Returns
    (s, r) with r = -g - H s, the tron.cpp prered trick.

    ``g_norm``: the caller's already-fetched ‖g‖ (skips a pull)."""
    if g_norm is None:
        g_norm = float(overlap.device_get(jnp.linalg.norm(g)))
    cg_tol = cg_tol_factor * g_norm
    s = jnp.zeros_like(g)
    r = -g
    d = r
    rtr = g_norm * g_norm
    for _ in range(max_cg):
        if np.sqrt(max(rtr, 0.0)) <= cg_tol:
            break
        hd = hvp(d)
        # ONE batch: curvature + the boundary-geometry scalars (the old
        # separate ‖s+αd‖ pull derives from these on host)
        dhd, dd, sd, ss = (
            float(v) for v in overlap.device_get((
                jnp.vdot(d, hd), jnp.vdot(d, d),
                jnp.vdot(s, d), jnp.vdot(s, s),
            ))
        )
        alpha = rtr / dhd if dhd > 0 else 0.0
        s_new_sq = ss + 2.0 * alpha * sd + alpha * alpha * dd
        hit = dhd <= 0 or np.sqrt(max(s_new_sq, 0.0)) >= delta
        if hit:
            # walk to the trust-region boundary and stop
            rad = np.sqrt(max(sd * sd + dd * (delta * delta - ss), 0.0))
            tau = (-sd + rad) / max(dd, 1e-30)
            s = s + tau * d
            r = r - tau * hd
            break
        s = s + alpha * d
        r = r - alpha * hd
        rtr_new = float(overlap.device_get(jnp.vdot(r, r)))
        beta = rtr_new / max(rtr, 1e-30)
        d = r + beta * d
        rtr = rtr_new
    return s, r


def minimize_tron_host(
    value_and_grad_fn: ValueAndGrad,
    hvp_fn: Callable[[Array, Array], Array],
    w0: Array,
    *,
    max_iter: int = 15,
    tol: float = 1e-5,
    max_cg: int = 20,
    max_improvement_failures: int = 16,
    box: Optional[BoxConstraints] = None,
    hvp_factory=None,
    track_coefficients: bool = False,
) -> OptResult:
    """Trust-region Newton whose evaluations run host-side code.

    ``hvp_fn(w, d) -> H(w) @ d``; ``hvp_factory(w) -> (d -> H(w) @ d)``
    lets the caller cache the w-only pieces (margins, d2 coefficients)
    once per outer iteration — with streamed data that saves one full
    disk/cache pass per CG step. Defaults mirror TRON.scala:260-265."""
    w = jnp.asarray(w0, jnp.float32)
    if box is not None:
        w = box.project(w)
    f_dev, g = value_and_grad_fn(w)
    evaluations = 1  # the one at w0
    # one batched fetch for the initial control scalars
    f, g0_norm = (
        float(v) for v in overlap.device_get((f_dev, jnp.linalg.norm(g)))
    )
    f0 = f
    g_norm = g0_norm
    delta = g0_norm
    tracker = Tracker.create(
        max_iter + 1,
        coef_dim=w.shape[0] if track_coefficients else None,
    ).record(f, jnp.float32(g0_norm), w if track_coefficients else None)
    reason = (
        GRADIENT_WITHIN_TOLERANCE if g0_norm == 0.0 else NOT_CONVERGED
    )
    it = 0
    failures = 0
    while reason == NOT_CONVERGED:
        hvp = (
            hvp_factory(w)
            if hvp_factory is not None
            else (lambda d, _w=w: hvp_fn(_w, d))
        )
        s, r = _truncated_cg_host(
            hvp, g, delta, max_cg=max_cg, g_norm=g_norm
        )
        w_trial = w + s
        s_raw = s
        if box is not None:
            w_trial = box.project(w_trial)
            s = w_trial - w
        f_new_dev, g_new = value_and_grad_fn(w_trial)
        evaluations += 1
        # the OUTER iteration's batch: every step/model control scalar
        # plus the device-computed convergence reason, in ONE fetch
        gs, s_r, f_new, snorm, g_norm_new, projected_any, reason_new = (
            overlap.device_get((
                jnp.vdot(g, s),
                jnp.vdot(s, r),
                f_new_dev,
                jnp.linalg.norm(s),
                jnp.linalg.norm(g_new),
                (
                    jnp.any(s != s_raw)
                    if box is not None else jnp.bool_(False)
                ),
                check_convergence(
                    jnp.int32(it + 1), jnp.float32(f), f_new_dev,
                    jnp.linalg.norm(g_new), jnp.float32(f0),
                    jnp.float32(g0_norm), max_iter=max_iter, tol=tol,
                ),
            ))
        )
        gs, f_new, snorm = float(gs), float(f_new), float(snorm)
        if bool(projected_any):
            # the CG residual r belongs to the UNPROJECTED step; with an
            # active box constraint the quadratic model must be re-
            # evaluated at the projected s (one extra Hv pass) or the
            # actred/prered trust-region test compares incompatible
            # models near the boundary
            prered = -(
                gs + 0.5 * float(overlap.device_get(jnp.vdot(s, hvp(s))))
            )
        else:
            prered = -0.5 * (gs - float(s_r))
        actred = f - f_new

        denom = f_new - f - gs
        alpha = _SIGMA3 if denom <= 0 else max(_SIGMA1, -0.5 * (gs / denom))
        if actred < _ETA0 * prered:
            delta = min(max(alpha, _SIGMA1) * snorm, _SIGMA2 * delta)
        elif actred < _ETA1 * prered:
            delta = max(_SIGMA1 * delta, min(alpha * snorm, _SIGMA2 * delta))
        elif actred < _ETA2 * prered:
            delta = max(_SIGMA1 * delta, min(alpha * snorm, _SIGMA3 * delta))
        else:
            delta = max(delta, min(alpha * snorm, _SIGMA3 * delta))

        accept = actred > _ETA0 * prered and np.isfinite(f_new)
        it += 1
        if accept:
            failures = 0
            g_norm = float(g_norm_new)
            reason = int(reason_new)
            w, f, g = w_trial, f_new, g_new
            tracker = tracker.record(
                f, jnp.float32(g_norm), w if track_coefficients else None
            )
        else:
            failures += 1
            if it >= max_iter or failures >= max_improvement_failures:
                reason = MAX_ITERATIONS
    return OptResult(
        coefficients=w,
        value=jnp.float32(f),
        grad_norm=jnp.linalg.norm(g),
        iterations=jnp.int32(it),
        reason=jnp.int32(reason),
        tracker=tracker,
        evaluations=jnp.int32(evaluations),
    )
