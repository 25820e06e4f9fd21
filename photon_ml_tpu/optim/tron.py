"""TRON: trust-region Newton with truncated conjugate gradient.

Reference: photon-ml .../optimization/TRON.scala (in-tree LIBLINEAR port:
outer trust-region loop with eta/sigma update rules at 103-256, inner
truncated CG calling hessianVector per step, <=20 CG iterations, defaults
maxIter=15 tol=1e-5; improvement-failure tolerance at 69-75).

On TPU every CG step's Hessian-vector product is one fused psum-ing kernel
(photon_ml_tpu.ops.objective.GLMObjective.hessian_vector) instead of a
cluster round-trip — the reference's hottest distributed loop becomes a
while_loop of matmul+psum. The whole optimizer is one jit program and vmaps
over entity banks like L-BFGS.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optim.common import (
    BoxConstraints,
    GRADIENT_WITHIN_TOLERANCE,
    MAX_ITERATIONS,
    NOT_CONVERGED,
    OptResult,
    Tracker,
    ValueAndGrad,
    check_convergence,
)

Array = jnp.ndarray

# LIBLINEAR trust-region constants (TRON.scala / tron.cpp).
_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0


class _CGState(NamedTuple):
    s: Array
    r: Array
    d: Array
    rtr: Array
    iters: Array
    done: Array


def _truncated_cg(
    hvp: Callable[[Array], Array],
    g: Array,
    delta: Array,
    *,
    max_cg: int,
    cg_tol_factor: float = 0.1,
    vdot=jnp.vdot,
    norm=jnp.linalg.norm,
):
    """Steihaug truncated CG: approximately solve H s = -g, ||s|| <= delta.

    Mirrors TRON.scala:259-341 (trustRegionConjugateGradientMethod).
    Returns ``(s, r)`` with r = -g - H s maintained through boundary exits,
    so the caller computes prered = -0.5*(g.s - s.r) without an extra
    Hessian-vector product (the tron.cpp trick).
    """
    cg_tol = cg_tol_factor * norm(g)

    def boundary_tau(s, d, delta):
        # tau >= 0 with ||s + tau d|| = delta
        dd = vdot(d, d)
        sd = vdot(s, d)
        ss = vdot(s, s)
        rad = jnp.sqrt(jnp.maximum(sd * sd + dd * (delta * delta - ss), 0.0))
        return (-sd + rad) / jnp.maximum(dd, 1e-30)

    def cond(st: _CGState):
        return (~st.done) & (st.iters < max_cg) & (jnp.sqrt(st.rtr) > cg_tol)

    def body(st: _CGState):
        hd = hvp(st.d)
        dhd = vdot(st.d, hd)
        # Negative curvature or radius hit: walk to the boundary and stop.
        alpha = st.rtr / jnp.where(dhd > 0, dhd, 1.0)
        s_new = st.s + alpha * st.d
        hit = (norm(s_new) >= delta) | (dhd <= 0)
        step = jnp.where(hit, boundary_tau(st.s, st.d, delta), alpha)
        s_out = st.s + step * st.d
        r_new = st.r - step * hd
        rtr_new = vdot(r_new, r_new)
        beta = rtr_new / jnp.maximum(st.rtr, 1e-30)
        d_new = r_new + beta * st.d
        return _CGState(
            s=s_out,
            r=r_new,
            d=jnp.where(hit, st.d, d_new),
            rtr=rtr_new,
            iters=st.iters + 1,
            done=st.done | hit,
        )

    r0 = -g
    init = _CGState(
        s=jnp.zeros_like(g),
        r=r0,
        d=r0,
        rtr=vdot(r0, r0),
        iters=jnp.zeros((), jnp.int32),
        done=jnp.zeros((), bool),
    )
    final = lax.while_loop(cond, body, init)
    return final.s, final.r


class _TronState(NamedTuple):
    w: Array
    f: Array
    g: Array
    delta: Array
    iteration: Array
    reason: Array
    failures: Array  # consecutive improvement failures
    tracker: Tracker
    evaluations: Array  # int32 value+gradient calls so far


def minimize_tron(
    value_and_grad_fn: ValueAndGrad,
    hvp_fn: Callable[[Array, Array], Array],
    w0: Array,
    *,
    max_iter: int = 15,
    tol: float = 1e-5,
    max_cg: int = 20,
    max_improvement_failures: int = 16,
    box: Optional[BoxConstraints] = None,
    track_coefficients: bool = False,
    axis_name: Optional[str] = None,
    hvp_factory=None,
) -> OptResult:
    """Trust-region Newton. ``hvp_fn(w, d) -> H(w) @ d``.

    ``hvp_factory(w) -> (d -> H(w) @ d)``: alternative to ``hvp_fn`` that
    lets the caller compute the w-only pieces of the Hessian (margins,
    second-derivative coefficients) ONCE per outer iteration instead of
    once per CG step — the HessianVectorAggregator caching analog. When
    given, ``hvp_fn`` is ignored (pass None).

    Defaults mirror TRON.scala:260-265 (maxIter=15, tol=1e-5, <=20 CG).

    ``axis_name``: run over a FEATURE-SHARDED coefficient block inside
    shard_map — every inner product / norm (outer loop AND truncated CG)
    psums over the axis, so the optimizer is numerically identical to its
    replicated self with fully sharded state (same contract as
    minimize_lbfgs).

    Under ``jax.vmap`` (the batched λ-grid path) both while_loops — the
    outer trust-region loop and the truncated CG — are carry-masked per
    member by the batching rule, so converged members freeze bit-stable
    while stragglers iterate (see minimize_lbfgs's note; pinned by the
    grid tests). Keep the ``cond``s pure per-member predicates.
    """
    from photon_ml_tpu.optim.lbfgs import make_global_prims

    vdot, norm, _ = make_global_prims(axis_name)
    if box is not None:
        w0 = box.project(w0)
    f0, g0 = value_and_grad_fn(w0)
    g0_norm = norm(g0)

    def cond(st: _TronState):
        return st.reason == NOT_CONVERGED

    def body(st: _TronState):
        hvp_local = (
            hvp_factory(st.w)
            if hvp_factory is not None
            else (lambda d: hvp_fn(st.w, d))
        )
        with jax.named_scope("tron.cg"):
            s, r = _truncated_cg(
                hvp_local, st.g, st.delta, max_cg=max_cg,
                vdot=vdot, norm=norm,
            )
        w_trial = st.w + s
        if box is not None:
            w_trial = box.project(w_trial)
            s = w_trial - st.w
        with jax.named_scope("tron.step"):
            f_new, g_new = value_and_grad_fn(w_trial)
        gs = vdot(st.g, s)
        # r = -g - H s from CG, so s.Hs = -s.(g + r) and
        # prered = -(g.s + 0.5 s.Hs) = -0.5 (g.s - s.r).
        prered = -0.5 * (gs - vdot(s, r))
        actred = st.f - f_new
        snorm = norm(s)

        # Step-size estimate for the radius update (tron.cpp alpha rule).
        denom = f_new - st.f - gs
        alpha = jnp.where(
            denom <= 0, _SIGMA3, jnp.maximum(_SIGMA1, -0.5 * (gs / denom))
        )
        delta = st.delta
        delta = jnp.where(
            actred < _ETA0 * prered,
            jnp.minimum(jnp.maximum(alpha, _SIGMA1) * snorm, _SIGMA2 * delta),
            jnp.where(
                actred < _ETA1 * prered,
                jnp.maximum(_SIGMA1 * delta, jnp.minimum(alpha * snorm, _SIGMA2 * delta)),
                jnp.where(
                    actred < _ETA2 * prered,
                    jnp.maximum(_SIGMA1 * delta, jnp.minimum(alpha * snorm, _SIGMA3 * delta)),
                    jnp.maximum(delta, jnp.minimum(alpha * snorm, _SIGMA3 * delta)),
                ),
            ),
        )

        accept = (actred > _ETA0 * prered) & jnp.isfinite(f_new)
        w2 = jnp.where(accept, w_trial, st.w)
        f2 = jnp.where(accept, f_new, st.f)
        g2 = jnp.where(accept, g_new, st.g)
        failures = jnp.where(accept, 0, st.failures + 1).astype(jnp.int32)

        it = st.iteration + 1
        g_norm = norm(g2)
        reason = check_convergence(
            it, st.f, f2, g_norm, f0, g0_norm, max_iter=max_iter, tol=tol
        )
        # Rejected steps should not trip the function-change test.
        reason = jnp.where(
            accept, reason, jnp.where(it >= max_iter, MAX_ITERATIONS, NOT_CONVERGED)
        )
        reason = jnp.where(
            (reason == NOT_CONVERGED) & (failures >= max_improvement_failures),
            MAX_ITERATIONS,
            reason,
        ).astype(jnp.int32)
        return _TronState(
            w=w2, f=f2, g=g2, delta=delta, iteration=it, reason=reason,
            failures=failures, tracker=st.tracker.record(
                f2, g_norm, w2 if track_coefficients else None
            ),
            evaluations=st.evaluations + 1,
        )

    init = _TronState(
        w=w0,
        f=f0,
        g=g0,
        delta=g0_norm,
        iteration=jnp.zeros((), jnp.int32),
        reason=jnp.where(
            g0_norm == 0.0, GRADIENT_WITHIN_TOLERANCE, NOT_CONVERGED
        ).astype(jnp.int32),
        failures=jnp.zeros((), jnp.int32),
        tracker=Tracker.create(
            max_iter + 1, w0.dtype,
            coef_dim=w0.shape[0] if track_coefficients else None,
        ).record(f0, g0_norm, w0 if track_coefficients else None),
        evaluations=jnp.ones((), jnp.int32),  # the one at w0
    )
    final = lax.while_loop(cond, body, init)
    return OptResult(
        coefficients=final.w,
        value=final.f,
        grad_norm=norm(final.g),
        iterations=final.iteration,
        reason=final.reason,
        tracker=final.tracker,
        evaluations=final.evaluations,
    )
