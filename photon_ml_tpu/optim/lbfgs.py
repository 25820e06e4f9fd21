"""L-BFGS and OWL-QN as single-jit ``lax.while_loop`` programs.

Reference: photon-ml .../optimization/LBFGS.scala (Breeze adapter, defaults
maxIter=100 m=10 tol=1e-7, box-constraint projection at :77) and
OWLQN.scala:43-91 (L1/elastic-net path with mutable l1RegWeight).

TPU-native design notes:
- The optimizer is *data-free*: it sees only ``value_and_grad(w)``. Run it
  under ``shard_map`` with a psum-ing objective → distributed fixed-effect
  training; ``jax.vmap`` it over a coefficient bank with batched objectives →
  millions of per-entity random-effect solves in one XLA program (the
  reference's RandomEffectCoordinate mapValues loop collapses into one
  vmapped while_loop).
- L-BFGS memory is a fixed [m, d] circular buffer; the two-loop recursion is
  a ``fori_loop`` over static m with validity masking — no dynamic shapes.
- Line search is projected Armijo backtracking plus cautious memory updates
  (skip pairs with y.s <= eps); Breeze's strong-Wolfe search is replaced by
  this while_loop-friendly equivalent.
- OWL-QN follows Andrew & Gao: pseudo-gradient, orthant-aligned direction,
  orthant projection of trial points; memory pairs use smooth gradients.
  L1 weight is a *runtime scalar* so one compilation serves a whole
  regularization path (the reference mutates `l1RegWeight` similarly).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optim.common import (
    BoxConstraints,
    GRADIENT_WITHIN_TOLERANCE,
    LINE_SEARCH_STALLED,
    NOT_CONVERGED,
    OptResult,
    Tracker,
    ValueAndGrad,
    backtracking_line_search,
    check_convergence,
)

Array = jnp.ndarray


class _Memory(NamedTuple):
    s: Array  # [m, d]
    y: Array  # [m, d]
    rho: Array  # [m]
    length: Array  # int32 number of valid pairs
    ptr: Array  # int32 next write slot


def _empty_memory(m: int, d: int, dtype) -> _Memory:
    return _Memory(
        s=jnp.zeros((m, d), dtype),
        y=jnp.zeros((m, d), dtype),
        rho=jnp.zeros((m,), dtype),
        length=jnp.zeros((), jnp.int32),
        ptr=jnp.zeros((), jnp.int32),
    )


def make_global_prims(axis_name: Optional[str]):
    """(vdot, norm, vsum) primitives — mesh-global when ``axis_name`` is
    set (psum over that axis), plain otherwise. Passing these through the
    optimizer makes the SAME L-BFGS program run over feature-sharded
    coefficient blocks: vectors stay device-local, only scalars cross the
    mesh (the reduce-scatter recipe of SURVEY §2.3's coefficient
    parallelism)."""
    if axis_name is None:
        return jnp.vdot, jnp.linalg.norm, jnp.sum

    def vdot(a, b):
        return lax.psum(jnp.vdot(a, b), axis_name)

    def norm(a):
        return jnp.sqrt(jnp.maximum(vdot(a, a), 0.0))

    def vsum(a):
        return lax.psum(jnp.sum(a), axis_name)

    return vdot, norm, vsum


def _two_loop_direction(g: Array, mem: _Memory, vdot=jnp.vdot) -> Array:
    """Classic two-loop recursion over the circular buffer; returns -H~ g."""
    m = mem.s.shape[0]
    alphas = jnp.zeros((m,), g.dtype)

    def backward(i, carry):
        q, alphas = carry
        idx = jnp.mod(mem.ptr - 1 - i, m)
        valid = i < mem.length
        a = jnp.where(valid, mem.rho[idx] * vdot(mem.s[idx], q), 0.0)
        q = q - a * mem.y[idx]
        return q, alphas.at[idx].set(a)

    q, alphas = lax.fori_loop(0, m, backward, (g, alphas))

    last = jnp.mod(mem.ptr - 1, m)
    ys = vdot(mem.s[last], mem.y[last])
    yy = vdot(mem.y[last], mem.y[last])
    gamma = jnp.where(mem.length > 0, ys / jnp.maximum(yy, 1e-30), 1.0)
    r = gamma * q

    def forward(i, r):
        idx = jnp.mod(mem.ptr - mem.length + i, m)
        valid = i < mem.length
        b = jnp.where(valid, mem.rho[idx] * vdot(mem.y[idx], r), 0.0)
        return r + jnp.where(valid, alphas[idx] - b, 0.0) * mem.s[idx]

    r = lax.fori_loop(0, m, forward, r)
    return -r


def _update_memory(mem: _Memory, s: Array, y: Array, vdot=jnp.vdot) -> _Memory:
    """Cautious update: store the pair only when y.s > eps (keeps H~ PD)."""
    ys = vdot(y, s)
    ok = ys > 1e-10
    ptr = mem.ptr
    new = _Memory(
        s=mem.s.at[ptr].set(s),
        y=mem.y.at[ptr].set(y),
        rho=mem.rho.at[ptr].set(1.0 / jnp.maximum(ys, 1e-30)),
        length=jnp.minimum(mem.length + 1, mem.s.shape[0]),
        ptr=jnp.mod(ptr + 1, mem.s.shape[0]),
    )
    return jax.tree.map(lambda a, b: jnp.where(ok, a, b), new, mem)


class _LoopState(NamedTuple):
    w: Array
    f: Array
    g: Array  # smooth gradient
    mem: _Memory
    iteration: Array
    reason: Array
    tracker: Tracker
    evaluations: Array  # int32 value+gradient calls so far


def minimize_lbfgs(
    value_and_grad_fn: ValueAndGrad,
    w0: Array,
    *,
    max_iter: int = 100,
    tol: float = 1e-7,
    history: int = 10,
    box: Optional[BoxConstraints] = None,
    ls_max_steps: int = 24,
    axis_name: Optional[str] = None,
    track_coefficients: bool = False,
) -> OptResult:
    """Minimize a smooth objective. jit/vmap/shard_map-safe.

    Defaults mirror LBFGS.scala:152-156 (maxIter=100, m=10, tol=1e-7).

    Under ``jax.vmap`` (the batched λ-grid path, problem.run_grid) the
    batching rule of ``lax.while_loop`` active-masks the carry per
    member: ``cond`` is this member's ``reason == NOT_CONVERGED``, so a
    converged member's whole state — coefficients, memory, tracker,
    reason — is selected UNCHANGED on every further trip and the loop
    exits when all members are done. The grid tests pin that freeze
    bitwise (test_grid_batched.py::TestFreezeSemantics); keep ``cond``
    a pure per-member predicate or the batched path loses it.

    ``axis_name``: run over a FEATURE-SHARDED coefficient block inside
    shard_map — w0 (and every state vector) is this device's block, and
    all inner products / norms psum over the axis, so the optimizer is
    numerically identical to its replicated self with fully sharded state.
    """
    vdot, norm, _ = make_global_prims(axis_name)
    project = (lambda w: box.project(w)) if box is not None else None
    w0 = w0 if project is None else project(w0)
    f0, g0 = value_and_grad_fn(w0)
    g0_norm = norm(g0)

    def cond(st: _LoopState):
        return st.reason == NOT_CONVERGED

    def body(st: _LoopState):
        with jax.named_scope("lbfgs.direction"):
            d = _two_loop_direction(st.g, st.mem, vdot)
            # Fall back to steepest descent if d is not a descent direction.
            descent = vdot(d, st.g) < 0
            d = jnp.where(descent, d, -st.g)
            t0 = jnp.where(
                st.mem.length > 0,
                jnp.ones((), st.f.dtype),
                1.0 / jnp.maximum(norm(d), 1.0),
            )
        with jax.named_scope("lbfgs.line_search"):
            ls = backtracking_line_search(
                value_and_grad_fn, st.w, st.f, st.g, d, t0,
                max_steps=ls_max_steps, project=project, vdot=vdot,
            )
        with jax.named_scope("lbfgs.memory"):
            mem = _update_memory(st.mem, ls.w - st.w, ls.g - st.g, vdot)
        it = st.iteration + 1
        g_norm = norm(ls.g)
        # A failed line search means no further progress is possible; check
        # BEFORE the function-change test (a stalled search has Δf == 0 and
        # would otherwise masquerade as convergence).
        reason = jnp.where(
            ls.ok,
            check_convergence(
                it, st.f, ls.f, g_norm, f0, g0_norm, max_iter=max_iter, tol=tol
            ),
            LINE_SEARCH_STALLED,
        ).astype(jnp.int32)
        return _LoopState(
            w=ls.w, f=ls.f, g=ls.g, mem=mem, iteration=it, reason=reason,
            tracker=st.tracker.record(
                ls.f, g_norm, ls.w if track_coefficients else None
            ),
            evaluations=st.evaluations + ls.evaluations,
        )

    init = _LoopState(
        w=w0,
        f=f0,
        g=g0,
        mem=_empty_memory(history, w0.shape[0], w0.dtype),
        iteration=jnp.zeros((), jnp.int32),
        reason=jnp.where(
            g0_norm == 0.0, GRADIENT_WITHIN_TOLERANCE, NOT_CONVERGED
        ).astype(jnp.int32),
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


# ---------------------------------------------------------------------------
# OWL-QN
# ---------------------------------------------------------------------------


def _pseudo_gradient(w: Array, g: Array, l1: Array) -> Array:
    """Andrew & Gao pseudo-gradient of f(w) + l1 * ||w||_1."""
    right = g + l1
    left = g - l1
    at_zero = jnp.where(right < 0, right, jnp.where(left > 0, left, 0.0))
    return jnp.where(w > 0, right, jnp.where(w < 0, left, at_zero))


def minimize_owlqn(
    value_and_grad_fn: ValueAndGrad,
    w0: Array,
    l1_weight,
    *,
    max_iter: int = 100,
    tol: float = 1e-7,
    history: int = 10,
    l1_mask: Optional[Array] = None,
    box: Optional[BoxConstraints] = None,
    ls_max_steps: int = 24,
    axis_name: Optional[str] = None,
    track_coefficients: bool = False,
) -> OptResult:
    """Minimize smooth(w) + l1_weight * ||w||_1 (OWL-QN).

    ``l1_weight`` is a runtime scalar — a whole elastic-net path reuses one
    compilation (the reference mutates OWLQN.l1RegWeight the same way,
    OWLQN.scala:43-91). ``l1_mask`` optionally exempts slots (the intercept)
    from the penalty. ``axis_name``: run over a feature-sharded coefficient
    block (see minimize_lbfgs) — the L1 term and pseudo-gradient are
    elementwise, so only the scalar reductions psum.

    ``box``: project every trial point into the hypercube AFTER the orthant
    projection — the reference's OWLQN subclasses LBFGS and inherits its
    line-search projection (OWLQN.scala:43-91, LBFGS.scala:77), so
    constrained elastic-net is a supported combination.
    """
    vdot, norm, vsum = make_global_prims(axis_name)
    if box is not None:
        w0 = box.project(w0)
    l1w = jnp.asarray(l1_weight, dtype=w0.dtype)
    mask = jnp.ones_like(w0) if l1_mask is None else l1_mask.astype(w0.dtype)
    l1_vec = l1w * mask

    def total(w, fsmooth):
        return fsmooth + vsum(l1_vec * jnp.abs(w))

    f0s, g0 = value_and_grad_fn(w0)
    pg0 = _pseudo_gradient(w0, g0, l1_vec)
    f0 = total(w0, f0s)
    g0_norm = norm(pg0)

    def cond(st: _LoopState):
        return st.reason == NOT_CONVERGED

    def body(st: _LoopState):
        with jax.named_scope("lbfgs.direction"):
            pg = _pseudo_gradient(st.w, st.g, l1_vec)
            d = _two_loop_direction(pg, st.mem, vdot)
            # Constrain direction to the descent orthant of the
            # pseudo-gradient.
            d = jnp.where(d * pg < 0, d, 0.0)
            orthant = jnp.where(st.w != 0, jnp.sign(st.w), jnp.sign(-pg))

        def project_orthant(w_t):
            w_t = jnp.where(jnp.sign(w_t) == orthant, w_t, 0.0)
            return w_t if box is None else box.project(w_t)

        def vg_total(w_t):
            fs, gs = value_and_grad_fn(w_t)
            return total(w_t, fs), gs  # returns SMOOTH gradient

        f_cur_total = total(st.w, st.f)
        t0 = jnp.where(
            st.mem.length > 0,
            jnp.ones((), st.f.dtype),
            1.0 / jnp.maximum(norm(d), 1.0),
        )
        with jax.named_scope("lbfgs.line_search"):
            ls = backtracking_line_search(
                vg_total, st.w, f_cur_total, pg, d, t0,
                max_steps=ls_max_steps, project=project_orthant, vdot=vdot,
            )
        # ls.f is the total value; recover smooth value for state/memory.
        f_smooth_new = ls.f - vsum(l1_vec * jnp.abs(ls.w))
        with jax.named_scope("lbfgs.memory"):
            mem = _update_memory(st.mem, ls.w - st.w, ls.g - st.g, vdot)
        it = st.iteration + 1
        pg_new = _pseudo_gradient(ls.w, ls.g, l1_vec)
        pg_norm = norm(pg_new)
        # Stalled line search reports LINE_SEARCH_STALLED, not convergence.
        reason = jnp.where(
            ls.ok,
            check_convergence(
                it, f_cur_total, ls.f, pg_norm, f0, g0_norm,
                max_iter=max_iter, tol=tol,
            ),
            LINE_SEARCH_STALLED,
        ).astype(jnp.int32)
        return _LoopState(
            w=ls.w, f=f_smooth_new, g=ls.g, mem=mem, iteration=it,
            reason=reason, tracker=st.tracker.record(
                ls.f, pg_norm, ls.w if track_coefficients else None
            ),
            evaluations=st.evaluations + ls.evaluations,
        )

    init = _LoopState(
        w=w0,
        f=f0s,
        g=g0,
        mem=_empty_memory(history, w0.shape[0], w0.dtype),
        iteration=jnp.zeros((), jnp.int32),
        reason=jnp.where(
            g0_norm == 0.0, GRADIENT_WITHIN_TOLERANCE, NOT_CONVERGED
        ).astype(jnp.int32),
        tracker=Tracker.create(
            max_iter + 1, w0.dtype,
            coef_dim=w0.shape[0] if track_coefficients else None,
        ).record(f0, g0_norm, w0 if track_coefficients else None),
        evaluations=jnp.ones((), jnp.int32),  # the one at w0
    )
    final = lax.while_loop(cond, body, init)
    pg_final = _pseudo_gradient(final.w, final.g, l1_vec)
    return OptResult(
        coefficients=final.w,
        value=total(final.w, final.f),
        grad_norm=norm(pg_final),
        iterations=final.iteration,
        reason=final.reason,
        tracker=final.tracker,
        evaluations=final.evaluations,
    )
