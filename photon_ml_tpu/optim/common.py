"""Shared optimizer machinery: convergence, line search, tracking, projection.

Reference: photon-ml .../optimization/Optimizer.scala (template method +
convergence checks at 156-170), OptimizerState.scala,
OptimizationStatesTracker.scala, OptimizationUtils (hypercube projection).

Everything is functional and statically shaped: optimizers are
``lax.while_loop`` programs whose state is a NamedTuple of arrays, so they
jit once, vmap over entity banks (the random-effect path) and run unchanged
under ``shard_map`` (the fixed-effect path, where the objective psums).

Convergence reasons mirror the reference's ``ConvergenceReason``:
  MAX_ITERATIONS         — hit the iteration budget
  FUNCTION_VALUES_WITHIN_TOLERANCE — |f_k - f_{k-1}| <= tol * |f_0|
  GRADIENT_WITHIN_TOLERANCE        — ||g_k|| <= tol * ||g_0||
(Optimizer.scala:156-170; relative-to-initial-state semantics kept exactly
so warm starts behave like `isReusingPreviousInitialState`.)
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax.numpy as jnp
from jax import lax

Array = jnp.ndarray

# Convergence reason codes (int32 so they live in jit state).
NOT_CONVERGED = 0
MAX_ITERATIONS = 1
FUNCTION_VALUES_WITHIN_TOLERANCE = 2
GRADIENT_WITHIN_TOLERANCE = 3
# A backtracking line search found no decreasing step (Breeze's
# LineSearchFailed / ObjectiveNotImproving analog) — distinct from
# hitting the iteration cap.
LINE_SEARCH_STALLED = 4

CONVERGENCE_REASON_NAMES = {
    NOT_CONVERGED: "NotConverged",
    MAX_ITERATIONS: "MaxIterations",
    FUNCTION_VALUES_WITHIN_TOLERANCE: "FunctionValuesWithinTolerance",
    GRADIENT_WITHIN_TOLERANCE: "GradientWithinTolerance",
    LINE_SEARCH_STALLED: "LineSearchStalled",
}


class BoxConstraints(NamedTuple):
    """Per-coefficient [lower, upper] box (OptimizationUtils'
    projectCoefficientsToHypercube analog). Use +-inf for unconstrained."""

    lower: Array  # [d]
    upper: Array  # [d]

    def project(self, w: Array) -> Array:
        return jnp.clip(w, self.lower, self.upper)


def project_coefficients_to_hypercube(w: Array, box: Optional[BoxConstraints]) -> Array:
    return w if box is None else box.project(w)


class Tracker(NamedTuple):
    """Per-iteration optimization trace, fixed-capacity stacked arrays.

    The TPU-native OptimizationStatesTracker: slot i holds (value, ||g||,
    elapsed-iteration marker) for iteration i; ``count`` marks the filled
    prefix. ``coefs`` (the ModelTracker analog) optionally stacks the
    coefficient vector per iteration — enabled by the optimizers'
    ``track_coefficients`` flag; None keeps the while_loop state small for
    the common case (and for vmapped entity banks).
    """

    values: Array  # [cap]
    grad_norms: Array  # [cap]
    count: Array  # int32
    coefs: Optional[Array] = None  # [cap, d] when tracking models

    @staticmethod
    def create(
        capacity: int, dtype=jnp.float32, coef_dim: Optional[int] = None
    ) -> "Tracker":
        return Tracker(
            values=jnp.zeros((capacity,), dtype),
            grad_norms=jnp.zeros((capacity,), dtype),
            count=jnp.zeros((), jnp.int32),
            coefs=(
                None
                if coef_dim is None
                else jnp.zeros((capacity, coef_dim), dtype)
            ),
        )

    def record(
        self, value: Array, grad_norm: Array, coef: Optional[Array] = None
    ) -> "Tracker":
        i = jnp.minimum(self.count, self.values.shape[0] - 1)
        return Tracker(
            values=self.values.at[i].set(value),
            grad_norms=self.grad_norms.at[i].set(grad_norm),
            count=self.count + 1,
            coefs=(
                self.coefs
                if self.coefs is None or coef is None
                else self.coefs.at[i].set(coef)
            ),
        )


class OptResult(NamedTuple):
    """Result of one optimize() call."""

    coefficients: Array
    value: Array
    grad_norm: Array
    iterations: Array  # int32
    reason: Array  # int32 convergence reason code
    tracker: Tracker
    # int32: value+gradient calls the solve made, the one at w0 included
    # (what the line search adds to ``iterations``); -1 = not counted (a
    # result restored from a snapshot written before the count existed)
    evaluations: Array = -1

    @property
    def reason_name(self) -> str:  # host-side convenience
        return CONVERGENCE_REASON_NAMES.get(int(self.reason), "?")


def check_convergence(
    iteration: Array,
    f_prev: Array,
    f_cur: Array,
    g_norm: Array,
    f0: Array,
    g0_norm: Array,
    *,
    max_iter: int,
    tol: float,
) -> Array:
    """Return the convergence-reason code (0 if not converged).

    Mirrors Optimizer.scala:156-170: relative function-change and relative
    gradient-norm tests against the *initial* state.
    """
    reason = jnp.where(
        jnp.abs(f_cur - f_prev) <= tol * jnp.abs(f0),
        FUNCTION_VALUES_WITHIN_TOLERANCE,
        NOT_CONVERGED,
    )
    reason = jnp.where(g_norm <= tol * g0_norm, GRADIENT_WITHIN_TOLERANCE, reason)
    reason = jnp.where(
        (reason == NOT_CONVERGED) & (iteration >= max_iter), MAX_ITERATIONS, reason
    )
    return reason.astype(jnp.int32)


ValueAndGrad = Callable[[Array], Tuple[Array, Array]]


def grid_member(result: OptResult, i: int) -> OptResult:
    """Member ``i`` of a grid-batched result (every field carries a
    leading grid axis)."""
    import jax

    return jax.tree.map(lambda a: a[i], result)


class LineSearchResult(NamedTuple):
    step: Array
    w: Array
    f: Array
    g: Array
    ok: Array  # bool: sufficient decrease achieved
    evaluations: Array  # int32: trial points evaluated (>= 1)


def backtracking_line_search(
    vg: ValueAndGrad,
    w: Array,
    f: Array,
    g: Array,
    direction: Array,
    t0: Array,
    *,
    c1: float = 1e-4,
    shrink: float = 0.5,
    max_steps: int = 24,
    project: Optional[Callable[[Array], Array]] = None,
    vdot: Callable[[Array, Array], Array] = jnp.vdot,
) -> LineSearchResult:
    """Armijo backtracking, optionally projecting each trial point.

    ``vdot`` may be a mesh-global dot (psum over a model axis) so the same
    search runs over sharded coefficient blocks.

    The reference delegates to Breeze's StrongWolfeLineSearch; here a
    projected-backtracking search plus a cautious-update rule in the L-BFGS
    memory (skip pairs with y.s <= eps) gives the same robustness with
    while_loop-friendly control flow (no data-dependent Python branching).
    """
    proj = project if project is not None else (lambda x: x)

    def trial(t):
        w_t = proj(w + t * direction)
        f_t, g_t = vg(w_t)
        return w_t, f_t, g_t

    def armijo_ok(w_t, f_t):
        # Armijo on the projected point: f_t <= f + c1 * g.(w_t - w)
        # (for unconstrained this reduces to the usual f + c1 t g.d).
        return (f_t <= f + c1 * vdot(g, w_t - w)) & jnp.isfinite(f_t)

    # The Armijo test lives in `cond` (pure arithmetic) so each loop trip
    # costs exactly ONE objective evaluation — the accepted unit step pays
    # a single value_and_grad call, which is the dominant cost when the
    # objective psums over a mesh.
    def cond(state):
        _, w_t, f_t, _, k = state
        return (~armijo_ok(w_t, f_t)) & (k < max_steps)

    def body(state):
        t, _, _, _, k = state
        t_next = t * shrink
        w_n, f_n, g_n = trial(t_next)
        return (t_next, w_n, f_n, g_n, k + 1)

    w1, f1, g1 = trial(t0)
    t, w_t, f_t, g_t, k = lax.while_loop(
        cond, body, (t0, w1, f1, g1, jnp.zeros((), jnp.int32))
    )
    ok = armijo_ok(w_t, f_t)
    # If the search never succeeded, keep the original point.
    w_out = jnp.where(ok, w_t, w)
    f_out = jnp.where(ok, f_t, f)
    g_out = jnp.where(ok, g_t, g)
    return LineSearchResult(
        step=t, w=w_out, f=f_out, g=g_out, ok=ok, evaluations=k + 1
    )
