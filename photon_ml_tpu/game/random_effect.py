"""Random-effect solver: per-entity GLM solves as vmapped while_loop banks.

Reference: photon-ml .../algorithm/RandomEffectCoordinate.scala:104-128 —
``activeData.join(optimizationProblems).join(modelsRDD).mapValues { local
optimizer.optimize }`` i.e. millions of independent single-node solves —
and optimization/game/RandomEffectOptimizationProblem.scala:41-130 (one
problem per entity, co-partitioned) with tracker aggregation
(RandomEffectOptimizationTracker.scala).

TPU-native: each bucket of equal-capacity entities is ONE
``jax.vmap(minimize_lbfgs)`` program over the entity axis — zero
cross-entity communication, matching the reference's key scalability
property, but with the per-entity JVM loop replaced by a single fused XLA
while_loop over [E_b, ...] blocks. Shard the entity axis over the mesh
("data" axis) for multi-chip (expert-parallel analog).
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.game.random_effect_data import (
    RandomEffectBucket,
    RandomEffectDataset,
    RowRuns,
)
from photon_ml_tpu.obs.registry import default_registry
from photon_ml_tpu.obs.trace import bound_to_current_span
from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.obs.trace import traced as obs_traced
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.spd_solve import solve_path, spd_solve
from photon_ml_tpu.optim.common import (
    CONVERGENCE_REASON_NAMES,
    FUNCTION_VALUES_WITHIN_TOLERANCE,
    GRADIENT_WITHIN_TOLERANCE,
    LINE_SEARCH_STALLED,
    NOT_CONVERGED,
    check_convergence,
)
from photon_ml_tpu.optim.config import (
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
)
from photon_ml_tpu.optim.lbfgs import minimize_lbfgs, minimize_owlqn
from photon_ml_tpu.optim.tron import minimize_tron
from photon_ml_tpu.utils.backend import effective_platform

Array = jnp.ndarray


def _named(base: str, coordinate: Optional[str] = None):
    """The function handed to jax.jit names the XLA module: ``base``,
    with the GAME coordinate's name after it when one is given (non-word
    characters as ``_``), so that a device trace tells one coordinate's
    programs from another's."""
    name = base
    if coordinate:
        name += "_" + re.sub(r"\W", "_", coordinate)

    def rename(f):
        f.__name__ = f.__qualname__ = name
        return f

    return rename


@dataclass
class RandomEffectTracker:
    """Aggregated per-entity convergence stats
    (RandomEffectOptimizationTracker analog)."""

    num_entities: int
    iterations_mean: float
    iterations_max: int
    reason_counts: Dict[str, int]


class LazyRandomEffectTracker:
    """RandomEffectTracker facade whose aggregated stats stay
    DEVICE-RESIDENT until first use (the overlap deferred-readback path):
    ``update_bank(..., defer_tracker=True)`` returns one of these instead
    of forcing a device->host round trip per bank update. The coordinate
    descent loop batch-fetches every coordinate's ``.deferred`` with ONE
    ``device_get`` per iteration (parallel/overlap.fetch_all); any other
    consumer that touches an attribute forces its own (counted) fetch, so
    behavior is identical to the eager tracker — only the transfer
    schedule changes."""

    __slots__ = ("deferred",)

    def __init__(self, deferred):
        self.deferred = deferred

    def _tracker(self) -> RandomEffectTracker:
        return self.deferred.result()

    @property
    def num_entities(self) -> int:
        return self._tracker().num_entities

    @property
    def iterations_mean(self) -> float:
        return self._tracker().iterations_mean

    @property
    def iterations_max(self) -> int:
        return self._tracker().iterations_max

    @property
    def reason_counts(self) -> Dict[str, int]:
        return self._tracker().reason_counts

    def __repr__(self) -> str:  # force: repr is a host-side consumer
        return repr(self._tracker())


# Solver namespaces shared across problem instances with equal
# (loss, config, regularization): a GAME combo grid builds a fresh
# RandomEffectOptimizationProblem per combo, and without sharing each
# re-traces (and, on a cold compile cache, re-compiles) every bucket
# program — the reg weights are traced arguments, so combos differing
# only in lambda are the same programs. The namespace also carries the shared AOT
# executable cache. FIFO-bounded; unhashable configs fall through to a
# fresh build.
_SOLVER_CACHE: dict = {}
_SOLVER_CACHE_MAX = 16
# bytes an element of a solver block: buckets, and the X and Gram blocks
# densified from them, are float32
_BLOCK_ITEMSIZE = 4


def _densify(ix, v, d_local):
    """Batched densification of each entity's [S, k] sparse rows into a
    dense X [E, S, D] block — as a fused compare-and-reduce over the
    nnz axis rather than a scatter: TPU scatters and gathers serialize
    per element while the VPU eats the k-reduction whole, with the exact
    same result. XLA fuses the [E, S, k, D] broadcast; it is never
    materialized."""
    with jax.named_scope("bank.densify"):
        d = jnp.arange(d_local, dtype=ix.dtype)
        return jnp.sum(
            v[..., :, None]
            * (ix[..., :, None] == d[None, None, None, :]),
            axis=2,
        )


def score_block(w, ix, v, identity=False):
    """Scores [E, S] of a solver block's rows ``ix, v [E, S, k]`` against
    its entities' bank rows ``w [E, D]``:
    ``score[e, s] = sum_j v[e, s, j] * w[e, ix[e, s, j]]``, each entry
    looked up as :func:`_densify` places it, by a compare against
    ``arange(D)`` and a reduce, and not by an element gather: on a v5e
    12.0 ms for [32768, 16, 32] x 1000 where the gather of the same
    16.8M elements takes 247 (my chip run, PR 34; of the forms tried,
    reducing over D first and then over k was the fastest, 23 ms for one
    reduce over both and 30 for densify-then-multiply, and the only one
    that writes no [E, S, D] block). The sum over D has one non-zero
    term, so the lookup is exact and the sum over k is the gather's
    own. ``identity``: the block's indices are the tiled arange
    (k == D) and ``v`` multiplies ``w`` with no compare. Elementwise
    float32 products and float32 sums: ``jax_default_matmul_precision``
    does not reach it."""
    with jax.named_scope("cd.score_block"):
        if identity:
            return jnp.sum(v * w[:, None, :], axis=-1)
        d = jnp.arange(w.shape[1], dtype=ix.dtype)
        looked = jnp.sum(
            jnp.where(ix[..., None] == d, w[:, None, None, :], 0.0), axis=-1
        )
        return jnp.sum(v * looked, axis=-1)


def _cached_bucket_solver(
    loss: PointwiseLoss,
    config: OptimizerConfig,
    regularization: RegularizationContext,
):
    from photon_ml_tpu.utils.memo import get_or_build

    return get_or_build(
        _SOLVER_CACHE, _SOLVER_CACHE_MAX,
        (loss, config, regularization),
        lambda: _bucket_solver(loss, config, regularization),
    )


def _bucket_solver(
    loss: PointwiseLoss,
    config: OptimizerConfig,
    regularization: RegularizationContext,
):
    """Build jit(solve)(bank_slice, bucket arrays, offsets, l1, l2)."""

    def entity_objective(ix, v, lab, off, w):
        def vg(coef):
            z = jnp.sum(v * jnp.take(coef, ix, axis=0), axis=-1) + off
            lv = loss.value(z, lab)
            ld = loss.d1(z, lab)
            c = w * ld
            val = jnp.sum(w * lv)
            grad = jnp.zeros_like(coef).at[ix.reshape(-1)].add(
                (v * c[:, None]).reshape(-1)
            )
            return val, grad

        def hvp(coef, direction):
            z = jnp.sum(v * jnp.take(coef, ix, axis=0), axis=-1) + off
            zd = jnp.sum(v * jnp.take(direction, ix, axis=0), axis=-1)
            c = w * loss.d2(z, lab) * zd
            return jnp.zeros_like(coef).at[ix.reshape(-1)].add(
                (v * c[:, None]).reshape(-1)
            )

        return vg, hvp

    use_tron = config.optimizer_type == OptimizerType.TRON
    use_owlqn = regularization.has_l1

    def _minimize(vg, hvp, coef0, l1):
        if use_tron:
            return minimize_tron(
                vg, hvp, coef0,
                max_iter=config.max_iter, tol=config.tolerance,
                max_cg=config.tron_max_cg,
            )
        if use_owlqn:
            return minimize_owlqn(
                vg, coef0, l1,
                max_iter=config.max_iter, tol=config.tolerance,
                history=config.lbfgs_history,
            )
        return minimize_lbfgs(
            vg, coef0,
            max_iter=config.max_iter, tol=config.tolerance,
            history=config.lbfgs_history,
        )

    # the functions handed to jax.jit name the XLA modules: bank_sparse,
    # bank_dense, bank_newton inside bank_fused / bank_fused_scan
    @jax.jit
    def bank_sparse(bank, ix, v, lab, off, w, l1, l2):
        def one(coef0, ix_e, v_e, lab_e, off_e, w_e):
            vg_raw, hvp_raw = entity_objective(ix_e, v_e, lab_e, off_e, w_e)

            def vg(c):
                val, g = vg_raw(c)
                return val + 0.5 * l2 * jnp.vdot(c, c), g + l2 * c

            def hvp(c, d):
                return hvp_raw(c, d) + l2 * d

            return _minimize(vg, hvp, coef0, l1)

        res = jax.vmap(one)(bank, ix, v, lab, off, w)
        return res.coefficients, res.iterations, res.reason

    def _make_dense(identity):
        """DENSE per-entity layout: one compare-and-reduce densification
        of each entity's rows into X [E, S, D] up front (see _densify),
        then every objective evaluation is a pair of batched matmuls
        riding the MXU instead of the serialized per-element gathers/
        scatters of the sparse path — a ~40x gradient-path win whenever
        S*D is small enough to afford the dense block. ``identity``:
        the bucket's indices are the tiled arange (k == D, the MF latent
        view) and X IS values — no densify broadcast at all."""

        @jax.jit
        def bank_dense(bank, ix, v, lab, off, w, l1, l2):
            X = v if identity else _densify(ix, v, bank.shape[1])

            def one(coef0, X_e, lab_e, off_e, w_e):
                def vg(c):
                    z = X_e @ c + off_e
                    lv = loss.value(z, lab_e)
                    ld = loss.d1(z, lab_e)
                    val = jnp.sum(w_e * lv) + 0.5 * l2 * jnp.vdot(c, c)
                    grad = X_e.T @ (w_e * ld) + l2 * c
                    return val, grad

                def hvp(c, d):
                    z = X_e @ c + off_e
                    zd = X_e @ d
                    return X_e.T @ (w_e * loss.d2(z, lab_e) * zd) + l2 * d

                return _minimize(vg, hvp, coef0, l1)

            res = jax.vmap(one)(bank, X, lab, off, w)
            return res.coefficients, res.iterations, res.reason

        return bank_dense

    def _damped_newton(coef0, lab_e, off_e, w_e, l2, x_dot, xt_dot,
                       newton_step):
        """One entity's damped Newton solve, shared by the dual
        (:func:`bank_newton`) and the primal (:func:`bank_primal`) kind:
        the same stopping rule and the same line search, so that on a
        block both can run they agree to rounding. ``x_dot(c)`` is
        ``X c`` ([S]) and ``xt_dot(r)`` is ``X' r`` ([D]);
        ``newton_step(c, z, cd, d2, g_vec)`` gives ``(u, step, z_step)``
        = ``(X g, -H^-1 g, X step)`` in the kind's own space."""
        max_iter = config.max_iter
        tol = config.tolerance

        def value(c, z):
            return jnp.sum(w_e * loss.value(z, lab_e)) + 0.5 * l2 * jnp.vdot(c, c)

        def grad_vec(z, c):
            # Exact g = X^T cd + l2 c, materialized in coefficient
            # space: the all-dual norm expansion (cd G cd + 2 l2 cd.Xc
            # + l2^2 ||c||^2) cancels catastrophically in float32 once
            # ||g|| is small relative to the individual terms,
            # mis-reporting convergence — so spend one [D, S] matvec
            # per iteration on the true gradient. The vector rides the
            # loop carry: the NEXT iteration's Cauchy fallback needs
            # exactly this gradient, so it costs no extra X pass.
            cd = w_e * loss.d1(z, lab_e)
            return xt_dot(cd) + l2 * c

        z0 = x_dot(coef0) + off_e
        f0 = value(coef0, z0)
        g0_vec = grad_vec(z0, coef0)
        g0_norm = jnp.linalg.norm(g0_vec)

        # state: (c, z, f, g_vec, iter, reason). z is carried
        # incrementally (z_t = z + alpha * z_step) — the only X touches
        # per iteration are the ones that materialize the step and the
        # exact gradient.
        def cond(st):
            return st[5] == NOT_CONVERGED

        def body(st):
            c, z, f, g_vec, it, _ = st
            cd = w_e * loss.d1(z, lab_e)  # dual gradient weights [S]
            d2 = w_e * loss.d2(z, lab_e)  # [S] >= 0 (convex)
            u, step, z_step = newton_step(c, z, cd, d2, g_vec)

            # Line search over 16 halving trials: 0-7 along the Newton
            # step, 8-15 along the exact Cauchy (steepest-descent)
            # step — the fallback for the rare entity whose float32 solve
            # left the Newton step non-descent (ill-conditioned system at
            # tiny l2). Every trial is pure z-space: the loss term
            # moves along the precomputed step's image and the l2 term is
            # a scalar quadratic in alpha, so no [D]-sized work or X
            # pass happens per trial.
            cc = jnp.vdot(c, c)
            cs_n = jnp.vdot(c, step)
            ss_n = jnp.vdot(step, step)
            cg_dot = jnp.vdot(c, g_vec)
            g_sq = jnp.vdot(g_vec, g_vec)  # exact, from the carry
            g_hg = jnp.vdot(u, d2 * u) + l2 * g_sq
            cauchy = g_sq / (g_hg + 1e-30)
            cs_c = -cauchy * cg_dot
            ss_c = cauchy * cauchy * g_sq
            z_step_c = -cauchy * u

            def trial(k):
                newton = k < 8
                a = jnp.exp2(-jnp.where(newton, k, k - 8).astype(z.dtype))
                z_t = z + a * jnp.where(newton, z_step, z_step_c)
                cs = jnp.where(newton, cs_n, cs_c)
                ss = jnp.where(newton, ss_n, ss_c)
                loss_t = jnp.sum(w_e * loss.value(z_t, lab_e))
                return a, loss_t + 0.5 * l2 * (
                    cc + 2.0 * a * cs + a * a * ss
                )

            def ls_cond(carry):
                k, _, f_t, _ = carry
                bad = (f_t > f) | ~jnp.isfinite(f_t)
                return bad & (k < 16)

            def ls_body(carry):
                k, _, _, f_min = carry
                k = k + 1
                a, f_t = trial(k)
                f_t = jnp.where(k < 16, f_t, jnp.inf)
                return k, a, f_t, jnp.minimum(f_min, f_t)

            with jax.named_scope("bank.newton.line_search"):
                a0, f0_t = trial(jnp.int32(0))
                k, alpha, f_t, f_min = jax.lax.while_loop(
                    ls_cond, ls_body,
                    (jnp.int32(0), a0, f0_t, f0_t),
                )
            # Strict decrease moves the iterate (monotone invariant);
            # when NO trial decreases but the best trial was a float32
            # near-tie, the entity is sitting on its optimum's noise
            # plateau — report convergence WITHOUT moving instead of a
            # bogus MaxIterations (and instead of accepting an uphill
            # step, which could random-walk past the convergence test).
            moved = (f_t <= f) & jnp.isfinite(f_t)
            plateau = ~moved & (f_min <= f + 1e-6 * (1.0 + jnp.abs(f)))
            newton_used = k < 8
            # the carried g_vec IS the gradient at (c, z) — the
            # fallback direction costs no extra X pass
            used_step = jnp.where(newton_used, step, -cauchy * g_vec)
            used_zstep = jnp.where(newton_used, z_step, z_step_c)
            c2 = jnp.where(moved, c + alpha * used_step, c)
            z2 = jnp.where(moved, z + alpha * used_zstep, z)
            f2 = jnp.where(moved, f_t, f)
            it2 = it + 1
            g2_vec = grad_vec(z2, c2)
            g_norm = jnp.linalg.norm(g2_vec)
            reason = jnp.where(
                moved,
                check_convergence(
                    it2, f, f2, g_norm, f0, g0_norm,
                    max_iter=max_iter, tol=tol,
                ),
                jnp.where(
                    plateau,
                    FUNCTION_VALUES_WITHIN_TOLERANCE,
                    LINE_SEARCH_STALLED,  # no decreasing step exists
                ),
            ).astype(jnp.int32)
            return (c2, z2, f2, g2_vec, it2, reason)

        init = (
            coef0, z0, f0, g0_vec, jnp.zeros((), jnp.int32),
            jnp.where(
                g0_norm == 0.0, GRADIENT_WITHIN_TOLERANCE, NOT_CONVERGED
            ).astype(jnp.int32),
        )
        c, _, _, _, it, reason = jax.lax.while_loop(cond, body, init)
        return c, it, reason

    def _make_newton(identity):
        @jax.jit
        def bank_newton(bank, ix, v, lab, off, w, l1, l2):
            """Damped Newton in the DUAL (sample) space — the TPU-first
            redesign of the per-entity solve.

            The reference runs L-BFGS per entity (RandomEffectCoordinate.
            scala:104-128); quasi-Newton line searches cost many objective
            evaluations, and under vmap the whole bucket pays the slowest
            lane's trials every iteration. But the reservoir cap
            (RandomEffectDataSet.scala:254-317) bounds each entity's active
            samples S by construction, so the exact Newton step is cheap in
            the sample space: H = X^T D X + l2 I has rank <= S + ridge, and
            by Woodbury

                H^-1 g = (1/l2) * (g - X^T (l2 I + D G)^-1 D X g),

            with G = X X^T ([S, S], built once). Each iteration is two X
            passes + one batched S x S solve; quadratic convergence replaces
            ~O(10) line-search evaluations per L-BFGS iteration with ~1
            halving check per Newton iteration. Requires l2 > 0 and a twice-
            differentiable loss — update_bank selects it host-side, for a
            block with no more samples an entity than features
            (:meth:`RandomEffectOptimizationProblem.dense_block_plan`;
            :func:`bank_primal` takes the others).
            """
            del l1  # smooth path only (OWL-QN handles l1)
            _, s_b, _ = ix.shape
            X = v if identity else _densify(ix, v, bank.shape[1])

            def one(coef0, X_e, lab_e, off_e, w_e):
                with jax.named_scope("bank.newton.gram"):
                    G = X_e @ X_e.T  # [S, S] sample Gram, one-time

                def newton_step(c, z, cd, d2, g_vec):
                    zp = z - off_e  # = X c
                    u = G @ cd + l2 * zp  # = X g, no X pass
                    # t = (l2 I + D G)^-1 D u via the symmetrized SPD system
                    # B = l2 I + Dh G Dh (Dh = sqrt(D)): t = Dh B^-1 Dh u.
                    # CG with S iterations is exact up to roundoff and runs
                    # ~6x faster than batched LU on TPU (no pivoting loops,
                    # matvecs ride the MXU); the safeguarded line search
                    # absorbs any residual inexactness.
                    dh = jnp.sqrt(d2)

                    def b_mv(x):
                        return l2 * x + dh * (G @ (dh * x))

                    rhs = dh * u

                    def cg_body(i, st):
                        x_c, r_c, p_c, rs = st
                        ap = b_mv(p_c)
                        alpha = rs / (jnp.vdot(p_c, ap) + 1e-30)
                        x_c = x_c + alpha * p_c
                        r_c = r_c - alpha * ap
                        rs2 = jnp.vdot(r_c, r_c)
                        p_c = r_c + (rs2 / (rs + 1e-30)) * p_c
                        return x_c, r_c, p_c, rs2

                    y0 = jnp.zeros_like(rhs)
                    with jax.named_scope("bank.newton.cg"):
                        y, _, _, _ = jax.lax.fori_loop(
                            0, s_b, cg_body,
                            (y0, rhs, rhs, jnp.vdot(rhs, rhs)),
                        )
                    t = dh * y
                    r = cd - t
                    step = -(X_e.T @ r) / l2 - c  # = -H^-1 g, ONE X pass
                    z_step = -(G @ r) / l2 - zp  # = X step, dual space
                    return u, step, z_step

                return _damped_newton(
                    coef0, lab_e, off_e, w_e, l2,
                    lambda c: X_e @ c, lambda r: X_e.T @ r, newton_step,
                )

            coefs, iters, reasons = jax.vmap(one)(bank, X, lab, off, w)
            return coefs, iters, reasons

        return bank_newton

    def _make_primal(identity):
        @jax.jit
        def bank_primal(bank, ix, v, lab, off, w, l1, l2):
            """Damped Newton in the PRIMAL (feature) space: the other
            regime from :func:`bank_newton`, an entity with MORE samples
            than features (S > D: a bias, D = 1, over every row of a
            heavy user; an ALS half-step, D = the rank, over a movie's
            tens of thousands of ratings). There the sample-space Gram
            ``[S, S]`` is the large object and the normal equations the
            small one:

                (X' diag(w l'') X + l2 I) step = -(X' (w l') + l2 c),

            ``[D, D]`` an entity, rebuilt each iteration (one pass over
            X on the MXU) and solved by a Cholesky factorization
            (:func:`photon_ml_tpu.ops.spd_solve.spd_solve`: under this
            ``vmap`` one kernel with the entity on the lanes). The
            stopping rule and the line search are :func:`bank_newton`'s
            (:func:`_damped_newton`). A squared loss is solved by the
            first step and stops on the second. Every matmul names its
            precision: the normal equations square the condition number,
            so they are float32 (``HIGHEST``) whatever the process
            default is."""
            del l1  # smooth path only (OWL-QN handles l1)
            X = v if identity else _densify(ix, v, bank.shape[1])
            eye = jnp.eye(bank.shape[1], dtype=X.dtype)
            hi = jax.lax.Precision.HIGHEST

            def one(coef0, X_e, lab_e, off_e, w_e):
                def x_dot(c):
                    return jnp.dot(X_e, c, precision=hi)

                def xt_dot(r):
                    return jnp.dot(r, X_e, precision=hi)

                def newton_step(c, z, cd, d2, g_vec):
                    del c, z, cd
                    with jax.named_scope("bank.primal.hessian"):
                        H = jax.lax.dot_general(
                            X_e * d2[:, None], X_e,
                            (((0,), (0,)), ((), ())), precision=hi,
                        ) + l2 * eye  # [D, D]
                    with jax.named_scope("bank.primal.solve"):
                        step = -spd_solve(H, g_vec)
                    return x_dot(g_vec), step, x_dot(step)

                return _damped_newton(
                    coef0, lab_e, off_e, w_e, l2, x_dot, xt_dot, newton_step
                )

            return jax.vmap(one)(bank, X, lab, off, w)

        return bank_primal

    n_reasons = max(CONVERGENCE_REASON_NAMES) + 1

    def _update_block(core, bank, codes, ix, v, lab, off, w, l1, l2,
                      values_of=None, operand=None):
        """One block's gather, solve, scatter and tracker reductions. A
        code past the bank's last row is a PADDING lane (the last
        sub-block of a split bucket, :meth:`RandomEffectOptimizationProblem
        ._solver_blocks`): it starts from zero on weight-0 rows, its
        scatter drops and it counts in no reduction, as the pod's pad
        lanes do. With ``values_of`` (:class:`ValuesOverride`) the block
        holds no values: ``v`` carries its keys (or, an override that
        reads ``rows``, the block's own sparse values beside ``ix``), and
        its values are made HERE, inside the program, so that only this
        block's are alive."""
        if values_of is not None:
            # (fn, "rows"): an override that reads the block's rows
            rows = isinstance(values_of, tuple)
            with jax.named_scope("bank.values_override"):
                v = values_of[0](operand, ix, v) if rows else values_of(
                    operand, v
                )
            if rows or ix.shape[-1] != v.shape[-1]:
                # the made values are an identity block; only the sparse
                # solver reads its indices
                ix = jnp.broadcast_to(
                    jnp.arange(v.shape[-1], dtype=ix.dtype), v.shape
                )
        real = codes < bank.shape[0]
        sl = jnp.take(bank, codes, axis=0, mode="fill", fill_value=0)
        new_sl, iters, reasons = core(sl, ix, v, lab, off, w, l1, l2)
        with jax.named_scope("bank.scatter_back"):
            bank = bank.at[codes].set(new_sl, mode="drop")
        iters = jnp.where(real, iters, 0)
        counts = jnp.bincount(
            jnp.where(real, reasons, n_reasons), length=n_reasons + 1
        )[:n_reasons]
        return bank, jnp.sum(iters), jnp.max(iters), counts

    def _donate():
        return (0,) if effective_platform() != "cpu" else ()

    def _fused(core, coordinate=None, values_of=None):
        """Single-dispatch bucket update: bank-row gather, solve, bank
        scatter, and the tracker reductions all inside ONE jit program —
        per-bucket host overhead (separate gather/scatter dispatches plus
        two [E]-sized device->host tracker transfers, each a synchronous
        round trip) otherwise dwarfs the ~ms solve itself.

        The bank operand is DONATED (where the backend supports donation):
        the scatter updates it in place instead of copying the full
        [E_total, D] bank per bucket — at the 1B-coefficient scale that
        copy would double peak bank memory and add a ~4 GB HBM pass per
        bucket. update_bank defensively copies the caller's bank ONCE
        before the bucket chain so outside references stay valid.

        ``coordinate`` goes into the XLA module's name (:func:`_named`:
        ``jit_bank_fused[_<coordinate>]``). ``values_of``: the
        program takes the override's operand last and the block's keys
        in the values' place."""

        # photon: sharding(axes=[], donates=[0])
        @partial(jax.jit, donate_argnums=_donate())
        @_named("bank_fused", coordinate)
        def bank_fused(bank_full, codes, ix, v, lab, off, w, l1, l2,
                       operand=None):
            return _update_block(
                core, bank_full, codes, ix, v, lab, off, w, l1, l2,
                values_of, operand,
            )

        return bank_fused

    def _fused_scan(core, coordinate=None, values_of=None):
        """The fused bucket update folded over a STACK of same-shape
        blocks by lax.scan — one dispatch for the whole group: a run of
        same-shape buckets, or the equal sub-blocks of one bucket over
        the dense budget. Profiled at the config-4 user-bank shape
        (round 5): the four sequential per-bucket dispatches left
        ~125 ms of host gaps between ~76 ms device programs; scanning
        removes the gaps. The bank threads through the scan carry
        (donated, in-place scatters), so only ONE block's dense staging
        (under ``values_of``, one block's made values) is live at a
        time."""

        # photon: sharding(axes=[], donates=[0])
        @partial(jax.jit, donate_argnums=_donate())
        @_named("bank_fused_scan", coordinate)
        def bank_fused_scan(bank_full, codes_s, ix_s, v_s, lab_s, off_s,
                            w_s, l1, l2, operand=None):
            def body(bank, args):
                bank, *stats = _update_block(
                    core, bank, *args, l1, l2, values_of, operand
                )
                return bank, tuple(stats)

            bank_full, (it_sums, it_maxs, counts) = jax.lax.scan(
                body, bank_full, (codes_s, ix_s, v_s, lab_s, off_s, w_s)
            )
            return (
                bank_full,
                jnp.sum(it_sums),
                jnp.max(it_maxs),
                jnp.sum(counts, axis=0),
            )

        return bank_fused_scan

    @jax.jit
    def hdiag(sl, ix, v, lab, off, w, l2):
        """Per-entity Hessian diagonals at the given bank rows:
        Hdiag_e[j] = sum_s w_s l''(z_s) x_{s,j}^2 + l2 — the
        computeVariances input (RandomEffectOptimizationProblem.
        scala:106-127 -> GeneralizedLinearOptimizationProblem
        computeVariances). One pass, not a solve: padded samples carry
        w = 0 and contribute nothing."""

        def one(c_e, ix_e, v_e, lab_e, off_e, w_e):
            z = jnp.sum(v_e * jnp.take(c_e, ix_e, axis=0), axis=-1) + off_e
            cd = w_e * loss.d2(z, lab_e)
            return jnp.zeros_like(c_e).at[ix_e.reshape(-1)].add(
                ((v_e * v_e) * cd[:, None]).reshape(-1)
            )

        return jax.vmap(one)(sl, ix, v, lab, off, w) + l2

    from types import SimpleNamespace

    cores = {
        "sparse": bank_sparse,
        "dense": _make_dense(False),
        "dense_id": _make_dense(True),
        "newton": _make_newton(False),
        "newton_id": _make_newton(True),
        "primal": _make_primal(False),
        "primal_id": _make_primal(True),
    }
    fused_programs: dict = {}

    def fused_for(kind, coordinate=None, scan=False, values_of=None):
        """The fused (``scan``: scanned) update program of a solver kind,
        its module named after ``coordinate`` when one is given
        (``jit_bank_fused[_scan]_<coordinate>``, non-word characters as
        ``_``): a device trace then tells one coordinate's bank from
        another's. Two banks differ in shape and compile apart whatever
        they are called, so the name costs no compile. ``values_of``:
        the program that makes a block's values itself
        (:class:`ValuesOverride`)."""
        key = (kind, coordinate, scan, values_of)
        if key not in fused_programs:
            build = _fused_scan if scan else _fused
            fused_programs[key] = build(cores[kind], coordinate, values_of)
        return fused_programs[key]

    return SimpleNamespace(
        **cores,
        **{f"fused_{kind}": fused_for(kind) for kind in cores},
        fused_for=fused_for,
        hdiag=hdiag,
    )


_LANES = 128


def _residual_windows(rows, starts, counts, capacity: int):
    """``[E, capacity]`` windows of the row vector: entity e's is its
    ``counts[e]`` values from row ``starts[e]`` on, zero after. ``rows``
    is the vector as ``[n / 128, 128]`` (zero-padded so that the last
    window's rows exist), so that a window is whole ROWS of it: the
    ``capacity / 128 + 1`` rows (at least 2) from ``starts[e] // 128``,
    each a row gather (0.4 ns a slot on a v5e where the element gather
    takes 7.9 and a slice gather 1.2 us an entity: ``PERF.md`` section 6,
    PR 38), every lane then turned left by ``starts[e] % 128`` (seven
    roll-and-select stages, one a bit of the shift) and taken from its
    own row or the next. Values are moved, never computed with: what
    comes out is bit-equal to the element gather's."""
    r = max(capacity // _LANES, 1)
    first, shift = starts // _LANES, (starts % _LANES)[None, :, None]
    # [r + 1, E, 128], the row the slow axis: the compiler's own layout
    # (an [E, r + 1] index array, 2 wide, takes it 7 s to compile)
    win = jnp.take(
        rows, first[None, :] + jnp.arange(r + 1, dtype=jnp.int32)[:, None],
        axis=0, mode="clip",
    )
    for bit in range(7):
        on = ((shift >> bit) & 1).astype(bool)
        win = jnp.where(on, jnp.roll(win, -(1 << bit), axis=2), win)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, _LANES), 2)
    win = jnp.where(lane < _LANES - shift, win[:r], win[1:])
    win = win.transpose(1, 0, 2).reshape(-1, r * _LANES)[:, :capacity]
    slot = jax.lax.broadcasted_iota(jnp.int32, win.shape, 1)
    return jnp.where(slot < counts[:, None], win, 0.0)


def _add_windows(rows, score, starts, counts):
    """``rows`` with entity e's ``counts[e]`` scores ``score[e]`` ADDED
    from row ``starts[e]`` of the vector on: the inverse of
    :func:`_residual_windows`, on the vector as ``[n / 128 + pad, 128]``.
    Each entity's ``[capacity]`` scores, zero past its real slots, as the
    ``capacity / 128 + 1`` lane rows from ``starts[e] // 128``: every lane
    turned RIGHT by ``starts[e] % 128`` (the same seven roll-and-select
    stages, the sign flipped) and taken from its own row or the one
    before, then whole rows added in (a row scatter: on a v5e 12.0 ms for
    138,493 runs over 28.45M slots, where the element scatter takes 193;
    ``PERF.md`` section 6). Entities whose
    runs meet share a lane row, so rows are added into zeros, never set;
    each row of the vector then holds one score and zeros, and what comes
    out is the element scatter's bits but for the sign of a zero."""
    e, capacity = score.shape
    r = max(-(-capacity // _LANES), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    win = jnp.where(slot < counts[:, None], score, 0.0)
    win = jnp.pad(win, ((0, 0), (0, (r + 1) * _LANES - capacity)))
    # [r + 1, E, 128], the row the slow axis, as _residual_windows has it
    win = win.reshape(e, r + 1, _LANES).transpose(1, 0, 2)
    shift = (starts % _LANES)[None, :, None]
    for bit in range(7):
        on = ((shift >> bit) & 1).astype(bool)
        win = jnp.where(on, jnp.roll(win, 1 << bit, axis=2), win)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, _LANES), 2)
    before = jnp.pad(win[:-1], ((1, 0), (0, 0), (0, 0)))
    win = jnp.where(lane >= shift, win, before)
    at = starts // _LANES + jnp.arange(r + 1, dtype=jnp.int32)[:, None]
    return rows.at[at].add(win)


@lru_cache(maxsize=None)
def _residual_program(coordinate: Optional[str]):
    """The ONE program a bank update runs to turn the ``[n]`` residual
    into every group's offsets (``RandomEffectOptimizationProblem
    ._bucket_offsets``), module ``jit_bank_residual[_<coordinate>]``
    (non-word characters as ``_``, as ``fused_for`` names the solver
    programs). ``specs`` and ``paths`` as
    :meth:`RandomEffectOptimizationProblem._residual_args` makes them, a
    ``(path, capacity)`` a group, ``keys`` the order's
    (:func:`_order_on_device`): ``windows``, a group of runs, reads
    windows of the row vector (:func:`_residual_windows`; a folded
    group's [B, E] starts as one [B * E]); ``sorted`` reads windows of
    the residual sorted by ``keys``, the dataset's entity order, ONE
    ``lax.sort`` in this program for every such group; ``slots`` the
    element gather through the group's ``row_index``, a padding slot (-1)
    reading 0. Values are moved, never computed with: all three give the
    same bits."""

    @partial(jax.jit, static_argnames=("paths",))
    @_named("bank_residual", coordinate)
    def bank_residual(residual, specs, keys=None, *, paths):
        vectors = {"windows": residual}
        if keys is not None:
            # keys are unique: a stable sort would order nothing more
            _, vectors["sorted"] = jax.lax.sort(
                (keys, residual), num_keys=1, is_stable=False
            )
        # each vector read as windows as rows of 128, zero-padded past the
        # widest window that starts at its last row and the row after that
        reach = max(capacity for _, capacity in paths) + 2 * _LANES
        rows = {
            path: jnp.pad(
                vector, (0, -(vector.shape[0] + reach) % _LANES + reach)
            ).reshape(-1, _LANES)
            for path, vector in vectors.items()
            if any(p == path for p, _ in paths)
        }
        out = []
        for spec, (path, capacity) in zip(specs, paths):
            if path == "slots":
                (at,) = spec
                out.append(
                    jnp.where(at >= 0, residual[jnp.maximum(at, 0)], 0.0)
                )
            else:
                starts, counts = spec
                win = _residual_windows(
                    rows[path], starts.reshape(-1), counts.reshape(-1),
                    capacity,
                )
                out.append(win.reshape(starts.shape + (capacity,)))
        return out

    return bank_residual


def slots_by_path(groups, paths) -> Dict[str, int]:
    """The slots of ``groups`` of solver blocks by their path of
    ``paths`` (:meth:`RandomEffectOptimizationProblem._residual_args`'),
    ``windows``, ``sorted`` and ``slots`` in that order: a block's real
    entities times its capacity, what the residual's read and the scores'
    placement count."""
    taken = {"windows": 0, "sorted": 0, "slots": 0}
    for members, (path, _) in zip(groups, paths):
        taken[path] += (
            sum(b.num_real for b in members) * members[0].bucket.capacity
        )
    return taken


def _order_on_device(dataset, which: str, paths):
    """The dataset's entity order on the device where some group of
    ``paths`` is ``sorted``, else None: ``which`` ``keys``, each row's
    position in the order (what the residual is sorted by), or ``rows``,
    the row at each position (what the scores are sorted back by). Each
    uploaded once and cached beside the groups' specs."""
    if not any(path == "sorted" for path, _ in paths):
        return None
    cache = dataset.__dict__.setdefault("_residual_device_cache", {})
    name = {"keys": "entity_order", "rows": "entity_order_rows"}[which]
    if name not in cache:
        cache[name] = jnp.asarray(getattr(dataset.entity_order, which))
    return cache[name]


class ValuesOverride(NamedTuple):
    """Feature values a bank update MAKES instead of reading: the solver
    program calls ``fn`` on the device for each block it runs and solves
    on the ``[E, S, k]`` identity block it returns. ``reads``: what of
    the block ``fn`` reads. ``"keys"``: ``fn(operand, keys)``, ``keys``
    the block's :attr:`RandomEffectBucket.override_keys` ``[E, S]`` (an
    ALS half-step: ``operand`` the partner side's factors, ``keys`` each
    rating's partner code). ``"rows"``: ``fn(operand, ix, v)``, the
    block's own sparse rows ``[E, S, k']`` (a factored random effect:
    ``operand`` the projection, the values each row's latent features).
    ``staged``: floats a slot ``fn`` stages beside what it returns (a
    densified row: its width), charged to the dense budget so that a
    block's staging stays under it. The values live only inside the
    program of one block (one sub-block of a split bucket), never beside
    another's. ``fn`` is part of the compiled program's identity: pass a
    module-level function, not a fresh closure."""

    fn: object
    operand: object  # pytree of device arrays, the same for every block
    reads: str = "keys"
    staged: int = 0

    @property
    def values_of(self):
        """What the fused programs are built from: ``fn`` (it reads
        keys), or ``(fn, "rows")``."""
        return self.fn if self.reads == "keys" else (self.fn, self.reads)

    @property
    def sig(self) -> tuple:
        return (self.fn, self.reads, self.staged) + tuple(
            tuple(a.shape) for a in jax.tree.leaves(self.operand)
        )


def _staged(override: Optional[ValuesOverride]) -> int:
    return 0 if override is None else override.staged


def _holds_values(override: Optional[ValuesOverride]) -> bool:
    """Whether a block's device arrays hold its stored values (no
    override, or one that reads the block's rows) or its keys."""
    return override is None or override.reads == "rows"


class _SolverBlock(NamedTuple):
    """What ONE solver program runs on the replicated bank: a whole
    bucket, or one of the ``sub_blocks`` equal sub-blocks of a bucket
    whose dense staging is over ``dense_bytes_budget``."""

    bucket_index: int  # in ``dataset.buckets``
    sub_block: int
    sub_blocks: int  # how many the bucket was split into (1: whole)
    kind: str
    bucket: RandomEffectBucket  # the bucket itself, or the sub-block's view
    num_real: int  # entities less the last sub-block's padding lanes


def _sub_block(a: np.ndarray, n_sub: int, j: int, fill=0) -> np.ndarray:
    """Sub-block ``j`` of ``n_sub`` equal ones of ``a`` along its entity
    axis, the last padded with ``fill``."""
    e_sub = -(-a.shape[0] // n_sub)
    a = a[j * e_sub:(j + 1) * e_sub]
    if a.shape[0] < e_sub:
        pad = np.full((e_sub - a.shape[0],) + a.shape[1:], fill, a.dtype)
        a = np.concatenate([a, pad])
    return a


def _sub_runs(runs: Optional[RowRuns], n_sub: int, j: int):
    """Sub-block ``j`` of ``n_sub`` of a bucket's runs: a slice of runs is
    runs, and a padding entity an empty one."""
    if runs is None or n_sub == 1:
        return runs
    return RowRuns(*(_sub_block(a, n_sub, j) for a in runs))


def _split_bucket(
    bucket: RandomEffectBucket, n_sub: int, pad_code: int
) -> List[RandomEffectBucket]:
    """``bucket`` as ``n_sub`` sub-blocks of one shape: views of its host
    arrays, the last padded with weight-0 entities on no row whose code
    ``pad_code`` lies past the bank (the fused programs' padding lanes)."""
    fill = {"entity_codes": pad_code, "row_index": -1, "override_keys": -1}
    names = ["entity_codes", "row_index", "indices", "values", "labels",
             "offsets", "weights"]
    if bucket.override_keys is not None:
        names.append("override_keys")
    return [
        RandomEffectBucket(
            identity_indices=bucket.identity_indices,
            row_runs=_sub_runs(bucket.row_runs, n_sub, j),
            **{
                name: _sub_block(
                    getattr(bucket, name), n_sub, j, fill.get(name, 0)
                )
                for name in names
            },
        )
        for j in range(n_sub)
    ]


@dataclass
class RandomEffectOptimizationProblem:
    """One solver config shared by all entities (the reference materializes
    an RDD of identical per-entity problems; here the per-entity state is
    just the bank row).

    ``mesh``: when set, every bucket's entity axis is sharded over the
    mesh's first axis — the expert-parallel analog of the reference's
    entity co-partitioning (RandomEffectDataSetPartitioner.scala:62-95).
    Load balance is by construction: a bucket's entities share one padded
    capacity, so equal-count splits are equal-cost (the reference needs a
    greedy partitioner because its per-entity costs vary).
    """

    loss: PointwiseLoss
    config: OptimizerConfig
    regularization: RegularizationContext
    reg_weight: float = 0.0
    mesh: Optional[object] = None
    # Per-entity data layout for the solves: "auto" densifies a bucket's
    # [E, S, k] sparse rows into [E, S, D] blocks when that fits the
    # budget below (matmul gradients instead of serialized TPU scatters
    # per line-search trial); "sparse"/"dense" force a layout.
    layout: str = "auto"
    dense_bytes_budget: int = 2 << 30
    # isComputingVariance (RandomEffectOptimizationProblem.scala:106-127):
    # the coordinate attaches bank_variances() to the model after each
    # bank update so saved per-entity models carry them
    compute_variances: bool = False

    def __post_init__(self):
        if self.layout not in ("auto", "sparse", "dense"):
            raise ValueError(f"unknown layout {self.layout!r}")
        self._solvers = _cached_bucket_solver(
            self.loss, self.config, self.regularization
        )
        # AOT-compiled bucket programs from the threaded warm pass,
        # keyed by (kind, bank shape, bucket indices shape). Lives ON the
        # (shared) solver namespace so equal-config problems — a combo
        # grid's fresh problem per combo — reuse compiled executables.
        if not hasattr(self._solvers, "aot_cache"):
            self._solvers.aot_cache = {}
        self._aot_cache: Dict[tuple, object] = self._solvers.aot_cache
        # Device-resident copies of each bucket's static arrays (indices/
        # values/labels/weights), keyed by id(bucket). Coordinate descent
        # calls update_bank once per iteration with identical bucket data —
        # only the bank rows and residual offsets change — and host->device
        # re-transfer of the big [E, S, k] blocks (41 MB at E=20k, S=16,
        # k=32) would otherwise dominate the ~1 ms solve. Entries hold only a
        # weakref to the bucket: callers that rebuild buckets every call
        # (factored-RE latent views, MF ALS half-steps) get their device
        # copies freed with the bucket instead of accumulating until OOM,
        # and a recycled id cannot alias because the dead entry removes
        # itself first.
        self._device_cache: Dict[int, Tuple[object, List[Array]]] = {}
        # per-dataset residual routers for the mesh path (static routing
        # tables + jitted all_to_all scatter; weakref like _device_cache)
        self._router_cache: Dict[int, Tuple[object, object]] = {}

    def _router_for(self, dataset):  # photon: entropy(id-keyed router memo; weakref-pinned, never serialized)
        import weakref

        key = id(dataset)
        hit = self._router_cache.get(key)
        if hit is not None and hit[0]() is dataset:
            return hit[1]
        from photon_ml_tpu.game.residual_routing import ResidualRouter

        router = ResidualRouter(self.mesh, dataset)
        cache = self._router_cache
        ref = weakref.ref(dataset, lambda _, k=key, c=cache: c.pop(k, None))
        cache[key] = (ref, router)
        return router

    def dense_block_plan(
        self, num_entities: int, capacity: int, d_local: int, identity: bool,
        staged: int = 0,
    ) -> Tuple[str, int]:
        """The one rule for a block of ``num_entities`` entities at
        ``capacity`` samples and ``d_local`` features each: the solver
        kind a dense staging of it runs, and how many of its entities ONE
        dense program may hold under ``dense_bytes_budget``. A cap of at
        least ``num_entities`` means the block runs whole; a smaller one
        has the caller split the block into equal sub-blocks of at most
        that many entities (a replicated bucket, :meth:`_solver_blocks`;
        a device's share on the pod path, game/pod.py), or run the sparse
        solver where it cannot split (:meth:`_bucket_kind`).
        ``("sparse", num_entities)`` where nothing dense may run: the
        layout says so, or not one entity fits.

        Which Newton: the DUAL kind (``newton``) holds an entity's
        sample-space Gram, ``capacity ** 2`` floats built once with
        ``capacity ** 2 * d_local`` multiply-adds; the PRIMAL kind
        (``primal``) its normal equations, ``d_local ** 2`` floats
        rebuilt each iteration with ``capacity * d_local ** 2``. They
        break even at ``capacity == d_local``, in floats and in
        multiply-adds alike, so a block with MORE samples an entity than
        features (``capacity > d_local``) runs the primal kind and every
        other the dual one: 16 rows of 1,000 features stay dual, a bias
        over 9,254 ratings or a rank-64 factor over 67,310 is primal
        (its Gram alone would be 16 GiB). ``staged``: floats a slot a
        values override stages beside its values
        (:attr:`ValuesOverride.staged`), charged to every kind."""
        if self.layout == "sparse":
            return "sparse", num_entities
        newton = self._newton_eligible()
        primal = newton and capacity > d_local
        # "_id": indices that are the tiled arange (k == local_dim, the MF
        # latent view): X IS values, no [E, S, k, D] densify broadcast
        kind = (
            "primal" if primal else "newton" if newton else "dense"
        ) + ("_id" if identity else "")
        if self.layout == "dense":
            return kind, num_entities
        if primal:
            # the X the program really holds (densified, stored or made by
            # a values override) and the [D, D] system
            floats = capacity * d_local + d_local * d_local
        else:
            # X [E, S, D], plus the dual Newton path's Gram G [E, S, S]
            # when that solver would actually run (the CG solve is
            # matrix-free — no second S x S block); charging a Gram to a
            # bucket that can only take the plain dense solver would
            # wrongly force the slow sparse path. Identity-indices
            # buckets pay no X at all (X IS values).
            floats = 0 if identity else capacity * d_local
            if newton:
                floats += capacity * capacity
        floats += capacity * staged
        if floats == 0:
            return kind, num_entities
        cap = self.dense_bytes_budget // (floats * _BLOCK_ITEMSIZE)
        return (kind, int(cap)) if cap > 0 else ("sparse", num_entities)

    def _bucket_kind(self, bucket, d_local: int) -> str:
        """Which solver program this bucket runs as ONE block (host-side
        selection): the dense kind where the WHOLE bucket fits the
        budget. What a block that cannot be split runs: a streamed
        segment, a bucket on the entity mesh; ``update_bank`` splits the
        others (:meth:`_solver_blocks`)."""
        e_b, s_b, _ = bucket.indices.shape
        kind, cap = self.dense_block_plan(
            e_b, s_b, d_local, bucket.identity_indices
        )
        return kind if cap >= e_b else "sparse"

    def _solver_blocks(
        self, dataset: RandomEffectDataset, d_local: int, *, split: bool,
        staged: int = 0,
    ) -> List[_SolverBlock]:
        """The dataset's buckets as the blocks the solver programs run,
        in bucket order. With ``split`` a bucket whose dense staging is
        over the budget becomes ``ceil(E_b / cap)`` equal sub-blocks of
        the dense kind (:meth:`dense_block_plan`, the rule the pod splits
        a device's share by): same shape, so ONE compiled program, and
        consecutive, so they fold into one scanned dispatch that hands
        the donated bank from one to the next. Without it (the entity
        mesh addresses a bucket by its index) a bucket is one block of
        :meth:`_bucket_kind`. ``staged``: a values override's
        (:attr:`ValuesOverride.staged`). The sub-block views are cached
        on the dataset, keyed by the split."""
        plans = []
        for bucket in dataset.buckets:
            e_b, s_b, _ = bucket.indices.shape
            kind, cap = self.dense_block_plan(
                e_b, s_b, d_local, bucket.identity_indices, staged
            )
            if cap >= e_b:
                plans.append((kind, 1))
            elif split:
                plans.append((kind, -(-e_b // cap)))
            else:
                plans.append(("sparse", 1))
        n_subs = tuple(n for _, n in plans)
        cache = dataset.__dict__.setdefault("_sub_block_cache", {})
        if n_subs not in cache:
            cache[n_subs] = [
                _split_bucket(b, n, dataset.num_entities) if n > 1 else [b]
                for b, n in zip(dataset.buckets, n_subs)
            ]
        blocks = []
        for bi, (subs, (kind, n)) in enumerate(zip(cache[n_subs], plans)):
            e_sub = subs[0].num_entities
            left = dataset.buckets[bi].num_entities
            for j, sub in enumerate(subs):
                blocks.append(
                    _SolverBlock(bi, j, n, kind, sub, min(e_sub, left - j * e_sub))
                )
        return blocks

    def _newton_eligible(self) -> bool:
        """The dual-space Newton solver needs l2 > 0 (Woodbury ridge), a
        twice-differentiable loss, and no l1/TRON machinery."""
        l1, l2 = self.regularization.split(self.reg_weight)
        return (
            l2 > 0.0
            and not l1
            and self.loss.has_hessian
            and self.config.optimizer_type != OptimizerType.TRON
        )

    def _use_dense(self, bucket, d_local: int) -> bool:
        return self._bucket_kind(bucket, d_local) != "sparse"

    def _bucket_cached(self, bucket, what, make):  # photon: entropy(id-keyed device-array memo; weakref-pinned, never serialized)
        """``make()``'s device arrays for ``bucket``, made once a
        ``what``. The cache holds a weakref: device copies die with the
        bucket."""
        import weakref

        key = (id(bucket), what)
        hit = self._device_cache.get(key)
        if hit is not None and hit[0]() is bucket:
            return hit[1]
        arrs = make()
        cache = self._device_cache
        ref = weakref.ref(bucket, lambda _, k=key, c=cache: c.pop(k, None))
        self._device_cache[key] = (ref, arrs)
        return arrs

    def _bucket_device_args(self, bucket, with_values=True) -> List[Array]:
        """Device-resident (mesh-sharded if configured) static arrays for a
        bucket, transferred once and reused across update_bank calls.
        ``with_values=False`` (the values_override path): the values'
        place holds the bucket's ``override_keys`` [E, S] instead. The
        bucket's ``row_index`` is not among them: who reads it (the
        residual's slot path, ``re_score``) asks :meth:`_bucket_rows`."""

        def make():
            arrs = [
                jnp.asarray(bucket.indices),
                jnp.asarray(
                    bucket.values if with_values else bucket.override_keys
                ),
                jnp.asarray(bucket.labels),
                jnp.asarray(bucket.weights),
                jnp.asarray(bucket.offsets),
            ]
            if self.mesh is not None:
                arrs, _ = self._shard_entity_axis(arrs)
            # entity codes stay unsharded: they index the full bank
            # host-side
            return arrs + [jnp.asarray(bucket.entity_codes)]

        return self._bucket_cached(bucket, with_values, make)

    def _bucket_rows(self, bucket) -> Array:
        """``bucket.row_index`` [E, S] on the device, uploaded when first
        asked for (a bucket of runs whose rows nothing scores from, an ALS
        half-step's, never uploads it)."""
        return self._bucket_cached(
            bucket, "rows", lambda: jnp.asarray(bucket.row_index)
        )

    def _shard_entity_axis(self, arrays):
        """Pad arrays' leading (entity) dim to the mesh axis size and place
        them entity-sharded; returns (padded arrays, real length)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh
        axis = mesh.axis_names[0]
        n_dev = int(mesh.shape[axis])
        sharding = NamedSharding(mesh, P(axis))
        e = arrays[0].shape[0]
        e_pad = ((e + n_dev - 1) // n_dev) * n_dev
        out = []
        for a in arrays:
            if e_pad != e:
                pad = jnp.zeros((e_pad - e,) + a.shape[1:], a.dtype)
                a = jnp.concatenate([a, pad])
            out.append(jax.device_put(a, sharding))
        return out, e

    def _route_residuals(self, dataset, residual_offsets):
        """Pre-loop residual-offset routing shared by update_bank and
        bank_variances: -> (offsets_f32, routed_buffers, router)."""
        routed = None
        router = None
        if residual_offsets is not None:
            residual_offsets = jnp.asarray(residual_offsets, jnp.float32)
            if self.mesh is not None and dataset.buckets:
                # ICI re-key: ONE all_to_all routes each row's offset to
                # its entity's owner device (the addScoresToOffsets
                # shuffle analog) instead of replicating the whole [n]
                # vector to every device.
                router = self._router_for(dataset)
                routed = router.route(residual_offsets)
        return residual_offsets, routed, router

    def _stacked_group_args(self, dataset, blocks, *, with_residuals,
                            with_values=True):
        """Device-stacked [B, ...] args for a same-shape group of solver
        blocks, built from the HOST arrays in one transfer per field and
        cached on the dataset: codes, indices, values (under a values
        override the blocks' keys), labels, stored offsets, weights. The
        stored offsets are stacked only when ``with_residuals`` is False;
        under a residual their place holds None and the offsets come from
        :meth:`_bucket_offsets`, which uploads what IT reads: [B, E]
        starts and counts for a group of runs, the [B, E, S]
        ``row_index`` (:meth:`_stacked_rows`) otherwise — never both (a
        dead [B, E, S] buffer would otherwise pin HBM for the dataset's
        lifetime).

        Accepted trade-off: a dataset that ALSO runs the per-bucket path
        (bank_variances / with_variances) holds its buckets in both this
        cache and the per-bucket device cache; the two paths do not
        co-occur within one update, and problems are variance-typed for
        their lifetime, so the overlap is rare in practice."""
        cache = dataset.__dict__.setdefault("_stacked_device_cache", {})
        key = (
            tuple(b[:3] for b in blocks), bool(with_residuals),
            bool(with_values),
        )
        hit = cache.get(key)
        if hit is not None:
            return hit
        bs = [b.bucket for b in blocks]
        out = (
            jnp.asarray(np.stack([b.entity_codes for b in bs])),
            jnp.asarray(np.stack([b.indices for b in bs])),
            # under a values override, the blocks' keys in the values' place
            jnp.asarray(np.stack([
                b.values if with_values else b.override_keys for b in bs
            ])),
            jnp.asarray(np.stack([b.labels for b in bs])),
            None
            if with_residuals
            else jnp.asarray(np.stack([b.offsets for b in bs])),
            jnp.asarray(np.stack([b.weights for b in bs])),
        )
        cache[key] = out
        return out

    @staticmethod
    def _stacked_rows(dataset, blocks) -> Array:
        """The group's ``row_index`` stacked [B, E, S] on the device,
        uploaded when first asked for and cached on the dataset beside
        :meth:`_stacked_group_args`' (the residual's slot path and
        ``re_score`` read the same copy)."""
        cache = dataset.__dict__.setdefault("_stacked_device_cache", {})
        key = (tuple(b[:3] for b in blocks), "rows")
        if key not in cache:
            cache[key] = jnp.asarray(
                np.stack([b.bucket.row_index for b in blocks])
            )
        return cache[key]

    def _residual_args(self, dataset, groups):
        """Where each of ``groups`` of solver blocks finds its slots in a
        row-aligned vector, on the device, cached on the dataset:
        ``(specs, paths)``, what :func:`_residual_program` reads the
        residual's offsets through and :func:`re_score` puts the scores
        back through. A group's spec is ``(starts, counts)`` [E] (a
        folded group: [B, E]) where every block of it observed runs in
        the row vector (:class:`RowRuns`; path ``windows``) or, failing
        that, in the vector sorted into the dataset's entity order
        (:attr:`RandomEffectDataset.entity_order`, observed here when
        first read; path ``sorted``), else ``(rows,)``, the group's
        ``row_index`` (path ``slots``: no order, as where a row is held
        twice or a bucket's real slots are not a prefix). ``paths``,
        static: ``(path, capacity)`` a group. The order itself is
        :func:`_order_on_device`'s."""
        cache = dataset.__dict__.setdefault("_residual_device_cache", {})
        key = tuple(tuple(b[:3] for b in members) for members in groups)
        if key in cache:
            return cache[key]

        def runs_of(members):
            runs = [b.bucket.row_runs for b in members]
            if all(r is not None for r in runs):
                return "windows", runs
            order = dataset.entity_order
            if order is None:
                return "slots", None
            runs = [
                _sub_runs(order.runs[b.bucket_index], b.sub_blocks,
                          b.sub_block)
                for b in members
            ]
            if all(r is not None for r in runs):
                return "sorted", runs
            return "slots", None

        specs, paths = [], []
        for members in groups:
            path, runs = runs_of(members)
            if runs is None:
                specs.append((
                    self._stacked_rows(dataset, members)
                    if len(members) > 1
                    else self._bucket_rows(members[0].bucket),
                ))
                paths.append((path, 0))
                continue
            fields = [np.stack(field) for field in zip(*runs)]
            if len(members) == 1:
                fields = [field[0] for field in fields]
            specs.append(tuple(jnp.asarray(f) for f in fields))
            paths.append((path, members[0].bucket.capacity))
        cache[key] = (tuple(specs), tuple(paths))
        return cache[key]

    def _bucket_offsets(
        self, dataset, groups, residual_offsets, coordinate=None
    ) -> List[Array]:
        """Every group's per-sample offsets from the residual on ONE
        device, [E, S] (a folded group: [B, E, S]), in the groups' order
        (the mesh path slices each bucket's slab out of the routed
        buffers instead, ``router.bucket_slab``). One program a dataset
        (:func:`_residual_program`, module
        ``jit_bank_residual_<coordinate>``), which stays on the device
        (the KeyValueScore residual currency never leaves it, SURVEY
        §7.9) and reads what :meth:`_residual_args` uploaded: windows of
        the row vector at [E] starts for a group whose entities' rows are
        runs, windows of the residual sorted into the dataset's entity
        order for a group whose rows are a run there, the element gather
        through the uploaded ``row_index`` for any other. Which path a
        group's slots took is counted here, on the host, where it is
        decided."""
        specs, paths = self._residual_args(dataset, groups)
        keys = _order_on_device(dataset, "keys", paths)
        slots = default_registry().counter(
            "photon_bank_residual_slots_total",
            "slots of the replicated bank's blocks whose offsets were "
            "read from the residual, by coordinate and path (windows: an "
            "entity's rows are a run; sorted: they are a run once the "
            "residual is sorted into the dataset's entity order; slots: "
            "the element gather)",
        )
        taken = slots_by_path(groups, paths)
        for path, count in taken.items():
            if count:
                slots.inc(count, coordinate=coordinate or "", path=path)
        with obs_span("bank.residual", groups=len(groups), **taken):
            return _residual_program(coordinate)(
                residual_offsets, specs, keys, paths=paths
            )

    def group_offsets(
        self, dataset, residual_offsets, *,
        override: Optional[ValuesOverride] = None,
        coordinate: Optional[str] = None,
    ):
        """``(groups, offsets)``: the groups of solver blocks an
        ``update_bank`` over ``dataset`` under ``override`` runs on one
        device, and their offsets from the ``[n]`` residual (one
        :func:`_residual_program` run), which ``update_bank`` takes as
        ``group_offsets``."""
        groups = self._update_groups(
            dataset, dataset.local_dim, staged=_staged(override)
        )
        residual_offsets = jnp.asarray(residual_offsets, jnp.float32)
        return groups, self._bucket_offsets(
            dataset, groups, residual_offsets, coordinate
        )

    @staticmethod
    def _program_sig(kind, coordinate, bank_shape, ix_shape, override,
                     scan=False):
        """What tells one compiled solver program from another in
        ``_aot_cache``; ``ix_shape`` with the leading stack axis for a
        scanned group."""
        sig = (kind, coordinate, tuple(bank_shape), tuple(ix_shape))
        if override is not None:
            sig += override.sig
        return (("scan",) + sig) if scan else sig

    def _bucket_plans(
        self,
        bank: Array,
        groups,
        *,
        override: Optional[ValuesOverride],
        l1_d,
        l2_d,
        coordinate: Optional[str] = None,
    ):
        """(sig, thunk) plans for every DISTINCT program of ``groups``
        (:meth:`_block_groups`); ``thunk()`` lowers the exact solver call
        and returns the compiled executable. Everything lowers from
        avals: nothing is uploaded or computed here."""
        plans = []
        seen_sigs = set()
        sds = jax.ShapeDtypeStruct
        f32, i32 = jnp.float32, jnp.int32
        values_of = override.values_of if override is not None else None
        extra = () if override is None else (jax.tree.map(
            lambda a: sds(a.shape, a.dtype), override.operand
        ),)
        for members in groups:
            kind, bucket = members[0].kind, members[0].bucket
            scan = len(members) > 1
            lead = (len(members),) if scan else ()
            ixk = lead + bucket.indices.shape
            sig = self._program_sig(
                kind, coordinate, bank.shape, ixk, override, scan
            )
            if sig in seen_sigs:
                continue  # identical program; one compile suffices
            seen_sigs.add(sig)
            es = lead + bucket.labels.shape
            # under an override of keys the values' place holds them [E, S]
            v_aval = (
                sds(ixk[:-1] + bucket.values.shape[-1:], f32)
                if _holds_values(override) else sds(es, i32)
            )

            def thunk(kind=kind, scan=scan, ixk=ixk, es=es, v_aval=v_aval,
                      bank=bank):
                return self._solvers.fused_for(
                    kind, coordinate, scan=scan, values_of=values_of
                ).lower(
                    bank, sds(es[:-1], i32), sds(ixk, i32), v_aval,
                    sds(es, f32), sds(es, f32), sds(es, f32), l1_d, l2_d,
                    *extra,
                ).compile()

            plans.append((sig, thunk))
        return plans

    @staticmethod
    def _block_groups(blocks, *, fold: bool):
        """Consecutive runs of same-kind, same-shape blocks (the lax.scan
        fold grouping: a bucket's sub-blocks always form one);
        singletons when folding is off."""
        groups: List[List[_SolverBlock]] = []
        for block in blocks:
            last = groups[-1][-1] if groups else None
            if (
                fold and last is not None and last.kind == block.kind
                and last.bucket.indices.shape == block.bucket.indices.shape
            ):
                groups[-1].append(block)
            else:
                groups.append([block])
        return groups

    def prepare(
        self, bank: Array, dataset: RandomEffectDataset,
        *, has_residual_offsets: bool = True,
        coordinate: Optional[str] = None,
        override: Optional[ValuesOverride] = None,
    ) -> None:
        """Host-side staging for a FUTURE update_bank over ``dataset``:
        device transfer of every block's static arrays (stacked group
        args on the fold path), residual routing tables on the mesh path,
        and AOT compiles of the solver programs. Idempotent — everything
        lands in the same caches update_bank reads — and safe to run on a
        background thread while ANOTHER coordinate's solves occupy the
        device (the overlap prefetched-dispatch lever: coordinate k+1's
        host prep runs under coordinate k's device work instead of as a
        serial gap between their dispatches). ``override``: the values
        override the update will run under (:class:`ValuesOverride`)."""
        if not dataset.buckets:
            return
        # update_bank's own groups (variance-typed problems run the
        # per-block path, so stage per-block device args — a stacked copy
        # would pin HBM the update never reads)
        groups = self._update_groups(
            dataset, bank.shape[1], with_variances=self.compute_variances,
            staged=_staged(override),
        )
        with_values = _holds_values(override)
        for members in groups:
            if len(members) > 1:
                self._stacked_group_args(
                    dataset, members, with_residuals=has_residual_offsets,
                    with_values=with_values,
                )
            else:
                self._bucket_device_args(
                    members[0].bucket, with_values=with_values
                )
        if self.mesh is None:
            if has_residual_offsets:
                _, paths = self._residual_args(dataset, groups)
                _order_on_device(dataset, "keys", paths)
            l1, l2 = self.regularization.split(self.reg_weight)
            self._warm_solvers(self._bucket_plans(
                bank, groups,
                override=override,
                l1_d=jnp.float32(l1), l2_d=jnp.float32(l2),
                coordinate=coordinate,
            ))
        elif has_residual_offsets:
            self._router_for(dataset)  # static routing tables, host-built

    def _update_groups(
        self, dataset, d_local: int, *, with_variances=False, staged=0
    ):
        """The groups of solver blocks ONE ``update_bank`` over
        ``dataset`` dispatches (:meth:`_solver_blocks`,
        :meth:`_block_groups`): split and folded on one device, a bucket
        a block on the entity mesh, which addresses buckets by index.
        ``staged``: a values override's (:attr:`ValuesOverride.staged`)."""
        foldable = self.mesh is None
        blocks = self._solver_blocks(
            dataset, d_local, split=foldable, staged=staged
        )
        return self._block_groups(
            blocks, fold=foldable and not with_variances and len(blocks) > 1
        )

    def prewarm(self, specs) -> None:
        """AOT-compile the solver programs of SEVERAL (bank, dataset,
        override, coordinate) updates in ONE
        threaded pool. The MF coordinate calls this before its first ALS
        half-step so BOTH sides' programs compile concurrently instead of
        serializing across half-steps."""
        if self.mesh is not None:
            return
        l1, l2 = self.regularization.split(self.reg_weight)
        l1_d, l2_d = jnp.float32(l1), jnp.float32(l2)
        plans = []
        for bank, dataset, override, coordinate in specs:
            plans += self._bucket_plans(
                bank, self._update_groups(
                    dataset, bank.shape[1], staged=_staged(override)
                ),
                override=override,
                l1_d=l1_d, l2_d=l2_d, coordinate=coordinate,
            )
        self._warm_solvers(plans)

    def _warm_solvers(self, plans) -> None:
        """AOT-compile each distinct bucket program from its own thread so
        they compile CONCURRENTLY: calling the jit wrappers one after
        another compiles one program at a time (each first call blocks
        on its own compile), while threaded ``lower().compile()``
        overlaps them. The compiles go through the persistent XLA cache
        (utils/backend.enable_compilation_cache), so a warm cache loads
        them instead. Compiled executables land in ``_aot_cache`` and
        the bucket loop calls them instead of the jit wrapper; a single
        fresh program takes the same path."""
        from concurrent.futures import ThreadPoolExecutor

        fresh = [
            (sig, thunk) for sig, thunk in plans if sig not in self._aot_cache
        ]
        if not fresh:
            return
        # (the pool's jax.trace / jax.lower / jax.compile spans parent to
        # this one and keep their own thread ids; the main thread waits)
        with (
            obs_span(
                "bank.warm_solvers", programs=len(fresh),
                cached=len(plans) - len(fresh),
            ),
            ThreadPoolExecutor(min(8, len(fresh))) as pool,
        ):
            compiled = list(pool.map(
                bound_to_current_span(lambda item: item[1]()), fresh
            ))
        for (sig, _), exe in zip(fresh, compiled):
            # FIFO-bounded: the cache lives on the SHARED solver
            # namespace (process lifetime via _SOLVER_CACHE), so a
            # long-lived driver sweeping many bank/bucket shapes must
            # not accumulate executables forever
            while len(self._aot_cache) >= 64:
                self._aot_cache.pop(next(iter(self._aot_cache)))
            self._aot_cache[sig] = exe

    @obs_traced("bank.update")
    def update_bank(
        self,
        bank: Array,  # [E, D]
        dataset: RandomEffectDataset,
        residual_offsets: Optional[Array] = None,  # [n] replaces offsets
        values_override: Optional[ValuesOverride] = None,
        with_variances: bool = False,
        defer_tracker: bool = False,
        coordinate: Optional[str] = None,
        group_offsets: Optional[List[Array]] = None,
    ):
        """Solve every entity against its active data; returns the new bank
        and an aggregated tracker — plus the per-entity variance bank when
        ``with_variances`` (the Hdiag pass runs inside the block loop with
        the already-routed offsets in hand, so the mesh path pays no second
        residual all_to_all).

        ``values_override``: feature values made on the device, inside
        each block's solver program, from the block's ``override_keys``
        (:class:`ValuesOverride`) — the MF ALS path gathers the partner
        side's current factors every half-step while the bucket
        STRUCTURE stays cached. A bucket over the dense budget splits
        into sub-blocks as any other, so at most one sub-block's values
        are alive.

        ``defer_tracker``: return a LazyRandomEffectTracker whose stats
        stay on device — the GAME CD loop folds every coordinate's
        tracker into ONE batched readback per iteration instead of one
        synchronous round trip per bank update.

        ``coordinate``: the GAME coordinate this bank belongs to. It
        names the solver programs' XLA modules, goes on the
        ``bank.dispatch`` spans and labels
        ``photon_bank_entities_total``, so that a trace and the registry
        tell one coordinate's bank from another's.

        ``group_offsets``: the offsets of this update's groups already
        made from the residual (:meth:`group_offsets`), in the residual's
        place: a caller that runs several updates under one residual
        makes them once.
        """
        l1, l2 = self.regularization.split(self.reg_weight)
        l1_d, l2_d = jnp.float32(l1), jnp.float32(l2)
        # Per-block stat vectors [iter_sum, iter_max, *reason_counts] stay
        # ON DEVICE until one stacked fetch at the end: every device->host
        # readback is a synchronous round trip that drains the dispatch
        # queue, so the loop stays fully async and the tracker costs one
        # sync total, not three per block.
        n_reals: List[int] = []
        stat_vecs: List[Array] = []
        if self.mesh is None and dataset.buckets:
            # one defensive copy so the fused updates can DONATE the bank
            # (in-place scatter per block) while the caller's reference
            # stays valid
            bank = jnp.array(bank, copy=True)
        with obs_span("bank.route_residuals"):
            residual_offsets, routed, router = self._route_residuals(
                dataset, residual_offsets
            )
        var_bank = jnp.zeros_like(bank) if with_variances else None
        if with_variances:
            from photon_ml_tpu.optim.problem import _VARIANCE_EPSILON
        # The blocks the solver programs run: a bucket, or the equal
        # sub-blocks of one over the dense budget (the entity mesh
        # addresses buckets by index and keeps them whole). Same-shape
        # block RUNS fold into one lax.scan dispatch (the profiled
        # ~125 ms of host gaps between per-bucket dispatches at the
        # config-4 shape, round 5); per-block paths keep handling the
        # mesh and the variances cases.
        override = values_override
        groups = self._update_groups(
            dataset, bank.shape[1], with_variances=with_variances,
            staged=_staged(override),
        )
        extra = () if override is None else (override.operand,)
        values_of = override.values_of if override is not None else None
        if self.mesh is None and dataset.buckets:
            self._warm_solvers(self._bucket_plans(
                bank, groups,
                override=override,
                l1_d=l1_d, l2_d=l2_d, coordinate=coordinate,
            ))
        solved = default_registry().counter(
            "photon_bank_entities_total",
            "entities the replicated bank updates solved, by coordinate "
            "and solver kind",
        )
        systems = default_registry().counter(
            "photon_bank_primal_systems_total",
            "[D, D] systems (D >= 2) the primal kind's Newton steps "
            "factor, one an entity, by coordinate and the way "
            "ops/spd_solve solves a batch of them",
        )
        solve = solve_path(bank.shape[1], effective_platform())
        offsets = group_offsets
        if (
            offsets is None and residual_offsets is not None
            and routed is None and groups
        ):
            offsets = self._bucket_offsets(
                dataset, groups, residual_offsets, coordinate
            )
        for gi, members in enumerate(groups):
            block = members[0]
            kind, bucket = block.kind, block.bucket
            n_real = sum(b.num_real for b in members)
            # counted where the kind is decided, on the host
            solved.inc(n_real, coordinate=coordinate or "", kind=kind)
            attrs = {"coordinate": coordinate} if coordinate else {}
            # a bias (D = 1) has no system to factor
            if kind.startswith("primal") and solve != "division":
                systems.inc(n_real, coordinate=coordinate or "", solve=solve)
                attrs["solve"] = solve
            dispatch_span = obs_span(
                "bank.dispatch", kind=kind, entities=n_real,
                capacity=bucket.capacity, sub_blocks=block.sub_blocks,
                **attrs,
            )
            if len(members) > 1:
                (
                    codes_s, ix_s, v_s, lab_s, off_s, w_s,
                ) = self._stacked_group_args(
                    dataset, members,
                    with_residuals=offsets is not None,
                    with_values=_holds_values(override),
                )
                if offsets is not None:
                    off_s = offsets[gi]
                fused_scan = self._aot_cache.get(self._program_sig(
                    kind, coordinate, bank.shape, ix_s.shape, override,
                    scan=True,
                )) or self._solvers.fused_for(
                    kind, coordinate, scan=True,
                    values_of=values_of,
                )
                with dispatch_span:
                    bank, it_sum, it_max, counts = fused_scan(
                        bank, codes_s, ix_s, v_s, lab_s, off_s, w_s,
                        l1_d, l2_d, *extra,
                    )
                n_reals.append(n_real)
                stat_vecs.append(
                    jnp.concatenate([jnp.stack([it_sum, it_max]), counts])
                )
                continue
            (
                ix_d, v_d, lab_d, w_d, off_d, codes_d,
            ) = self._bucket_device_args(
                bucket, with_values=_holds_values(override)
            )
            if override is not None and self.mesh is not None:
                # the mesh path's solvers take values, not keys: this
                # bucket's are made whole, beside no other's
                v_d = override.fn(override.operand, v_d)
            if routed is not None:
                # mesh path: slice this bucket's slab out of the routed
                # per-device buffers — already entity-sharded
                off_d = router.bucket_slab(
                    routed, block.bucket_index, bucket.capacity
                )
            elif offsets is not None:
                off_d = offsets[gi]
            if self.mesh is None:
                # fused path: gather + solve + scatter + tracker reductions
                # in one dispatch; AOT-warmed programs run their compiled
                # executable directly
                fused = self._aot_cache.get(self._program_sig(
                    kind, coordinate, bank.shape, bucket.indices.shape,
                    override,
                )) or self._solvers.fused_for(
                    kind, coordinate,
                    values_of=values_of,
                )
                with dispatch_span:
                    bank, it_sum, it_max, counts = fused(
                        bank, codes_d, ix_d, v_d, lab_d, off_d, w_d,
                        l1_d, l2_d, *extra,
                    )
            else:
                # padded entities carry zero data: their solve converges at
                # iteration 0 on a zero gradient — inert and cheap
                sl = bank[codes_d]
                (sl,), _ = self._shard_entity_axis([sl])
                solver = getattr(self._solvers, kind)
                with dispatch_span:
                    new_sl, iters, reasons = solver(
                        sl, ix_d, v_d, lab_d, off_d, w_d, l1_d, l2_d
                    )
                new_sl = new_sl[:n_real]
                iters = iters[:n_real]
                reasons = reasons[:n_real]
                bank = bank.at[codes_d].set(new_sl)
                it_sum = jnp.sum(iters)
                it_max = jnp.max(iters)
                counts = jnp.bincount(
                    reasons, length=max(CONVERGENCE_REASON_NAMES) + 1
                )
            if with_variances:
                # Hdiag at the just-solved rows, same off_d — no re-route
                # (a sub-block's padding lanes: zero rows, dropped codes)
                sl_new = jnp.take(
                    bank, codes_d, axis=0, mode="fill", fill_value=0
                )
                if self.mesh is not None:
                    (sl_new,), _ = self._shard_entity_axis([sl_new])
                hd = self._solvers.hdiag(
                    sl_new, ix_d, v_d, lab_d, off_d, w_d, l2_d
                )
                var_bank = var_bank.at[codes_d].set(
                    1.0 / (hd[:bucket.num_entities] + _VARIANCE_EPSILON),
                    mode="drop",
                )
            n_reals.append(n_real)
            stat_vecs.append(
                jnp.concatenate([jnp.stack([it_sum, it_max]), counts])
            )
        if stat_vecs:
            from photon_ml_tpu.parallel import overlap

            total = sum(n_reals)

            def _finalize(all_stats, total=total):
                iter_sum = int(all_stats[:, 0].sum())
                iter_max = int(all_stats[:, 1].max())
                count_vec = all_stats[:, 2:].sum(axis=0)
                counts_dict: Dict[str, int] = {
                    CONVERGENCE_REASON_NAMES.get(code, "?"): int(cnt)
                    for code, cnt in enumerate(count_vec)
                    if cnt
                }
                return RandomEffectTracker(
                    num_entities=total,
                    iterations_mean=iter_sum / total,
                    iterations_max=iter_max,
                    reason_counts=counts_dict,
                )

            deferred = overlap.Deferred(jnp.stack(stat_vecs), _finalize)
            if defer_tracker and not deferred.done:
                # stats stay device-resident; the CD loop batch-fetches
                tracker = LazyRandomEffectTracker(deferred)
            else:
                # ONE explicit readback (transfer-guard safe)
                tracker = deferred.result()
        else:
            tracker = RandomEffectTracker(0, 0.0, 0, {})
        if with_variances:
            return bank, tracker, var_bank
        return bank, tracker

    def bank_variances(
        self,
        bank: Array,  # [E, D]
        dataset: RandomEffectDataset,
        residual_offsets: Optional[Array] = None,
    ) -> Array:
        """Per-entity coefficient variances 1/(Hdiag + eps) at the bank
        solution, [E, D] aligned with the bank (isComputingVariance:
        RandomEffectOptimizationProblem.scala:106-127 plumbs variance
        computation into every per-entity solve; the per-entity Bayesian
        models save them via ModelProcessingUtils.scala:44-189). One
        vmapped Hdiag pass per bucket — no solve."""
        from photon_ml_tpu.optim.problem import _VARIANCE_EPSILON

        _, l2 = self.regularization.split(self.reg_weight)
        l2_d = jnp.float32(l2)
        residual_offsets, routed, router = self._route_residuals(
            dataset, residual_offsets
        )
        variances = jnp.zeros_like(bank)
        offsets = None
        if residual_offsets is not None and routed is None and dataset.buckets:
            # a bucket a group, whole
            offsets = self._bucket_offsets(
                dataset,
                self._block_groups(
                    self._solver_blocks(dataset, bank.shape[1], split=False),
                    fold=False,
                ),
                residual_offsets,
            )
        for bi, bucket in enumerate(dataset.buckets):
            (
                ix_d, v_d, lab_d, w_d, off_d, codes_d,
            ) = self._bucket_device_args(bucket)
            if routed is not None:
                off_d = router.bucket_slab(routed, bi, bucket.capacity)
            elif offsets is not None:
                off_d = offsets[bi]
            n_real = bucket.num_entities
            sl = bank[codes_d]
            if self.mesh is not None:
                (sl,), _ = self._shard_entity_axis([sl])
            hd = self._solvers.hdiag(sl, ix_d, v_d, lab_d, off_d, w_d, l2_d)
            variances = variances.at[codes_d].set(
                1.0 / (hd[:n_real] + _VARIANCE_EPSILON)
            )
        return variances

    def regularization_term(self, bank: Array) -> float:
        """Sum of per-entity reg terms (Coordinate.regTerm analog)."""
        from photon_ml_tpu.parallel import overlap

        return float(
            overlap.device_get(self.regularization_term_device(bank))
        )

    def regularization_term_device(self, bank: Array) -> Array:
        """The reg term as a DEVICE scalar — no readback: the overlap
        path folds it into the CD iteration's one batched fetch instead
        of two scalar pulls per coordinate per iteration."""
        l1, l2 = self.regularization.split(self.reg_weight)
        term = 0.5 * l2 * jnp.sum(bank * bank)
        if l1:
            term = term + l1 * jnp.sum(jnp.abs(bank))
        return term


def device_row_view(dataset: RandomEffectDataset):
    """Cached device copies of the row-aligned arrays (codes clamped,
    valid mask, local indices, local values). Scoring runs once per
    coordinate per CD iteration; without the cache every call re-uploads
    the whole [n, k] table (the round-2 per-iteration PCIe leak)."""
    hit = dataset.__dict__.get("_device_rows")
    if hit is None:
        hit = (
            jnp.maximum(jnp.asarray(dataset.row_entity_codes), 0),
            jnp.asarray(dataset.row_entity_codes >= 0),
            jnp.asarray(dataset.row_local_indices),
            jnp.asarray(dataset.row_local_values),
        )
        dataset.__dict__["_device_rows"] = hit
    return hit


# The widest local space a block's rows are looked up in by compare: the
# compare costs 0.71 ns an entry for every 1,000 dims (12.0 ms for 16.8M
# entries at D = 1000), the element gather 14 ns whatever the width (my
# chip runs, PR 34), so past ~19,000 dims the gather is the cheaper one.
_SCORE_BLOCK_MAX_DIM = 16384


def scores_from_block(kind: str, d_local: int) -> bool:
    """Whether the rows of a solver block of ``kind`` are scored from the
    block (:func:`score_block`): a block the dense solvers run, over a
    local space narrow enough for the compare to beat the gather."""
    return kind != "sparse" and d_local <= _SCORE_BLOCK_MAX_DIM


class _ScorePlan(NamedTuple):
    """How one dataset's rows are scored under one problem's block
    split: the groups of dense solver blocks whose rows are scored from
    the block (:func:`score_block`), and the rows no such block holds,
    which keep the gather."""

    groups: Tuple[List[_SolverBlock], ...]
    # (rows, codes, valid, ix, v) device arrays of the rows left to the
    # gather: a compacted row list (``valid`` None), the whole row view
    # where no block scores (``rows`` None), or None where none is left
    rest: Optional[tuple]
    block_rows: int
    gather_rows: int
    # (rows, codes, ix, v) device arrays of the PASSIVE rows, where the
    # plan keeps them apart from ``rest`` (:func:`score_plan`'s
    # ``passive_apart``): per-entity chunks (:func:`_passive_chunks`),
    # counted as ``chunk_rows``, or flat rows that keep the gather,
    # counted among ``gather_rows``
    passive: Optional[tuple] = None
    chunk_rows: int = 0
    passive_chunks: int = 0
    passive_padding: int = 0  # chunk slots on no row

    @property
    def kernel(self) -> str:
        return score_kernel_name(
            self.block_rows, self.gather_rows, self.chunk_rows
        )


def score_kernel_name(
    block_rows: int, gather_rows: int, chunk_rows: int = 0
) -> str:
    """``blocks`` | ``chunks`` | ``gather``, or those that scored rows
    joined by ``+`` (``blocks+chunks``, ``blocks+gather``, ...): what a
    random effect's ``cd.score`` span says of a scoring pass."""
    paths = (
        ("blocks", block_rows), ("chunks", chunk_rows),
        ("gather", gather_rows),
    )
    return "+".join(p for p, rows in paths if rows) or "gather"


def _passive_chunks(dataset: RandomEffectDataset, rows: np.ndarray,
                    capacity: int, budget: int) -> tuple:
    """The passive ``rows`` of ``dataset`` in the shape of a solver
    block: grouped by entity (a stable sort of their codes), each
    entity's rows cut into chunks of ``capacity``, the last padded with
    slots on no row (``rows`` -1, ``v`` 0). ``(rows [C, S], codes [C],
    ix [C, S, k], v [C, S, k])`` host arrays; stacked ``[B, C / B, ...]``
    in equal sub-blocks, the last padded with chunks of an entity past
    the bank, where the compare's live arrays (the chunks' bank rows and
    their looked-up entries) would pass ``budget`` bytes."""
    codes = dataset.row_entity_codes[rows]
    order = np.argsort(codes, kind="stable")
    rows, codes = rows[order], codes[order]
    entities, first, counts = np.unique(
        codes, return_index=True, return_counts=True
    )
    per = -(-counts // capacity)  # chunks an entity
    pos = np.arange(len(rows)) - np.repeat(first, counts)
    chunk = np.repeat(np.cumsum(per) - per, counts) + pos // capacity
    slot = pos % capacity
    num_chunks = int(per.sum())
    k = dataset.row_local_indices.shape[1]
    c_rows = np.full((num_chunks, capacity), -1, np.int32)
    c_ix = np.zeros(
        (num_chunks, capacity, k), dataset.row_local_indices.dtype
    )
    c_v = np.zeros((num_chunks, capacity, k), np.float32)
    c_rows[chunk, slot] = rows
    c_ix[chunk, slot] = dataset.row_local_indices[rows]
    c_v[chunk, slot] = dataset.row_local_values[rows]
    out = (c_rows, np.repeat(entities, per).astype(np.int32), c_ix, c_v)
    live = _BLOCK_ITEMSIZE * num_chunks * (dataset.local_dim + capacity * k)
    n_sub = -(-live // budget)
    if n_sub <= 1:
        return out
    fills = (-1, dataset.num_entities, 0, 0)
    return tuple(
        np.stack([_sub_block(a, n_sub, j, fill) for j in range(n_sub)])
        for a, fill in zip(out, fills)
    )


@lru_cache(maxsize=1)
def _default_problem() -> "RandomEffectOptimizationProblem":
    """What a caller that holds no problem scores through
    (``RandomEffectModel.score``): the dataclass's default layout and
    ``dense_bytes_budget``, so that no bucket is scored whole over it."""
    from photon_ml_tpu.ops.losses import LOGISTIC

    return RandomEffectOptimizationProblem(
        LOGISTIC, OptimizerConfig(), RegularizationContext()
    )


def score_plan(
    dataset: RandomEffectDataset,
    problem: Optional["RandomEffectOptimizationProblem"] = None,
    staged: int = 0,
    passive_apart: bool = True,
) -> _ScorePlan:
    """The :class:`_ScorePlan` of ``dataset`` under ``problem``, decided
    from the data: a row that a DENSE solver block holds (the blocks
    ``update_bank`` runs, :meth:`RandomEffectOptimizationProblem.
    _solver_blocks`) is scored from that block; a passive row, a row of
    a block the budget or the layout leaves to the sparse solver or whose
    local space is too wide to compare against
    (:func:`scores_from_block`), every row of a view without buckets
    and every row under the entity mesh (its blocks are entity-sharded)
    keep the gather. ``staged``: the values override's the update runs
    under (:attr:`ValuesOverride.staged`), so that the blocks are its.
    On the replicated bank the passive rows (valid rows that no bucket
    holds: the reservoir cap's leftovers) go to ``passive`` instead of
    ``rest``, for a program of their own (:func:`re_score_passive`): as
    per-entity chunks of the widest bucket's capacity, scored from the
    chunk like a block's rows, where the bank is narrow enough for the
    compare (:func:`scores_from_block`'s width), else as flat rows that
    keep the gather; ``passive_apart=False`` leaves them in ``rest``,
    for a caller that scores every gathered row itself (the factored
    coordinate). Cached on the dataset, keyed by the split."""
    problem = problem or _default_problem()
    blocks = []
    if problem.mesh is None and dataset.buckets:
        blocks = problem._solver_blocks(
            dataset, dataset.local_dim, split=True, staged=staged
        )
    # the update's own fold rule, so that scoring finds the device arrays
    # the update holds
    fold = not problem.compute_variances and len(blocks) > 1
    d_local = dataset.local_dim
    apart = bool(
        passive_apart and problem.mesh is None and dataset.num_passive_rows
    )
    chunked = apart and bool(dataset.buckets) and (
        d_local <= _SCORE_BLOCK_MAX_DIM
    )
    key = (
        tuple((b[:3], scores_from_block(b.kind, d_local)) for b in blocks),
        fold,
        apart,
        chunked and problem.dense_bytes_budget,
    )
    cache = dataset.__dict__.setdefault("_score_plan_cache", {})
    plan = cache.get(key)
    if plan is not None:
        return plan
    groups = tuple(
        members
        for members in problem._block_groups(blocks, fold=fold)
        if scores_from_block(members[0].kind, d_local)
    )
    codes = np.asarray(dataset.row_entity_codes)
    left = codes >= 0
    num_valid = int(left.sum())
    passive, passive_rows, chunks = None, 0, {}
    if apart:
        held = np.zeros(left.shape, bool)
        for bucket in dataset.buckets:
            held[bucket.row_index[bucket.row_index >= 0]] = True
        rows = np.nonzero(left & ~held)[0]
        left &= held
        passive_rows = len(rows)
        if chunked:
            passive = _passive_chunks(
                dataset, rows, max(b.capacity for b in dataset.buckets),
                problem.dense_bytes_budget,
            )
            chunks = dict(
                passive_chunks=int(passive[1].size),
                passive_padding=int(passive[0].size) - passive_rows,
            )
        else:
            passive = (
                rows.astype(np.int32), codes[rows],
                dataset.row_local_indices[rows],
                dataset.row_local_values[rows],
            )
        passive = tuple(jnp.asarray(a) for a in passive)
    if not groups and not apart:
        rest = (None,) + device_row_view(dataset)
    else:
        for members in groups:
            for block in members:
                rows = block.bucket.row_index
                left[rows[rows >= 0]] = False
        rows = np.nonzero(left)[0]
        rest = None
        if len(rows):
            rest = (
                jnp.asarray(rows.astype(np.int32)),
                jnp.asarray(codes[rows]),
                None,
                jnp.asarray(dataset.row_local_indices[rows]),
                jnp.asarray(dataset.row_local_values[rows]),
            )
    chunk_rows = passive_rows if chunked else 0
    gather_rows = int(left.sum()) + passive_rows - chunk_rows
    plan = _ScorePlan(
        groups, rest, num_valid - gather_rows - chunk_rows, gather_rows,
        passive, chunk_rows, **chunks,
    )
    cache[key] = plan
    return plan


def score_random_effect(
    bank: Array,  # [E, D]
    dataset: RandomEffectDataset,
    problem: Optional["RandomEffectOptimizationProblem"] = None,
) -> Array:
    """Row-aligned scores [n]: score_i = x_i(local) . bank[entity_i].

    Covers active AND passive rows (passive scoring with locally-projected
    features is equivalent to the reference's back-projected model scoring:
    features unseen in the entity's active data have zero coefficients,
    RandomEffectCoordinate.scala:178-199).

    ``problem``: the one whose ``update_bank`` solves ``dataset``; the
    rows its dense blocks hold are scored from the device arrays it
    already holds for them (:func:`score_plan`) and placed through the
    specs its residual reads (:func:`score_places`), the passive rows by
    :func:`re_score_passive`."""
    problem = problem or _default_problem()
    plan = score_plan(dataset, problem)
    specs, order, paths = score_places(problem, dataset, plan)
    scores = re_score(
        bank, score_blocks(problem, dataset, plan, specs), plan.rest, order,
        identity=tuple(m[0].bucket.identity_indices for m in plan.groups),
        num_rows=int(dataset.row_entity_codes.shape[0]), paths=paths,
    )
    if plan.passive is not None:
        scores = re_score_passive(scores, bank, *plan.passive)
    return scores


def score_places(problem, dataset, plan: _ScorePlan):
    """How each group of ``plan`` puts its ``[E, S]`` scores into the row
    vector: ``(specs, order, paths)``, the specs and ``(path, capacity)``
    a group that the bank update's residual reads through
    (:meth:`RandomEffectOptimizationProblem._residual_args`, the same
    rule and the same cached device arrays: ``windows``, ``sorted`` or
    ``slots``), and ``order``, the rows of the dataset's entity order
    (:func:`_order_on_device`) where some group is ``sorted``."""
    specs, paths = problem._residual_args(dataset, plan.groups)
    return specs, _order_on_device(dataset, "rows", paths), paths


def score_blocks(problem, dataset, plan: _ScorePlan, specs=None) -> tuple:
    """``(codes, ix, v, *place)`` on the device for each group of ``plan``
    (stacked ``[B, ...]`` for a folded group): the copies ``problem``'s
    bank update holds, and where the group's scores go, its spec of
    ``specs`` (:func:`score_places`) where given, else its
    ``row_index``."""
    blocks = []
    for i, members in enumerate(plan.groups):
        if len(members) > 1:
            codes, ix, v, _, _, _ = problem._stacked_group_args(
                dataset, members, with_residuals=True
            )
        else:
            ix, v, _, _, _, codes = problem._bucket_device_args(
                members[0].bucket
            )
        if specs is not None:
            place = specs[i]
        elif len(members) > 1:
            place = (problem._stacked_rows(dataset, members),)
        else:
            place = (problem._bucket_rows(members[0].bucket),)
        blocks.append((codes, ix, v) + tuple(place))
    return tuple(blocks)


def _place_scores(out, bank, args, identity, path="slots"):
    """``out`` with the rows of one block put in: ``args`` ``(codes, ix,
    v, *place)``, stacked ``[B, E_sub, ...]`` for sub-blocks, which are
    scanned one at a time so that only one's temporaries are live. Whole
    bank rows taken as ``_update_block`` takes them, :func:`score_block`,
    and the ``[E, S]`` scores put in on ``path``: ``slots``, ``place``
    ``(rows,)``, the ``[n]`` ``out`` set by ``rows`` (a padding slot, -1,
    and a padding entity, whose code lies past the bank, drop);
    ``windows`` and ``sorted``, ``place`` ``(starts, counts)``, ``out``
    a vector as lane rows that each entity's run is added into
    (:func:`_add_windows`; a padding entity has no real slot)."""

    def put(out, args):
        codes, ix, v, *place = args
        w = jnp.take(bank, codes, axis=0, mode="fill", fill_value=0)
        score = score_block(w, ix, v, identity)
        if path != "slots":
            return _add_windows(out, score, *place)
        (rows,) = place
        at = jnp.where(rows >= 0, rows, out.shape[0])
        return out.at[at.reshape(-1)].set(score.reshape(-1), mode="drop")

    if args[0].ndim == 2:
        return jax.lax.scan(lambda o, a: (put(o, a), None), out, args)[0]
    return put(out, args)


@partial(jax.jit, static_argnames=("identity", "num_rows", "paths"))
def re_score(bank, blocks, rest, order=None, *, identity, num_rows, paths):
    """One named program (module ``re_score``, scope ``cd.score``) a
    device trace can place. ``blocks``: ``(codes, ix, v, *place)`` of
    each group of solver blocks, stacked ``[B, E_sub, ...]`` for a folded
    group, each scored from the block and placed on its ``(path,
    capacity)`` of ``paths`` (:func:`_place_scores`), which
    the dataset observes as the residual's read does
    (:func:`score_places`): ``windows``, a group whose entities' rows are
    runs of the row vector, adds each run into that vector held as lane
    rows; ``sorted``, a group whose rows are runs of the dataset's entity
    order, adds them into ONE vector in that order, which ONE unstable
    sort by ``order`` (each position's row: unique keys, so a
    permutation) takes back to row order, the rows no such group holds
    coming back as 0; ``slots``, any other, the element scatter by
    ``row_index``. Rows are added, not set, because entities whose runs
    meet share a lane row; the three vectors hold each row's score in one
    of them and zeros in the others, so they are summed. ``rest``: the
    rows no block holds (:class:`_ScorePlan`), gathered an element at a
    time. A row with no entity scores 0. Every row is the element
    scatter's bits, but for the sign of a zero."""
    with jax.named_scope("cd.score"):
        # a run's lane rows reach past the last row by up to a window
        reach = max((c for _, c in paths), default=0) + 2 * _LANES
        lane_rows = -(-(num_rows + reach) // _LANES)
        into = {
            path: jnp.zeros(
                (num_rows,) if path == "slots" else (lane_rows, _LANES),
                jnp.float32,
            )
            for path, _ in paths
        }
        for args, ident, (path, _) in zip(blocks, identity, paths):
            into[path] = _place_scores(into[path], bank, args, ident, path)
        out = into.pop("slots", None)
        for path, rows in into.items():
            placed = rows.reshape(-1)[:num_rows]
            if path == "sorted":
                _, placed = jax.lax.sort(
                    (order, placed), num_keys=1, is_stable=False
                )
            out = placed if out is None else out + placed
        if out is None:
            out = jnp.zeros((num_rows,), jnp.float32)
        if rest is not None:
            rows, codes, valid, ix, v = rest
            score = gather_scores(bank, codes, ix, v)
            if valid is not None:
                score = jnp.where(valid, score, 0.0)
            out = score if rows is None else out.at[rows].set(score)
        return out


@jax.jit
def re_score_passive(scores, bank, rows, codes, ix, v):
    """The passive rows' scores put into ``scores``: one named program
    (module ``re_score_passive``, scope ``cd.score``) apart from
    :func:`re_score`, so that a device trace tells what the rows the
    solves never see cost. A passive row is scored through its entity's
    own map: the row was remapped with the features the map lacks
    dropped, which is the reference's own behaviour (a feature outside an
    entity's map has no coefficient, RandomEffectCoordinate.scala:
    178-199). Per-entity chunks (``rows [C, S]``, or ``[B, C / B, S]``
    in sub-blocks; :func:`_passive_chunks`) are scored from the chunk as
    a solver block's rows are (:func:`_place_scores`); flat rows
    (``rows [n]``, a bank too wide for the compare) by the element
    gather."""
    with jax.named_scope("cd.score"):
        if rows.ndim == 1:
            return scores.at[rows].set(gather_scores(bank, codes, ix, v))
        return _place_scores(scores, bank, (codes, ix, v, rows), False)


def gather_scores(bank, codes, ix, v):
    """``sum_j v[i, j] * bank[codes[i], ix[i, j]]`` by an element gather
    (XLA fuses the row take into it; the [n, D] rows are never
    written): 13-14 ns an element on a v5e (ledger, PR 33), so only for
    rows no solver block or passive chunk holds."""
    w_rows = jnp.take(bank, codes, axis=0)  # [n, D]
    return jnp.sum(v * jnp.take_along_axis(w_rows, ix, axis=1), axis=-1)


def dryrun_entity_bank(mesh) -> None:
    """Tiny entity-sharded vmapped solve for the multi-chip dry run:
    bank rows sharded over the mesh's first axis (expert-parallel analog)."""
    from functools import partial

    from jax.sharding import NamedSharding, PartitionSpec as P
    from photon_ml_tpu.ops.losses import LOGISTIC

    axis = mesh.axis_names[0]
    n_dev = mesh.devices.size
    E, S, K, D = 2 * n_dev, 4, 4, 8
    rng = np.random.default_rng(0)
    solver = _bucket_solver(
        LOGISTIC, OptimizerConfig(max_iter=3), RegularizationContext()
    ).sparse
    sharding = NamedSharding(mesh, P(axis))
    bank = jax.device_put(jnp.zeros((E, D), jnp.float32), sharding)
    args = (
        jax.device_put(jnp.asarray(rng.integers(0, D, size=(E, S, K), dtype=np.int32)), sharding),
        jax.device_put(jnp.asarray(rng.normal(size=(E, S, K)).astype(np.float32)), sharding),
        jax.device_put(jnp.asarray((rng.uniform(size=(E, S)) > 0.5).astype(np.float32)), sharding),
        jax.device_put(jnp.zeros((E, S), jnp.float32), sharding),
        jax.device_put(jnp.ones((E, S), jnp.float32), sharding),
    )
    new_bank, iters, reasons = solver(bank, *args, jnp.float32(0.0), jnp.float32(0.1))
    # numeric oracle, not just finiteness: the sharded solve must equal
    # the same solver on unsharded (single-device) arrays
    host_args = tuple(jax.device_get(a) for a in args)
    oracle_bank, _, _ = solver(
        jnp.zeros((E, D), jnp.float32),
        *(jnp.asarray(a) for a in host_args),
        jnp.float32(0.0), jnp.float32(0.1),
    )
    np.testing.assert_allclose(
        np.asarray(new_bank), np.asarray(oracle_bank), atol=5e-3
    )
