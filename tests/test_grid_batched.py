"""Batched λ-grid training (ISSUE 5): the vmapped grid engine against the
warm-started sequential path.

Pins the contract, not just the happy path:
- per-λ parity with the sequential trainer within the measured fp32
  envelopes (rtol 2e-3 class for the LBFGS family, tighter for TRON),
  on both the scatter and tiled kernels;
- active-mask freeze semantics — a converged member's state is
  BIT-stable while stragglers run on;
- one compiled program serves any same-shape grid (0 re-lowerings) and
  the whole grid's result scalars come back in ONE counted readback;
- the --grid-mode auto policy's memory-budget / streaming fallbacks;
- the feature-sharded grid twin on the (data, model) mesh.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu import training
from photon_ml_tpu.data.batch import SparseBatch
from photon_ml_tpu.optim.config import (
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.optim.problem import create_glm_problem
from photon_ml_tpu.parallel import overlap
from photon_ml_tpu.task import TaskType

LAMBDAS = [10.0, 1.0, 0.1, 0.01]


def _synth_batch(rng, n=500, d=48, k=6, weighted=False, offsets=False):
    indices = rng.integers(0, d, size=(n, k)).astype(np.int32)
    values = rng.normal(size=(n, k)).astype(np.float32)
    labels = (rng.uniform(size=n) > 0.5).astype(np.float32)
    return SparseBatch(
        indices=jnp.asarray(indices),
        values=jnp.asarray(values),
        labels=jnp.asarray(labels),
        offsets=jnp.asarray(
            rng.normal(size=n).astype(np.float32) * 0.1
            if offsets else np.zeros(n, np.float32)
        ),
        weights=jnp.asarray(
            rng.uniform(0.5, 2.0, size=n).astype(np.float32)
            if weighted else np.ones(n, np.float32)
        ),
    )


def _assert_grid_parity(r_seq, r_bat, *, value_rtol, coef_atol):
    for lam in r_seq:
        vs, vb = float(r_seq[lam].value), float(r_bat[lam].value)
        assert vb == pytest.approx(vs, rel=value_rtol), lam
        np.testing.assert_allclose(
            np.asarray(r_bat[lam].coefficients),
            np.asarray(r_seq[lam].coefficients),
            atol=coef_atol,
            err_msg=f"lambda={lam}",
        )


class TestGridParityScatter:
    @pytest.mark.parametrize(
        "opt,reg,alpha",
        [
            (OptimizerType.LBFGS, RegularizationType.L2, None),
            (OptimizerType.TRON, RegularizationType.L2, None),
            (OptimizerType.LBFGS, RegularizationType.ELASTIC_NET, 0.5),
        ],
    )
    def test_matches_cold_sequential_exactly(self, rng, opt, reg, alpha):
        """Against the UN-warm-started sequential path the batched grid
        walks the same per-member iterate sequence — near-exact (the only
        noise is vmap's fused-reduction ordering)."""
        batch = _synth_batch(rng, weighted=True, offsets=True)
        kw = dict(
            optimizer_type=opt, regularization_type=reg,
            regularization_weights=LAMBDAS, elastic_net_alpha=alpha,
        )
        _, r_seq = training.train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, 48, warm_start=False, **kw
        )
        _, r_bat = training.train_grid_batched(
            batch, TaskType.LOGISTIC_REGRESSION, 48, **kw
        )
        # values effectively exact; coefficients see the fp32 reorder
        # noise amplified through line-search branch points (the round-8
        # "~1e-4 relative" class — atol 1e-3 is the seed-safe margin)
        _assert_grid_parity(
            r_seq, r_bat, value_rtol=1e-5, coef_atol=1e-3
        )

    def test_matches_warm_sequential_within_envelope(self, rng):
        """Against the DEFAULT warm-started sequential path both land on
        the same per-λ optimum, reached along different iterate paths —
        the rtol-2e-3-class LBFGS envelope."""
        batch = _synth_batch(rng)
        kw = dict(
            regularization_type=RegularizationType.L2,
            regularization_weights=LAMBDAS,
        )
        _, r_seq = training.train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, 48, warm_start=True, **kw
        )
        _, r_bat = training.train_grid_batched(
            batch, TaskType.LOGISTIC_REGRESSION, 48, **kw
        )
        _assert_grid_parity(r_seq, r_bat, value_rtol=2e-3, coef_atol=5e-3)

    def test_tron_matches_warm_sequential_tight(self, rng):
        """TRON's trust-region walk is insensitive to the start point on
        these convex fits — tighter envelope than the LBFGS class."""
        batch = _synth_batch(rng)
        kw = dict(
            optimizer_type=OptimizerType.TRON,
            regularization_type=RegularizationType.L2,
            regularization_weights=LAMBDAS,
        )
        _, r_seq = training.train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, 48, warm_start=True, **kw
        )
        _, r_bat = training.train_grid_batched(
            batch, TaskType.LOGISTIC_REGRESSION, 48, **kw
        )
        _assert_grid_parity(r_seq, r_bat, value_rtol=1e-4, coef_atol=1e-3)

    def test_models_box_and_normalization_broadcast(self, rng):
        """Box constraints, normalization (shift/factor) and offsets all
        broadcast across the grid axis: batched models equal the cold
        sequential models in the ORIGINAL feature space."""
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.optim.common import BoxConstraints

        d = 48
        batch = _synth_batch(rng, d=d, offsets=True)
        norm = NormalizationContext(
            factor=jnp.asarray(
                rng.uniform(0.5, 2.0, size=d).astype(np.float32)
            ),
            shift=jnp.asarray(
                rng.normal(size=d).astype(np.float32) * 0.1
            ),
        )
        box = BoxConstraints(
            lower=jnp.full((d,), -0.3, jnp.float32),
            upper=jnp.full((d,), 0.3, jnp.float32),
        )
        kw = dict(
            regularization_type=RegularizationType.L2,
            regularization_weights=LAMBDAS,
            normalization=norm, box=box, compute_variances=True,
        )
        m_seq, _ = training.train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, d, warm_start=False, **kw
        )
        m_bat, _ = training.train_grid_batched(
            batch, TaskType.LOGISTIC_REGRESSION, d, **kw
        )
        for lam in LAMBDAS:
            np.testing.assert_allclose(
                np.asarray(m_bat[lam].coefficients.means),
                np.asarray(m_seq[lam].coefficients.means),
                atol=1e-4, err_msg=f"lambda={lam}",
            )
            np.testing.assert_allclose(
                np.asarray(m_bat[lam].coefficients.variances),
                np.asarray(m_seq[lam].coefficients.variances),
                rtol=1e-3, err_msg=f"lambda={lam}",
            )


class TestGridParityTiled:
    def test_tiled_kernel_matches_sequential(self, rng):
        """The tiled kernel's grid path (one fused schedule walk for the
        whole grid via the custom_vmap rule) against the sequential tiled
        fits — the bf16x2w-vs-exact-f32 pass difference bounds the drift
        (~1e-5 relative, the documented mxu envelope)."""
        from photon_ml_tpu.ops.tiled_sparse import (
            TileParams,
            tiled_batch_from_sparse,
        )

        d = 90
        batch = _synth_batch(rng, n=300, d=d)
        tb = tiled_batch_from_sparse(
            batch, d, params=TileParams(s_hi=8, s_lo=8, chunk=32)
        )
        kw = dict(
            regularization_type=RegularizationType.L2,
            regularization_weights=[1.0, 0.1],
            kernel="tiled",
        )
        _, r_seq = training.train_generalized_linear_model(
            tb, TaskType.LOGISTIC_REGRESSION, d, warm_start=False, **kw
        )
        _, r_bat = training.train_grid_batched(
            tb, TaskType.LOGISTIC_REGRESSION, d, **kw
        )
        _assert_grid_parity(r_seq, r_bat, value_rtol=2e-3, coef_atol=5e-3)


class TestFreezeSemantics:
    def test_converged_member_is_bit_stable(self, rng):
        """Active-mask freeze: once a member converges, later while_loop
        trips (driven by the stragglers) must not move it AT ALL. Two
        runs whose only difference is how long the stragglers run must
        agree BITWISE on the early-converged member."""
        batch = _synth_batch(rng)
        problem_short = create_glm_problem(
            TaskType.LOGISTIC_REGRESSION, 48,
            config=OptimizerConfig(
                optimizer_type=OptimizerType.LBFGS, max_iter=10,
                tolerance=1e-9,
            ),
            regularization=RegularizationContext(RegularizationType.L2),
        )
        problem_long = create_glm_problem(
            TaskType.LOGISTIC_REGRESSION, 48,
            config=OptimizerConfig(
                optimizer_type=OptimizerType.LBFGS, max_iter=60,
                tolerance=1e-9,
            ),
            regularization=RegularizationContext(RegularizationType.L2),
        )
        # member 0: heavy regularization, converges in a few trips;
        # member 1: near-unregularized at a tight tolerance — the
        # straggler that keeps the batched loop running
        grid = [1000.0, 1e-6]
        _, r_short = problem_short.run_grid(batch, grid)
        _, r_long = problem_long.run_grid(batch, grid)
        it0 = int(r_short.iterations[0])
        assert it0 < 10, "fast member unexpectedly slow"
        assert int(r_long.iterations[1]) > it0, (
            "straggler should out-iterate the fast member"
        )
        # fast member froze at the same trip in both programs: bitwise
        # identical state even though the long run kept looping
        assert int(r_long.iterations[0]) == it0
        assert np.array_equal(
            np.asarray(r_short.coefficients[0]),
            np.asarray(r_long.coefficients[0]),
        ), "converged member's coefficients moved after convergence"
        assert float(r_short.value[0]) == float(r_long.value[0])
        assert int(r_short.reason[0]) == int(r_long.reason[0])


class TestCompileAndReadbackContract:
    def test_one_program_serves_any_same_shape_grid(self, rng):
        """The λ vector is a TRACED argument: after the first grid solve
        compiles, a different grid of the same shape re-lowers NOTHING
        (0 jit lowerings) — the 1-compile-for-the-whole-grid contract."""
        import jax._src.test_util as jtu

        batch = _synth_batch(rng)
        problem = create_glm_problem(
            TaskType.LOGISTIC_REGRESSION, 48,
            config=OptimizerConfig(optimizer_type=OptimizerType.LBFGS),
            regularization=RegularizationContext(RegularizationType.L2),
        )
        problem.run_grid(batch, LAMBDAS)  # compile once
        with jtu.count_jit_and_pmap_lowerings() as count:
            _, result = problem.run_grid(batch, [5.0, 0.5, 0.05, 2.0])
        assert count() == 0, (
            f"same-shape grid re-lowered {count()} program(s)"
        )
        assert result.coefficients.shape == (4, 48)

    def test_whole_grid_is_one_batched_readback(self, rng):
        """run_grid leaves every scalar device-resident (0 readbacks);
        grid_result_scalars then materializes the WHOLE grid in exactly
        ONE counted overlap.device_get."""
        batch = _synth_batch(rng)
        models, results = training.train_grid_batched(
            batch, TaskType.LOGISTIC_REGRESSION, 48,
            regularization_type=RegularizationType.L2,
            regularization_weights=LAMBDAS,
        )
        overlap.reset_readback_stats()
        scalars = training.grid_result_scalars(results)
        assert overlap.readback_stats() == 1
        assert set(scalars) == set(LAMBDAS)
        for lam, (iters, value, reason, evaluations) in scalars.items():
            assert iters >= 1 and np.isfinite(value) and reason != 0
            assert evaluations >= iters + 1  # the one at w0 included


class TestGridModePolicy:
    def test_resolve_modes(self):
        rgm = training.resolve_grid_mode
        common = dict(num_weights=4, dim=1000)
        assert rgm("sequential", **common) == "sequential"
        assert rgm("batched", **common) == "batched"
        assert rgm("auto", **common) == "batched"
        # single-member grids have nothing to batch
        assert rgm("auto", num_weights=1, dim=1000) == "sequential"
        # budget fallback: the G x d state bank exceeds the budget
        assert rgm(
            "auto", num_weights=4, dim=1 << 20,
            memory_budget_bytes=1 << 20,
        ) == "sequential"
        bank = training.grid_bank_bytes(4, 1 << 20)
        assert rgm(
            "auto", num_weights=4, dim=1 << 20,
            memory_budget_bytes=bank,
        ) == "batched"
        # streaming: auto falls back, explicit batched is an error
        assert rgm("auto", streaming=True, **common) == "sequential"
        with pytest.raises(ValueError, match="streaming"):
            rgm("batched", streaming=True, **common)
        with pytest.raises(ValueError, match="unknown grid mode"):
            rgm("eager", **common)

    def test_tron_bank_is_smaller_than_lbfgs(self):
        assert training.grid_bank_bytes(
            4, 1000, OptimizerType.TRON
        ) < training.grid_bank_bytes(4, 1000, OptimizerType.LBFGS)


class TestFeatureShardedGrid:
    @pytest.mark.parametrize(
        "opt,reg,alpha",
        [
            (OptimizerType.LBFGS, RegularizationType.L2, None),
            (OptimizerType.TRON, RegularizationType.L2, None),
            (OptimizerType.LBFGS, RegularizationType.ELASTIC_NET, 0.5),
        ],
    )
    def test_matches_cold_sequential(self, rng, opt, reg, alpha):
        """The shard_map(vmap(optimizer)) twin on the (data, model) mesh
        against the sequential feature-sharded sweep (cold starts)."""
        from photon_ml_tpu.parallel.mesh import (
            DATA_AXIS,
            MODEL_AXIS,
            make_mesh,
        )

        batch = _synth_batch(rng, n=320, d=56)
        mesh = make_mesh((4, 2), (DATA_AXIS, MODEL_AXIS))
        kw = dict(
            mesh=mesh, regularization_type=reg, elastic_net_alpha=alpha,
            regularization_weights=[1.0, 0.1, 10.0], optimizer_type=opt,
        )
        _, r_seq = training.train_feature_sharded(
            batch, TaskType.LOGISTIC_REGRESSION, 56, warm_start=False, **kw
        )
        _, r_bat = training.train_grid_batched_feature_sharded(
            batch, TaskType.LOGISTIC_REGRESSION, 56, **kw
        )
        _assert_grid_parity(r_seq, r_bat, value_rtol=1e-5, coef_atol=1e-3)
