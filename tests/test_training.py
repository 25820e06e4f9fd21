"""Model/problem/training tests: variance estimates, warm-started lambda
grids, normalization invariance, down-samplers.

Mirrors the reference's integration strategy (NormalizationIntegTest's
invariant "training with normalization == training on pre-transformed
data"; DistributedOptimizationProblemIntegTest variance checks) with
validator-style assertions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.batch import make_dense_batch, make_sparse_batch
from photon_ml_tpu.data.sampler import (
    binary_classification_down_sample,
    default_down_sample,
)
from photon_ml_tpu.models import logistic_regression_model
from photon_ml_tpu.ops.normalization import (
    NormalizationType,
    build_normalization,
)
from photon_ml_tpu.optim import OptimizerType, RegularizationType
from photon_ml_tpu.optim.problem import create_glm_problem
from photon_ml_tpu.task import TaskType
from photon_ml_tpu.training import train_generalized_linear_model


def logistic_data(rng, n=512, d=6, intercept=True):
    x = rng.normal(size=(n, d)).astype(np.float32)
    if intercept:
        x[:, -1] = 1.0  # intercept column
    w = rng.normal(size=d).astype(np.float32)
    y = (1 / (1 + np.exp(-x @ w)) > rng.uniform(size=n)).astype(np.float32)
    return x, y


class TestProblem:
    def test_variances_linear_regression(self, rng):
        # For squared loss, H = X^T X (weights 1), so variances ~ 1/diag.
        n, d = 128, 4
        x = rng.normal(size=(n, d)).astype(np.float32)
        y = (x @ np.ones(d)).astype(np.float32)
        batch = make_dense_batch(x, y)
        problem = create_glm_problem(
            TaskType.LINEAR_REGRESSION, d, compute_variances=True
        )
        coefficients, _ = problem.run(batch)
        expect = 1.0 / np.sum(x**2, axis=0)
        np.testing.assert_allclose(
            np.asarray(coefficients.variances), expect, rtol=1e-4
        )

    def test_poisson_trains(self, rng):
        n, d = 4096, 4
        x = (0.3 * rng.normal(size=(n, d))).astype(np.float32)
        w = np.array([0.5, -0.3, 0.2, 0.1], np.float32)
        y = rng.poisson(np.exp(x @ w)).astype(np.float32)
        batch = make_dense_batch(x, y)
        problem = create_glm_problem(TaskType.POISSON_REGRESSION, d)
        coefficients, result = problem.run(batch, reg_weight=1e-3)
        assert np.all(np.isfinite(np.asarray(coefficients.means)))
        np.testing.assert_allclose(np.asarray(coefficients.means), w, atol=0.3)

    def test_svm_rejects_tron(self, rng):
        from photon_ml_tpu.optim import OptimizerConfig

        problem = create_glm_problem(
            TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
            4,
            config=OptimizerConfig(OptimizerType.TRON),
        )
        x, y = logistic_data(rng, n=64, d=4)
        with pytest.raises(ValueError):
            problem.run(make_dense_batch(x, y))

    def test_svm_trains_with_lbfgs(self, rng):
        x, y = logistic_data(rng, n=256, d=5)
        batch = make_dense_batch(x, y)
        problem = create_glm_problem(TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM, 5)
        coefficients, _ = problem.run(batch, reg_weight=0.01)
        model = logistic_regression_model(coefficients)
        pred = np.asarray(model.predict_class(batch))
        w = np.asarray(batch.weights)
        acc = np.sum((pred == np.asarray(batch.labels)) * w) / w.sum()
        assert acc > 0.6


class TestTraining:
    def test_lambda_grid_shrinks_norms(self, rng):
        x, y = logistic_data(rng)
        batch = make_dense_batch(x, y)
        models, results = train_generalized_linear_model(
            batch,
            TaskType.LOGISTIC_REGRESSION,
            6,
            regularization_type=RegularizationType.L2,
            regularization_weights=[0.1, 10.0, 1000.0],
        )
        norms = {
            lam: float(jnp.linalg.norm(m.means)) for lam, m in models.items()
        }
        assert norms[1000.0] < norms[10.0] < norms[0.1]

    def test_warm_start_converges_faster(self, rng):
        x, y = logistic_data(rng)
        batch = make_dense_batch(x, y)
        _, warm = train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, 6,
            regularization_type=RegularizationType.L2,
            regularization_weights=[1.0, 10.0], warm_start=True,
        )
        _, cold = train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, 6,
            regularization_type=RegularizationType.L2,
            regularization_weights=[1.0, 10.0], warm_start=False,
        )
        assert int(warm[1.0].iterations) <= int(cold[1.0].iterations)

    def test_normalization_invariance(self, rng):
        """Training with standardization context == training on
        pre-standardized data (NormalizationIntegTest invariant)."""
        n, d = 256, 5
        x = (rng.normal(size=(n, d)) * np.array([5.0, 0.1, 2.0, 1.0, 1.0])
             + np.array([1.0, -3.0, 0.5, 0.0, 0.0])).astype(np.float32)
        x[:, -1] = 1.0  # intercept
        w = rng.normal(size=d).astype(np.float32)
        y = (1 / (1 + np.exp(-x @ w)) > rng.uniform(size=n)).astype(np.float32)

        mean = x.mean(axis=0)
        std = x.std(axis=0, ddof=0)
        norm = build_normalization(
            NormalizationType.STANDARDIZATION,
            mean=mean, std=std, max_magnitude=np.abs(x).max(axis=0),
            intercept_index=d - 1,
        )
        batch_raw = make_dense_batch(x, y)
        models_norm, _ = train_generalized_linear_model(
            batch_raw, TaskType.LOGISTIC_REGRESSION, d,
            regularization_weights=[0.0], normalization=norm,
            intercept_index=d - 1,
        )
        # Manually transformed data (intercept col untouched).
        x2 = (x - mean) / np.where(std > 0, std, 1.0)
        x2[:, -1] = 1.0
        models_pre, _ = train_generalized_linear_model(
            make_dense_batch(x2.astype(np.float32), y),
            TaskType.LOGISTIC_REGRESSION, d, regularization_weights=[0.0],
        )
        # models_norm is already back in original space; map the
        # pre-transformed model back by hand to compare.
        w_pre = np.asarray(models_pre[0.0].means)
        factor = 1.0 / np.where(std > 0, std, 1.0)
        w_back = w_pre * factor
        w_back[-1] = w_pre[-1] - np.sum((mean * factor)[:-1] * w_pre[:-1])
        np.testing.assert_allclose(
            np.asarray(models_norm[0.0].means), w_back, atol=2e-2
        )


class TestSamplers:
    def test_binary_keeps_positives(self, rng):
        x, y = logistic_data(rng, n=200, d=4)
        batch = make_dense_batch(x, y)
        key = jax.random.PRNGKey(0)
        out = binary_classification_down_sample(key, batch, 0.3)
        w = np.asarray(out.weights)
        lab = np.asarray(batch.labels)
        orig_w = np.asarray(batch.weights)
        # positives untouched
        np.testing.assert_allclose(w[lab > 0.5], orig_w[lab > 0.5])
        # kept negatives rescaled by 1/rate
        kept_neg = (lab <= 0.5) & (w > 0) & (orig_w > 0)
        np.testing.assert_allclose(w[kept_neg], orig_w[kept_neg] / 0.3)
        # expected weight mass approximately preserved
        assert w[lab <= 0.5].sum() == pytest.approx(
            orig_w[lab <= 0.5].sum(), rel=0.35
        )

    def test_default_unbiased_mass(self, rng):
        x, y = logistic_data(rng, n=400, d=4)
        batch = make_dense_batch(x, y)
        out = default_down_sample(jax.random.PRNGKey(1), batch, 0.5)
        assert float(np.asarray(out.weights).sum()) == pytest.approx(
            float(np.asarray(batch.weights).sum()), rel=0.2
        )


class TestKernelSwitch:
    """The tiled/scatter kernel switch must not change training results
    (task 'single construction switch' — optim.problem.create_glm_problem)."""

    def test_tiled_training_matches_scatter(self, rng):
        import numpy as np
        import jax.numpy as jnp
        from photon_ml_tpu.data.batch import make_sparse_batch
        from photon_ml_tpu.task import TaskType
        from photon_ml_tpu.training import train_generalized_linear_model

        n, d, k = 120, 40, 5
        rows, labels = [], []
        w_true = rng.normal(size=d)
        for _ in range(n):
            ix = rng.choice(d, size=k, replace=False)
            vs = rng.normal(size=k)
            z = float((w_true[ix] * vs).sum())
            labels.append(float(rng.uniform() < 1 / (1 + np.exp(-z))))
            rows.append((ix.tolist(), vs.tolist()))
        batch = make_sparse_batch(rows, labels)

        kwargs = dict(
            regularization_weights=[1.0, 0.1],
            max_iter=25,
        )
        m_sc, _ = train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, d, kernel="scatter", **kwargs
        )
        m_ti, _ = train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, d, kernel="tiled", **kwargs
        )
        for lam in m_sc:
            # bf16x2 gradient noise (~1e-5/eval) compounds over the L-BFGS
            # trajectory; solutions agree to ~0.2% relative, which is well
            # inside statistical noise for a fitted GLM.
            np.testing.assert_allclose(
                np.asarray(m_ti[lam].coefficients.means),
                np.asarray(m_sc[lam].coefficients.means),
                rtol=0.02, atol=1e-2,
            )

    def test_auto_resolves_scatter_on_cpu(self):
        from photon_ml_tpu.optim.problem import resolve_kernel

        assert resolve_kernel("auto") == "scatter"  # tests run on CPU
        assert resolve_kernel("tiled") == "tiled"
        assert resolve_kernel("scatter") == "scatter"

    def test_auto_resolves_tiled_for_a_tiled_batch(self, rng):
        """Only the tiled objective can read a TiledSparseBatch, so
        "auto" picks it on any platform (interpreted on the CPU) and the
        fit runs; "auto" used to pick scatter here and the fit failed."""
        import jax.numpy as jnp
        from photon_ml_tpu.data.batch import make_sparse_batch
        from photon_ml_tpu.ops.tiled_sparse import TileParams, ensure_tiled
        from photon_ml_tpu.optim.config import OptimizerConfig
        from photon_ml_tpu.optim.problem import (
            create_glm_problem,
            resolve_kernel,
        )
        from photon_ml_tpu.task import TaskType

        d = 24
        rows = [
            (rng.choice(d, size=3, replace=False).tolist(), [1.0, -1.0, 0.5])
            for _ in range(32)
        ]
        labels = (rng.uniform(size=32) > 0.5).astype(float).tolist()
        sparse = make_sparse_batch(rows, labels)
        tiled = ensure_tiled(
            sparse, d, params=TileParams(s_hi=8, s_lo=8, chunk=128)
        )
        assert resolve_kernel("auto", sparse) == "scatter"  # CPU
        assert resolve_kernel("auto", tiled) == "tiled"
        problem = create_glm_problem(
            TaskType.LOGISTIC_REGRESSION, d,
            config=OptimizerConfig(max_iter=2),
            kernel=resolve_kernel("auto", tiled),
        )
        coef, result = problem.run(tiled)
        assert int(result.iterations) >= 1
        assert bool(jnp.all(jnp.isfinite(coef.means)))
