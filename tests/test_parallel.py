"""Distributed-path tests on the virtual 8-device CPU mesh: data-parallel
fit == single-device fit (``GLMOptimizationProblem.run(mesh=)``),
feature-axis sharding exactness (``feature_sharded_glm_fit``): the
multi-chip paths the drivers run and ``__graft_entry__`` dry-runs.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import make_sparse_batch
from photon_ml_tpu.ops.losses import LOGISTIC
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim import minimize_lbfgs
from photon_ml_tpu.optim.config import (
    OptimizerConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.optim.problem import create_glm_problem
from photon_ml_tpu.parallel import (
    DATA_AXIS,
    MODEL_AXIS,
    make_mesh,
)
from photon_ml_tpu.task import TaskType


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    return make_mesh((8,), (DATA_AXIS,))


@pytest.fixture(scope="module")
def mesh4x2():
    return make_mesh((4, 2), (DATA_AXIS, MODEL_AXIS))


def sparse_problem(rng, n=256, d=32, k=8):
    rows = []
    labels = []
    w_true = rng.normal(size=d).astype(np.float32)
    for _ in range(n):
        ix = rng.choice(d, size=k, replace=False)
        vs = rng.normal(size=k).astype(np.float32)
        z = float(np.sum(w_true[ix] * vs))
        labels.append(float(1 / (1 + np.exp(-z)) > rng.uniform()))
        rows.append((ix.tolist(), vs.tolist()))
    return make_sparse_batch(rows, labels, pad_rows_to=8), w_true


def l2_problem(d, max_iter):
    """The problem ``glm_driver`` builds: logistic, L-BFGS, L2."""
    return create_glm_problem(
        TaskType.LOGISTIC_REGRESSION, d,
        config=OptimizerConfig(max_iter=max_iter),
        regularization=RegularizationContext(RegularizationType.L2),
    )


class TestDataParallel:
    """``GLMOptimizationProblem.run(mesh=)``: the whole fit inside one
    shard_map program over the data axis, the path ``glm_driver`` runs."""

    def test_matches_single_device(self, mesh8, rng):
        # one iteration from a non-zero start: the value at the start is
        # the objective's value there, and the step taken is along its
        # gradient, so both are compared coordinate by coordinate
        batch, _ = sparse_problem(rng)
        d = 32
        problem = l2_problem(d, max_iter=1)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        _, local = problem.run(batch, initial=w, reg_weight=0.1)
        _, dist = problem.run(batch, initial=w, reg_weight=0.1, mesh=mesh8)
        assert int(dist.iterations) == int(local.iterations) == 1
        np.testing.assert_allclose(
            np.asarray(dist.tracker.values), np.asarray(local.tracker.values),
            rtol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(dist.tracker.grad_norms),
            np.asarray(local.tracker.grad_norms), rtol=1e-4,
        )
        np.testing.assert_allclose(
            np.asarray(dist.coefficients - w),
            np.asarray(local.coefficients - w), atol=1e-4,
        )
        assert float(jnp.linalg.norm(local.coefficients - w)) > 1e-3

    def test_whole_fit_in_shard_map(self, mesh8, rng):
        batch, _ = sparse_problem(rng)
        d = 32
        obj = GLMObjective(LOGISTIC, d)
        _, res = l2_problem(d, max_iter=50).run(
            batch, reg_weight=0.1, mesh=mesh8
        )
        local = minimize_lbfgs(
            lambda w: obj.value_and_gradient(w, batch, 0.1),
            jnp.zeros(d), max_iter=50,
        )
        np.testing.assert_allclose(
            np.asarray(res.coefficients), np.asarray(local.coefficients),
            atol=5e-3,
        )


class TestEntityAllToAll:
    """The shuffle analog: re-key rows to entity-owning devices in-jit."""

    def test_round_trip_lossless(self, mesh8, rng):
        from photon_ml_tpu.parallel.shuffle import (
            entity_all_to_all,
            reshard_capacity,
        )

        n, n_dev, k = 256, 8, 4
        codes = rng.integers(0, 40, size=n).astype(np.int32)
        codes[::17] = -1  # padding rows sprinkled in
        values = rng.normal(size=n).astype(np.float32)
        feats = rng.normal(size=(n, k)).astype(np.float32)
        cap = reshard_capacity(codes, n_dev)
        out = entity_all_to_all(
            mesh8,
            jnp.asarray(codes),
            {"v": jnp.asarray(values), "x": jnp.asarray(feats)},
            cap=cap,
        )
        assert int(np.asarray(out.dropped).sum()) == 0
        real = codes >= 0
        assert int(np.asarray(out.received).sum()) == int(real.sum())
        out_codes = np.asarray(out.entity_codes)
        out_v = np.asarray(out.payload["v"])
        got = out_codes >= 0
        # multiset of (code, value) pairs survives the re-shard
        sent = sorted(zip(codes[real].tolist(), values[real].tolist()))
        recv = sorted(zip(out_codes[got].tolist(), out_v[got].tolist()))
        assert sent == recv
        # each device block holds only entities it owns (code % n_dev)
        per_dev = out_codes.reshape(n_dev, -1)
        for d in range(n_dev):
            owned = per_dev[d][per_dev[d] >= 0]
            assert np.all(owned % n_dev == d)
        # payload rows stay aligned with their codes
        out_x = np.asarray(out.payload["x"])
        code_to_row = {}
        for i in range(n):
            if real[i]:
                code_to_row.setdefault(
                    (codes[i], round(float(values[i]), 5)), feats[i]
                )
        for j in np.nonzero(got)[0][:20]:
            key = (out_codes[j], round(float(out_v[j]), 5))
            np.testing.assert_allclose(out_x[j], code_to_row[key], rtol=1e-6)

    def test_overflow_is_reported(self, mesh8, rng):
        from photon_ml_tpu.parallel.shuffle import entity_all_to_all

        n = 64
        codes = np.zeros(n, np.int32)  # every row -> device 0
        out = entity_all_to_all(
            mesh8,
            jnp.asarray(codes),
            {"v": jnp.ones(n, jnp.float32)},
            cap=8,  # each source may send only 8 rows to device 0
        )
        # 8 sources x 8 rows each = 64 slots but only 8 rows per source fit
        assert int(np.asarray(out.received).sum()) == n - int(
            np.asarray(out.dropped).sum()
        )
        assert int(np.asarray(out.dropped).sum()) == 0  # 8 rows/src fit cap
        out2 = entity_all_to_all(
            mesh8,
            jnp.asarray(codes),
            {"v": jnp.ones(n, jnp.float32)},
            cap=4,
        )
        assert int(np.asarray(out2.dropped).sum()) == n - 8 * 4


class TestFeatureSharded:
    def test_sparse_sharded_fit_matches_replicated(self, mesh4x2, rng):
        from photon_ml_tpu.parallel import feature_shard_sparse_batch
        from photon_ml_tpu.parallel.distributed import (
            feature_sharded_glm_fit,
        )

        # d chosen NOT to divide into equal blocks so d_pad > d and the
        # padded-slot assertion below is non-vacuous.
        batch, _ = sparse_problem(rng, n=128, d=45, k=8)
        d = 45
        obj = GLMObjective(LOGISTIC, d)
        sharded, block_dim = feature_shard_sparse_batch(
            batch, d, num_blocks=2, rows_multiple=4
        )
        d_pad = 2 * block_dim
        assert d_pad > d
        fit = feature_sharded_glm_fit(
            obj, mesh4x2, layout="sparse", optimizer="lbfgs", max_iter=50
        )
        res = fit(jnp.zeros(d_pad), sharded, jnp.float32(0.1))
        local = minimize_lbfgs(
            lambda w_: obj.value_and_gradient(w_, batch, 0.1),
            jnp.zeros(d), max_iter=50,
        )
        np.testing.assert_allclose(
            np.asarray(res.coefficients)[:d],
            np.asarray(local.coefficients), atol=5e-3,
        )
        # Padded vocabulary slots never see data => exactly zero.
        np.testing.assert_array_equal(np.asarray(res.coefficients)[d:], 0.0)

    def test_sparse_sharded_owlqn_matches_replicated(self, mesh4x2, rng):
        from photon_ml_tpu.optim.lbfgs import minimize_owlqn
        from photon_ml_tpu.parallel import feature_shard_sparse_batch
        from photon_ml_tpu.parallel.distributed import (
            feature_sharded_glm_fit,
        )

        batch, _ = sparse_problem(rng, n=128, d=45, k=8)
        d = 45
        obj = GLMObjective(LOGISTIC, d)
        sharded, block_dim = feature_shard_sparse_batch(
            batch, d, num_blocks=2, rows_multiple=4
        )
        fit = feature_sharded_glm_fit(
            obj, mesh4x2, layout="sparse", optimizer="owlqn", max_iter=50
        )
        res = fit(
            jnp.zeros(2 * block_dim), sharded,
            jnp.float32(0.05), jnp.float32(0.2),
            jnp.ones(2 * block_dim, jnp.float32),
        )
        local = minimize_owlqn(
            lambda w_: obj.value_and_gradient(w_, batch, 0.05),
            jnp.zeros(d), 0.2, max_iter=50,
        )
        np.testing.assert_allclose(
            np.asarray(res.coefficients)[:d],
            np.asarray(local.coefficients), atol=5e-3,
        )
        # L1 must produce sparsity, identically in both runs
        assert (np.asarray(res.coefficients)[:d] == 0).sum() == (
            np.asarray(local.coefficients) == 0
        ).sum()

    def test_sparse_sharded_value_and_grad_exact(self, mesh4x2, rng):
        from photon_ml_tpu.parallel import feature_shard_sparse_batch
        from photon_ml_tpu.parallel.distributed import (
            feature_sharded_sparse_value_and_grad,
        )

        batch, _ = sparse_problem(rng, n=64, d=40, k=8)
        d = 40
        obj = GLMObjective(LOGISTIC, d)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v_local, g_local = obj.value_and_gradient(w, batch, 0.2)
        sharded, block_dim = feature_shard_sparse_batch(
            batch, d, num_blocks=2, rows_multiple=4
        )
        w_pad = jnp.zeros(2 * block_dim).at[:d].set(w)
        vg = feature_sharded_sparse_value_and_grad(obj, mesh4x2)
        v, g = vg(w_pad, sharded, jnp.float32(0.2))
        np.testing.assert_allclose(float(v), float(v_local), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(g)[:d], np.asarray(g_local), atol=1e-4
        )


class TestFeatureShardedCompositions:
    """The reference composes normalization, variances, box constraints
    and per-iteration model tracking freely with distribution
    (NormalizationContext.scala:119-157, DistributedOptimizationProblem
    .scala:79-93, LBFGS.scala:77, Driver.scala:329-372); each combination
    must match the replicated path exactly (fp32 noise only)."""

    def _problem(self, rng, n=128, d=45, k=8):
        batch, _ = sparse_problem(rng, n=n, d=d, k=k)
        return batch, d

    def _norm(self, batch, d):
        from photon_ml_tpu.data.stats import compute_summary
        from photon_ml_tpu.ops.normalization import (
            NormalizationType,
            build_normalization,
        )

        s = compute_summary(batch, d)
        return build_normalization(
            NormalizationType.STANDARDIZATION,
            mean=s.mean, std=s.std, max_magnitude=s.max_magnitude,
        )

    @pytest.mark.parametrize("kernel", ["scatter", "tiled"])
    def test_normalization_matches_replicated(self, mesh4x2, rng, kernel):
        from photon_ml_tpu.task import TaskType
        from photon_ml_tpu.training import (
            train_feature_sharded,
            train_generalized_linear_model,
        )
        from photon_ml_tpu.optim import RegularizationType

        batch, d = self._problem(rng)
        norm = self._norm(batch, d)
        kwargs = dict(
            regularization_type=RegularizationType.L2,
            regularization_weights=[0.5], max_iter=40,
        )
        m_rep, _ = train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, d, normalization=norm,
            kernel="scatter", **kwargs,
        )
        m_sh, _ = train_feature_sharded(
            batch, TaskType.LOGISTIC_REGRESSION, d, mesh=mesh4x2,
            normalization=norm, kernel=kernel, **kwargs,
        )
        np.testing.assert_allclose(
            np.asarray(m_sh[0.5].means), np.asarray(m_rep[0.5].means),
            atol=5e-3,
        )

    def test_box_matches_replicated(self, mesh4x2, rng):
        from photon_ml_tpu.optim.common import BoxConstraints
        from photon_ml_tpu.task import TaskType
        from photon_ml_tpu.training import (
            train_feature_sharded,
            train_generalized_linear_model,
        )
        from photon_ml_tpu.optim import RegularizationType

        batch, d = self._problem(rng)
        box = BoxConstraints(
            lower=jnp.full((d,), -0.2, jnp.float32),
            upper=jnp.full((d,), 0.2, jnp.float32),
        )
        kwargs = dict(
            regularization_type=RegularizationType.L2,
            regularization_weights=[0.1], max_iter=40,
        )
        m_rep, _ = train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, d, box=box,
            kernel="scatter", **kwargs,
        )
        m_sh, _ = train_feature_sharded(
            batch, TaskType.LOGISTIC_REGRESSION, d, mesh=mesh4x2,
            box=box, kernel="scatter", **kwargs,
        )
        w = np.asarray(m_sh[0.1].means)
        assert np.all(w >= -0.2 - 1e-6) and np.all(w <= 0.2 + 1e-6)
        # the box must actually bind somewhere or this test is vacuous
        assert np.any(np.isclose(np.abs(w), 0.2, atol=1e-4))
        np.testing.assert_allclose(
            w, np.asarray(m_rep[0.1].means), atol=5e-3
        )

    @pytest.mark.parametrize("kernel", ["scatter", "tiled"])
    def test_variances_match_replicated(self, mesh4x2, rng, kernel):
        from photon_ml_tpu.task import TaskType
        from photon_ml_tpu.training import (
            train_feature_sharded,
            train_generalized_linear_model,
        )
        from photon_ml_tpu.optim import RegularizationType

        batch, d = self._problem(rng)
        kwargs = dict(
            regularization_type=RegularizationType.L2,
            regularization_weights=[1.0], max_iter=40,
        )
        m_rep, _ = train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, d,
            compute_variances=True, kernel="scatter", **kwargs,
        )
        m_sh, _ = train_feature_sharded(
            batch, TaskType.LOGISTIC_REGRESSION, d, mesh=mesh4x2,
            compute_variances=True, kernel=kernel, **kwargs,
        )
        assert m_sh[1.0].coefficients.variances is not None
        np.testing.assert_allclose(
            np.asarray(m_sh[1.0].coefficients.variances),
            np.asarray(m_rep[1.0].coefficients.variances), rtol=2e-3,
        )

    def test_track_models_matches_replicated(self, mesh4x2, rng):
        from photon_ml_tpu.task import TaskType
        from photon_ml_tpu.training import (
            train_feature_sharded,
            train_generalized_linear_model,
        )
        from photon_ml_tpu.optim import RegularizationType

        batch, d = self._problem(rng)
        kwargs = dict(
            regularization_type=RegularizationType.L2,
            regularization_weights=[0.5], max_iter=10,
        )
        _, r_rep = train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, d, track_models=True,
            kernel="scatter", **kwargs,
        )
        _, r_sh = train_feature_sharded(
            batch, TaskType.LOGISTIC_REGRESSION, d, mesh=mesh4x2,
            track_models=True, kernel="scatter", **kwargs,
        )
        rep, sh = r_rep[0.5], r_sh[0.5]
        assert sh.tracker.coefs is not None
        n_rep = int(rep.tracker.count)
        assert int(sh.tracker.count) == n_rep
        np.testing.assert_allclose(
            np.asarray(sh.tracker.coefs)[:n_rep],
            np.asarray(rep.tracker.coefs)[:n_rep], atol=5e-3,
        )

    def test_tron_normalization_matches_replicated(self, mesh4x2, rng):
        from photon_ml_tpu.optim import OptimizerType, RegularizationType
        from photon_ml_tpu.task import TaskType
        from photon_ml_tpu.training import (
            train_feature_sharded,
            train_generalized_linear_model,
        )

        batch, d = self._problem(rng)
        norm = self._norm(batch, d)
        kwargs = dict(
            optimizer_type=OptimizerType.TRON,
            regularization_type=RegularizationType.L2,
            regularization_weights=[1.0], max_iter=15,
        )
        m_rep, _ = train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, d, normalization=norm,
            kernel="scatter", **kwargs,
        )
        m_sh, _ = train_feature_sharded(
            batch, TaskType.LOGISTIC_REGRESSION, d, mesh=mesh4x2,
            normalization=norm, kernel="scatter", **kwargs,
        )
        np.testing.assert_allclose(
            np.asarray(m_sh[1.0].means), np.asarray(m_rep[1.0].means),
            atol=5e-3,
        )

    def test_owlqn_box_norm_composed(self, mesh4x2, rng):
        # the full stack at once: elastic-net OWL-QN + box + intercept
        # exemption on the sharded path, vs the replicated problem layer
        from photon_ml_tpu.optim.common import BoxConstraints
        from photon_ml_tpu.task import TaskType
        from photon_ml_tpu.training import (
            train_feature_sharded,
            train_generalized_linear_model,
        )
        from photon_ml_tpu.optim import RegularizationType

        batch, d = self._problem(rng)
        box = BoxConstraints(
            lower=jnp.full((d,), -0.3, jnp.float32),
            upper=jnp.full((d,), 0.3, jnp.float32),
        )
        kwargs = dict(
            regularization_type=RegularizationType.ELASTIC_NET,
            elastic_net_alpha=0.5,
            regularization_weights=[0.2], max_iter=40,
        )
        m_rep, _ = train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, d, box=box,
            kernel="scatter", **kwargs,
        )
        m_sh, _ = train_feature_sharded(
            batch, TaskType.LOGISTIC_REGRESSION, d, mesh=mesh4x2,
            box=box, kernel="scatter", **kwargs,
        )
        w = np.asarray(m_sh[0.2].means)
        assert np.all(w >= -0.3 - 1e-6) and np.all(w <= 0.3 + 1e-6)
        np.testing.assert_allclose(
            w, np.asarray(m_rep[0.2].means), atol=5e-3
        )
