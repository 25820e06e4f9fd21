"""Tiled kernel x mesh composition: the fast kernel and data parallelism
run TOGETHER (the reference's hot loop is simultaneously fast and
distributed — ValueAndGradientAggregator.scala:235-250; round 2 fell back
to the scatter objective under a mesh).

All tests run the Pallas kernels in interpret mode on the virtual 8-device
CPU mesh from tests/conftest.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from photon_ml_tpu.data.batch import make_sparse_batch
from photon_ml_tpu.ops.losses import LOGISTIC
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.tiled_sparse import (
    TileParams,
    TiledGLMObjective,
    build_sharded_tiled_batch,
    ensure_tiled_sharded,
)
from photon_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh
from photon_ml_tpu.task import TaskType
from photon_ml_tpu.training import train_generalized_linear_model

PARAMS = TileParams(s_hi=8, s_lo=8, chunk=32)  # window 64, tiny for tests


def random_problem(rng, n=203, d=150, k=6, intercept=False):
    """``intercept``: one more column, d - 1, with an entry in every row."""
    rows, labels = [], []
    for _ in range(n):
        nnz = int(rng.integers(1, k + 1))
        ix = rng.choice(d - intercept, size=nnz, replace=False).tolist()
        vs = rng.normal(size=nnz).tolist()
        if intercept:
            ix.append(d - 1)
            vs.append(float(rng.normal()))
        labels.append(float(rng.uniform() > 0.5))
        rows.append((ix, vs))
    return make_sparse_batch(rows, labels, weights=rng.uniform(0.5, 2.0, n)), d


class TestShardedTiledBatch:
    def test_leaf_shapes_stack_per_shard(self, rng):
        batch, d = random_problem(rng)
        n_shards = 4
        tb = build_sharded_tiled_batch(
            batch, d, n_shards, params=PARAMS
        )
        assert tb.meta.data_shards == n_shards
        # per-shard static views divide every leaf's leading axis
        assert tb.labels.shape[0] == n_shards * tb.meta.num_rows
        assert tb.z_sched.step_out.shape[0] % n_shards == 0
        assert tb.g_sched.step_out.shape[0] % n_shards == 0
        assert tb.z_sched.out_pos.shape[0] % n_shards == 0
        # every nonzero entry appears once per schedule (chunk slots +
        # spill tail), across all shards
        nnz = int(np.count_nonzero(np.asarray(batch.values)))
        assert (
            np.count_nonzero(np.asarray(tb.z_sched.vals))
            + np.count_nonzero(np.asarray(tb.z_sched.spill_vals))
        ) == nnz
        assert (
            np.count_nonzero(np.asarray(tb.g_sched.vals))
            + np.count_nonzero(np.asarray(tb.g_sched.spill_vals))
        ) == nnz

    def test_a_dense_column_is_split_over_the_shards_rows(self, rng):
        """Found over the WHOLE batch, kept as one [K, rows] segment a
        shard along axis 0, so the ``P(axis)`` split of every leaf hands a
        device its own rows' values (row r of the batch at position r)."""
        batch, d = random_problem(rng, intercept=True)
        n_shards = 4
        tb = build_sharded_tiled_batch(batch, d, n_shards, params=PARAMS)
        R = tb.meta.num_rows
        assert tb.dense_cols.tolist() == [d - 1] * n_shards
        assert tb.dense_vals.shape == (n_shards, R)
        live = np.asarray(batch.weights) > 0
        at, slot = np.where(np.asarray(batch.indices) == d - 1)
        want = np.zeros(n_shards * R, np.float32)
        want[at] = np.asarray(batch.values)[at, slot]
        want[: len(live)][~live] = 0  # built-out rows hold no entry
        np.testing.assert_array_equal(
            np.asarray(tb.dense_vals).reshape(-1), want
        )
        nnz = int(np.count_nonzero(np.asarray(batch.values)[live]))
        for sched in (tb.z_sched, tb.g_sched):
            assert (
                np.count_nonzero(np.asarray(sched.vals))
                + np.count_nonzero(np.asarray(sched.spill_vals))
                + int(live.sum())
            ) == nnz
        # without one, the batch is the pytree it was
        plain = build_sharded_tiled_batch(
            random_problem(rng)[0], d, n_shards, params=PARAMS
        )
        assert plain.dense_cols is None and plain.dense_vals is None

    @pytest.mark.parametrize(
        "method", ["value_and_gradient", "hessian_vector", "hessian_diagonal"]
    )
    def test_a_dense_column_on_a_four_device_mesh(self, rng, method):
        """Each device adds its own rows' side term; the gradient's joins
        ``vector_sum`` before the one ``psum`` (no collective of its
        own: the same count as without a dense column)."""
        batch, d = random_problem(rng, intercept=True)
        mesh = make_mesh((4,), devices=jax.devices()[:4])
        tb = build_sharded_tiled_batch(batch, d, 4, params=PARAMS, mesh=mesh)
        assert tb.dense_vals.sharding.spec == P(DATA_AXIS)
        obj = TiledGLMObjective(
            LOGISTIC, d, axis_name=DATA_AXIS, interpret=True
        )
        oracle = GLMObjective(LOGISTIC, d)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v = jnp.asarray(rng.normal(size=d).astype(np.float32))
        l2 = jnp.float32(0.3)

        def on_mesh(f):
            return jax.jit(shard_map(
                f, mesh=mesh, in_specs=(P(), P(), P(DATA_AXIS), P()),
                out_specs=P(), check_vma=False,
            ))

        if method == "value_and_gradient":
            f = on_mesh(lambda w, v, b, l2: obj.value_and_gradient(w, b, l2))
            value, got = f(w, v, tb, l2)
            ov, want = oracle.value_and_gradient(w, batch, l2)
            np.testing.assert_allclose(float(value), float(ov), rtol=1e-5)
            plain = build_sharded_tiled_batch(
                random_problem(rng)[0], d, 4, params=PARAMS, mesh=mesh
            )
            psums = [
                str(f.trace(w, v, b, l2).jaxpr).count("psum")
                for b in (tb, plain)
            ]
            assert psums[0] == psums[1] > 0
        elif method == "hessian_vector":
            got = on_mesh(obj.hessian_vector)(w, v, tb, l2)
            want = oracle.hessian_vector(w, v, batch, l2)
        else:
            got = on_mesh(
                lambda w, v, b, l2: obj.hessian_diagonal(w, b, l2)
            )(w, v, tb, l2)
            want = oracle.hessian_diagonal(w, batch, l2)
        scale = float(np.max(np.abs(np.asarray(want))))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4 * scale
        )

    def test_per_shard_blocks_monotone(self, rng):
        batch, d = random_problem(rng)
        n_shards = 4
        tb = build_sharded_tiled_batch(batch, d, n_shards, params=PARAMS)
        gz = tb.z_sched.step_out.shape[0] // n_shards
        gg = tb.g_sched.step_out.shape[0] // n_shards
        for s in range(n_shards):
            z_out = np.asarray(tb.z_sched.step_out[s * gz:(s + 1) * gz])
            g_out = np.asarray(tb.g_sched.step_out[s * gg:(s + 1) * gg])
            assert np.all(np.diff(z_out) >= 0)
            assert np.all(np.diff(g_out) >= 0)

    def test_value_and_gradient_matches_scatter(self, rng):
        batch, d = random_problem(rng)
        mesh = make_mesh()
        n_shards = int(mesh.shape[DATA_AXIS])
        tb = build_sharded_tiled_batch(
            batch, d, n_shards, params=PARAMS, mesh=mesh
        )
        obj = TiledGLMObjective(
            LOGISTIC, d, axis_name=DATA_AXIS, interpret=True
        )
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))

        @jax.jit
        @lambda f: shard_map(
            f, mesh=mesh, in_specs=(P(), P(DATA_AXIS), P()),
            out_specs=(P(), P()), check_vma=False,
        )
        def vg(w, b, l2):
            return obj.value_and_gradient(w, b, l2)

        value, grad = vg(w, tb, jnp.float32(0.3))
        oracle = GLMObjective(LOGISTIC, d)
        ov, og = oracle.value_and_gradient(w, batch, jnp.float32(0.3))
        np.testing.assert_allclose(float(value), float(ov), rtol=2e-4)
        np.testing.assert_allclose(
            np.asarray(grad), np.asarray(og), rtol=3e-3, atol=3e-5
        )

    def test_hessian_vector_matches_scatter(self, rng):
        batch, d = random_problem(rng, n=97)
        mesh = make_mesh()
        tb = ensure_tiled_sharded(batch, d, mesh, params=PARAMS)
        obj = TiledGLMObjective(
            LOGISTIC, d, axis_name=DATA_AXIS, interpret=True
        )
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v = jnp.asarray(rng.normal(size=d).astype(np.float32))

        @jax.jit
        @lambda f: shard_map(
            f, mesh=mesh, in_specs=(P(), P(), P(DATA_AXIS), P()),
            out_specs=P(), check_vma=False,
        )
        def hv(w, v, b, l2):
            return obj.hessian_vector(w, v, b, l2)

        got = hv(w, v, tb, jnp.float32(0.1))
        oracle = GLMObjective(LOGISTIC, d)
        want = oracle.hessian_vector(w, v, batch, jnp.float32(0.1))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=3e-3, atol=3e-5
        )

    def test_ensure_idempotent(self, rng):
        batch, d = random_problem(rng)
        mesh = make_mesh()
        tb = ensure_tiled_sharded(batch, d, mesh, params=PARAMS)
        tb2 = ensure_tiled_sharded(tb, d, mesh, params=PARAMS)
        assert tb2 is tb

    def test_schedule_cache_across_fresh_wrappers(self, rng):
        """A fresh SparseBatch sharing indices/values/weights with a prior
        call (the GAME CD pattern: only offsets change per sweep) reuses
        the cached schedules — no rebuild — while the new offsets land in
        the returned batch."""
        batch, d = random_problem(rng)
        mesh = make_mesh()
        tb = ensure_tiled_sharded(batch, d, mesh, params=PARAMS)
        shifted = batch._replace(offsets=batch.offsets + 1.0)
        tb2 = ensure_tiled_sharded(shifted, d, mesh, params=PARAMS)
        assert tb2.z_sched.vals is tb.z_sched.vals  # schedules reused
        n = batch.labels.shape[0]
        np.testing.assert_allclose(
            np.asarray(tb2.offsets)[:n], np.asarray(batch.offsets) + 1.0
        )
        # different values array -> genuine rebuild
        scaled = batch._replace(values=batch.values * 2.0)
        tb3 = ensure_tiled_sharded(scaled, d, mesh, params=PARAMS)
        assert tb3.z_sched.vals is not tb.z_sched.vals

    def test_shard_count_mismatch_raises(self, rng):
        batch, d = random_problem(rng)
        mesh = make_mesh()
        tb = build_sharded_tiled_batch(batch, d, 2, params=PARAMS)
        with pytest.raises(ValueError, match="laid out for 2"):
            ensure_tiled_sharded(tb, d, mesh)


class TestFeatureShardedTiled:
    def test_matches_replicated_lbfgs(self, rng):
        # 10B-coef layout on the fast kernel: 2-D (data=4, model=2) mesh,
        # tiled block-local schedules vs the plain replicated fit
        from photon_ml_tpu.ops.tiled_sparse import feature_shard_tiled_batch
        from photon_ml_tpu.parallel.distributed import (
            feature_sharded_glm_fit,
        )
        from photon_ml_tpu.parallel.mesh import MODEL_AXIS

        n, d, k = 120, 100, 5
        w_true = rng.normal(size=d)
        rows, labels = [], []
        for _ in range(n):
            ix = rng.choice(d, size=k, replace=False)
            vs = rng.normal(size=k)
            z = float((w_true[ix] * vs).sum())
            labels.append(float(rng.uniform() < 1 / (1 + np.exp(-z))))
            rows.append((ix.tolist(), vs.tolist()))
        batch = make_sparse_batch(rows, labels)
        mesh = make_mesh((4, 2), (DATA_AXIS, MODEL_AXIS))
        sharded, block_dim = feature_shard_tiled_batch(
            batch, d, 4, 2, params=PARAMS, mesh=mesh
        )
        obj = GLMObjective(LOGISTIC, d)
        fit = feature_sharded_glm_fit(
            obj, mesh, sharded.meta, layout="tiled", optimizer="lbfgs",
            max_iter=25, interpret=True,
        )
        res = fit(
            jnp.zeros(2 * block_dim, jnp.float32), sharded, jnp.float32(0.5)
        )
        # oracle: plain single-device L-BFGS on the scatter objective
        from photon_ml_tpu.optim.lbfgs import minimize_lbfgs

        oracle = minimize_lbfgs(
            lambda w: obj.value_and_gradient(w, batch, jnp.float32(0.5)),
            jnp.zeros(d, jnp.float32), max_iter=25,
        )
        np.testing.assert_allclose(
            np.asarray(res.coefficients)[:d],
            np.asarray(oracle.coefficients),
            atol=5e-3,
        )
        np.testing.assert_allclose(
            float(res.value), float(oracle.value), rtol=1e-4
        )

    @pytest.mark.parametrize("kernel", ["scatter", "tiled"])
    def test_feature_sharded_tron_matches_replicated(self, rng, kernel):
        # sharded trust-region Newton: every CG inner product psums over
        # the model axis (the treeAggregate-per-CG-iteration loop on ICI).
        # kernel="tiled" runs the Pallas z/g schedules for BOTH the
        # objective and the Hv factory (tiled_block_local_hvp_factory).
        from photon_ml_tpu.optim.config import OptimizerType, RegularizationType
        from photon_ml_tpu.optim.tron import minimize_tron
        from photon_ml_tpu.ops.objective import GLMObjective as _G
        from photon_ml_tpu.parallel.mesh import MODEL_AXIS
        from photon_ml_tpu.training import train_feature_sharded

        n, d, k = 120, 64, 5
        w_true = rng.normal(size=d)
        rows, labels = [], []
        for _ in range(n):
            ix = rng.choice(d, size=k, replace=False)
            vs = rng.normal(size=k)
            z = float((w_true[ix] * vs).sum())
            labels.append(float(rng.uniform() < 1 / (1 + np.exp(-z))))
            rows.append((ix.tolist(), vs.tolist()))
        batch = make_sparse_batch(rows, labels)
        mesh = make_mesh((4, 2), (DATA_AXIS, MODEL_AXIS))
        models, results = train_feature_sharded(
            batch, TaskType.LOGISTIC_REGRESSION, d,
            mesh=mesh,
            regularization_type=RegularizationType.L2,
            regularization_weights=[0.5],
            max_iter=12,
            tolerance=1e-5,
            optimizer_type=OptimizerType.TRON,
            kernel=kernel,
        )
        obj = _G(LOGISTIC, d)
        oracle = minimize_tron(
            lambda w: obj.value_and_gradient(w, batch, jnp.float32(0.5)),
            lambda w, dd: obj.hessian_vector(w, dd, batch, jnp.float32(0.5)),
            jnp.zeros(d, jnp.float32), max_iter=12, tol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(models[0.5].coefficients.means),
            np.asarray(oracle.coefficients),
            atol=5e-3,
        )
        np.testing.assert_allclose(
            float(results[0.5].value), float(oracle.value), rtol=1e-4
        )

    def test_feature_sharded_tron_guards(self, rng):
        from photon_ml_tpu.optim.config import OptimizerType, RegularizationType
        from photon_ml_tpu.parallel.mesh import MODEL_AXIS
        from photon_ml_tpu.training import train_feature_sharded

        batch, d = random_problem(rng, n=32, d=16, k=3)
        mesh = make_mesh((4, 2), (DATA_AXIS, MODEL_AXIS))
        with pytest.raises(ValueError, match="twice-differentiable"):
            train_feature_sharded(
                batch, TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM, d,
                mesh=mesh, optimizer_type=OptimizerType.TRON,
            )
        with pytest.raises(ValueError, match="L1/ELASTIC_NET"):
            train_feature_sharded(
                batch, TaskType.LOGISTIC_REGRESSION, d,
                mesh=mesh, optimizer_type=OptimizerType.TRON,
                regularization_type=RegularizationType.L1,
            )

    def test_train_feature_sharded_tiled_owlqn(self, rng):
        # elastic-net grid through the public entry point, tiled kernel
        from photon_ml_tpu.parallel.mesh import MODEL_AXIS
        from photon_ml_tpu.training import train_feature_sharded
        from photon_ml_tpu.optim.config import RegularizationType

        n, d, k = 96, 60, 4
        w_true = rng.normal(size=d)
        rows, labels = [], []
        for _ in range(n):
            ix = rng.choice(d, size=k, replace=False)
            vs = rng.normal(size=k)
            z = float((w_true[ix] * vs).sum())
            labels.append(float(rng.uniform() < 1 / (1 + np.exp(-z))))
            rows.append((ix.tolist(), vs.tolist()))
        batch = make_sparse_batch(rows, labels)
        mesh = make_mesh((4, 2), (DATA_AXIS, MODEL_AXIS))
        kwargs = dict(
            mesh=mesh,
            regularization_type=RegularizationType.ELASTIC_NET,
            elastic_net_alpha=0.5,
            regularization_weights=[0.3],
            max_iter=25,
        )
        m_scatter, _ = train_feature_sharded(
            batch, TaskType.LOGISTIC_REGRESSION, d, kernel="scatter", **kwargs
        )
        m_tiled, _ = train_feature_sharded(
            batch, TaskType.LOGISTIC_REGRESSION, d, kernel="tiled", **kwargs
        )
        np.testing.assert_allclose(
            np.asarray(m_tiled[0.3].coefficients.means),
            np.asarray(m_scatter[0.3].coefficients.means),
            atol=5e-3,
        )


class TestTiledMeshTraining:
    def test_mesh_matches_single_device_tiled(self, rng):
        # end-to-end lambda grid: tiled+mesh vs scatter single-device agree
        # (no silent fallback anywhere). Labels come from a planted model so
        # the optimum is well-conditioned (separable data would amplify fp
        # reduction-order noise into large coefficient differences).
        n, d, k = 157, 40, 5
        w_true = rng.normal(size=d)
        rows, labels = [], []
        for _ in range(n):
            ix = rng.choice(d, size=k, replace=False)
            vs = rng.normal(size=k)
            z = float((w_true[ix] * vs).sum())
            labels.append(float(rng.uniform() < 1 / (1 + np.exp(-z))))
            rows.append((ix.tolist(), vs.tolist()))
        batch = make_sparse_batch(rows, labels)
        kwargs = dict(regularization_weights=[1.0, 0.1], max_iter=25)
        m_scatter, _ = train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, d, **kwargs
        )
        m_mesh, _ = train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, d,
            kernel="tiled", mesh=make_mesh(), **kwargs
        )
        for lam in m_scatter:
            np.testing.assert_allclose(
                np.asarray(m_mesh[lam].coefficients.means),
                np.asarray(m_scatter[lam].coefficients.means),
                atol=5e-3,
            )
