"""GAME layer tests: dataset building, entity grouping + reservoir cap,
Pearson filter, projections, vmapped RE solves vs direct per-entity
solves, coordinate descent objective decrease, factored RE + MF.

Mirrors GameIntegTest/GameTestUtils validator-style checks.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.game import (
    CoordinateDescent,
    FactoredRandomEffectConfiguration,
    FactoredRandomEffectCoordinate,
    FeatureShardConfiguration,
    FixedEffectCoordinate,
    MatrixFactorizationCoordinate,
    ProjectorType,
    RandomEffectCoordinate,
    RandomEffectDataConfiguration,
    RandomEffectOptimizationProblem,
    build_game_dataset,
    build_random_effect_dataset,
    score_random_effect,
)
from photon_ml_tpu.ops.losses import LINEAR, LOGISTIC
from photon_ml_tpu.optim import (
    OptimizerConfig,
    RegularizationContext,
    RegularizationType,
    minimize_lbfgs,
)
from photon_ml_tpu.optim.problem import create_glm_problem
from photon_ml_tpu.task import TaskType


def make_records(rng, n=200, n_users=10, d_global=6, d_user=4):
    """Synthetic GLMix data: global effect + per-user effect."""
    w_global = np.linspace(-1, 1, d_global)
    w_user = rng.normal(size=(n_users, d_user)).astype(np.float32)
    recs = []
    for i in range(n):
        u = int(rng.integers(0, n_users))
        xg = rng.normal(size=d_global).astype(np.float32)
        xu = rng.normal(size=d_user).astype(np.float32)
        z = float(xg @ w_global + xu @ w_user[u])
        y = float(1 / (1 + np.exp(-z)) > rng.uniform())
        recs.append({
            "uid": f"r{i}",
            "response": y,
            "userId": f"user{u:03d}",
            "features": [
                {"name": f"g{j}", "term": "", "value": float(xg[j])}
                for j in range(d_global)
            ],
            "userFeatures": [
                {"name": f"u{j}", "term": "", "value": float(xu[j])}
                for j in range(d_user)
            ],
        })
    return recs, w_global, w_user


SHARDS = [
    FeatureShardConfiguration("globalShard", ["features"], add_intercept=True),
    FeatureShardConfiguration("userShard", ["userFeatures"], add_intercept=True),
]


class TestGameDataset:
    def test_build(self, rng):
        recs, _, _ = make_records(rng)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        assert ds.num_real_rows == 200
        assert ds.shards["globalShard"].dim == 7  # 6 + intercept
        assert ds.shards["userShard"].dim == 5
        assert ds.entity_indexes["userId"].num_entities == 10
        codes = ds.entity_codes["userId"]
        assert codes[:200].min() >= 0 and codes[:200].max() == 9
        # padding rows have weight 0 and code -1
        assert np.all(ds.weights[200:] == 0)

    def test_metadata_map_ids(self, rng):
        recs = [
            {"response": 1.0, "metadataMap": {"queryId": "q1"}, "features": []},
            {"response": 0.0, "metadataMap": {"queryId": "q2"}, "features": []},
        ]
        ds = build_game_dataset(
            recs, [FeatureShardConfiguration("g", ["features"])], ["queryId"]
        )
        assert ds.entity_indexes["queryId"].num_entities == 2

    def test_scoring_mode_no_response(self):
        recs = [{"features": [{"name": "a", "term": "", "value": 1.0}]}]
        with pytest.raises(ValueError):
            build_game_dataset(recs, [FeatureShardConfiguration("g", ["features"])])
        ds = build_game_dataset(
            recs, [FeatureShardConfiguration("g", ["features"])],
            is_response_required=False,
        )
        assert ds.labels[0] == 0.0


class TestRandomEffectDataset:
    def test_grouping_and_buckets(self, rng):
        recs, _, _ = make_records(rng)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds,
            RandomEffectDataConfiguration("userId", "userShard"),
        )
        assert red.num_entities == 10
        # every real row appears exactly once across buckets
        seen = []
        for b in red.buckets:
            seen.extend(b.row_index[b.row_index >= 0].tolist())
        assert sorted(seen) == sorted(
            np.nonzero((ds.weights > 0) & (ds.entity_codes["userId"] >= 0))[0].tolist()
        )
        # bucket capacities are powers of two and weights pad with 0
        for b in red.buckets:
            assert (b.capacity & (b.capacity - 1)) == 0
            assert np.all(b.weights[b.row_index < 0] == 0)

    def test_reservoir_cap_rescales_weights(self, rng):
        recs, _, _ = make_records(rng, n=300, n_users=3)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds,
            RandomEffectDataConfiguration(
                "userId", "userShard", active_data_upper_bound=16
            ),
        )
        assert red.num_active_rows == 3 * 16
        assert red.num_passive_rows == 300 - 48
        # weight mass approximately preserved per entity
        for b in red.buckets:
            for e in range(b.num_entities):
                cnt_total = np.sum(
                    ds.entity_codes["userId"][:300] == b.entity_codes[e]
                )
                mass = b.weights[e].sum()
                assert mass == pytest.approx(cnt_total, rel=1e-5)

    def test_pearson_filter_bounds_dim(self, rng):
        recs, _, _ = make_records(rng, n=60, n_users=30)  # ~2 rows/user
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds,
            RandomEffectDataConfiguration(
                "userId", "userShard", features_to_samples_ratio=1.0
            ),
        )
        # with ratio 1 and ~2 samples, local dims stay small (<= samples+icept)
        assert red.local_dim <= 8

    def test_random_projection(self, rng):
        recs, _, _ = make_records(rng)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds,
            RandomEffectDataConfiguration(
                "userId", "userShard",
                projector_type=ProjectorType.RANDOM,
                random_projection_dim=3,
            ),
        )
        assert red.local_dim == 3
        assert red.random_projection.shape == (5, 3)
        # intercept column preserved: last latent dim is the intercept slot
        icept = ds.shards["userShard"].intercept_index
        col = red.random_projection[:, 2]
        expect = np.zeros(5); expect[icept] = 1.0
        np.testing.assert_allclose(col, expect)


class TestRandomEffectSolver:
    def test_matches_direct_solves(self, rng):
        recs, _, _ = make_records(rng, n=120, n_users=5)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("userId", "userShard")
        )
        problem = RandomEffectOptimizationProblem(
            LOGISTIC, OptimizerConfig(max_iter=100),
            RegularizationContext(RegularizationType.L2), reg_weight=1.0,
        )
        bank = jnp.zeros((red.num_entities, red.local_dim), jnp.float32)
        bank, tracker = problem.update_bank(bank, red)
        assert tracker.num_entities == 5

        # direct per-entity solve from the raw rows must agree
        codes = ds.entity_codes["userId"]
        for e in range(3):
            rows = np.nonzero((codes == e) & (ds.weights > 0))[0]
            proj = red.projection[e]
            D_e = int((proj >= 0).sum())
            gl2loc = {int(g): l for l, g in enumerate(proj[:D_e])}
            sd = ds.shards["userShard"]
            def vg(w):
                val = 0.0
                grad = jnp.zeros(D_e)
                for i in rows:
                    ix = [gl2loc[int(g)] for g, v in zip(sd.indices[i], sd.values[i]) if v != 0]
                    vs = [float(v) for v in sd.values[i] if v != 0]
                    z = sum(v * w[l] for l, v in zip(ix, vs)) + ds.offsets[i]
                    val = val + ds.weights[i] * LOGISTIC.value(z, ds.labels[i])
                    d1 = ds.weights[i] * LOGISTIC.d1(z, ds.labels[i])
                    for l, v in zip(ix, vs):
                        grad = grad.at[l].add(d1 * v)
                return val + 0.5 * jnp.vdot(w, w), grad + w
            direct = minimize_lbfgs(vg, jnp.zeros(D_e))
            np.testing.assert_allclose(
                np.asarray(bank[e][:D_e]), np.asarray(direct.coefficients),
                atol=5e-3,
            )

    def test_dense_layout_matches_sparse(self, rng):
        """The densified (batched-matmul) solver must agree with the
        gather/scatter solver entity for entity — same optimizer, two
        data layouts."""
        recs, _, _ = make_records(rng, n=160, n_users=6)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("userId", "userShard")
        )
        banks = {}
        trackers = {}
        # ELASTIC_NET keeps both layouts on the SAME optimizer (OWL-QN) —
        # isolating the layout change (dense + pure L2 would auto-select
        # the Newton solver, covered by test_newton_solver_matches_lbfgs).
        for layout in ("sparse", "dense"):
            problem = RandomEffectOptimizationProblem(
                LOGISTIC, OptimizerConfig(max_iter=100),
                RegularizationContext(RegularizationType.ELASTIC_NET, 0.5),
                reg_weight=1.0, layout=layout,
            )
            bank = jnp.zeros((red.num_entities, red.local_dim), jnp.float32)
            banks[layout], trackers[layout] = problem.update_bank(bank, red)
        # atol: the two layouts reduce in different float32 orders, so the
        # OWL-QN optima land within convergence tolerance of each other,
        # not bitwise — on CPU hosts the worst element lands ~3e-4 apart
        # (the seed's 2e-4 tripped on exactly 2/30 elements)
        np.testing.assert_allclose(
            np.asarray(banks["dense"]), np.asarray(banks["sparse"]),
            atol=5e-4,
        )
        # Both layouts must actually converge (exact reason-for-reason
        # equality would be flaky: the two float32 reduction orders can
        # trip different tolerance tests at the boundary).
        for tracker in trackers.values():
            assert tracker.reason_counts.get("MaxIterations", 0) == 0

    def test_newton_solver_matches_lbfgs(self, rng):
        """The dual-space Newton path (auto-selected for dense + L2 + twice
        -differentiable loss) must reach the same optimum as L-BFGS — same
        convex objective, different algorithm."""
        recs, _, _ = make_records(rng, n=160, n_users=6)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("userId", "userShard")
        )
        banks = {}
        # layout="dense" + L2 auto-selects Newton; layout="sparse" is LBFGS
        for layout in ("sparse", "dense"):
            problem = RandomEffectOptimizationProblem(
                LOGISTIC, OptimizerConfig(max_iter=100),
                RegularizationContext(RegularizationType.L2),
                reg_weight=1.0, layout=layout,
            )
            if layout == "dense":
                assert problem._use_dense(red.buckets[0], red.local_dim)
            bank = jnp.zeros((red.num_entities, red.local_dim), jnp.float32)
            banks[layout], tracker = problem.update_bank(bank, red)
        np.testing.assert_allclose(
            np.asarray(banks["dense"]), np.asarray(banks["sparse"]),
            atol=2e-3,
        )
        # Newton converges in far fewer iterations than L-BFGS
        assert tracker.iterations_max <= 20

    def test_bank_variances_match_direct(self, rng):
        """bank_variances = 1/(Hdiag + eps) per entity at the solution,
        Hdiag[j] = sum_i w_i l''(z_i) x_ij^2 + l2 (isComputingVariance,
        RandomEffectOptimizationProblem.scala:106-127)."""
        from photon_ml_tpu.optim.problem import _VARIANCE_EPSILON

        recs, _, _ = make_records(rng, n=120, n_users=5)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("userId", "userShard")
        )
        problem = RandomEffectOptimizationProblem(
            LOGISTIC, OptimizerConfig(max_iter=100),
            RegularizationContext(RegularizationType.L2), reg_weight=1.0,
        )
        bank = jnp.zeros((red.num_entities, red.local_dim), jnp.float32)
        bank, _ = problem.update_bank(bank, red)
        variances = np.asarray(problem.bank_variances(bank, red))
        assert variances.shape == bank.shape
        assert (variances > 0).all()

        codes = ds.entity_codes["userId"]
        sd = ds.shards["userShard"]
        for e in range(3):
            rows = np.nonzero((codes == e) & (ds.weights > 0))[0]
            proj = red.projection[e]
            D_e = int((proj >= 0).sum())
            gl2loc = {int(g): l for l, g in enumerate(proj[:D_e])}
            hd = np.full(D_e, 1.0)  # l2 = reg_weight
            for i in rows:
                z = ds.offsets[i]
                for g, v in zip(sd.indices[i], sd.values[i]):
                    if v != 0:
                        z += v * float(bank[e, gl2loc[int(g)]])
                d2 = float(ds.weights[i]) * float(LOGISTIC.d2(z, ds.labels[i]))
                for g, v in zip(sd.indices[i], sd.values[i]):
                    if v != 0:
                        hd[gl2loc[int(g)]] += d2 * float(v) ** 2
            np.testing.assert_allclose(
                variances[e, :D_e], 1.0 / (hd + _VARIANCE_EPSILON), rtol=2e-4
            )

    def test_scores_cover_all_rows(self, rng):
        recs, _, _ = make_records(rng)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("userId", "userShard",
                                              active_data_upper_bound=8)
        )
        bank = jnp.ones((red.num_entities, red.local_dim), jnp.float32)
        s = np.asarray(score_random_effect(bank, red))
        # passive rows (beyond cap) must be scored too
        assert np.count_nonzero(s[:200]) > 150


@pytest.mark.slow
class TestCoordinateDescent:
    def _setup(self, rng, task=TaskType.LOGISTIC_REGRESSION):
        recs, _, _ = make_records(rng, n=300, n_users=8)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("userId", "userShard")
        )
        fe = FixedEffectCoordinate(
            name="global",
            dataset=ds,
            problem=create_glm_problem(
                task, ds.shards["globalShard"].dim,
                config=OptimizerConfig(max_iter=30),
                regularization=RegularizationContext(RegularizationType.L2),
            ),
            feature_shard_id="globalShard",
            reg_weight=0.1,
        )
        re = RandomEffectCoordinate(
            name="per-user",
            dataset=ds,
            re_dataset=red,
            problem=RandomEffectOptimizationProblem(
                LOGISTIC if task == TaskType.LOGISTIC_REGRESSION else LINEAR,
                OptimizerConfig(max_iter=30),
                RegularizationContext(RegularizationType.L2),
                reg_weight=1.0,
            ),
        )
        return ds, {"global": fe, "per-user": re}

    def test_objective_decreases(self, rng):
        ds, coords = self._setup(rng)
        cd = CoordinateDescent(coords, ds, TaskType.LOGISTIC_REGRESSION)
        result = cd.run(num_iterations=3)
        obj = result.objective_history
        assert len(obj) == 3
        assert obj[-1] <= obj[0] + 1e-6, obj
        # mixed model beats fixed-effect-only on training loss
        cd_fe = CoordinateDescent(
            {"global": coords["global"]}, ds, TaskType.LOGISTIC_REGRESSION
        )
        fe_only = cd_fe.run(num_iterations=1)
        assert obj[-1] < fe_only.objective_history[-1]

    def test_warm_start_model(self, rng):
        ds, coords = self._setup(rng)
        cd = CoordinateDescent(coords, ds, TaskType.LOGISTIC_REGRESSION)
        r1 = cd.run(num_iterations=2)
        r2 = cd.run(num_iterations=1, initial_model=r1.model)
        assert r2.objective_history[-1] <= r1.objective_history[0] + 1e-6

    def test_update_sequence_validation(self, rng):
        ds, coords = self._setup(rng)
        with pytest.raises(ValueError, match="unknown"):
            CoordinateDescent(
                coords, ds, TaskType.LOGISTIC_REGRESSION,
                update_sequence=["global", "nope"],
            )


class TestFixedEffectCoordinateTiled:
    """A fixed effect whose problem is built on the tiled objective (what
    the GAME driver resolves on a TPU; here the kernels run in interpret
    mode): same answers as the scatter one, schedules built once."""

    MAX_ITER = 10

    def _coord(self, ds, kernel, *, variances=False, rate=1.0, mesh=None):
        return FixedEffectCoordinate(
            name="global",
            dataset=ds,
            problem=create_glm_problem(
                TaskType.LOGISTIC_REGRESSION, ds.shards["globalShard"].dim,
                config=OptimizerConfig(max_iter=self.MAX_ITER),
                regularization=RegularizationContext(RegularizationType.L2),
                compute_variances=variances, kernel=kernel,
            ),
            feature_shard_id="globalShard",
            reg_weight=0.1,
            down_sampling_rate=rate,
            mesh=mesh,
        )

    @staticmethod
    def _builds(spans):
        return [s for s in spans if s.name == "tiled.schedule_build"]

    @pytest.mark.parametrize(
        "case", ["plain", "variances", "down_sampled", "data_mesh"]
    )
    def test_matches_scatter_and_builds_its_schedules_once(self, rng, case):
        from photon_ml_tpu.obs import trace as obs_trace
        from photon_ml_tpu.ops import schedule_cache
        from photon_ml_tpu.ops.tiled_sparse import TiledSparseBatch
        from photon_ml_tpu.parallel.mesh import make_mesh

        recs, _, _ = make_records(rng, n=300, n_users=8)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        kw = dict(
            variances=case == "variances",
            rate=0.5 if case == "down_sampled" else 1.0,
            mesh=(
                make_mesh((2,), devices=jax.devices()[:2])
                if case == "data_mesh" else None
            ),
        )
        residuals = [
            jnp.asarray(0.3 * rng.normal(size=ds.num_rows), jnp.float32)
            for _ in range(2)
        ]
        fitted, spans, builds = {}, {}, {}
        for kernel in ("scatter", "tiled"):
            coord = self._coord(ds, kernel, **kw)
            assert coord.kernel == kernel
            model, models = coord.initialize_model(), []
            with obs_trace.tracing_scope(True):
                obs_trace.tracer().clear()
                for residual in residuals:
                    before = schedule_cache.stats().builds
                    model, result = coord.update_model(model, residual)
                    models.append(model)
                    spans.setdefault(kernel, []).append(
                        obs_trace.tracer().drain()
                    )
                    builds.setdefault(kernel, []).append(
                        schedule_cache.stats().builds - before
                    )
            assert int(result.iterations) >= 2
            fitted[kernel] = models
        # the z and the g schedule, in the first update and never again
        # (the residual, the draw's weights: row vectors, no cache key)
        shards = 2 if case == "data_mesh" else 1
        assert builds == {"scatter": [0, 0], "tiled": [2 * shards, 0]}
        first, second = (self._builds(s) for s in spans["tiled"])
        # (a span a schedule on one device, one for the mesh layout's 2 x 2)
        assert [s.attrs["cache"] for s in first] == ["miss"] * (
            1 if case == "data_mesh" else 2
        )
        # the shard's seven always-present features, the intercept among
        # them, are no tile's: a float32 side array of the batch
        dense = ds.shards["globalShard"].intercept_index
        assert {s.attrs["dense_columns"] for s in first} == {7}
        assert dense in np.asarray(coord.__dict__["_tiled"].dense_cols)
        assert second == [] and not any(map(self._builds, spans["scatter"]))
        for per_update in spans["tiled"]:
            dispatch, = [s for s in per_update if s.name == "fit.dispatch"]
            assert dispatch.attrs["kernel"] == "tiled"
        assert isinstance(coord.__dict__["_tiled"], TiledSparseBatch)
        for scatter, tiled in zip(fitted["scatter"], fitted["tiled"]):
            np.testing.assert_allclose(
                np.asarray(tiled.model.means),
                np.asarray(scatter.model.means), atol=2e-3,
            )
            if case == "variances":
                np.testing.assert_allclose(
                    np.asarray(tiled.model.coefficients.variances),
                    np.asarray(scatter.model.coefficients.variances),
                    rtol=1e-3,
                )
        # two residuals, two different solves
        assert not np.allclose(
            np.asarray(fitted["tiled"][0].model.means),
            np.asarray(fitted["tiled"][1].model.means), atol=1e-3,
        )

    def test_coordinate_descent_prefetches_the_build_and_names_the_kernel(
        self, rng
    ):
        """Through CoordinateDescent.run: the first coordinate's prepare
        is prefetched under the other coordinates' starting scores, so the
        schedules are built on the worker, once, outside cd.update; the
        span and the log line say which objective ran."""
        from photon_ml_tpu.obs import trace as obs_trace
        from photon_ml_tpu.parallel import overlap
        from photon_ml_tpu.utils.logging_util import PhotonLogger

        class Lines(PhotonLogger):
            def __init__(self):
                super().__init__()
                self.lines = []

            def info(self, msg, *args):
                self.lines.append(msg % args)

        recs, _, _ = make_records(rng, n=300, n_users=8)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("userId", "userShard")
        )
        coords = {
            "global": self._coord(ds, "tiled"),
            "per-user": RandomEffectCoordinate(
                name="per-user", dataset=ds, re_dataset=red,
                problem=RandomEffectOptimizationProblem(
                    LOGISTIC, OptimizerConfig(max_iter=5),
                    RegularizationContext(RegularizationType.L2),
                    reg_weight=1.0,
                ),
            ),
        }
        cd = CoordinateDescent(
            coords, ds, TaskType.LOGISTIC_REGRESSION, logger=Lines()
        )
        runs = []
        with overlap.overlap_scope(True), obs_trace.tracing_scope(True):
            for _ in range(2):
                obs_trace.tracer().clear()
                result = cd.run(num_iterations=1)
                runs.append(obs_trace.tracer().drain())
        assert np.isfinite(result.objective_history[-1])
        first, second = runs
        by_id = {s.span_id: s for s in first}
        builds = self._builds(first)
        assert len(builds) == 2 and self._builds(second) == []
        for s in builds:  # on the worker: under no span of the main thread
            assert s.parent_id is None or by_id[s.parent_id].name != "cd.update"
        waits = [s for s in first if s.name == "cd.prefetch_wait"]
        assert "global" in {s.attrs["coordinate"] for s in waits}
        # kernel, and its variant; the random effect says how it scored
        for name, re_kernel in (("cd.update", None), ("cd.score", "blocks")):
            said = {
                (s.attrs["coordinate"], s.attrs.get("kernel"), s.attrs.get("mxu"))
                for s in first if s.name == name
            }
            assert said == {
                ("global", "tiled", "bf16x2w"), ("per-user", re_kernel, None)
            }
        assert any(
            line.startswith("coordinate global: ")
            and line.endswith(", kernel=tiled") for line in cd.logger.lines
        )

    def test_prepare_alone_builds_the_schedules(self, rng):
        from photon_ml_tpu.obs import trace as obs_trace

        recs, _, _ = make_records(rng, n=300, n_users=8)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        coord = self._coord(ds, "tiled")
        with obs_trace.tracing_scope(True):
            obs_trace.tracer().clear()
            coord.prepare()
            prepared = obs_trace.tracer().drain()
            coord.update_model(coord.initialize_model(), None)
            updated = obs_trace.tracer().drain()
        assert len(self._builds(prepared)) == 2
        assert self._builds(updated) == []

    @pytest.mark.parametrize("rate", [1.0, 0.5])
    def test_the_row_vectors_are_refreshed_on_the_device(self, rng, rate):
        """The hit path moves no row vector through the host: it traces
        under jit, where a host pull of the residual would raise."""
        recs, _, _ = make_records(rng, n=300, n_users=8)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        coord = self._coord(ds, "tiled", rate=rate)
        coord.prepare()
        base = coord.__dict__["_tiled"]
        residual = jnp.asarray(rng.normal(size=ds.num_rows), jnp.float32)
        def row_vectors(r):
            batch = coord._tiled_batch(r)
            return batch.offsets, batch.weights

        offsets, weights = jax.jit(row_vectors)(residual)
        n, total = ds.num_rows, base.labels.shape[0]
        assert offsets.shape == weights.shape == (total,) and total > n
        np.testing.assert_array_equal(
            np.asarray(offsets), np.pad(ds.offsets + np.asarray(residual),
                                        (0, total - n)),
        )
        kept = np.asarray(weights) > 0
        assert not kept[n:].any() and kept.sum() <= (ds.weights > 0).sum()
        assert (kept.sum() < (ds.weights > 0).sum()) == (rate < 1.0)
        # the coordinate's own copy keeps the build-time weights
        np.testing.assert_array_equal(
            np.asarray(base.weights)[:n], ds.weights
        )


class TestDriverResolvesTheFixedEffectKernel:
    """GameTrainingDriver._build_coordinates picks the fixed effect's
    objective as glm_driver does: resolve_kernel("auto", batch)."""

    def _coords(self, tmp_path, rng, *, rate=1, factored=False, **params):
        from photon_ml_tpu.cli.game_training_driver import (
            GameTrainingDriver,
            GameTrainingParams,
            expand_config_grid,
        )
        from photon_ml_tpu.game.config import FixedEffectDataConfiguration

        recs, _, _ = make_records(rng, n=120, n_users=6)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        re_cfg = RandomEffectDataConfiguration(
            "userId", "userShard", projector_type=ProjectorType.IDENTITY
        )
        p = GameTrainingParams(
            train_input_dirs=[str(tmp_path / "unused")],
            output_dir=str(tmp_path / "out"),
            task_type=TaskType.LOGISTIC_REGRESSION,
            feature_shards=SHARDS,
            fixed_effect_data_configs={
                "global": FixedEffectDataConfiguration("globalShard")
            },
            fixed_effect_opt_configs={
                "global": f"5,1e-6,0.1,{rate},LBFGS,L2"
            },
            random_effect_data_configs={"per-user": re_cfg},
            random_effect_opt_configs={"per-user": "5,1e-6,1.0,1,LBFGS,L2"},
            factored_re_configs=(
                {"per-user": FactoredRandomEffectConfiguration(
                    latent_space_dimension=2, num_inner_iterations=1)}
                if factored else {}
            ),
            **params,
        )
        driver = GameTrainingDriver(p)
        combo = expand_config_grid(
            {**p.fixed_effect_opt_configs, **p.random_effect_opt_configs}
        )[0]
        reds = {"per-user": build_random_effect_dataset(ds, re_cfg)}
        return driver, lambda **kw: driver._build_coordinates(
            ds, reds, combo, **kw
        )

    @pytest.fixture
    def on_tpu(self, monkeypatch):
        from photon_ml_tpu.utils import backend

        monkeypatch.setattr(backend, "effective_platform", lambda: "tpu")

    def test_scatter_on_the_cpu(self, tmp_path, rng):
        from photon_ml_tpu.ops.objective import GLMObjective

        _, build = self._coords(tmp_path, rng)
        fe = build()["global"]
        assert type(fe.problem.objective) is GLMObjective
        assert fe.kernel == "scatter"

    @pytest.mark.parametrize("rate", [1, 0.5])
    def test_tiled_on_a_tpu_whatever_the_sampling_rate(
        self, tmp_path, rng, on_tpu, rate
    ):
        """Down-sampling keeps the kernel: the draw's weights are row
        metadata of the schedules built once (TestFixedEffectCoordinate
        Tiled's down_sampled case), not part of any cache key."""
        from photon_ml_tpu.ops.tiled_sparse import TiledGLMObjective

        _, build = self._coords(tmp_path, rng, rate=rate)
        fe = build()["global"]
        assert isinstance(fe.problem.objective, TiledGLMObjective)
        # the objective's default variant, as every other tiled user: the
        # intercept, whose rounding "highest" was once paid for, is a
        # float32 side term of the batch (PERF.md section 6, PR 32)
        assert fe.problem.objective.mxu == fe.mxu == "bf16x2w"
        assert fe.kernel == "tiled" and fe.down_sampling_rate == rate

    def test_the_projection_problem_stays_scatter(self, tmp_path, rng, on_tpu):
        from photon_ml_tpu.ops.objective import GLMObjective

        _, build = self._coords(tmp_path, rng, factored=True)
        coords = build()
        assert coords["global"].kernel == "tiled"
        projection = coords["per-user"].projection_problem
        assert type(projection.objective) is GLMObjective

    def test_the_batched_grid_and_the_feature_mesh_keep_scatter(
        self, tmp_path, rng, on_tpu
    ):
        _, build = self._coords(tmp_path, rng)
        assert build(fe_kernel="scatter")["global"].kernel == "scatter"
        driver, build = self._coords(
            tmp_path, rng, distributed="feature",
            delete_output_dir_if_exists=True,
        )
        fe = build()["global"]
        assert fe._is_feature_sharded() and fe.kernel == "scatter"
        # the data-parallel mesh follows the rule
        driver.params.distributed = "auto"
        fe = build()["global"]
        assert fe.mesh is not None and fe.kernel == "tiled"


class TestFactoredRandomEffect:
    def test_trains_and_scores(self, rng):
        recs, _, _ = make_records(rng, n=200, n_users=6)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds,
            RandomEffectDataConfiguration(
                "userId", "userShard", projector_type=ProjectorType.IDENTITY
            ),
        )
        fre = FactoredRandomEffectCoordinate(
            name="factored",
            dataset=ds,
            re_dataset=red,
            problem=RandomEffectOptimizationProblem(
                LOGISTIC, OptimizerConfig(max_iter=15),
                RegularizationContext(RegularizationType.L2), reg_weight=1.0,
            ),
            projection_problem=create_glm_problem(
                TaskType.LOGISTIC_REGRESSION,
                red.local_dim * 2,
                config=OptimizerConfig(max_iter=15),
                regularization=RegularizationContext(RegularizationType.L2),
            ),
            config=FactoredRandomEffectConfiguration(
                latent_space_dimension=2, num_inner_iterations=1
            ),
            reg_weight_projection=0.1,
        )
        model = fre.initialize_model()
        assert model.projection.shape == (red.local_dim, 2)
        new_model, _ = fre.update_model(model, None)
        s = fre.score(new_model)
        assert np.all(np.isfinite(np.asarray(s)))
        # training reduced the loss vs initial (zero bank scores 0)
        loss0 = float(jnp.sum(jnp.asarray(ds.weights) * LOGISTIC.value(
            jnp.zeros(ds.num_rows), jnp.asarray(ds.labels))))
        loss1 = float(jnp.sum(jnp.asarray(ds.weights) * LOGISTIC.value(
            s + jnp.asarray(ds.offsets), jnp.asarray(ds.labels))))
        assert loss1 < loss0


class TestMatrixFactorization:
    def test_als_reduces_loss(self, rng):
        # rating ~ user . item latent
        n_users, n_items, K = 12, 15, 3
        U = rng.normal(size=(n_users, K))
        V = rng.normal(size=(n_items, K))
        recs = []
        for i in range(400):
            u = int(rng.integers(0, n_users))
            it = int(rng.integers(0, n_items))
            recs.append({
                "response": float(U[u] @ V[it] + 0.1 * rng.normal()),
                "userId": f"u{u}",
                "itemId": f"i{it}",
                "features": [],
            })
        ds = build_game_dataset(
            recs, [FeatureShardConfiguration("g", ["features"])],
            ["userId", "itemId"],
        )
        mf = MatrixFactorizationCoordinate(
            name="mf",
            dataset=ds,
            row_effect_type="userId",
            col_effect_type="itemId",
            num_latent_factors=K,
            problem=RandomEffectOptimizationProblem(
                LINEAR, OptimizerConfig(max_iter=20),
                RegularizationContext(RegularizationType.L2), reg_weight=0.1,
            ),
            num_inner_iterations=3,
        )
        model = mf.initialize_model()
        lab = jnp.asarray(ds.labels); w = jnp.asarray(ds.weights)
        def mse(m):
            s = mf.score(m)
            return float(jnp.sum(w * (s - lab) ** 2) / jnp.sum(w))
        before = mse(model)
        model, _ = mf.update_model(model, None)
        after = mse(model)
        assert after < before * 0.5, (before, after)

    def test_identity_solvers_match_densify(self, rng):
        """The *_id solver variants (X = values, no densify broadcast —
        the MF latent-view fast path) must produce the same solves as
        the general densify path on identity-index data."""
        from photon_ml_tpu.game.random_effect import _bucket_solver
        from photon_ml_tpu.ops.losses import LOGISTIC as _LOG

        E, S, k = 50, 8, 4
        solvers = _bucket_solver(
            _LOG, OptimizerConfig(max_iter=50),
            RegularizationContext(RegularizationType.L2),
        )
        ix = np.tile(np.arange(k, dtype=np.int32)[None, None, :], (E, S, 1))
        v = rng.normal(size=(E, S, k)).astype(np.float32)
        lab = (rng.uniform(size=(E, S)) > 0.5).astype(np.float32)
        w = np.ones((E, S), np.float32)
        off = np.zeros((E, S), np.float32)
        bank = jnp.zeros((E, k), jnp.float32)
        args = (
            jnp.asarray(ix), jnp.asarray(v), jnp.asarray(lab),
            jnp.asarray(off), jnp.asarray(w),
            jnp.float32(0.0), jnp.float32(0.5),
        )
        for base, ident in (("dense", "dense_id"), ("newton", "newton_id")):
            out_b, _, _ = getattr(solvers, base)(bank, *args)
            out_i, _, _ = getattr(solvers, ident)(bank, *args)
            np.testing.assert_allclose(
                np.asarray(out_i), np.asarray(out_b), atol=1e-5,
                err_msg=base,
            )

    def test_cap_class_merge_bounds_padding(self, rng):
        """The MF capacity classes (a class a power of two) must never pad
        an entity's sample capacity more than 4x — a heavy-tailed count
        distribution where no class holds 25% of entities must not
        collapse everything onto the largest class."""
        # entity i gets ~2^(i mod 10) ratings: every cap class ~10%
        counts = [2 ** (i % 10) for i in range(40)]
        rows = np.repeat(np.arange(40, dtype=np.int32), counts)
        n = len(rows)
        cols = rng.integers(0, 5, size=n).astype(np.int32)
        recs = [
            {
                "uid": f"r{i}",
                "response": float(rng.normal()),
                "userId": f"u{rows[i]}",
                "itemId": f"i{cols[i]}",
                "features": [],
            }
            for i in range(n)
        ]
        ds = build_game_dataset(
            recs, [FeatureShardConfiguration("g", ["features"])],
            ["userId", "itemId"],
        )
        mf = MatrixFactorizationCoordinate(
            name="mf", dataset=ds, row_effect_type="userId",
            col_effect_type="itemId", num_latent_factors=2,
            problem=RandomEffectOptimizationProblem(
                LINEAR, OptimizerConfig(max_iter=5),
                RegularizationContext(RegularizationType.L2), reg_weight=1.0,
            ),
        )
        row_codes = ds.entity_codes["userId"]
        col_codes = ds.entity_codes["itemId"]
        view = mf._side_structure("row", row_codes, col_codes, 40)
        per_entity = np.bincount(
            row_codes[(ds.weights > 0) & (row_codes >= 0)], minlength=40
        )
        for b in view.buckets:
            assert b.identity_indices
            S = b.row_index.shape[1]
            for e, code in enumerate(b.entity_codes):
                c = per_entity[code]
                cap = 1 << int(np.ceil(np.log2(max(c, 1))))
                assert S <= 4 * cap, (int(code), c, cap, S)
        # every entity appears in exactly one bucket
        all_codes = np.concatenate([b.entity_codes for b in view.buckets])
        assert sorted(all_codes.tolist()) == list(range(40))


@pytest.mark.slow
class TestMediumScaleGame:
    """Stress above toy size: 30k rows, 2k entities, full CD with residual
    passing — exercises bucketing, the dense/Newton auto-layout, device
    caching, and the fused bank updates at a size where a quadratic or
    per-entity-dispatch design would visibly blow up."""

    def test_coordinate_descent_30k_rows(self, rng):
        import time

        n, n_users = 30_000, 2_000
        recs, _, _ = make_records(rng, n=n, n_users=n_users,
                                  d_global=20, d_user=8)
        t0 = time.perf_counter()
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("userId", "userShard")
        )
        build_s = time.perf_counter() - t0
        assert ds.num_real_rows == n
        assert red.num_entities == n_users

        coords = {
            "global": FixedEffectCoordinate(
                name="global", dataset=ds,
                problem=create_glm_problem(
                    TaskType.LOGISTIC_REGRESSION,
                    ds.shards["globalShard"].dim,
                    config=OptimizerConfig(max_iter=20),
                    regularization=RegularizationContext(
                        RegularizationType.L2
                    ),
                ),
                feature_shard_id="globalShard", reg_weight=0.1,
            ),
            "per-user": RandomEffectCoordinate(
                name="per-user", dataset=ds, re_dataset=red,
                problem=RandomEffectOptimizationProblem(
                    LOGISTIC, OptimizerConfig(max_iter=20),
                    RegularizationContext(RegularizationType.L2),
                    reg_weight=1.0,
                ),
            ),
        }
        t0 = time.perf_counter()
        res = CoordinateDescent(
            coords, ds, TaskType.LOGISTIC_REGRESSION,
            update_sequence=["global", "per-user"],
        ).run(2)
        cd_s = time.perf_counter() - t0
        # objective decreases monotonically across CD iterations
        hist = res.objective_history
        assert len(hist) == 2 and hist[1] <= hist[0]
        # per-entity solves actually converge at this scale
        tracker = res.trackers["per-user"][-1]
        assert tracker.num_entities == n_users
        assert (
            tracker.reason_counts.get("MaxIterations", 0) < n_users * 0.02
        )
        # design sanity: the whole thing stays minutes-free on 1 CPU device
        assert build_s < 120 and cd_s < 300, (build_s, cd_s)




_BUILD_TIMING_SCRIPT = """
import json, sys, time
import numpy as np
sys.path.insert(0, {repo!r})
from photon_ml_tpu.game import (
    RandomEffectDataConfiguration, build_random_effect_dataset,
)
from photon_ml_tpu.game.data import EntityIndex, GameDataset, ShardData
from photon_ml_tpu.utils.index_map import IndexMap

rng = np.random.default_rng(42)
n, E, d, k = {n}, {E}, {d}, {k}
imap = IndexMap({{f"f{{i}}": i for i in range(d)}})
ds = GameDataset(
    uids=[str(i) for i in range(n)],
    labels=(rng.uniform(size=n) > 0.5).astype(np.float32),
    offsets=np.zeros(n, np.float32),
    weights=np.ones(n, np.float32),
    shards={{"userShard": ShardData(
        indices=rng.integers(0, d, size=(n, k)).astype(np.int32),
        values=rng.normal(size=(n, k)).astype(np.float32),
        index_map=imap, intercept_index=None)}},
    entity_codes={{"userId": rng.integers(0, E, size=n).astype(np.int32)}},
    entity_indexes={{"userId": EntityIndex(
        "userId", [f"u{{i}}" for i in range(E)], {{}})}},
    num_real_rows=n,
)
t0 = time.thread_time()
red = build_random_effect_dataset(
    ds, RandomEffectDataConfiguration(
        "userId", "userShard", active_data_upper_bound={cap}))
build_s = time.thread_time() - t0
caps_cover = all(
    int((b.row_index >= 0).sum(axis=1).max()) <= b.capacity
    and int((b.row_index >= 0).sum(axis=1).min()) >= 1
    for b in red.buckets
)
print(json.dumps({{
    "build_s": build_s,
    "num_entities": red.num_entities,
    "num_active_rows": red.num_active_rows,
    "num_passive_rows": red.num_passive_rows,
    "placed": sum(int((b.row_index >= 0).sum()) for b in red.buckets),
    "caps_cover": caps_cover,
    "total_weight_mass": sum(float(b.weights.sum()) for b in red.buckets),
}}))
"""


def _hermetic_build(n, E, d, k, cap=None):
    """Build the 1M-row RE dataset (and time it) in a FRESH interpreter:
    in the parent, the full suite's accumulated heap makes direct-reclaim
    page faults bill to the building thread's CPU time, flaking any
    in-process bound on a small box. The subprocess returns BOTH the
    hermetic thread-CPU build time and the correctness summaries, so the
    parent never constructs the 1M-row dataset at all."""
    import json as _json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = _BUILD_TIMING_SCRIPT.format(
        repo=repo, n=n, E=E, d=d, k=k, cap=cap if cap is not None else "None"
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return _json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
class TestLargeScaleREBuild:
    """1M rows x 8 nnz with 100k entities through the REAL vectorized
    path (argsort + bincount + flat scatter, no per-row or per-entity
    Python loops), built and timed hermetically in a subprocess."""

    def test_million_row_build(self):
        r = _hermetic_build(n=1_000_000, E=100_000, d=50_000, k=8)
        assert r["num_entities"] == 100_000
        assert r["num_active_rows"] == 1_000_000
        # each bucket's capacity covers its members; every active row
        # landed in exactly one bucket slot
        assert r["caps_cover"]
        assert r["placed"] == 1_000_000
        # regression guard: a reintroduced per-row loop costs 17-77 s at
        # this scale (round 2); the fresh interpreter makes the bound
        # immune to suite-level memory pressure and host load
        assert r["build_s"] < 15.0, r["build_s"]

    def test_million_row_build_with_cap(self):
        r = _hermetic_build(n=1_000_000, E=100_000, d=30_000, k=8, cap=8)
        assert r["num_active_rows"] + r["num_passive_rows"] == 1_000_000
        # reservoir weight mass preserved per entity (sum over buckets)
        assert abs(r["total_weight_mass"] - 1_000_000) < 1e-3 * 1_000_000
        assert r["build_s"] < 15.0, r["build_s"]


@pytest.mark.slow
class TestDeviceResidentResiduals:
    """At steady state the coordinate-descent loop does
    no implicit device->host transfer — residuals, offsets, and scores
    stay jnp end-to-end (SURVEY §7.9 device-resident KeyValueScore); the
    tracker/objective readbacks are single EXPLICIT device_get calls."""

    def test_steady_state_no_implicit_d2h(self, rng):
        recs, _, _ = make_records(rng, n=200, n_users=6)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("userId", "userShard")
        )
        coords = {
            "global": FixedEffectCoordinate(
                name="global",
                dataset=ds,
                problem=create_glm_problem(
                    TaskType.LOGISTIC_REGRESSION,
                    ds.shards["globalShard"].dim,
                    config=OptimizerConfig(max_iter=5),
                    regularization=RegularizationContext(
                        RegularizationType.L2
                    ),
                ),
                feature_shard_id="globalShard",
                reg_weight=0.1,
            ),
            "per-user": RandomEffectCoordinate(
                name="per-user",
                dataset=ds,
                re_dataset=red,
                problem=RandomEffectOptimizationProblem(
                    LOGISTIC,
                    OptimizerConfig(max_iter=5),
                    RegularizationContext(RegularizationType.L2),
                    reg_weight=1.0,
                ),
            ),
        }

        def make_cd():
            return CoordinateDescent(
                coords, ds, TaskType.LOGISTIC_REGRESSION,
                update_sequence=["global", "per-user"],
            )

        # iteration 1 warms every device cache (feature tables, row views)
        warm = make_cd().run(1)
        # steady state: the same coordinates must run with implicit
        # device->host transfers disallowed (explicit device_get is fine)
        with jax.transfer_guard_device_to_host("disallow"):
            res = make_cd().run(1)
        assert np.isfinite(res.objective_history[-1])


@pytest.mark.slow
class TestFilePathScale:
    """Through the REAL path: Avro files -> native
    column decode -> vectorized GAME dataset assembly -> vectorized RE
    build, at a volume where any per-record Python loop in the chain
    would visibly blow up."""

    def test_200k_rows_from_avro_files(self, tmp_path, rng):
        import time

        from photon_ml_tpu.game.data import build_game_dataset_from_files
        from photon_ml_tpu.io import native_avro
        from photon_ml_tpu.io.avro_codec import write_container

        from conftest import game_example_schema

        if not native_avro.available():
            pytest.skip(
                "native avro decoder unavailable: the point of this test "
                "is the REAL (native-decode) load path"
            )
        n, n_users, d_g, d_u = 200_000, 20_000, 6, 4
        rows_per_file = 50_000
        schema = game_example_schema()
        u_codes = rng.integers(0, n_users, size=n)
        n_users_seen = len(np.unique(u_codes))
        for fi in range(n // rows_per_file):
            recs = []
            base = fi * rows_per_file
            for i in range(rows_per_file):
                u = int(u_codes[base + i])
                recs.append({
                    "uid": f"r{base + i}",
                    "response": float(rng.uniform() > 0.5),
                    "metadataMap": {"userId": f"user{u}"},
                    "features": [
                        {"name": f"g{j}", "term": "",
                         "value": float(rng.normal())}
                        for j in range(d_g)
                    ],
                    "userFeatures": [
                        {"name": f"u{j}", "term": "",
                         "value": float(rng.normal())}
                        for j in range(d_u)
                    ],
                })
            write_container(
                str(tmp_path / f"part-{fi}.avro"), schema, recs
            )
            del recs

        t0 = time.perf_counter()
        ds = build_game_dataset_from_files(
            [str(tmp_path)], SHARDS, ["userId"]
        )
        load_s = time.perf_counter() - t0
        assert ds.num_real_rows == n
        assert ds.entity_indexes["userId"].num_entities == n_users_seen

        t0 = time.perf_counter()
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("userId", "userShard")
        )
        re_s = time.perf_counter() - t0
        assert red.num_entities == n_users_seen
        assert red.num_active_rows == n
        placed = sum(int((b.row_index >= 0).sum()) for b in red.buckets)
        assert placed == n
        # the whole chain is vectorized/native: generous 1-core CI bounds
        # that still catch any reintroduced per-record hot loop
        assert load_s < 120, load_s
        assert re_s < 10, re_s


class TestBucketScanFold:
    """Same-shape bucket groups fold into ONE lax.scan dispatch
    (round 5, the RE-bank ceiling profile): the folded update must equal
    the per-bucket path exactly."""

    def _data(self, rng, n_buckets=4, E=64, S=8, K=6, D=32):
        from types import SimpleNamespace

        from photon_ml_tpu.game.random_effect_data import RandomEffectBucket

        buckets = []
        for b in range(n_buckets):
            idx = rng.integers(0, D, size=(E, S, K)).astype(np.int32)
            val = rng.normal(size=(E, S, K)).astype(np.float32)
            z = (val * 0.3).sum(axis=2)
            lab = (rng.uniform(size=(E, S)) < 1 / (1 + np.exp(-z))).astype(
                np.float32
            )
            buckets.append(RandomEffectBucket(
                entity_codes=np.arange(b * E, (b + 1) * E, dtype=np.int32),
                row_index=np.full((E, S), -1, np.int32),
                indices=idx, values=val, labels=lab,
                offsets=np.zeros((E, S), np.float32),
                weights=np.ones((E, S), np.float32),
            ))
        return SimpleNamespace(buckets=buckets), n_buckets * E, D

    def test_fold_matches_per_bucket(self, rng):
        import jax.numpy as jnp

        from photon_ml_tpu.game.random_effect import (
            RandomEffectOptimizationProblem,
        )
        from photon_ml_tpu.ops.losses import LOGISTIC
        from photon_ml_tpu.optim.config import (
            OptimizerConfig,
            RegularizationContext,
            RegularizationType,
        )

        data, n_e, D = self._data(rng)

        def run(with_variances):
            problem = RandomEffectOptimizationProblem(
                loss=LOGISTIC,
                config=OptimizerConfig(max_iter=20, tolerance=1e-6),
                regularization=RegularizationContext(RegularizationType.L2),
                reg_weight=1.0,
            )
            bank = jnp.zeros((n_e, D), jnp.float32)
            if with_variances:
                # variances disable the fold -> per-bucket oracle path
                bank, tracker, _ = problem.update_bank(
                    bank, data, with_variances=True
                )
            else:
                bank, tracker = problem.update_bank(bank, data)
            return np.asarray(bank), tracker

        bank_fold, tr_fold = run(False)
        bank_oracle, tr_oracle = run(True)
        np.testing.assert_allclose(bank_fold, bank_oracle, atol=1e-5)
        assert tr_fold.num_entities == tr_oracle.num_entities
        # differently-compiled XLA programs may flip a convergence check
        # by a rounding ulp; compare the stat with slack, not ==
        assert tr_fold.iterations_mean == pytest.approx(
            tr_oracle.iterations_mean, abs=0.1
        )

    def test_fold_with_residual_offsets(self, rng):
        import jax.numpy as jnp

        from photon_ml_tpu.game.random_effect import (
            RandomEffectOptimizationProblem,
        )
        from photon_ml_tpu.ops.losses import LOGISTIC
        from photon_ml_tpu.optim.config import (
            OptimizerConfig,
            RegularizationContext,
            RegularizationType,
        )
        from photon_ml_tpu.game.random_effect_data import RandomEffectBucket
        from types import SimpleNamespace

        # row_index >= 0 so residual offsets route through the fold's
        # stacked gather: rebuild the buckets with real row indices
        data, n_e, D = self._data(rng, n_buckets=3, E=32, S=4)
        n_rows = 512
        buckets = []
        for b in data.buckets:
            buckets.append(RandomEffectBucket(
                entity_codes=b.entity_codes,
                row_index=rng.integers(
                    0, n_rows, size=b.labels.shape
                ).astype(np.int32),
                indices=b.indices, values=b.values, labels=b.labels,
                offsets=b.offsets, weights=b.weights,
            ))
        # rows drawn with repeats are no permutation: no entity order
        data = SimpleNamespace(buckets=buckets, entity_order=None)
        residual = jnp.asarray(
            rng.normal(size=n_rows).astype(np.float32) * 0.1
        )
        problem = RandomEffectOptimizationProblem(
            loss=LOGISTIC,
            config=OptimizerConfig(max_iter=15, tolerance=1e-6),
            regularization=RegularizationContext(RegularizationType.L2),
            reg_weight=1.0,
        )
        bank = jnp.zeros((3 * 32, D), jnp.float32)
        bank_fold, _ = problem.update_bank(
            bank, data, residual_offsets=residual
        )
        # oracle: per-bucket path (variances disable the fold)
        problem2 = RandomEffectOptimizationProblem(
            loss=LOGISTIC,
            config=OptimizerConfig(max_iter=15, tolerance=1e-6),
            regularization=RegularizationContext(RegularizationType.L2),
            reg_weight=1.0,
        )
        bank_oracle, _, _ = problem2.update_bank(
            jnp.zeros((3 * 32, D), jnp.float32), data,
            residual_offsets=residual, with_variances=True,
        )
        np.testing.assert_allclose(
            np.asarray(bank_fold), np.asarray(bank_oracle), atol=1e-5
        )
