"""A fixed effect on the tiled objective scores on it too.

``FixedEffectCoordinate.score`` runs the margin kernel over the tiled
batch the coordinate solves on (here in interpret mode, on the CPU's
virtual devices) where the schedules hold every entry that scores, and
keeps the gather (``compute_scores`` on the ``SparseBatch``) elsewhere:
one scoring contract, two implementations.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.game import (
    CoordinateDescent,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
    RandomEffectDataConfiguration,
    RandomEffectOptimizationProblem,
    build_game_dataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.game.coordinate import fe_score
from photon_ml_tpu.game.coordinate_descent import cd_total
from photon_ml_tpu.game.data import GameDataset, ShardData
from photon_ml_tpu.game.model import FixedEffectModel
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.glm import compute_scores, create_model
from photon_ml_tpu.obs import trace as obs_trace
from photon_ml_tpu.obs.registry import default_registry
from photon_ml_tpu.ops.losses import LOGISTIC
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.optim import (
    OptimizerConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.optim.problem import create_glm_problem
from photon_ml_tpu.parallel.mesh import make_mesh, replicated
from photon_ml_tpu.task import TaskType
from photon_ml_tpu.utils.index_map import IdentityIndexMap
from tests.test_game import SHARDS, make_records

WINDOW = 8192  # TileParams' default row and feature window
TASK = TaskType.LOGISTIC_REGRESSION
# what the kernel's default variant keeps of a product: a bfloat16 hi+lo
# pair, ~16 mantissa bits, twice a product (coefficient, then contribution)
KERNEL_RTOL = 4e-5


def _dataset(rng, n, dim, k=6, weights=None):
    """``n`` rows of ``k`` entries over ``dim`` hashed features: nearly all
    ids in the whole windows, a few in the last, partial one, whose tiles
    then hold less than the spill cap and spill whole."""
    body = (dim // WINDOW) * WINDOW
    indices = rng.integers(0, body, size=(n, k)).astype(np.int32)
    tail = rng.uniform(size=(n, k)) < 0.01
    indices[tail] = rng.integers(body, dim, size=int(tail.sum()))
    values = rng.normal(size=(n, k)).astype(np.float32)
    return GameDataset(
        uids=[str(i) for i in range(n)],
        labels=(rng.uniform(size=n) < 0.5).astype(np.float32),
        offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32) if weights is None else weights,
        shards={"globalShard": ShardData(
            indices, values, IdentityIndexMap(dim), None
        )},
        entity_codes={},
        entity_indexes={},
        num_real_rows=n,
    )


def _coord(ds, kernel="tiled", *, mesh=None, norm=None, max_iter=10):
    problem = create_glm_problem(
        TASK, ds.shards["globalShard"].dim,
        config=OptimizerConfig(max_iter=max_iter, tolerance=1e-9),
        regularization=RegularizationContext(RegularizationType.L2),
        norm=norm, kernel=kernel,
    )
    # (the objective as ``create_glm_problem`` hands it over, which is how
    # the GAME driver's coordinate runs it: the kernel at "bf16x2w")
    return FixedEffectCoordinate(
        name="global", dataset=ds, problem=problem,
        feature_shard_id="globalShard", reg_weight=0.1, mesh=mesh,
    )


def _model(rng, dim):
    means = jnp.asarray(rng.normal(size=dim), jnp.float32)
    return FixedEffectModel(
        create_model(TASK, Coefficients(means, None)), "globalShard"
    )


def _gather(ds, model):
    return np.asarray(
        compute_scores(model.model.means, ds.batch_for_shard("globalShard"))
    )


def _assert_the_gathers(got, want):
    """(to rounding: ``fe_score`` fuses the same sum in another order)"""
    np.testing.assert_allclose(
        got, want, rtol=0, atol=2e-7 * np.max(np.abs(want))
    )


def _scored(kernel):
    return default_registry().counter("photon_fe_scores_total").value(
        coordinate="global", kernel=kernel
    )


def _builds(spans):
    return [s for s in spans if s.name == "tiled.schedule_build"]


def _spilled(coord):
    return int(np.count_nonzero(
        np.asarray(coord.__dict__["_tiled"].z_sched.spill_vals)
    ))


class TestScoresOnTheMarginKernel:
    def test_one_device_matches_the_gather(self, rng):
        """Rows no multiple of the window, spilled entries, a non-zero
        model, and a normalisation the scores must NOT see (the model
        holds original-space means)."""
        dim = WINDOW + 37
        ds = _dataset(rng, 1003, dim)
        norm = NormalizationContext(
            factor=jnp.asarray(rng.uniform(0.5, 2.0, size=dim), jnp.float32),
            shift=jnp.asarray(rng.normal(size=dim), jnp.float32),
        )
        coord, model = _coord(ds, norm=norm), _model(rng, dim)
        before = _scored("tiled")
        got = coord.score(model)
        assert coord.score_kernel == "tiled" and _scored("tiled") == before + 1
        assert coord.mxu == "bf16x2w"
        assert got.shape == (1003,) and _spilled(coord) > 0
        assert coord.__dict__["_tiled"].labels.shape[0] == WINDOW
        want = _gather(ds, model)
        assert np.max(np.abs(np.asarray(got) - want)) <= KERNEL_RTOL * np.max(
            np.abs(want)
        )
        # the zero model run() starts from scores zero
        zero = coord.score(coord.initialize_model())
        assert not np.asarray(zero).any()

    def test_a_data_mesh_scores_each_shard_on_its_own_schedule(self, rng):
        """Four devices, every one holding rows: dataset row order, equal
        to the one-device result, and replicated over the mesh, the layout
        the CD score algebra sums without compiling anew."""
        dim = WINDOW + 37
        n = 3 * WINDOW + 1901
        ds = _dataset(rng, n, dim, k=3)
        mesh = make_mesh((4,), devices=jax.devices()[:4])
        model = _model(rng, dim)
        sharded, single = _coord(ds, mesh=mesh), _coord(ds)
        got = sharded.score(model)
        base = sharded.__dict__["_tiled"]
        assert base.meta.data_shards == 4 and base.meta.num_rows == WINDOW
        assert sharded.score_kernel == "tiled" and _spilled(sharded) > 0
        assert got.shape == (n,)
        assert got.sharding.is_equivalent_to(replicated(mesh), 1)
        want = _gather(ds, model)
        scale = np.max(np.abs(want))
        assert np.max(np.abs(np.asarray(got) - want)) <= KERNEL_RTOL * scale
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(single.score(model)),
            atol=1e-6 * scale,
        )
        # the zero model (one device's array) and a solved one (the
        # mesh's) share one program, and so does the sum that takes them
        total = cd_total(jnp.zeros((n,), jnp.float32), got)
        compiled = fe_score._cache_size(), cd_total._cache_size()
        again = sharded.score(sharded.initialize_model())
        cd_total(jnp.zeros((n,), jnp.float32), again)
        assert (fe_score._cache_size(), cd_total._cache_size()) == compiled
        assert total.shape == (n,)


class TestWhatKeepsTheGather:
    def _spans_of_one_run(self, coords, ds):
        cd = CoordinateDescent(coords, ds, TASK)
        with obs_trace.tracing_scope(True):
            obs_trace.tracer().clear()
            cd.run(num_iterations=1)
            spans = obs_trace.tracer().drain()
        return [
            s.attrs.get("kernel") for s in spans
            if s.name == "cd.score" and s.attrs["coordinate"] == "global"
        ]

    def test_a_built_out_row_with_features(self, rng):
        """``_sparse_coo`` drops weight-0 rows from the schedules: the
        kernel would score such a row 0 where the contract says x . w."""
        dim = WINDOW + 37
        weights = np.ones(1003, np.float32)
        weights[17] = 0.0
        ds = _dataset(rng, 1003, dim, weights=weights)
        coord, model = _coord(ds), _model(rng, dim)
        before = _scored("gather"), _scored("tiled")
        got = np.asarray(coord.score(model))
        assert coord.score_kernel == "gather"
        assert (_scored("gather"), _scored("tiled")) == (
            before[0] + 1, before[1]
        )
        want = _gather(ds, model)
        assert abs(want[17]) > 1e-3
        _assert_the_gathers(got, want)
        assert self._spans_of_one_run({"global": coord}, ds) == [
            "gather", "gather"
        ]

    def test_a_built_out_row_without_features_does_not(self, rng):
        """The dataset's row padding: weight 0 and no entry, 0 either way."""
        recs, _, _ = make_records(rng, n=300, n_users=8)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        assert ds.num_rows > ds.num_real_rows == 300
        coord = _coord(ds)
        model = _model(rng, ds.shards["globalShard"].dim)
        got = np.asarray(coord.score(model))
        assert coord.score_kernel == "tiled" and got.shape == (ds.num_rows,)
        np.testing.assert_allclose(got, _gather(ds, model), atol=1e-5)
        assert self._spans_of_one_run({"global": coord}, ds) == [
            "tiled", "tiled"
        ]

    def test_the_scatter_objective_and_the_feature_mesh(self, rng):
        from photon_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

        recs, _, _ = make_records(rng, n=120, n_users=6)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        model = _model(rng, ds.shards["globalShard"].dim)
        want = _gather(ds, model)
        feature_mesh = make_mesh(
            (2, 2), (DATA_AXIS, MODEL_AXIS), jax.devices()[:4]
        )
        for coord in (
            _coord(ds, "scatter"),
            _coord(ds, "scatter", mesh=make_mesh((2,), devices=jax.devices()[:2])),
            _coord(ds, "tiled", mesh=feature_mesh),
        ):
            before = _scored("gather")
            got = np.asarray(coord.score(model))
            assert coord.score_kernel == "gather"
            assert _scored("gather") == before + 1
            assert "_tiled" not in coord.__dict__  # no schedule built to score
            _assert_the_gathers(got, want)
        assert self._spans_of_one_run({"global": _coord(ds, "scatter")}, ds) == [
            "gather", "gather"
        ]


class TestCoordinateDescentOnTheKernel:
    def test_reaches_the_scatter_descents_objective(self, rng):
        """A tiled fixed effect (solve and scores on the kernel) beside a
        random effect: each iteration's objective is the scatter
        descent's."""
        recs, _, _ = make_records(rng, n=300, n_users=8)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("userId", "userShard")
        )
        history, scored = {}, {}
        for kernel in ("scatter", "tiled"):
            coords = {
                "global": _coord(ds, kernel, max_iter=40),
                "per-user": RandomEffectCoordinate(
                    name="per-user", dataset=ds, re_dataset=red,
                    problem=RandomEffectOptimizationProblem(
                        LOGISTIC, OptimizerConfig(max_iter=20),
                        RegularizationContext(RegularizationType.L2),
                        reg_weight=1.0,
                    ),
                ),
            }
            before = _scored("tiled")
            result = CoordinateDescent(coords, ds, TASK).run(num_iterations=2)
            history[kernel] = result.objective_history
            scored[kernel] = _scored("tiled") - before
        # the starting model, then the solved one of each iteration
        assert scored == {"scatter": 0, "tiled": 3}
        assert len(history["tiled"]) == 2
        np.testing.assert_allclose(
            history["tiled"], history["scatter"], rtol=1e-5
        )


class TestTheSchedulesAreBuiltOnce:
    @pytest.mark.parametrize("first", ["score", "prepare", "beside"])
    def test_score_and_prepare_share_one_build(self, rng, first):
        """Whichever of ``score()`` and ``prepare()`` comes first builds
        the z and the g schedule; the other, on its own thread or not,
        finds them."""
        from photon_ml_tpu.parallel import overlap

        ds = _dataset(rng, 1003, WINDOW + 37)
        coord = _coord(ds)
        model = coord.initialize_model()
        with overlap.overlap_scope(True), obs_trace.tracing_scope(True):
            obs_trace.tracer().clear()
            if first == "score":
                coord.score(model)
                coord.prepare(model)
            elif first == "prepare":
                coord.prepare(model)
                coord.score(model)
            else:  # as CoordinateDescent.run() has them: prefetch, then score
                future = overlap.submit(coord.prepare, model)
                coord.score(model)
                overlap.wait(future)
            coord.update_model(model, None)
            coord.score(model)
            spans = obs_trace.tracer().drain()
        assert len(_builds(spans)) == 2
        assert [s.attrs["cache"] for s in _builds(spans)] == ["miss", "miss"]
