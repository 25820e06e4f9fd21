"""Pod-scale GAME (game/pod.py): entity-sharded random-effect banks,
two-hop all_to_all residual routing, cross-replica sharded updates.

Weak-scaling contract pinned here:
- sharded CD == replicated CD (objective and coefficients inside the
  established fp32 envelopes) at 1/2/4/8 virtual devices;
- ZERO host gathers on the routed update/score path (counted via the
  overlap.device_get seam), one batched readback per CD iteration;
- per-device bank + optimizer-state bytes at N shards <= ~1/N of the
  replicated bank (plus hash-padding slack) — the memory story that
  makes "hundreds of billions of coefficients" (PAPER.md) a mesh-size
  property instead of a host-size property;
- streaming x sharded composes end-to-end through the training driver.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.game.config import (
    ProjectorType,
    RandomEffectDataConfiguration,
)
from photon_ml_tpu.game.coordinate import (
    FixedEffectCoordinate,
    PodRandomEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
from photon_ml_tpu.game.data import EntityIndex, GameDataset, ShardData
from photon_ml_tpu.game.pod import (
    EntityShardSpec,
    PodRandomEffectProblem,
    ShardedREBank,
    per_device_bytes,
)
from photon_ml_tpu.game.random_effect import (
    RandomEffectOptimizationProblem,
    score_random_effect,
)
from photon_ml_tpu.game.random_effect_data import build_random_effect_dataset
from photon_ml_tpu.game.residual_routing import PodResidualRouter
from photon_ml_tpu.ops.losses import LOGISTIC, loss_for_task
from photon_ml_tpu.optim.config import (
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
)
from photon_ml_tpu.optim.problem import create_glm_problem
from photon_ml_tpu.parallel import overlap
from photon_ml_tpu.parallel.mesh import entity_mesh
from photon_ml_tpu.task import TaskType
from photon_ml_tpu.utils.index_map import IndexMap, feature_key

sys.path.insert(0, os.path.dirname(__file__))


# ---------------------------------------------------------------------------
# synthetic data
# ---------------------------------------------------------------------------


def _synthetic_re(seed=0, n=257, E=37, d=12, k=4):
    """GameDataset + IDENTITY-projected RandomEffectDataset with weight-0
    rows, multiple capacity classes and an uneven entity histogram."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, E, size=n).astype(np.int32)
    ix = rng.integers(0, d, size=(n, k)).astype(np.int32)
    v = rng.normal(size=(n, k)).astype(np.float32)
    lab = (rng.uniform(size=n) > 0.5).astype(np.float32)
    w = np.ones(n, np.float32)
    w[::17] = 0.0
    off = (rng.normal(size=n) * 0.1).astype(np.float32)
    imap = IndexMap.build(
        (feature_key(f"f{i}", "") for i in range(d)), add_intercept=False
    )
    ds = GameDataset(
        uids=[str(i) for i in range(n)],
        labels=lab, offsets=off, weights=w,
        shards={
            "s": ShardData(
                indices=ix, values=v, index_map=imap, intercept_index=None
            )
        },
        entity_codes={"user": codes},
        entity_indexes={
            "user": EntityIndex.build(
                "user", [f"e{i:03d}" for i in range(E)]
            )
        },
        num_real_rows=n,
    )
    red = build_random_effect_dataset(
        ds,
        RandomEffectDataConfiguration(
            random_effect_type="user", feature_shard_id="s",
            projector_type=ProjectorType.IDENTITY,
        ),
    )
    return ds, red


def _problem(**kw):
    from photon_ml_tpu.optim.config import RegularizationType

    kw.setdefault("reg_weight", 0.5)
    return RandomEffectOptimizationProblem(
        LOGISTIC, OptimizerConfig(max_iter=5),
        RegularizationContext(RegularizationType.L2), **kw
    )


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------


class TestPodResidualRouter:
    @pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
    def test_route_in_out_round_trip(self, n_dev, rng):
        """route_out(route_in(x)) == x on every owned row: the two hops
        are exact inverses on the block layout."""
        mesh = entity_mesh(n_dev)
        codes = rng.integers(-1, 23, size=130).astype(np.int64)
        router = PodResidualRouter(mesh, codes)
        vals = rng.normal(size=130).astype(np.float32)
        slots = router.route_in(jnp.asarray(vals))
        back = np.asarray(router.route_out(slots))[:130]
        np.testing.assert_array_equal(
            back[codes >= 0], vals[codes >= 0]
        )
        assert (back[codes < 0] == 0).all()

    def test_slots_land_on_hash_owner(self, rng):
        """Every routed value sits in the slot table of the device its
        entity hashes to (code % n_dev)."""
        mesh = entity_mesh(4)
        codes = rng.integers(0, 17, size=64).astype(np.int64)
        router = PodResidualRouter(mesh, codes)
        for owner in range(4):
            gids = router.slot_row[owner]
            owned = gids[gids >= 0]
            assert (codes[owned] % 4 == owner).all()
        # each row appears exactly once across the owner tables
        all_gids = router.slot_row[router.slot_row >= 0]
        assert sorted(all_gids.tolist()) == list(range(64))

    def test_zero_host_readbacks(self, rng):
        mesh = entity_mesh(4)
        codes = rng.integers(0, 11, size=40).astype(np.int64)
        router = PodResidualRouter(mesh, codes)
        vals = jnp.asarray(rng.normal(size=40).astype(np.float32))
        overlap.reset_readback_stats()
        out = router.route_out(router.route_in(vals))
        out.block_until_ready()
        assert overlap.readback_stats() == 0


# ---------------------------------------------------------------------------
# sharded bank
# ---------------------------------------------------------------------------


class TestShardedBank:
    @pytest.mark.parametrize("n_dev", [1, 3, 8])
    def test_global_round_trip(self, n_dev, rng):
        mesh = entity_mesh(n_dev)
        spec = EntityShardSpec(n_dev, 41)
        bank = rng.normal(size=(41, 7)).astype(np.float32)
        sb = ShardedREBank.from_global(mesh, spec, bank)
        np.testing.assert_array_equal(np.asarray(sb.to_global()), bank)

    def test_per_device_bytes_scale_with_shards(self):
        """THE weak-scaling pin: at 8 shards each device holds ~1/8 of
        the replicated bank's bytes (exact here — E divides 8)."""
        E, d = 1024, 16
        replicated_bytes = E * d * 4
        sb = ShardedREBank.zeros(
            entity_mesh(8), EntityShardSpec(8, E), d
        )
        assert sb.per_device_bytes() == replicated_bytes // 8

    def test_hash_placement(self):
        """Entity e lives on shard e % n at local row e // n."""
        spec = EntityShardSpec(4, 10)
        rows = spec.sharded_row_of(np.arange(10))
        e_loc = spec.rows_per_shard
        assert e_loc == 3
        np.testing.assert_array_equal(
            rows, (np.arange(10) % 4) * e_loc + np.arange(10) // 4
        )


# ---------------------------------------------------------------------------
# sharded update parity
# ---------------------------------------------------------------------------


class TestShardedUpdateParity:
    @pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
    def test_update_and_score_match_replicated(self, n_dev, rng):
        """The tentpole parity: sharded update_bank == replicated
        update_bank (converged entities freeze bitwise under vmap, so
        the split-by-hash grouping cannot perturb any entity's solve),
        tracker aggregates equal, routed scores equal replicated
        scores."""
        ds, red = _synthetic_re()
        resid = jnp.asarray(
            ds.offsets + (rng.normal(size=ds.num_rows) * 0.05).astype(
                np.float32
            )
        )
        ref_bank, ref_tracker = _problem().update_bank(
            jnp.zeros((red.num_entities, red.local_dim), jnp.float32),
            red, residual_offsets=resid,
        )
        ref_scores = np.asarray(score_random_effect(ref_bank, red))

        pod = PodRandomEffectProblem(_problem(), entity_mesh(n_dev))
        new_bank, tracker = pod.update_bank(
            pod.init_bank(red), red, residual_offsets=resid
        )
        np.testing.assert_allclose(
            np.asarray(new_bank.to_global()), np.asarray(ref_bank),
            atol=1e-5, rtol=1e-5,
        )
        assert tracker.num_entities == ref_tracker.num_entities
        assert tracker.iterations_mean == ref_tracker.iterations_mean
        assert tracker.reason_counts == ref_tracker.reason_counts
        np.testing.assert_allclose(
            np.asarray(pod.score(new_bank, red)), ref_scores,
            atol=1e-5, rtol=1e-5,
        )

    def test_variances_match_replicated(self, rng):
        ds, red = _synthetic_re()
        resid = jnp.asarray(ds.offsets)
        ref_bank, _, ref_var = _problem().update_bank(
            jnp.zeros((red.num_entities, red.local_dim), jnp.float32),
            red, residual_offsets=resid, with_variances=True,
        )
        pod = PodRandomEffectProblem(_problem(), entity_mesh(4))
        bank, _, var = pod.update_bank(
            pod.init_bank(red), red, residual_offsets=resid,
            with_variances=True,
        )
        np.testing.assert_allclose(
            np.asarray(bank.to_global()), np.asarray(ref_bank),
            atol=1e-5, rtol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(var.to_global()), np.asarray(ref_var),
            atol=1e-5, rtol=1e-5,
        )

    def test_tron_kind_matches_replicated(self, rng):
        """Solver-family selection rides the GLOBAL bucket shapes, so a
        TRON config exercises the same kind on both paths."""
        from photon_ml_tpu.optim.config import RegularizationType

        ds, red = _synthetic_re(n=127, E=13)
        resid = jnp.asarray(ds.offsets)

        def tron_problem():
            return RandomEffectOptimizationProblem(
                LOGISTIC, OptimizerConfig(
                    max_iter=4, optimizer_type=OptimizerType.TRON
                ),
                RegularizationContext(RegularizationType.L2),
                reg_weight=0.3,
            )

        ref_bank, _ = tron_problem().update_bank(
            jnp.zeros((red.num_entities, red.local_dim), jnp.float32),
            red, residual_offsets=resid,
        )
        pod = PodRandomEffectProblem(tron_problem(), entity_mesh(4))
        bank, _ = pod.update_bank(
            pod.init_bank(red), red, residual_offsets=resid
        )
        np.testing.assert_allclose(
            np.asarray(bank.to_global()), np.asarray(ref_bank),
            atol=2e-5, rtol=1e-4,
        )

    def test_update_requires_residual_vector(self):
        _, red = _synthetic_re()
        pod = PodRandomEffectProblem(_problem(), entity_mesh(2))
        with pytest.raises(ValueError, match="row-aligned"):
            pod.update_bank(pod.init_bank(red), red)

    def test_base_problem_must_be_meshless(self):
        with pytest.raises(ValueError, match="mesh-less"):
            PodRandomEffectProblem(
                _problem(mesh=entity_mesh(2)), entity_mesh(2)
            )


# ---------------------------------------------------------------------------
# routed-path readback discipline
# ---------------------------------------------------------------------------


class TestRoutedPathDiscipline:
    def test_zero_host_gathers_in_update_and_score(self, rng):
        """The acceptance pin: the residual-routing hot path (route in,
        sharded solve, score, route back) crosses the host boundary
        exactly ZERO times — every device_get in the package is counted
        through the overlap seam."""
        ds, red = _synthetic_re()
        pod = PodRandomEffectProblem(_problem(), entity_mesh(8))
        pod.prepare(red)  # stage tables/blocks outside the counted window
        bank = pod.init_bank(red)
        resid = jnp.asarray(ds.offsets)
        with overlap.overlap_scope(True):
            overlap.reset_readback_stats()
            bank, tracker = pod.update_bank(
                bank, red, residual_offsets=resid, defer_tracker=True
            )
            scores = pod.score(bank, red)
            scores.block_until_ready()
            jax.block_until_ready(bank.data)
            assert overlap.readback_stats() == 0
            # the deferred tracker fetch is the CD loop's ONE batched
            # readback — forcing it is exactly one counted crossing
            overlap.fetch_all([tracker.deferred])
            assert overlap.readback_stats() == 1

    def test_cd_loop_one_readback_per_iteration(self, rng):
        ds, red = _synthetic_re(n=96, E=11)
        cd = _build_cd(ds, red, entity_mesh(4))
        with overlap.overlap_scope(True):
            overlap.reset_readback_stats()
            cd.run(2)
            assert overlap.readback_stats() == 2


# ---------------------------------------------------------------------------
# CD parity + weak-scaling bytes
# ---------------------------------------------------------------------------


def _build_cd(ds, red, pod_mesh=None, num_fe_iter=5):
    task = TaskType.LOGISTIC_REGRESSION
    loss = loss_for_task(task)
    fe_problem = create_glm_problem(
        task, ds.shards["s"].dim, config=OptimizerConfig(max_iter=num_fe_iter)
    )
    coords = {
        "fixed": FixedEffectCoordinate(
            name="fixed", dataset=ds, problem=fe_problem,
            feature_shard_id="s", reg_weight=0.1,
        ),
    }
    rep = _problem()
    if pod_mesh is None:
        coords["per-user"] = RandomEffectCoordinate(
            name="per-user", dataset=ds, re_dataset=red, problem=rep
        )
    else:
        coords["per-user"] = PodRandomEffectCoordinate(
            name="per-user", dataset=ds, re_dataset=red, problem=rep,
            mesh=pod_mesh,
        )
    return CoordinateDescent(coords, ds, task)


class TestShardedCDParity:
    @pytest.mark.parametrize("n_dev", [2, 8])
    def test_full_cd_matches_replicated(self, n_dev, rng):
        ds, red = _synthetic_re(n=96, E=11)
        ref = _build_cd(ds, red).run(2)
        res = _build_cd(ds, red, entity_mesh(n_dev)).run(2)
        np.testing.assert_allclose(
            res.objective_history, ref.objective_history, rtol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(res.model.models["per-user"].bank),
            np.asarray(ref.model.models["per-user"].bank),
            atol=1e-3, rtol=1e-3,
        )
        np.testing.assert_allclose(
            np.asarray(res.model.models["fixed"].model.means),
            np.asarray(ref.model.models["fixed"].model.means),
            atol=1e-3, rtol=1e-3,
        )


def _driver_pod_cd(tmp_path, ds, red, entity_shards=4):
    """The descent the pod cell runs, through the GAME driver's own
    coordinates: ``--entity-shards`` of the eight virtual devices, a
    data-parallel fixed effect over the same devices, the users' bank
    hash-partitioned over them."""
    from photon_ml_tpu.cli import game_training_driver as gtd

    driver = gtd.GameTrainingDriver(gtd.params_from_args([
        "--task-type", "LOGISTIC_REGRESSION",
        "--feature-shard-id-to-feature-section-keys-map", "s:features",
        "--fixed-effect-data-configurations", "global:s,1",
        "--fixed-effect-optimization-configurations",
        "global:10,1e-7,1.0,1,LBFGS,L2",
        "--random-effect-data-configurations",
        "per-user:user,s,1,none,none,none,IDENTITY",
        "--random-effect-optimization-configurations",
        "per-user:20,1e-5,1.0,1,LBFGS,L2",
        "--updating-sequence", "global,per-user",
        "--num-iterations", "1",
        "--entity-shards", str(entity_shards),
        "--train-input-dirs", str(tmp_path / "unused"),
        "--output-dir", str(tmp_path / "out"),
    ]))
    p = driver.params
    combo = gtd.expand_config_grid(
        {**p.fixed_effect_opt_configs, **p.random_effect_opt_configs}
    )[0]
    coords = driver._build_coordinates(ds, {"per-user": red}, combo)
    assert isinstance(coords["per-user"], PodRandomEffectCoordinate)
    return CoordinateDescent(
        coords, ds, p.task_type, update_sequence=p.updating_sequence
    )


def _cd_step(cd):
    """One step as the benchmark's cells take it: ``run(1)`` from zero,
    closed on the models, the trackers read."""
    result = cd.run(1)
    jax.block_until_ready([
        result.model.get_model("global").model.coefficients.means,
        result.model.get_model("per-user").sharded_bank.data,
    ])
    assert np.isfinite(result.objective_history[-1])
    int(result.trackers["global"][-1].iterations)
    float(result.trackers["per-user"][-1].iterations_mean)
    return result


class TestWarmPodDescent:
    def test_zero_lowerings_in_warm_pod_steps(self, tmp_path):
        """Once one step has run, two more steps of the entity-mesh
        descent on 4 of the 8 devices lower NOTHING: no program of the
        pod path (router, bank, scoring, fixed effect, objective) takes a
        static argument, a shape or a cache key that is new on a later
        step. A step that lowers inside the timed window costs the pod
        cell its compile time on every step."""
        import jax._src.test_util as jtu

        ds, red = _synthetic_re(n=192, E=23)
        cd = _driver_pod_cd(tmp_path, ds, red)
        _cd_step(cd)  # warm
        with jtu.count_jit_and_pmap_lowerings() as count:
            _cd_step(cd)
            _cd_step(cd)
        assert count() == 0, count()


class TestWeakScalingBytes:
    def test_per_device_bank_bytes_bounded_at_8_shards(self):
        """Acceptance: at N=8, per-device RE bank + optimizer-state
        bytes <= (1/8 + slack) of the replicated path for the same
        model. Slack covers hash padding (<= one row per shard) only."""
        E, d = 1000, 32  # deliberately NOT divisible by 8
        n_dev = 8
        replicated = E * d * 4
        spec = EntityShardSpec(n_dev, E)
        mesh = entity_mesh(n_dev)
        bank = ShardedREBank.zeros(mesh, spec, d)
        var = ShardedREBank.zeros(mesh, spec, d)
        got = per_device_bytes(bank, var)
        pad_slack = n_dev * spec.rows_per_shard * d * 4 - replicated
        assert got <= (2 * replicated) // n_dev + pad_slack + 4096
        # and the sharded total equals the padded bank, not N copies
        total = sum(
            int(s.data.nbytes)
            for a in (bank.data, var.data)
            for s in a.addressable_shards
        )
        assert total == 2 * n_dev * spec.rows_per_shard * d * 4

    def test_dataset_blocks_shard_too(self, rng):
        """The staged per-entity data (solver blocks + scoring slots)
        also scales down per device: at 8 shards each device stages
        < 40% of what 1 shard stages (padding keeps it above 1/8 at
        this tiny size)."""
        _, red = _synthetic_re(n=1024, E=128, d=8, k=4)
        v1 = PodRandomEffectProblem(_problem(), entity_mesh(1)).pod_view(red)
        v8 = PodRandomEffectProblem(_problem(), entity_mesh(8)).pod_view(red)
        assert (
            v8.per_device_data_bytes() < 0.4 * v1.per_device_data_bytes()
        )


# ---------------------------------------------------------------------------
# streaming x sharded
# ---------------------------------------------------------------------------


class TestStreamingSharded:
    def test_streamed_sharded_matches_streamed_replicated(
        self, tmp_path, rng
    ):
        """Streaming composes with entity sharding: same objectives,
        same final banks (the segment split by hash + psum chunk scoring
        reproduce the replicated streamed math bitwise-or-near)."""
        from test_streaming_game import (
            FE_DATA, RE_DATA, SHARDS, _combo, _write_game_files,
        )

        from photon_ml_tpu.game.streaming import train_streaming_game

        train = str(tmp_path / "train")
        _write_game_files(train, rng, n_files=2, rows_per_file=80)
        combo = _combo("30,1e-6,0.5,1,TRON,L2", "30,1e-6,1.0,1,LBFGS,L2")
        ref, _ = train_streaming_game(
            [train], SHARDS, FE_DATA, RE_DATA, combo,
            TaskType.LOGISTIC_REGRESSION, num_iterations=2,
            memory_budget_bytes=100 * 60,
        )
        res, extras = train_streaming_game(
            [train], SHARDS, FE_DATA, RE_DATA, combo,
            TaskType.LOGISTIC_REGRESSION, num_iterations=2,
            memory_budget_bytes=100 * 60,
            entity_mesh=entity_mesh(4),
        )
        assert extras["store"].count >= 2
        np.testing.assert_allclose(
            res.objective_history, ref.objective_history, rtol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(res.game_model.get_model("per-user").bank),
            np.asarray(ref.game_model.get_model("per-user").bank),
            atol=1e-5, rtol=1e-5,
        )

    def test_driver_streaming_sharded_end_to_end(self, tmp_path, rng):
        """--streaming --entity-shards through the real driver: same
        objective history as the replicated streamed driver run, model
        artifact round-trips."""
        from test_streaming_game import (
            FE_DATA, RE_DATA, SHARDS, _write_game_files,
        )

        from photon_ml_tpu.cli.game_training_driver import (
            GameTrainingDriver,
            GameTrainingParams,
        )
        from photon_ml_tpu.game.model_io import load_game_model

        train = str(tmp_path / "train")
        _write_game_files(train, rng, n_files=2, rows_per_file=80)

        def run(tag, entity_shards):
            params = GameTrainingParams(
                train_input_dirs=[train],
                output_dir=str(tmp_path / tag),
                task_type=TaskType.LOGISTIC_REGRESSION,
                feature_shards=SHARDS,
                fixed_effect_data_configs=dict(FE_DATA),
                fixed_effect_opt_configs={
                    "global": "30,1e-6,0.5,1,TRON,L2"
                },
                random_effect_data_configs=dict(RE_DATA),
                random_effect_opt_configs={
                    "per-user": "30,1e-6,1.0,1,LBFGS,L2"
                },
                num_iterations=2,
                streaming=True,
                stream_memory_budget=100 * 60,
                entity_shards=entity_shards,
            )
            GameTrainingDriver(params).run()
            return json.load(
                open(os.path.join(params.output_dir, "metrics.json"))
            )

        m_sharded = run("out-sharded", 4)
        m_ref = run("out-ref", None)
        np.testing.assert_allclose(
            m_sharded["objective_history"], m_ref["objective_history"],
            rtol=1e-6,
        )
        loaded = load_game_model(
            os.path.join(str(tmp_path / "out-sharded"), "best-model")
        )
        assert set(loaded.coordinate_names()) == {"global", "per-user"}

    def test_driver_in_memory_sharded_end_to_end(self, tmp_path, rng):
        """--entity-shards through the IN-MEMORY driver path (pod
        coordinates, lazy-bank model export, validation scoring):
        objective parity vs the replicated driver run, artifact
        round-trips."""
        from test_streaming_game import (
            FE_DATA, RE_DATA, SHARDS, _write_game_files,
        )

        from photon_ml_tpu.cli.game_training_driver import (
            GameTrainingDriver,
            GameTrainingParams,
        )
        from photon_ml_tpu.game.model_io import load_game_model

        train = str(tmp_path / "train")
        val = str(tmp_path / "val")
        _write_game_files(train, rng, n_files=1, rows_per_file=120)
        _write_game_files(val, rng, n_files=1, rows_per_file=80)

        def run(tag, entity_shards):
            params = GameTrainingParams(
                train_input_dirs=[train],
                validate_input_dirs=[val],
                output_dir=str(tmp_path / tag),
                task_type=TaskType.LOGISTIC_REGRESSION,
                feature_shards=SHARDS,
                fixed_effect_data_configs=dict(FE_DATA),
                fixed_effect_opt_configs={
                    "global": "20,1e-6,0.5,1,LBFGS,L2"
                },
                random_effect_data_configs=dict(RE_DATA),
                random_effect_opt_configs={
                    "per-user": "20,1e-6,1.0,1,LBFGS,L2"
                },
                num_iterations=2,
                distributed="off",
                entity_shards=entity_shards,
            )
            GameTrainingDriver(params).run()
            return json.load(
                open(os.path.join(params.output_dir, "metrics.json"))
            )

        m_sharded = run("mem-sharded", -1)  # all 8 virtual devices
        m_ref = run("mem-ref", None)
        np.testing.assert_allclose(
            m_sharded["objective_history"], m_ref["objective_history"],
            rtol=1e-5,
        )
        assert m_sharded["validation_history"]
        loaded = load_game_model(
            os.path.join(str(tmp_path / "mem-sharded"), "best-model")
        )
        assert set(loaded.coordinate_names()) == {"global", "per-user"}

    def test_streaming_sharded_rejects_variances(self, tmp_path):
        from photon_ml_tpu.game.streaming import (
            StreamingRandomEffectCoordinate,
        )

        with pytest.raises(ValueError, match="compute_variances"):
            StreamingRandomEffectCoordinate(
                name="x", store=None, spilled=None,
                problem=_problem(compute_variances=True),
                config=RandomEffectDataConfiguration(
                    "user", "s", projector_type=ProjectorType.IDENTITY
                ),
                local_dim=4,
                mesh=entity_mesh(2),
            )


# ---------------------------------------------------------------------------
# driver policy
# ---------------------------------------------------------------------------


class TestEntityShardPolicy:
    def test_resolve_entity_shards(self):
        from photon_ml_tpu.training import resolve_entity_shards

        assert resolve_entity_shards(None, num_devices=8) is None
        assert resolve_entity_shards(0, num_devices=8) is None
        assert resolve_entity_shards(-1, num_devices=8) == 8
        assert resolve_entity_shards(1, num_devices=8) == 1
        assert resolve_entity_shards(4, num_devices=8) == 4
        with pytest.raises(ValueError, match="out of range"):
            resolve_entity_shards(9, num_devices=8)

    def test_driver_rejects_entity_shards_with_factored(self):
        from photon_ml_tpu.cli.game_training_driver import GameTrainingParams
        from photon_ml_tpu.game.config import (
            FactoredRandomEffectConfiguration,
            FeatureShardConfiguration,
            FixedEffectDataConfiguration,
        )

        params = GameTrainingParams(
            train_input_dirs=["x"],
            output_dir="y",
            feature_shards=[
                FeatureShardConfiguration("g", ["features"])
            ],
            fixed_effect_data_configs={
                "fe": FixedEffectDataConfiguration("g")
            },
            fixed_effect_opt_configs={"fe": "10,1e-6,0.1,1,LBFGS,L2"},
            random_effect_data_configs={
                "re": RandomEffectDataConfiguration("user", "g")
            },
            random_effect_opt_configs={"re": "10,1e-6,0.1,1,LBFGS,L2"},
            factored_re_configs={
                "re": FactoredRandomEffectConfiguration(2, 1)
            },
            entity_shards=4,
        )
        with pytest.raises(ValueError, match="plain random-effect"):
            params.validate()

    @staticmethod
    def _driver(out_dir, **kw):
        from photon_ml_tpu.cli.game_training_driver import (
            GameTrainingDriver,
            GameTrainingParams,
        )
        from photon_ml_tpu.game.config import (
            FeatureShardConfiguration,
            FixedEffectDataConfiguration,
        )

        return GameTrainingDriver(GameTrainingParams(
            train_input_dirs=["x"],
            output_dir=str(out_dir),
            feature_shards=[
                FeatureShardConfiguration("g", ["features"])
            ],
            fixed_effect_data_configs={
                "fe": FixedEffectDataConfiguration("g")
            },
            fixed_effect_opt_configs={"fe": "10,1e-6,0.1,1,LBFGS,L2"},
            random_effect_data_configs={
                "re": RandomEffectDataConfiguration("user", "g")
            },
            random_effect_opt_configs={"re": "10,1e-6,0.1,1,LBFGS,L2"},
            **kw,
        ))

    def test_partial_entity_mesh_restricts_data_mesh(self, tmp_path):
        """--entity-shards N < visible devices: the driver's data and FE
        meshes must span EXACTLY the pod entity device set. CD row
        currency (scores, residuals) is committed to the entity
        devices, and jit refuses `residual + new_score` across two
        device sets (regression: distributed=auto + entity_shards=2
        used to build an 8-device data mesh next to the 2-device pod
        mesh and crash in the first CD iteration)."""
        d = self._driver(
            tmp_path / "a", distributed="auto", entity_shards=2
        )
        pod_ids = [dev.id for dev in d._entity_mesh().devices.flat]
        assert [dev.id for dev in d._mesh().devices.flat] == pod_ids
        assert [dev.id for dev in d._fe_mesh().devices.flat] == pod_ids

        # full entity mesh: the data mesh spans all devices unchanged
        d = self._driver(
            tmp_path / "b", distributed="auto", entity_shards=-1
        )
        assert d._mesh().devices.size == len(jax.devices())

        # 1-entity-shard run is effectively single-device: no data mesh
        # (unmeshed FE scores follow the pod placement)
        d = self._driver(
            tmp_path / "c", distributed="auto", entity_shards=1
        )
        assert d._mesh() is None

        # feature mode: the 2-D (data, model) FE mesh restricts too
        d = self._driver(
            tmp_path / "d",
            distributed="feature", entity_shards=4, model_shards=2,
        )
        fe = d._fe_mesh()
        assert sorted(dev.id for dev in fe.devices.flat) == sorted(
            dev.id for dev in d._entity_mesh().devices.flat
        )
        assert fe.shape["model"] == 2
        with pytest.raises(ValueError, match="does not divide"):
            self._driver(
                tmp_path / "e",
                distributed="feature", entity_shards=3, model_shards=2,
            )._fe_mesh()

    def test_driver_auto_distributed_partial_shards(self, tmp_path, rng):
        """The regression flow end to end: in-memory driver,
        distributed=auto, entity_shards=2 of 8."""
        from test_streaming_game import (
            FE_DATA, RE_DATA, SHARDS, _write_game_files,
        )

        from photon_ml_tpu.cli.game_training_driver import (
            GameTrainingDriver,
            GameTrainingParams,
        )

        train = str(tmp_path / "train")
        _write_game_files(train, rng, n_files=1, rows_per_file=120)
        params = GameTrainingParams(
            train_input_dirs=[train],
            output_dir=str(tmp_path / "out"),
            task_type=TaskType.LOGISTIC_REGRESSION,
            feature_shards=SHARDS,
            fixed_effect_data_configs=dict(FE_DATA),
            fixed_effect_opt_configs={
                "global": "20,1e-6,0.5,1,LBFGS,L2"
            },
            random_effect_data_configs=dict(RE_DATA),
            random_effect_opt_configs={
                "per-user": "20,1e-6,1.0,1,LBFGS,L2"
            },
            num_iterations=2,
            distributed="auto",
            entity_shards=2,
        )
        GameTrainingDriver(params).run()
        m = json.load(
            open(os.path.join(params.output_dir, "metrics.json"))
        )
        h = m["objective_history"]
        assert len(h) == 2 and h[1] <= h[0] + 1e-6


# ---------------------------------------------------------------------------
# serving: one entity shard of a sharded model
# ---------------------------------------------------------------------------


class TestServingEntityShard:
    def _full_and_shards(self, n_shards=4, E=23, d=6):
        from photon_ml_tpu.serving.model_bank import bank_from_arrays

        rng = np.random.default_rng(3)
        ids = sorted(f"user{i:04d}" for i in range(E))
        bank = rng.normal(size=(E, d)).astype(np.float32)
        kw = dict(
            fixed=[("fe", "g", rng.normal(size=(d,)).astype(np.float32))],
            random=[("re", "user", "g", bank, ids)],
            shard_widths={"g": 4},
            entity_pad_to=8,
        )
        full = bank_from_arrays(**kw)
        shards = [
            bank_from_arrays(**kw, entity_shard=(s, n_shards))
            for s in range(n_shards)
        ]
        return ids, bank, full, shards

    def test_owned_rows_match_full_bank(self):
        ids, bank, full, shards = self._full_and_shards()
        for s, sb in enumerate(shards):
            idx = sb.entity_rows["user"]
            assert idx.shard == (s, 4)
            for code, raw in enumerate(ids):
                row = idx.row_of(raw)
                if code % 4 == s:
                    assert row >= 0
                    np.testing.assert_array_equal(
                        np.asarray(sb.arrays["re"][row]), bank[code]
                    )
                else:
                    # unknown-shard entity: row -1 -> FE-only scoring,
                    # the batcher's existing masked-row semantics
                    assert row == -1

    def test_shards_partition_the_entity_set(self):
        ids, _, full, shards = self._full_and_shards()
        owned = [set(sb.entity_rows["user"].ids) for sb in shards]
        union = set().union(*owned)
        assert union == set(ids)
        assert sum(len(o) for o in owned) == len(ids)  # disjoint

    def test_shard_bank_is_smaller(self):
        _, _, full, shards = self._full_and_shards()
        full_bytes = full.device_bytes()
        for sb in shards:
            assert sb.device_bytes() < full_bytes

    def test_sharded_artifact_load_scores_fe_only_off_shard(self, rng):
        """End-to-end through build_model_bank + the micro-batcher: a
        server loading ONE entity shard of a trained GAME artifact
        scores owned entities BITWISE like the full bank and FE-only
        (bitwise the unknown-entity path) for entities another shard
        owns."""
        from test_serving import make_bank, synth_model, synth_records

        from photon_ml_tpu.game.data import build_game_dataset
        from photon_ml_tpu.serving.batcher import (
            MicroBatcher,
            requests_from_dataset,
        )
        from photon_ml_tpu.serving.programs import ServingPrograms

        recs = synth_records(rng)
        from test_serving import SHARDS as SERVING_SHARDS

        ds = build_game_dataset(recs, SERVING_SHARDS, ["userId"])
        lm = synth_model(rng, drop_user=False)
        full = make_bank(lm, ds)
        shard0 = make_bank(lm, ds, entity_shard=(0, 2))

        def score_all(bank_, reqs):
            programs = ServingPrograms((1, 8, 64))
            programs.ensure_compiled(bank_)
            with MicroBatcher(lambda: bank_, programs) as mb:
                futs = [mb.submit(r) for r in reqs]
                return np.asarray([f.result() for f in futs], np.float32)

        reqs = requests_from_dataset(ds, full)
        full_scores = score_all(full, reqs)
        shard_scores = score_all(shard0, reqs)
        # FE-only reference: the same rows with their entity UNKNOWN
        import dataclasses

        fe_reqs = [
            dataclasses.replace(r, entity_ids={"userId": "no-such-user"})
            for r in reqs
        ]
        fe_only = score_all(full, fe_reqs)

        owned_ids = set(shard0.entity_rows["userId"].ids)
        for i, r in enumerate(reqs):
            raw = r.entity_ids.get("userId")
            if raw in owned_ids:
                assert shard_scores[i] == full_scores[i]
            else:
                assert shard_scores[i] == fe_only[i]
        # both cases actually occur in the trace
        assert any(r.entity_ids.get("userId") in owned_ids for r in reqs)
        assert any(
            r.entity_ids.get("userId") not in owned_ids for r in reqs
        )
