"""Out-of-core GAME training (game/streaming.py): streamed coordinate
descent over spilled chunks under a host-memory budget.

Parity philosophy: the streamed CD runs the SAME math as the in-memory
CD (same index spaces — both maps sort keys; same entity codes; same
bucket contents; same residual algebra) but accumulates objective
partials chunk-by-chunk and drives the FE solve host-side. fp32
reordering noise (~1e-7/evaluation) is amplified through optimizer
iterates, so coefficient agreement lands at ~1e-4 relative after a full
CD run (the TRON fixed effect is the tightest pairing — its host driver
walks the in-jit iterate sequence step for step); the OBJECTIVE agrees
much tighter (the round-7 measured envelopes).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from photon_ml_tpu.game.config import (
    FeatureShardConfiguration,
    FixedEffectDataConfiguration,
    ProjectorType,
    RandomEffectDataConfiguration,
)
from photon_ml_tpu.evaluation import EvaluatorType
from photon_ml_tpu.io.avro_codec import write_container
from photon_ml_tpu.optim.config import GLMOptimizationConfiguration
from photon_ml_tpu.task import TaskType


def _write_game_files(base, rng, *, n_files=3, rows_per_file=80, n_users=6,
                      d_g=5, d_u=3):
    from conftest import game_example_schema

    os.makedirs(base, exist_ok=True)
    w_g = np.linspace(-1, 1, d_g)
    w_u = np.random.default_rng(7).normal(size=(n_users, d_u))
    for fi in range(n_files):
        recs = []
        for i in range(rows_per_file):
            u = int(rng.integers(0, n_users))
            xg = rng.normal(size=d_g)
            xu = rng.normal(size=d_u)
            z = float(xg @ w_g + xu @ w_u[u])
            recs.append({
                "uid": f"f{fi}-{i}",
                "response": float(1 / (1 + np.exp(-z)) > rng.uniform()),
                "metadataMap": {"userId": f"user{u}"},
                "features": [
                    {"name": f"g{j}", "term": "", "value": float(xg[j])}
                    for j in range(d_g)
                ],
                "userFeatures": [
                    {"name": f"u{j}", "term": "", "value": float(xu[j])}
                    for j in range(d_u)
                ],
            })
        write_container(
            os.path.join(base, f"part-{fi}.avro"),
            game_example_schema(), recs,
        )


SHARDS = [
    FeatureShardConfiguration("globalShard", ["features"]),
    FeatureShardConfiguration("userShard", ["userFeatures"]),
]
FE_DATA = {"global": FixedEffectDataConfiguration("globalShard")}
RE_DATA = {
    "per-user": RandomEffectDataConfiguration(
        "userId", "userShard", projector_type=ProjectorType.IDENTITY
    )
}


def _combo(fe_spec, re_spec):
    return {
        "global": GLMOptimizationConfiguration.parse(fe_spec),
        "per-user": GLMOptimizationConfiguration.parse(re_spec),
    }


def _in_memory_cd(train_dir, combo, num_iterations):
    from photon_ml_tpu.game.coordinate import (
        FixedEffectCoordinate,
        RandomEffectCoordinate,
    )
    from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
    from photon_ml_tpu.game.data import build_game_dataset_from_files
    from photon_ml_tpu.game.random_effect import (
        RandomEffectOptimizationProblem,
    )
    from photon_ml_tpu.game.random_effect_data import (
        build_random_effect_dataset,
    )
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.optim.problem import create_glm_problem

    task = TaskType.LOGISTIC_REGRESSION
    ds = build_game_dataset_from_files([train_dir], SHARDS, ["userId"])
    red = build_random_effect_dataset(ds, RE_DATA["per-user"])
    coords = {
        "global": FixedEffectCoordinate(
            name="global", dataset=ds,
            problem=create_glm_problem(
                task, ds.shards["globalShard"].dim,
                config=combo["global"].optimizer_config,
                regularization=combo["global"].regularization,
                intercept_index=ds.shards["globalShard"].intercept_index,
            ),
            feature_shard_id="globalShard",
            reg_weight=combo["global"].reg_weight,
        ),
        "per-user": RandomEffectCoordinate(
            name="per-user", dataset=ds, re_dataset=red,
            problem=RandomEffectOptimizationProblem(
                loss_for_task(task),
                combo["per-user"].optimizer_config,
                combo["per-user"].regularization,
                reg_weight=combo["per-user"].reg_weight,
            ),
        ),
    }
    return CoordinateDescent(coords, ds, task).run(num_iterations), ds, red


class TestStreamingGameParity:
    def test_matches_in_memory_cd(self, tmp_path, rng):
        """Streamed GAME CD over >= 3 chunks == in-memory CD: same data,
        same RNG, same index/entity spaces. TRON fixed effect (host
        driver == in-jit iterate sequence), LBFGS random effects (the
        SAME fused bucket solvers run on identical bucket contents)."""
        from photon_ml_tpu.game.streaming import train_streaming_game

        train = str(tmp_path / "train")
        _write_game_files(train, rng)
        combo = _combo("50,1e-6,0.5,1,TRON,L2", "50,1e-6,1.0,1,LBFGS,L2")
        ref, _, _ = _in_memory_cd(train, combo, 2)
        res, extras = train_streaming_game(
            [train], SHARDS, FE_DATA, RE_DATA, combo,
            TaskType.LOGISTIC_REGRESSION, num_iterations=2,
            memory_budget_bytes=100 * 80,  # tiny -> many chunks
        )
        assert extras["store"].count >= 3
        # objective parity is tight (sum reordering only)
        np.testing.assert_allclose(
            res.objective_history, ref.objective_history, rtol=1e-4
        )
        ref_fe = np.asarray(ref.model.get_model("global").model.means)
        st_fe = np.asarray(res.game_model.get_model("global").model.means)
        np.testing.assert_allclose(st_fe, ref_fe, rtol=2e-3, atol=3e-4)
        ref_bank = np.asarray(ref.model.get_model("per-user").bank)
        st_bank = np.asarray(res.game_model.get_model("per-user").bank)
        np.testing.assert_allclose(st_bank, ref_bank, rtol=2e-3, atol=3e-4)

    def test_single_chunk_single_iteration_is_tight(self, tmp_path, rng):
        """With one CD iteration the only drift is inside the solves:
        the TRON FE and the bucket RE land at ~1e-5 of the in-memory
        fit (the coefficient-parity envelope before CD-level residual
        coupling compounds it)."""
        from photon_ml_tpu.game.streaming import train_streaming_game

        train = str(tmp_path / "train")
        _write_game_files(train, rng)
        combo = _combo("50,1e-6,0.5,1,TRON,L2", "50,1e-6,1.0,1,LBFGS,L2")
        ref, _, _ = _in_memory_cd(train, combo, 1)
        res, extras = train_streaming_game(
            [train], SHARDS, FE_DATA, RE_DATA, combo,
            TaskType.LOGISTIC_REGRESSION, num_iterations=1,
            memory_budget_bytes=100 * 80,
        )
        assert extras["store"].count >= 3
        ref_fe = np.asarray(ref.model.get_model("global").model.means)
        st_fe = np.asarray(res.game_model.get_model("global").model.means)
        scale = np.abs(ref_fe).max()
        assert np.abs(st_fe - ref_fe).max() <= 2e-4 * scale

    def test_bucket_structure_matches_in_memory(self, tmp_path, rng):
        """The spilled grouping reproduces the in-memory buckets: same
        entity->capacity classes, same per-entity sample sets in the
        same (ascending global row) order."""
        from photon_ml_tpu.game.random_effect_data import (
            build_random_effect_dataset,
        )
        from photon_ml_tpu.game.data import build_game_dataset_from_files
        from photon_ml_tpu.game.streaming import (
            SpilledREBuckets,
            scan_game_stream,
            stage_game_stream,
        )

        train = str(tmp_path / "train")
        _write_game_files(train, rng)
        imaps, eidx, stats = scan_game_stream(
            [train], SHARDS, ["userId"]
        )
        store, _ = stage_game_stream(
            [train], SHARDS, ["userId"], imaps, eidx, stats,
            rows_per_chunk=64,
        )
        spilled = SpilledREBuckets(
            store, "userId", "userShard", stats.entity_counts["userId"],
        )
        ds = build_game_dataset_from_files([train], SHARDS, ["userId"])
        red = build_random_effect_dataset(ds, RE_DATA["per-user"])
        mem = {}
        for b in red.buckets:
            for e_i, code in enumerate(b.entity_codes):
                rows = b.row_index[e_i]
                mem[int(code)] = (
                    b.capacity, rows[rows >= 0].tolist()
                )
        st = {}
        for codes, arrs in spilled.iter_segments():
            for e_i, code in enumerate(codes):
                rows = arrs["rows"][e_i]
                st[int(code)] = (
                    arrs["rows"].shape[1], rows[rows >= 0].tolist()
                )
        assert st == mem

    def test_streamed_validation_matches_in_memory_auc(self, tmp_path, rng):
        """Streamed GAME validation (histogram AUC over chunks) lands
        within 1e-3 of the exact sort-based AUC on the same scores."""
        from photon_ml_tpu.evaluation import (
            Evaluator,
        )
        from photon_ml_tpu.game.streaming import train_streaming_game

        import jax.numpy as jnp

        train = str(tmp_path / "train")
        val = str(tmp_path / "val")
        _write_game_files(train, rng)
        _write_game_files(val, rng, n_files=2, rows_per_file=150)
        combo = _combo("40,1e-6,0.5,1,TRON,L2", "40,1e-6,1.0,1,LBFGS,L2")
        res, extras = train_streaming_game(
            [train], SHARDS, FE_DATA, RE_DATA, combo,
            TaskType.LOGISTIC_REGRESSION, num_iterations=1,
            memory_budget_bytes=100 * 80, validate_paths=[val],
            evaluator_types=[EvaluatorType.parse("AUC")],
        )
        streamed_auc = res.validation_history[-1]["AUC"]
        # exact reference: rebuild total scores chunk-wise from the
        # exported model banks over the staged validation chunks
        vstore = extras["validate_store"]
        zs, labs, wgts = [], [], []
        fe = res.game_model.get_model("global")
        re_m = res.game_model.get_model("per-user")
        for i in range(vstore.count):
            c = vstore.chunk(i)
            w = np.asarray(fe.model.means)
            z = (c["v__globalShard"] * w[c["ix__globalShard"]]).sum(axis=1)
            codes = c["code__userId"]
            valid = (codes >= 0) & (c["wgt"] > 0)
            bank = np.asarray(re_m.bank)
            rows = bank[np.maximum(codes, 0)]
            z_u = np.take_along_axis(
                rows, c["ix__userShard"], axis=1
            )
            z = z + np.where(valid, (c["v__userShard"] * z_u).sum(axis=1), 0)
            zs.append(z + c["off"])
            labs.append(c["lab"])
            wgts.append(c["wgt"])
        z = np.concatenate(zs)
        exact = float(Evaluator(EvaluatorType.parse("AUC")).evaluate(
            jnp.asarray(z), jnp.asarray(np.concatenate(labs)),
            jnp.asarray(np.concatenate(wgts)),
        ))
        assert abs(streamed_auc - exact) < 1e-3


class TestStreamingGameGates:
    def _params(self, tmp_path, **kw):
        from photon_ml_tpu.cli.game_training_driver import GameTrainingParams

        base = dict(
            train_input_dirs=[str(tmp_path / "train")],
            output_dir=str(tmp_path / "out"),
            task_type=TaskType.LOGISTIC_REGRESSION,
            feature_shards=SHARDS,
            fixed_effect_data_configs=dict(FE_DATA),
            fixed_effect_opt_configs={"global": "20,1e-6,0.1,1,LBFGS,L2"},
            random_effect_data_configs=dict(RE_DATA),
            random_effect_opt_configs={"per-user": "20,1e-6,1.0,1,LBFGS,L2"},
            streaming=True,
        )
        base.update(kw)
        return GameTrainingParams(**base)

    def test_rejects_non_identity_projector(self, tmp_path):
        p = self._params(
            tmp_path,
            random_effect_data_configs={
                "per-user": RandomEffectDataConfiguration(
                    "userId", "userShard",
                    projector_type=ProjectorType.INDEX_MAP,
                )
            },
        )
        with pytest.raises(ValueError, match="IDENTITY projector"):
            p.validate()

    def test_rejects_active_data_cap(self, tmp_path):
        p = self._params(
            tmp_path,
            random_effect_data_configs={
                "per-user": RandomEffectDataConfiguration(
                    "userId", "userShard",
                    active_data_upper_bound=4,
                    projector_type=ProjectorType.IDENTITY,
                )
            },
        )
        with pytest.raises(ValueError, match="active-data-upper-bound"):
            p.validate()

    def test_streaming_checkpoint_supported_sharded_evaluator_not(
        self, tmp_path
    ):
        # round 11 (reliability layer): streaming + --checkpoint-dir is
        # now a SUPPORTED combination (staged-store manifests + per-
        # iteration CD snapshots), so validate() must accept it
        p = self._params(tmp_path, checkpoint_dir=str(tmp_path / "ckpt"))
        p.validate()
        p = self._params(
            tmp_path, evaluator_types=[EvaluatorType.parse("AUC:userId")]
        )
        with pytest.raises(ValueError, match="sharded evaluator"):
            p.validate()

    def test_rejects_budget_without_streaming_glm(self, tmp_path):
        from photon_ml_tpu.cli.glm_driver import GLMParams

        p = GLMParams(
            train_dir="x", output_dir="y", stream_memory_budget=1 << 20
        )
        with pytest.raises(ValueError, match="stream-memory-budget"):
            p.validate()


@pytest.mark.slow
class TestStreamingGameDriver:
    def test_driver_end_to_end(self, tmp_path, rng):
        """Streamed driver: trains over >= 3 chunks, streams validation,
        writes the standard best-model layout (round-trips through
        load_game_model) and reports the budget + RSS high-water in
        metrics.json."""
        from photon_ml_tpu.cli.game_training_driver import (
            GameTrainingDriver,
            GameTrainingParams,
        )
        from photon_ml_tpu.game.model_io import load_game_model

        train = str(tmp_path / "train")
        val = str(tmp_path / "val")
        _write_game_files(train, rng)
        _write_game_files(val, rng, n_files=2, rows_per_file=150)
        params = GameTrainingParams(
            train_input_dirs=[train],
            validate_input_dirs=[val],
            output_dir=str(tmp_path / "out"),
            task_type=TaskType.LOGISTIC_REGRESSION,
            feature_shards=SHARDS,
            fixed_effect_data_configs=dict(FE_DATA),
            fixed_effect_opt_configs={"global": "50,1e-6,0.5,1,TRON,L2"},
            random_effect_data_configs=dict(RE_DATA),
            random_effect_opt_configs={"per-user": "50,1e-6,1.0,1,LBFGS,L2"},
            num_iterations=2,
            evaluator_types=[EvaluatorType.parse("AUC")],
            streaming=True,
            stream_memory_budget=100 * 80,
        )
        GameTrainingDriver(params).run()
        out = params.output_dir
        m = json.load(open(os.path.join(out, "metrics.json")))
        assert len(m["objective_history"]) == 2
        assert m["objective_history"][-1] <= m["objective_history"][0]
        assert m["validation_history"][-1]["AUC"] > 0.6
        assert m["streaming"]["num_chunks"] >= 3
        assert m["streaming"]["peak_rss_bytes"] > 0
        assert m["streaming"]["diagnostics"]["reservoir_rows"] > 0
        loaded = load_game_model(os.path.join(out, "best-model"))
        assert set(loaded.coordinate_names()) == {"global", "per-user"}


@pytest.mark.slow
class TestStreamingGameBoundedMemory:
    def test_peak_rss_bounded_by_budget(self, tmp_path):
        """Train a multi-chunk GAME set under a tiny
        --stream-memory-budget and assert the process high-water stays
        under budget + fixed slack (the python/jax baseline + models),
        NOT under the dataset size: the record form of the stream is
        hundreds of MB; the budget is 2 MB."""
        script = r"""
import os, resource, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.dirname(sys.argv[0]) or ".")
from photon_ml_tpu.io.avro_codec import write_container
from photon_ml_tpu.io import schemas
from photon_ml_tpu.game.config import (FeatureShardConfiguration,
    FixedEffectDataConfiguration, RandomEffectDataConfiguration,
    ProjectorType)
from photon_ml_tpu.optim.config import GLMOptimizationConfiguration
from photon_ml_tpu.task import TaskType

tmp = sys.argv[1]
schema = {
    "name": "GameExample", "type": "record",
    "fields": [
        {"name": "uid", "type": ["null", "string"], "default": None},
        {"name": "response", "type": "double"},
        {"name": "metadataMap",
         "type": ["null", {"type": "map", "values": "string"}],
         "default": None},
        {"name": "features",
         "type": {"type": "array", "items": schemas.FEATURE_AVRO}},
        {"name": "userFeatures",
         "type": {"type": "array", "items": "FeatureAvro"}},
    ],
}
rng = np.random.default_rng(0)
n_files, rows, d_g, d_u, n_users = 4, 12_000, 24, 8, 400
for fi in range(n_files):
    recs = []
    for i in range(rows):
        u = int(rng.integers(0, n_users))
        xg = rng.normal(size=d_g); xu = rng.normal(size=d_u)
        recs.append({
            "uid": f"{fi}-{i}",
            "response": float(rng.uniform() > 0.5),
            "metadataMap": {"userId": f"user{u}"},
            "features": [
                {"name": f"g{j}", "term": "", "value": float(xg[j])}
                for j in range(d_g)
            ],
            "userFeatures": [
                {"name": f"u{j}", "term": "", "value": float(xu[j])}
                for j in range(d_u)
            ],
        })
    write_container(f"{tmp}/part-{fi}.avro", schema, recs)
    del recs

from photon_ml_tpu.game.streaming import train_streaming_game

shards = [FeatureShardConfiguration("globalShard", ["features"]),
          FeatureShardConfiguration("userShard", ["userFeatures"])]
fe = {"global": FixedEffectDataConfiguration("globalShard")}
re = {"per-user": RandomEffectDataConfiguration(
    "userId", "userShard", projector_type=ProjectorType.IDENTITY)}
combo = {"global": GLMOptimizationConfiguration.parse("8,1e-5,0.5,1,LBFGS,L2"),
         "per-user": GLMOptimizationConfiguration.parse("8,1e-5,1.0,1,LBFGS,L2")}
BUDGET = 2 << 20
base = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
res, extras = train_streaming_game(
    [tmp], shards, fe, re, combo, TaskType.LOGISTIC_REGRESSION,
    num_iterations=1, memory_budget_bytes=BUDGET)
peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
assert extras["store"].count >= 3, extras["store"].count
print("CHUNKS", extras["store"].count)
print("DELTA_KB", peak - base)
"""
        out = subprocess.run(
            [sys.executable, "-c", script, str(tmp_path)],
            capture_output=True, text=True, timeout=560,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert out.returncode == 0, out.stderr[-3000:]
        delta_kb = int(out.stdout.split("DELTA_KB")[-1].strip())
        # 48k rows of record dicts are >200 MB transient; training's RSS
        # growth over the post-import/post-datagen base must stay in the
        # budget + jit/compile + model class (NOT the dataset class).
        # Budget is 2 MB; allow 96 MB of interpreter/XLA slack.
        assert delta_kb < 96_000, delta_kb
