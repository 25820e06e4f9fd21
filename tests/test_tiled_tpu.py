"""Real-TPU (non-interpret) test tier: the framework's hot paths on the
actual chip, each checked against a CPU oracle in the same process.

The pytest harness pins everything to virtual CPU devices
(tests/conftest.py) and a backend can only be chosen before JAX
initializes — so this tier drives the real chip from ONE SUBPROCESS with
the default (TPU) environment (module-scoped fixture; TPU init and
compiles are paid once; the pytest parent never touches the chip, which
belongs to one process at a time). Every section runs even when an
earlier one fails, and each pytest test asserts its own section's
marker. Gated behind PHOTON_TPU_TESTS=1: the sandbox has no chip, so the
tier is run through the chip tool.

Sections (SURVEY §4: test on the real execution target):
  1. tiled Pallas kernels (all mxu variants + spill hybrid + the
     MXU-packed one-hot expansion) vs scatter
  2. GLM driver-path fit at the a1a shape, tiled-on-TPU vs scatter-on-CPU
  3. random-effect bank update on TPU vs the same solve on CPU
  4. MF ALS warm step on TPU vs the same coordinate on CPU
  5. streaming cached evaluation (tiled chunk cache) vs in-memory scatter
  6. 1-device-mesh tiled fit (shard_map) vs the replicated fit
  7. FEATURE-SHARDED fit under a 1x1 (data, model) mesh vs the CPU oracle
  8. full GAME coordinate-descent step on chip vs the CPU oracle (the
     whole composition: FE solve + RE bank + residuals + objective,
     through the overlap layer's deferred readbacks)
  9. the GAME driver's own fixed-effect coordinate at chip_smoke.py's
     leg-B width: dispatches kernel=tiled, builds its schedules once,
     and lands where the scatter objective does
 10. ops/spd_solve under vmap: the lanes kernel (not XLA's Cholesky) at
     a width that pads and at the ALS half-step's, vs a float64 solve

Run with:  PHOTON_TPU_TESTS=1 python -m pytest tests/test_tiled_tpu.py -v
"""

import os
import subprocess
import sys

import pytest

_CHECK = r"""
import functools, shutil, sys, tempfile, traceback
from types import SimpleNamespace

import numpy as np, jax, jax.numpy as jnp
assert jax.devices()[0].platform == "tpu", jax.devices()
from photon_ml_tpu.utils.backend import enable_compilation_cache
enable_compilation_cache()
cpu = jax.devices("cpu")[0]

from photon_ml_tpu.data.batch import SparseBatch
from photon_ml_tpu.game import (
    CoordinateDescent, FeatureShardConfiguration, FixedEffectCoordinate,
    RandomEffectCoordinate, RandomEffectDataConfiguration,
    RandomEffectOptimizationProblem, build_game_dataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.game.coordinate import MatrixFactorizationCoordinate
from photon_ml_tpu.game.data import EntityIndex, GameDataset
from photon_ml_tpu.game.random_effect_data import RandomEffectBucket
from photon_ml_tpu.io import schemas
from photon_ml_tpu.io.avro_codec import write_container
from photon_ml_tpu.io.input_format import AvroInputDataFormat
from photon_ml_tpu.io.streaming import StreamingGLMObjective, scan_stream
from photon_ml_tpu.ops.losses import LINEAR, LOGISTIC
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.tiled_sparse import (
    TileParams, TiledGLMObjective, build_tiled_batch,
)
from photon_ml_tpu.optim import RegularizationType
from photon_ml_tpu.optim.config import (
    OptimizerConfig, OptimizerType, RegularizationContext,
)
from photon_ml_tpu.optim.problem import create_glm_problem
from photon_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh
from photon_ml_tpu.task import TaskType
from photon_ml_tpu.training import (
    train_feature_sharded, train_generalized_linear_model,
)

tpu_dev = jax.devices()[0]


# ---- 1. tiled Pallas kernels vs the scatter oracle (on chip) ----------
def tiled_kernels():
    rng = np.random.default_rng(0)
    n, k, d = 2048, 16, 20000
    indices = rng.integers(0, d, size=(n, k), dtype=np.int64)
    values = rng.normal(size=(n, k)).astype(np.float32)
    labels = (rng.uniform(size=n) > 0.5).astype(np.float32)
    offsets = rng.normal(size=n).astype(np.float32) * 0.1
    weights = rng.uniform(0.5, 1.5, size=n).astype(np.float32)
    rows = np.repeat(np.arange(n, dtype=np.int64), k)
    tb = build_tiled_batch(rows, indices.reshape(-1), values.reshape(-1),
                           labels, offsets, weights, d)
    sb = SparseBatch(indices=jnp.asarray(indices.astype(np.int32)),
                     values=jnp.asarray(values), labels=jnp.asarray(labels),
                     offsets=jnp.asarray(offsets),
                     weights=jnp.asarray(weights))
    oobj = GLMObjective(LOGISTIC, d)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.01)
    for mxu, tol in (("highest", 1e-4), ("bf16x2w", 1e-3)):
        tobj = TiledGLMObjective(LOGISTIC, d, mxu=mxu)
        v1, g1 = jax.jit(tobj.value_and_gradient)(w, tb, 0.1)
        v2, g2 = jax.jit(oobj.value_and_gradient)(w, sb, 0.1)
        ge = float(jnp.max(jnp.abs(g1 - g2)) / (jnp.max(jnp.abs(g2)) + 1e-9))
        hv1 = jax.jit(tobj.hessian_vector)(w, w * 0.5, tb, 0.1)
        hv2 = jax.jit(oobj.hessian_vector)(w, w * 0.5, sb, 0.1)
        he = float(
            jnp.max(jnp.abs(hv1 - hv2)) / (jnp.max(jnp.abs(hv2)) + 1e-9))
        hd1 = jax.jit(tobj.hessian_diagonal)(w, tb, 0.1)
        hd2 = jax.jit(oobj.hessian_diagonal)(w, sb, 0.1)
        de = float(
            jnp.max(jnp.abs(hd1 - hd2)) / (jnp.max(jnp.abs(hd2)) + 1e-9))
        assert max(ge, he, de) < tol, (mxu, ge, he, de)

    # spill-to-scatter hybrid ON CHIP: force tile remainders through the
    # spill path (cap > remainder) and hold the same tolerance
    tb_spill = build_tiled_batch(
        rows, indices.reshape(-1), values.reshape(-1), labels, offsets,
        weights, d, params=TileParams(chunk=4096, spill_cap=3000))
    assert int(np.count_nonzero(np.asarray(tb_spill.z_sched.spill_vals))) > 0
    assert int(np.count_nonzero(np.asarray(tb_spill.g_sched.spill_vals))) > 0
    tobj = TiledGLMObjective(LOGISTIC, d, mxu="bf16x2w")
    v1, g1 = jax.jit(tobj.value_and_gradient)(w, tb_spill, 0.1)
    v2, g2 = jax.jit(oobj.value_and_gradient)(w, sb, 0.1)
    ge = float(jnp.max(jnp.abs(g1 - g2)) / (jnp.max(jnp.abs(g2)) + 1e-9))
    assert ge < 1e-3, ("spill", ge)

    # MXU-packed one-hot expansion ON CHIP: bit-identical to the compare
    # build (both produce exact 0/1 one-hots) — the Mosaic lowering of the
    # distance-matmul route must not change numerics
    tobj_moh = TiledGLMObjective(LOGISTIC, d, mxu="bf16x2w", onehot="mxu")
    vm, gm = jax.jit(tobj_moh.value_and_gradient)(w, tb, 0.1)
    vc, gc = jax.jit(TiledGLMObjective(LOGISTIC, d, mxu="bf16x2w")
                     .value_and_gradient)(w, tb, 0.1)
    assert float(vm) == float(vc), ("mxu-onehot value", float(vm), float(vc))
    assert bool(jnp.all(gm == gc)), "mxu-onehot grad differs from compare build"


# ---- 2. GLM training-path fit at the a1a shape: TPU tiled vs CPU ------
A1A_KWARGS = dict(regularization_type=RegularizationType.L2,
                  regularization_weights=[1.0, 0.1], max_iter=50)


@functools.cache
def a1a_batch():
    r = np.random.default_rng(1)
    na, da, ka = 1605, 123, 14
    ixa = np.stack([r.choice(da, size=ka, replace=False) for _ in range(na)])
    va = r.normal(size=(na, ka)).astype(np.float32)
    wt = r.normal(size=da).astype(np.float32)
    za = (wt[ixa] * va).sum(axis=1)
    ya = (r.uniform(size=na) < 1 / (1 + np.exp(-za))).astype(np.float32)
    return SparseBatch(
        indices=jnp.asarray(ixa.astype(np.int32)), values=jnp.asarray(va),
        labels=jnp.asarray(ya), offsets=jnp.zeros(na, jnp.float32),
        weights=jnp.ones(na, jnp.float32)), da


@functools.cache
def a1a_tpu_models():
    batch, dim = a1a_batch()
    return train_generalized_linear_model(
        batch, TaskType.LOGISTIC_REGRESSION, dim, kernel="tiled",
        **A1A_KWARGS)[0]


@functools.cache
def a1a_cpu_models():
    batch, dim = a1a_batch()
    with jax.default_device(cpu):
        host = jax.device_get(batch)
        batch_cpu = SparseBatch(*(jnp.asarray(np.asarray(a)) for a in host))
        return train_generalized_linear_model(
            batch_cpu, TaskType.LOGISTIC_REGRESSION, dim, kernel="scatter",
            **A1A_KWARGS)[0]


def _a1a_close(got, want, what):
    for lam in (1.0, 0.1):
        err = float(np.max(np.abs(np.asarray(got[lam].means)
                                  - np.asarray(want[lam].means))))
        assert err < 5e-3, (what, lam, err)


def glm_fit_a1a():
    _a1a_close(a1a_tpu_models(), a1a_cpu_models(), "a1a")


# ---- 3. random-effect bank update: TPU vs CPU oracle ------------------
def re_bank_update():
    r = np.random.default_rng(2)
    E, S, K2 = 256, 8, 16
    idx = r.integers(0, 32, size=(E, S, K2), dtype=np.int32)
    val = r.normal(size=(E, S, K2)).astype(np.float32)
    w_ent = r.normal(size=(E, 1, 32)).astype(np.float32) * 0.5
    z = np.take_along_axis(np.broadcast_to(w_ent, (E, S, 32)), idx, axis=2)
    z = (z * val).sum(axis=2)
    lab = (r.uniform(size=(E, S)) < 1 / (1 + np.exp(-z))).astype(np.float32)
    bucket = RandomEffectBucket(
        entity_codes=np.arange(E, dtype=np.int32),
        row_index=np.full((E, S), -1, np.int32),
        indices=idx, values=val, labels=lab,
        offsets=np.zeros((E, S), np.float32),
        weights=np.ones((E, S), np.float32))
    dataset = SimpleNamespace(buckets=[bucket])

    def bank_update():
        problem = RandomEffectOptimizationProblem(
            loss=LOGISTIC,
            config=OptimizerConfig(OptimizerType.LBFGS, max_iter=20,
                                   tolerance=1e-5, lbfgs_history=5),
            regularization=RegularizationContext(),
            reg_weight=1.0)
        bank0 = jnp.zeros((E, 32), jnp.float32)
        bank, _ = problem.update_bank(bank0, dataset)
        return np.asarray(bank)

    bank_tpu = bank_update()
    with jax.default_device(cpu):
        bank_cpu = bank_update()
    err = float(np.max(np.abs(bank_tpu - bank_cpu)))
    assert err < 5e-3, ("re_bank", err)


# ---- 4. MF ALS warm step: TPU vs CPU oracle ---------------------------
def mf_warm_step():
    r = np.random.default_rng(3)
    nr, nc, K3, nrat = 400, 300, 8, 4000
    rws = r.integers(0, nr, size=nrat).astype(np.int32)
    cls = r.integers(0, nc, size=nrat).astype(np.int32)
    rt = r.normal(0, 0.4, size=(nr, K3)).astype(np.float32)
    ct = r.normal(0, 0.4, size=(nc, K3)).astype(np.float32)
    ratings = ((rt[rws] * ct[cls]).sum(axis=1)
               + 0.2 * r.normal(size=nrat)).astype(np.float32)

    def eindex(prefix, count):
        ids = [f"{prefix}{i}" for i in range(count)]
        return EntityIndex(prefix, ids, {v: i for i, v in enumerate(ids)})

    def mf_step():
        ds = GameDataset(
            uids=[""] * nrat, labels=ratings,
            offsets=np.zeros(nrat, np.float32),
            weights=np.ones(nrat, np.float32), shards={},
            entity_codes={"userId": rws, "itemId": cls},
            entity_indexes={"userId": eindex("u", nr),
                            "itemId": eindex("i", nc)},
            num_real_rows=nrat)
        coord = MatrixFactorizationCoordinate(
            name="mf", dataset=ds, row_effect_type="userId",
            col_effect_type="itemId", num_latent_factors=K3,
            problem=RandomEffectOptimizationProblem(
                loss=LINEAR,
                config=OptimizerConfig(OptimizerType.LBFGS, max_iter=15,
                                       tolerance=1e-5, lbfgs_history=5),
                regularization=RegularizationContext(),
                reg_weight=1.0))
        model = coord.initialize_model()
        model, _ = coord.update_model(model)   # structure build + compile
        model, _ = coord.update_model(model)   # the warm per-CD-iteration step
        return np.asarray(model.row_latent), np.asarray(model.col_latent)

    row_tpu, col_tpu = mf_step()
    with jax.default_device(cpu):
        row_cpu, col_cpu = mf_step()
    err = max(float(np.max(np.abs(row_tpu - row_cpu))),
              float(np.max(np.abs(col_tpu - col_cpu))))
    assert err < 5e-3, ("mf", err)


# ---- 5. streaming cached evaluation (tiled chunk cache) on chip -------
def streaming_cached_eval():
    tmp = tempfile.mkdtemp(prefix="photon-tpu-stream-")
    try:
        r = np.random.default_rng(4)
        ds_d = 5000
        for fi in range(2):
            recs = []
            for i in range(400):
                ix = r.choice(ds_d, size=8, replace=False)
                vs = r.normal(size=8)
                recs.append({"uid": f"{fi}-{i}",
                             "label": float(r.uniform() > 0.5),
                             "features": [{"name": str(int(j)), "term": "",
                                           "value": float(v)}
                                          for j, v in zip(ix, vs)],
                             "offset": 0.0, "weight": 1.0})
            write_container(f"{tmp}/p{fi}.avro",
                            schemas.TRAINING_EXAMPLE_AVRO, recs)
        fmt = AvroInputDataFormat()
        index_map, stats = scan_stream([tmp], fmt)
        sobj = StreamingGLMObjective([tmp], fmt, index_map, stats,
                                     TaskType.LOGISTIC_REGRESSION,
                                     rows_per_chunk=256, kernel="tiled")
        ws = jnp.asarray(r.normal(size=sobj.dim).astype(np.float32) * 0.1)
        v1, g1 = sobj.value_and_gradient(ws, 0.3)   # populate (scatter)
        v2, g2 = sobj.value_and_gradient(ws, 0.3)   # cached (tiled Pallas)
        assert sobj._tiled_chunk_count, "tiled chunk cache was not built on TPU"
        assert abs(float(v2) - float(v1)) / abs(float(v1)) < 2e-4, (v1, v2)
        gerr = float(
            jnp.max(jnp.abs(g2 - g1)) / (jnp.max(jnp.abs(g1)) + 1e-9))
        assert gerr < 2e-3, gerr
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ---- 6. 1-device-mesh tiled fit (shard_map) vs replicated -------------
def one_device_mesh_fit():
    batch, dim = a1a_batch()
    mesh = make_mesh((1,), (DATA_AXIS,), devices=[tpu_dev])
    m_mesh, _ = train_generalized_linear_model(
        batch, TaskType.LOGISTIC_REGRESSION, dim, kernel="tiled",
        mesh=mesh, **A1A_KWARGS)
    _a1a_close(m_mesh, a1a_tpu_models(), "mesh")


# ---- 7. feature-sharded fit under a 1x1 (data, model) mesh ------------
def feature_sharded_1x1_mesh_fit():
    batch, dim = a1a_batch()
    mesh11 = make_mesh((1, 1), (DATA_AXIS, MODEL_AXIS), devices=[tpu_dev])
    m_fs, _ = train_feature_sharded(
        batch, TaskType.LOGISTIC_REGRESSION, dim, mesh=mesh11,
        kernel="tiled", **A1A_KWARGS)
    _a1a_close(m_fs, a1a_cpu_models(), "feature-sharded")


# ---- 8. full GAME coordinate-descent step on chip vs CPU oracle -------
def game_cd_step():
    r = np.random.default_rng(5)
    recs = []
    for i in range(160):
        u = int(r.integers(0, 8))
        xg = r.normal(size=5); xu = r.normal(size=3)
        recs.append({
            "uid": f"r{i}", "response": float(r.uniform() > 0.5),
            "userId": f"u{u}",
            "features": [{"name": f"g{j}", "term": "", "value": float(xg[j])}
                         for j in range(5)],
            "userFeatures": [{"name": f"f{j}", "term": "",
                              "value": float(xu[j])} for j in range(3)],
        })
    game_shards = [
        FeatureShardConfiguration(
            "globalShard", ["features"], add_intercept=True),
        FeatureShardConfiguration(
            "userShard", ["userFeatures"], add_intercept=True),
    ]
    l2 = RegularizationContext(RegularizationType.L2)

    def cd_step():
        ds = build_game_dataset(recs, game_shards, ["userId"])
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("userId", "userShard"))
        coords = {
            "fixed": FixedEffectCoordinate(
                name="fixed", dataset=ds,
                problem=create_glm_problem(
                    TaskType.LOGISTIC_REGRESSION,
                    ds.shards["globalShard"].dim,
                    config=OptimizerConfig(max_iter=10),
                    regularization=l2),
                feature_shard_id="globalShard", reg_weight=0.5),
            "perUser": RandomEffectCoordinate(
                name="perUser", dataset=ds, re_dataset=red,
                problem=RandomEffectOptimizationProblem(
                    LOGISTIC, OptimizerConfig(max_iter=10), l2,
                    reg_weight=1.0)),
        }
        res = CoordinateDescent(
            coords, ds, TaskType.LOGISTIC_REGRESSION,
            update_sequence=["fixed", "perUser"],
        ).run(2)
        return (np.asarray(res.model.get_model("fixed").model.means),
                np.asarray(res.model.get_model("perUser").bank),
                np.asarray(res.objective_history))

    fe_t, bank_t, hist_t = cd_step()
    with jax.default_device(cpu):
        fe_c, bank_c, hist_c = cd_step()
    assert float(np.max(np.abs(fe_t - fe_c))) < 5e-3, "GAME CD FE means"
    assert float(np.max(np.abs(bank_t - bank_c))) < 5e-3, "GAME CD RE bank"
    np.testing.assert_allclose(hist_t, hist_c, atol=1e-3)


# ---- 9. the GAME driver's fixed effect at leg-B width runs the kernel ---
def game_driver_fixed_effect_is_tiled():
    from photon_ml_tpu.cli import game_training_driver as gtd
    from photon_ml_tpu.game.data import ShardData
    from photon_ml_tpu.obs import trace as obs_trace
    from photon_ml_tpu.utils.index_map import IdentityIndexMap

    r = np.random.default_rng(9)
    n, k, d = 262144, 64, 1 << 20  # chip_smoke.py leg B: 16,384 users x 16
    indices = np.concatenate(
        [r.integers(0, d, size=(n, k)), np.full((n, 1), d)], axis=1
    ).astype(np.int32)
    values = np.concatenate(
        [r.normal(size=(n, k)) / 8.0, np.ones((n, 1))], axis=1
    ).astype(np.float32)
    ds = GameDataset(
        uids=[str(i) for i in range(n)],
        labels=(r.uniform(size=n) > 0.5).astype(np.float32),
        offsets=np.zeros(n, np.float32), weights=np.ones(n, np.float32),
        shards={"globalShard": ShardData(
            indices, values, IdentityIndexMap(d, add_intercept=True), d)},
        entity_codes={}, entity_indexes={}, num_real_rows=n,
    )
    tmp = tempfile.mkdtemp()
    try:
        driver = gtd.GameTrainingDriver(gtd.params_from_args([
            "--train-input-dirs", tmp + "/unused",
            "--output-dir", tmp + "/out",
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "globalShard:features",
            "--fixed-effect-data-configurations", "global:globalShard,1",
            "--fixed-effect-optimization-configurations",
            "global:10,1e-7,1.0,1,LBFGS,L2",
            "--updating-sequence", "global", "--distributed", "off",
        ]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    combo = gtd.expand_config_grid(driver.params.fixed_effect_opt_configs)[0]
    residuals = [
        jnp.asarray(0.1 * r.normal(size=n), jnp.float32) for _ in range(2)
    ]

    l2 = combo["global"].reg_weight
    oracle = jax.jit(GLMObjective(LOGISTIC, d + 1).value)

    def two_updates(coord):
        model, out = coord.initialize_model(), []
        for residual in residuals:
            obs_trace.tracer().clear()
            model, result = coord.update_model(model, residual)
            w = model.model.means
            # the float32 scatter objective AT the coefficients reached
            at_w = float(oracle(w, ds.batch_for_shard("globalShard", residual), l2))
            out.append((float(result.value), at_w, obs_trace.tracer().drain()))
        return out

    with obs_trace.tracing_scope(True):
        fe = driver._build_coordinates(ds, {}, combo)["global"]
        assert fe.kernel == "tiled", fe.kernel
        tiled = two_updates(fe)
        scatter = two_updates(driver._build_coordinates(
            ds, {}, combo, fe_kernel="scatter")["global"])
    for i, (_, _, spans) in enumerate(tiled):
        kernels = [s.attrs["kernel"] for s in spans if s.name == "fit.dispatch"]
        assert kernels == ["tiled"], kernels
        builds = [s for s in spans if s.name == "tiled.schedule_build"]
        # both schedules in the first update, none in the second
        assert len(builds) == (2 if i == 0 else 0), (i, len(builds))
    # Ten L-BFGS iterations on two kernels part ways from the fourth on
    # (PERF.md section 7), so not coefficient by coordinate: the value the
    # solve reports is the float32 objective where it stands (the cd cell's
    # fixed_value_gap), and it got as far down as the scatter solve did.
    for (said, at_w, _), (_, scatter_at_w, _) in zip(tiled, scatter):
        assert abs(said - at_w) <= 1e-6 * abs(at_w), (said, at_w)
        assert at_w <= scatter_at_w * (1 + 2e-2), (at_w, scatter_at_w)


# ---- 10. the batched SPD solve: the lanes kernel vs float64 ------------
def spd_solve_lanes():
    from photon_ml_tpu.ops import spd_solve as mod

    r = np.random.default_rng(10)
    for D, E in ((21, 300), (64, 1000)):
        assert mod.solve_path(D, mod.effective_platform()) == "lanes"
        q, _ = np.linalg.qr(r.normal(size=(E, D, D)))
        spectrum = np.geomspace(np.ones(E), np.geomspace(10.0, 1e4, E), D, axis=1)
        H = ((q * spectrum[:, None, :]) @ np.swapaxes(q, 1, 2)).astype(np.float32)
        g = r.normal(size=(E, D)).astype(np.float32)
        solve = jax.jit(jax.vmap(mod.spd_solve))
        assert "tpu_custom_call" in solve.lower(H, g).compile().as_text()
        want = np.linalg.solve(H.astype(np.float64), g.astype(np.float64)[..., None])[..., 0]

        def errors(x):
            x = np.asarray(x)
            return np.linalg.norm(x - want, axis=1) / np.linalg.norm(want, axis=1)

        got = errors(solve(H, g))
        cho = errors(jax.vmap(mod._cho_solve)(jnp.asarray(H), jnp.asarray(g)))
        cond = np.linalg.cond(H.astype(np.float64))
        room = 4 * np.maximum(cho, 0.1 * np.finfo(np.float32).eps * cond)
        assert (got <= room).all(), ("spd_solve", D, got.max(), cho.max())


# Every section runs even when an earlier one fails, so one call to the
# chip reports all of them; the exit status says whether any failed.
failed = []
for section, marker in MARKERS.items():
    try:
        globals()[section]()
    except Exception:
        traceback.print_exc()
        failed.append(section)
    else:
        print(marker, flush=True)
sys.exit(1 if failed else 0)
"""

# section function in _CHECK -> the marker it prints on success
_MARKERS = {
    "tiled_kernels": "TPU_TILED_OK",
    "glm_fit_a1a": "TPU_GLM_FIT_OK",
    "re_bank_update": "TPU_RE_BANK_OK",
    "mf_warm_step": "TPU_MF_OK",
    "streaming_cached_eval": "TPU_STREAMING_OK",
    "one_device_mesh_fit": "TPU_MESH_FIT_OK",
    "feature_sharded_1x1_mesh_fit": "TPU_FEATURE_SHARDED_OK",
    "game_cd_step": "TPU_GAME_CD_OK",
    "game_driver_fixed_effect_is_tiled": "TPU_GAME_DRIVER_FE_TILED_OK",
    "spd_solve_lanes": "TPU_SPD_SOLVE_OK",
}

pytestmark = pytest.mark.skipif(
    os.environ.get("PHOTON_TPU_TESTS") != "1",
    reason="real-TPU test; set PHOTON_TPU_TESTS=1 to run",
)


@pytest.fixture(scope="module")
def tpu_run():
    """One subprocess on the real chip executing every section; sections
    print a marker on success. TPU init + compiles are paid once."""
    # undo what tests/conftest.py pins for the CPU suite
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in (
            "JAX_PLATFORMS", "XLA_FLAGS", "JAX_ENABLE_COMPILATION_CACHE",
        )
    }
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (
        repo_root + os.pathsep + env.get("PYTHONPATH", "")
    ).rstrip(os.pathsep)
    proc = subprocess.run(
        [sys.executable, "-c", f"MARKERS = {_MARKERS!r}\n{_CHECK}"],
        env=env,
        capture_output=True,
        text=True,
        timeout=1100,
    )
    return proc


@pytest.mark.parametrize("section", sorted(_MARKERS))
def test_on_real_tpu(tpu_run, section):
    marker = _MARKERS[section]
    if marker not in tpu_run.stdout:
        raise AssertionError(
            f"section {section!r} did not reach {marker}; rc="
            f"{tpu_run.returncode}\nstdout tail: {tpu_run.stdout[-1500:]}"
            f"\nstderr tail: {tpu_run.stderr[-3000:]}"
        )
