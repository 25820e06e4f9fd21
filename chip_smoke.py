#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the three user-facing drivers once, in ONE process, at the full
widths of one model the repo supports: GLMix at the widths of BASELINE
config 4 (a fixed effect over 2^20 hashed dimensions at 64 nnz/row plus a
per-user random effect over a 1,000-feature shard at 32 nnz/row, identity
projector, so the bank is [users, 1000]). Widths are never cut; the users
are a stated share of config 4's 100,000 (``SmokeSize.users``). The data is
generated here from a fixed seed and written where the drivers read it.

  leg A  glm_driver            one lambda (the Pallas kernel), then a
                               three-lambda grid (the batched program)
  leg B  game_training_driver  fixed (the Pallas kernel) + per-user
                               coordinate descent, saved
  leg C  serving_driver        replays validation requests against leg
                               B's saved model, with one hot swap
  reference check              tiled objective vs the float32 scatter
                               objective at leg A's shapes, on the chip

With several devices visible, ``--distributed auto`` builds the data mesh
for legs A and B and leg B adds ``--entity-shards <devices>``; the script
then also checks that rows and bank sit on every device in equal shares.

Exits non-zero, printing no result, when JAX finds no TPU. On success
stdout is two lines, each one JSON object: the report (versions, sizes,
per-leg observations, parity figures, memory, compile cache; its wall
times are smoke observations, not benchmark numbers), and LAST the result,
which is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
with the device as JAX reports it.

Takes no arguments. The tier-1 tests call the legs at a tiny size on the
CPU (tests/test_chip_smoke.py), which is how the script is debugged before
chip time is spent.
"""

from __future__ import annotations

import itertools
import json
import os
import shutil
import sys
import time
from dataclasses import asdict, dataclass
from typing import Dict, List

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK_DIR = os.path.join(ROOT, ".chip_smoke_work")
SEED = 20260926

SHARD_MAP = "globalShard:features|userShard:userFeatures"
INTERCEPT_MAP = "globalShard:true|userShard:false"

# Leg B's objective history on ONE chip at SmokeSize() (TPU v5 lite, jax
# 0.9.0; my chip run, PR 22). A run on several chips must land within
# 1e-3 of it: sharding the rows and the bank may not change the fit. That
# holds because leg B's solves run to convergence: cut at a fixed
# iteration count, L-BFGS under a different reduction order (data mesh
# against none) was 0.9% away in objective at iteration 20 on the chip.
# To refresh after a change to the solvers, run on one chip
# and copy "cd_objective_history" from the report line.
ONE_CHIP_OBJECTIVE_HISTORY = (28004.5546875, 25185.376953125)  # PR 32, tiled FE at bf16x2w


@dataclass(frozen=True)
class SmokeSize:
    """The model's widths and the share of it one run holds. The defaults
    are the chip run; the CPU tests pass a tiny instance."""

    users: int = 16_384          # 16% of config 4's 100,000 users
    rows_per_user: int = 16      # -> 262,144 rows, config 4's n_fixed
    fixed_dim: int = 1 << 20
    fixed_nnz: int = 64
    user_dim: int = 1000
    user_nnz: int = 32
    val_rows_per_user: int = 1
    requests: int = 512          # replayed by leg C, from the validation rows
    parity_scores: int = 32      # scores recomputed on the host
    parts: int = 8               # training part files (one writer process each)
    re_model_files: int = 16     # --num-output-files-for-random-effect-model
    min_fixed_dim: int = 1_000_000

    @property
    def train_rows(self) -> int:
        return self.users * self.rows_per_user

    @property
    def val_rows(self) -> int:
        return self.users * self.val_rows_per_user


# ---------------------------------------------------------------------------
# data: logistic labels from a planted model, written as Avro part files
# ---------------------------------------------------------------------------

_EXAMPLE_SCHEMA = {
    "name": "GlmixExample",
    "type": "record",
    "fields": [
        {"name": "uid", "type": "string"},
        {"name": "response", "type": "double"},
        {"name": "userId", "type": "string"},
        {
            "name": "features",
            "type": {
                "type": "array",
                "items": {
                    "name": "FeatureAvro",
                    "type": "record",
                    "fields": [
                        {"name": "name", "type": "string"},
                        {"name": "term", "type": "string"},
                        {"name": "value", "type": "double"},
                    ],
                },
            },
        },
        {
            "name": "userFeatures",
            "type": {"type": "array", "items": "FeatureAvro"},
        },
    ],
}


def _user_id(u: int) -> str:
    return f"user{u:07d}"


def _planted_model(size: SmokeSize):
    """(w_fixed [fixed_dim], w_user [users, user_dim]) from SEED: normal
    weights on a random fifth of the coordinates, scaled so the fixed
    part of a row's margin has standard deviation 3.5 and the per-user
    part 1.5. At 16 rows per feature that
    is what leaves a fixed-effect-only fit a validation AUC near 0.62."""
    rng = np.random.default_rng([SEED, 0])
    density = 0.2

    def draw(shape, nnz, margin_std):
        w = rng.normal(size=shape) * (rng.random(shape) < density)
        return (w * margin_std / np.sqrt(nnz * density)).astype(np.float32)

    return (
        draw(size.fixed_dim, size.fixed_nnz, 3.5),
        draw((size.users, size.user_dim), size.user_nnz, 1.5),
    )


def _write_part(job) -> int:
    """Generate rows [start, stop) of one split and write one part file.
    Runs in a writer process that never touches a JAX backend."""
    os.environ["JAX_PLATFORMS"] = "cpu"  # before photon_ml_tpu imports jax
    from photon_ml_tpu.io.avro_codec import write_container

    size, split_id, part, start, stop, path = job
    w_fixed, w_user = _planted_model(size)
    rng = np.random.default_rng([SEED, 1 + split_id, part])
    m = stop - start
    users = np.arange(start, stop) % size.users
    g_ix = rng.integers(0, size.fixed_dim, size=(m, size.fixed_nnz))
    g_v = rng.normal(size=(m, size.fixed_nnz)).astype(np.float32)
    # user features without repeats in a row (a bag names a feature once)
    u_ix = np.argpartition(
        rng.random((m, size.user_dim)), size.user_nnz - 1, axis=1
    )[:, : size.user_nnz]
    u_v = rng.normal(size=(m, size.user_nnz)).astype(np.float32)
    z = (w_fixed[g_ix] * g_v).sum(axis=1) + (
        np.take_along_axis(w_user[users], u_ix, axis=1) * u_v
    ).sum(axis=1)
    labels = (rng.random(m) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    tag = "tv"[split_id]

    def records():
        for i in range(m):
            yield {
                "uid": f"{tag}{start + i}",
                "response": float(labels[i]),
                "userId": _user_id(int(users[i])),
                "features": [
                    {"name": f"g{a}", "term": "", "value": b}
                    for a, b in zip(g_ix[i].tolist(), g_v[i].tolist())
                ],
                "userFeatures": [
                    {"name": f"u{a}", "term": "", "value": b}
                    for a, b in zip(u_ix[i].tolist(), u_v[i].tolist())
                ],
            }

    return write_container(path, _EXAMPLE_SCHEMA, records())


def generate_dataset(size: SmokeSize, work_dir: str) -> Dict[str, str]:
    """Write train/ (``size.parts`` files), validate/, requests/ (the
    first ``size.requests`` validation rows) and the per-bag feature
    lists under ``work_dir``; returns their paths."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    from photon_ml_tpu.io.avro_codec import read_avro_records, write_container
    from photon_ml_tpu.io.name_term_list import save_name_and_term_feature_sets

    paths = {
        k: os.path.join(work_dir, k)
        for k in ("train", "validate", "requests", "feature-lists")
    }
    jobs = []
    per = -(-size.train_rows // size.parts)
    for part in range(size.parts):
        lo, hi = part * per, min((part + 1) * per, size.train_rows)
        jobs.append((
            size, 0, part, lo, hi,
            os.path.join(paths["train"], f"part-{part:05d}.avro"),
        ))
    jobs.append((
        size, 1, 0, 0, size.val_rows,
        os.path.join(paths["validate"], "part-00000.avro"),
    ))
    # the writers are host-only children: spawn (not fork) so none
    # inherits this process's JAX client
    with ProcessPoolExecutor(
        max_workers=min(len(jobs), os.cpu_count() or 1),
        mp_context=multiprocessing.get_context("spawn"),
    ) as pool:
        written = list(pool.map(_write_part, jobs))
    assert sum(written[:-1]) == size.train_rows, written
    assert written[-1] == size.val_rows, written
    write_container(
        os.path.join(paths["requests"], "part-00000.avro"),
        _EXAMPLE_SCHEMA,
        itertools.islice(
            read_avro_records([paths["validate"]]), size.requests),
    )
    save_name_and_term_feature_sets(
        {
            "features": [f"g{i}\t" for i in range(size.fixed_dim)],
            "userFeatures": [f"u{i}\t" for i in range(size.user_dim)],
        },
        paths["feature-lists"],
    )
    return paths


# ---------------------------------------------------------------------------
# device facts
# ---------------------------------------------------------------------------


def versions() -> Dict[str, str]:
    from importlib import metadata

    out = {}
    for package in ("jax", "jaxlib", "libtpu"):
        try:
            out[package] = metadata.version(package)
        except metadata.PackageNotFoundError:
            out[package] = "unknown"
    return out


def memory_per_device() -> List[Dict[str, object]]:
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({
            "device": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return out


def cache_entries(cache_dir: str) -> int:
    """Executables in the persistent compile cache (JAX keeps an
    ``-atime`` sidecar per entry; those are not counted)."""
    return sum(1 for f in os.listdir(cache_dir) if not f.endswith("-atime"))


class CacheCounter:
    """Counts persistent-cache hits and misses through jax.monitoring."""

    def __init__(self):
        import jax.monitoring

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def sync_probe() -> Dict[str, object]:
    """One jitted program of known length (N, then 2N, dependent bf16
    matmuls) timed to ``block_until_ready`` and timed to a value
    readback. If doubling the work doubles the first and the two agree,
    ``block_until_ready`` really waits for the device and a host clock
    around it is a valid step time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def chain(m, x):
        def body(_, a):
            # x is all 0.5: (a @ x) / 1024 == a, so the chain stays finite
            return jnp.dot(a, x, preferred_element_type=jnp.bfloat16) / 1024

        return lax.fori_loop(0, m, body, x)

    x = jnp.full((2048, 2048), 0.5, jnp.bfloat16)
    n = 200

    def median_ms(m, sync) -> float:
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            sync(chain(m, x))
            times.append((time.perf_counter() - t0) * 1e3)
        return float(np.median(times))

    float(chain(1, x)[0, 0])  # compile; loop length is a traced operand
    t0 = time.perf_counter()
    pending = chain(n, x)
    dispatch_ms = (time.perf_counter() - t0) * 1e3
    pending.block_until_ready()
    out = {
        "program": f"{n} (then {2 * n}) dependent 2048^3 bf16 matmuls",
        "dispatch_only_ms": round(dispatch_ms, 3),
        "block_until_ready_ms": round(
            median_ms(n, lambda a: a.block_until_ready()), 3),
        "block_until_ready_2x_ms": round(
            median_ms(2 * n, lambda a: a.block_until_ready()), 3),
        "value_readback_ms": round(
            median_ms(n, lambda a: float(a[0, 0])), 3),
        "value_readback_2x_ms": round(
            median_ms(2 * n, lambda a: float(a[0, 0])), 3),
    }
    bur, rb = out["block_until_ready_ms"], out["value_readback_ms"]
    out["agree"] = bool(abs(bur - rb) <= max(0.15 * rb, 2.0))
    return out


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------


def _spread_over_all_devices(arr, what: str) -> List[int]:
    """Assert ``arr``'s shards sit on every visible device in equal
    shares; returns the per-device leading-dimension counts."""
    import jax

    shards = arr.addressable_shards
    devices = {s.device for s in shards}
    assert len(devices) == len(jax.devices()), (
        f"{what}: on {len(devices)} of {len(jax.devices())} devices"
    )
    counts = sorted({s.data.shape for s in shards})
    assert len(counts) == 1 and not arr.sharding.is_fully_replicated, (
        f"{what}: shards {counts}, replicated="
        f"{arr.sharding.is_fully_replicated}"
    )
    return [int(s.data.shape[0]) for s in shards]


def _rows_per_device(batch, mesh, what: str) -> List[int]:
    """Place ``batch``'s rows as the data-parallel solves place them
    (``ensure_data_sharded`` over the driver's own mesh) and assert every
    device holds an equal share."""
    from photon_ml_tpu.parallel.mesh import ensure_data_sharded

    assert mesh is not None, f"{what}: the driver built no mesh"
    placed = ensure_data_sharded(batch, mesh)
    return _spread_over_all_devices(placed.labels, what)


def leg_a(size: SmokeSize, paths: Dict[str, str], work_dir: str,
          *, on_chip: bool) -> Dict[str, object]:
    """glm_driver with its default --kernel/--distributed/--grid-mode:
    (i) one lambda, (ii) a three-lambda grid on the same input."""
    import jax

    from photon_ml_tpu.cli import glm_driver

    def run(tag: str, lambdas: str):
        out_dir = os.path.join(work_dir, f"glm-{tag}")
        driver = glm_driver.GLMDriver(glm_driver.params_from_args([
            "--training-data-directory", paths["train"],
            "--validating-data-directory", paths["validate"],
            "--output-directory", out_dir,
            "--format", "RESPONSE_PREDICTION",
            "--task", "LOGISTIC_REGRESSION",
            "--optimizer", "LBFGS",
            "--regularization-type", "L2",
            "--regularization-weights", lambdas,
            # A fixed count, not a free-running fit: L-BFGS stops on a
            # loose rule (one step's decrease against the INITIAL value),
            # so two float32 implementations left to stop by themselves
            # end 1e-2 apart in coefficients at this width (XLA on the
            # chip against XLA on the CPU as much as against the Pallas
            # kernel; my chip run, PR 22). At 15 iterations both paths
            # take the same steps and the parity below measures the
            # kernels: 2e-5 at 10 iterations, 3e-3 at 20.
            "--num-iterations", "15",
            "--delete-output-dirs-if-exist", "true",
        ]))
        t0 = time.perf_counter()
        driver.run()
        wall = time.perf_counter() - t0
        for rel in ("models/models.avro", "best-model/model.avro",
                    "metrics.json"):
            assert os.path.isfile(os.path.join(out_dir, rel)), rel
        return driver, wall

    single, wall_single = run("single", "1")
    dim = single._data.num_features
    assert dim >= size.min_fixed_dim, dim
    assert single._resolved_grid_mode(dim) == "sequential"
    stats = single._schedule_cache_stats
    if on_chip:
        # --kernel auto must have taken the tiled Pallas path: only a
        # tiled conversion builds (or loads) a tile schedule
        assert stats.get("builds", 0) + stats.get("hits", 0) > 0, stats
    out = {
        "fixed_dim": int(dim),
        "wall_s_single_lambda": round(wall_single, 2),
        "auc_single_lambda": single.validation_metrics[1.0]["AUC"],
        "tile_schedule_builds": int(stats.get("builds", 0)),
    }
    if len(jax.devices()) > 1:
        out["rows_per_device"] = _rows_per_device(
            single._data.batch, single._mesh(), "leg A training rows")

    grid, wall_grid = run("grid", "0.1,1,10")
    assert grid._resolved_grid_mode(dim) == "batched"
    assert sorted(grid.models) == [0.1, 1.0, 10.0], sorted(grid.models)
    w_single = np.asarray(single.models[1.0].coefficients.means)
    w_grid = np.asarray(grid.models[1.0].coefficients.means)
    assert np.isfinite(w_single).all() and np.isfinite(w_grid).all()
    parity = float(np.max(np.abs(w_single - w_grid)))
    assert parity <= 5e-3, f"batched vs single-lambda coefficients: {parity}"
    aucs = {str(lam): m["AUC"] for lam, m in grid.validation_metrics.items()}
    assert out["auc_single_lambda"] > 0.58, out
    assert max(aucs.values()) > 0.58, aucs
    out.update({
        "wall_s_lambda_grid": round(wall_grid, 2),
        "auc_lambda_grid": aucs,
        "batched_vs_single_max_abs_diff": parity,
    })
    if on_chip:
        out["reference_check"] = reference_check(single)
    return out


def reference_check(glm) -> Dict[str, object]:
    """Outside any timing: at leg A's shapes, the tiled objective against
    the float32 scatter GLMObjective at one random w, both on the chip,
    and the proof that the tiled program holds a Mosaic kernel."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.tiled_sparse import TiledGLMObjective, ensure_tiled
    from photon_ml_tpu.optim.problem import create_glm_problem, resolve_kernel

    data = glm._data
    dim = data.num_features
    kernel = resolve_kernel("auto", data.batch)
    assert kernel == "tiled", kernel
    tiled = create_glm_problem(
        glm.params.task, dim, kernel=kernel).objective
    assert isinstance(tiled, TiledGLMObjective) and tiled.interpret is False
    reference = GLMObjective(tiled.loss, dim)
    tiled_batch = ensure_tiled(data.batch, dim)
    w = jnp.asarray(
        np.random.default_rng([SEED, 9]).normal(size=dim).astype(np.float32)
        * 0.05
    )
    tiled_vg = jax.jit(tiled.value_and_gradient)
    text = tiled_vg.lower(w, tiled_batch, 1.0).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic custom call in the program"
    v_t, g_t = tiled_vg(w, tiled_batch, 1.0)
    v_r, g_r = jax.jit(reference.value_and_gradient)(w, data.batch, 1.0)
    value_err = abs(float(v_t) - float(v_r)) / abs(float(v_r))
    grad_err = float(
        jnp.max(jnp.abs(g_t - g_r)) / (jnp.max(jnp.abs(g_r)) + 1e-30))
    assert value_err < 1e-3 and grad_err < 1e-3, (value_err, grad_err)
    return {
        "tpu_custom_call": True,
        "interpret": tiled.interpret,
        "value_rel_err": value_err,
        "grad_rel_err": grad_err,
        "rows": int(data.batch.labels.shape[0]),
        "dim": int(dim),
    }


def leg_b(size: SmokeSize, paths: Dict[str, str], work_dir: str
          ) -> Dict[str, object]:
    """game_training_driver: fixed effect + per-user random effect, two
    coordinate-descent iterations, saved to an output dir."""
    import jax

    from photon_ml_tpu.cli import game_training_driver as gtd

    out_dir = os.path.join(work_dir, "game")
    n_dev = len(jax.devices())
    argv = [
        "--train-input-dirs", paths["train"],
        "--validate-input-dirs", paths["validate"],
        "--output-dir", out_dir,
        "--task-type", "LOGISTIC_REGRESSION",
        "--feature-shard-id-to-feature-section-keys-map", SHARD_MAP,
        "--feature-shard-id-to-intercept-map", INTERCEPT_MAP,
        "--feature-name-and-term-set-path", paths["feature-lists"],
        "--fixed-effect-data-configurations", "global:globalShard,1",
        "--fixed-effect-optimization-configurations",
        "global:100,1e-7,1.0,1,LBFGS,L2",
        "--random-effect-data-configurations",
        "per-user:userId,userShard,1,none,none,none,IDENTITY",
        "--random-effect-optimization-configurations",
        "per-user:20,1e-5,1.0,1,LBFGS,L2",
        "--updating-sequence", "global,per-user",
        "--num-iterations", "2",
        "--evaluator-types", "AUC",
        "--num-output-files-for-random-effect-model",
        str(size.re_model_files),
        "--delete-output-dir-if-exists", "true",
    ]
    if n_dev > 1:
        argv += ["--entity-shards", str(n_dev)]
    from photon_ml_tpu.ops import schedule_cache

    driver = gtd.GameTrainingDriver(gtd.params_from_args(argv))
    before = schedule_cache.stats()
    t0 = time.perf_counter()
    driver.run()
    wall = time.perf_counter() - t0
    after = schedule_cache.stats()
    schedules = (after.builds + after.hits) - (before.builds + before.hits)
    if jax.devices()[0].platform == "tpu":
        # the driver resolves its fixed effect's objective as glm_driver
        # does: only a tiled conversion builds (or loads) a tile schedule
        assert schedules > 0, (before, after)

    with open(os.path.join(out_dir, "metrics.json")) as f:
        metrics = json.load(f)
    history = [float(v) for v in metrics["objective_history"]]
    assert len(history) == 2 and all(np.isfinite(history)), history
    assert history[1] < history[0], f"CD objective did not fall: {history}"
    auc = float(metrics["validation_history"][-1]["AUC"])
    assert auc > 0.58, auc
    model_dir = os.path.join(out_dir, "best-model")
    out = {
        "wall_s": round(wall, 2),
        "cd_objective_history": history,
        "validation_auc": auc,
        "model_dir": model_dir,
        "entity_shards": n_dev if n_dev > 1 else 0,
        "tile_schedule_builds": int(after.builds - before.builds),
        "timers_s": {
            k: round(float(v), 2) for k, v in metrics["timers"].items()
        },
    }
    if n_dev > 1:
        model = driver.best_result[0].model.get_model("per-user")
        out["bank_rows_per_device"] = _spread_over_all_devices(
            model.sharded_bank.data, "user bank")
        out["rows_per_device"] = _rows_per_device(
            driver._train_dataset.batch_for_shard("globalShard"),
            driver._fe_mesh(), "leg B training rows")
    return out


def _saved_model_dims(model_dir: str, size: SmokeSize, wanted_users):
    """Read back from the SAVED model: the fixed-effect coefficients by
    feature key, the per-user coefficients of ``wanted_users`` found in
    the first random-effect part files, and the user shard's dimension
    (distinct feature names across the users read)."""
    from photon_ml_tpu.io.avro_codec import read_avro_records

    fe_recs = list(read_avro_records(
        os.path.join(model_dir, "fixed-effect", "global", "coefficients")))
    fixed = {m["name"]: m["value"] for m in fe_recs[0]["means"]}
    coef_dir = os.path.join(
        model_dir, "random-effect", "per-user", "coefficients")
    parts = sorted(os.listdir(coef_dir))
    assert len(parts) == size.re_model_files, parts
    per_user: Dict[str, Dict[str, float]] = {}
    user_features = set()
    for part in parts:
        for rec in read_avro_records([os.path.join(coef_dir, part)]):
            names = {m["name"]: m["value"] for m in rec["means"]}
            user_features.update(names)
            if rec["modelId"] in wanted_users:
                per_user[rec["modelId"]] = names
        if len(per_user) >= size.parity_scores:
            break
    return fixed, per_user, len(user_features)


def leg_c(size: SmokeSize, paths: Dict[str, str], work_dir: str,
          model_dir: str) -> Dict[str, object]:
    """serving_driver: replay the request trace against leg B's saved
    model on one device, hot-swapping the same model half way."""
    from photon_ml_tpu.cli import serving_driver
    from photon_ml_tpu.io.avro_codec import read_avro_records

    out_dir = os.path.join(work_dir, "serving")
    driver = serving_driver.ServingDriver(serving_driver.params_from_args([
        "--game-model-input-dir", model_dir,
        "--request-paths", paths["requests"],
        "--output-dir", out_dir,
        "--feature-shard-id-to-feature-section-keys-map", SHARD_MAP,
        "--feature-shard-id-to-intercept-map", INTERCEPT_MAP,
        "--feature-name-and-term-set-path", paths["feature-lists"],
        "--task-type", "LOGISTIC_REGRESSION",
        "--evaluator-types", "AUC",
        "--swap-model-dir", model_dir,
        "--swap-after-requests", str(size.requests // 2),
        "--delete-output-dir-if-exists", "true",
    ]))
    t0 = time.perf_counter()
    driver.run()
    wall = time.perf_counter() - t0

    with open(os.path.join(out_dir, "metrics.json")) as f:
        metrics = json.load(f)
    assert metrics["outcomes"] == {"ok": size.requests}, metrics["outcomes"]
    programs = metrics["programs"]
    assert programs["cold_dispatch_compiles"] == 0, programs
    swaps = metrics["swap_history"]
    assert len(swaps) == 1 and swaps[0]["ok"], swaps
    assert swaps[0]["recompiled_programs"] == 0, swaps
    scores = np.asarray(driver.results, np.float32)
    assert scores.shape == (size.requests,) and np.isfinite(scores).all()

    # host-side float32 recomputation from the saved coefficients
    requests = list(read_avro_records([paths["requests"]]))
    fixed, per_user, user_dim = _saved_model_dims(
        model_dir, size, {r["userId"] for r in requests})
    assert len(fixed) >= size.min_fixed_dim, len(fixed)
    assert user_dim == size.user_dim, user_dim
    checked, worst = 0, 0.0
    for rec, got in zip(requests, scores):
        user = per_user.get(rec["userId"])
        if user is None:
            continue
        want = np.float32(fixed["(INTERCEPT)"])
        for f in rec["features"]:
            want += np.float32(f["value"]) * np.float32(fixed[f["name"]])
        for f in rec["userFeatures"]:
            want += np.float32(f["value"]) * np.float32(
                user.get(f["name"], 0.0))
        worst = max(worst, abs(float(want) - float(got)))
        checked += 1
        if checked == size.parity_scores:
            break
    assert checked == size.parity_scores, checked
    assert worst <= 1e-3, f"served vs host-recomputed scores: {worst}"
    return {
        "wall_s": round(wall, 2),
        "requests": size.requests,
        "answered": int(metrics["outcomes"]["ok"]),
        "compiled_programs": programs["compiled_programs"],
        "cold_dispatch_compiles": programs["cold_dispatch_compiles"],
        "recompiled_programs": swaps[0]["recompiled_programs"],
        "saved_fixed_dim": len(fixed),
        "saved_user_local_dim": user_dim,
        "scores_checked": checked,
        "scores_max_abs_diff": worst,
        "replay_auc": metrics.get("AUC"),
    }


def native_builders() -> Dict[str, str]:
    """Force the three g++-built helpers and say which ran; a fallback
    to the numpy/Python twin would hide a broken toolchain."""
    from photon_ml_tpu.io import native_avro
    from photon_ml_tpu.ops import tiled_sparse
    from photon_ml_tpu.utils import native_build, native_index

    assert tiled_sparse._tile_lib(), "native tile-schedule builder missing"
    assert native_avro.available(), "native Avro decoder missing"
    native_index._lib()
    report = native_build.report()
    assert set(report) == {"tile_schedule", "avro_reader", "index_store"}
    assert all(v in ("built", "cached") for v in report.values()), report
    return report


def run_legs(size: SmokeSize, work_dir: str, *, on_chip: bool
             ) -> Dict[str, object]:
    """Generate the data and run the three legs in this process."""
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    t0 = time.perf_counter()
    paths = generate_dataset(size, work_dir)
    out: Dict[str, object] = {
        "wall_s_generate": round(time.perf_counter() - t0, 2)}
    out["leg_a_glm"] = leg_a(size, paths, work_dir, on_chip=on_chip)
    out["leg_b_game"] = leg_b(size, paths, work_dir)
    out["leg_c_serving"] = leg_c(
        size, paths, work_dir, out["leg_b_game"]["model_dir"])
    return out


def emit(report: Dict[str, object], devices) -> None:
    """Write stdout: the report, then the result line the driver reads,
    which holds the keys below and no others."""
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(json.dumps({"device": device, **report}))
    print(json.dumps({"ok": True, "device": device}), flush=True)


def main() -> int:
    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU: jax.devices() = {devices}", file=sys.stderr)
        return 2
    from photon_ml_tpu.utils.backend import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    entries_before = cache_entries(cache_dir)
    counter = CacheCounter()
    size = SmokeSize()
    # printed only if every phase below passes
    result: Dict[str, object] = {
        "versions": versions(),
        "size": {
            **asdict(size), "train_rows": size.train_rows,
            "val_rows": size.val_rows,
        },
        "native_builders": native_builders(),
        "sync_probe": sync_probe(),
    }
    probe = result["sync_probe"]
    assert probe["block_until_ready_2x_ms"] > 1.5 * probe[
        "block_until_ready_ms"], probe
    try:
        result.update(run_legs(size, WORK_DIR, on_chip=True))
    finally:
        shutil.rmtree(WORK_DIR, ignore_errors=True)
    memory = memory_per_device()
    print(f"chip_smoke: memory per device {memory}", file=sys.stderr)
    if len(devices) > 1:
        # "everything on device 0" must fail the run
        peaks = [m["peak_bytes_in_use"] for m in memory]
        assert min(peaks) >= 0.1 * max(peaks), (
            f"device memory is not spread: {memory}")
        history = result["leg_b_game"]["cd_objective_history"]
        print(f"chip_smoke: leg B objective history {history} on "
              f"{len(devices)} chips, {ONE_CHIP_OBJECTIVE_HISTORY} on one",
              file=sys.stderr)
        np.testing.assert_allclose(
            history, ONE_CHIP_OBJECTIVE_HISTORY, rtol=1e-3)
        result["one_chip_objective_history"] = list(
            ONE_CHIP_OBJECTIVE_HISTORY)
    result.update({
        "memory_per_device": memory,
        "compile_cache": {
            "dir": cache_dir,
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "entries_before": entries_before,
            "entries_after": cache_entries(cache_dir),
            "hits": counter.hits,
            "misses": counter.misses,
        },
        "notes": [
            "wall_s values are smoke observations, not benchmark numbers",
            "game_training_driver resolves its fixed effect's objective "
            "as glm_driver does (resolve_kernel('auto', batch)): leg B's "
            "fixed effect runs the tiled Pallas kernel on a TPU, its "
            "schedules built once (leg_b_game.tile_schedule_builds); only "
            "--distributed feature keeps the scatter layout",
        ],
        "wall_s_total": round(time.perf_counter() - t_start, 2),
    })
    emit(result, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
