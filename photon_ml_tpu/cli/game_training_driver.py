"""GAME training driver + CLI.

Reference: photon-ml .../cli/game/training/Driver.scala:642-757 (run:
prepareFeatureMaps -> prepareGameDataSet -> prepareTrainingDataSet ->
evaluators -> train over the config grid -> save models) and
Params.scala:199-426 (option names kept verbatim: ``train-input-dirs``,
``feature-shard-id-to-feature-section-keys-map``,
``fixed-effect-data-configurations``, per-coordinate config maps in the
``coord1:cfg|coord2:cfg`` string DSL with grid expansion).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
from dataclasses import dataclass, field
from itertools import product
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax.numpy as jnp

from photon_ml_tpu.evaluation import Evaluator, EvaluatorType
from photon_ml_tpu.game.config import (
    FactoredRandomEffectConfiguration,
    FeatureShardConfiguration,
    FixedEffectDataConfiguration,
    MatrixFactorizationConfiguration,
    RandomEffectDataConfiguration,
)
from photon_ml_tpu.game.coordinate import (
    FactoredRandomEffectCoordinate,
    FixedEffectCoordinate,
    MatrixFactorizationCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
from photon_ml_tpu.game.data import GameDataset, build_game_dataset_from_files
from photon_ml_tpu.game.model import GameModel
from photon_ml_tpu.game.model_io import save_game_model
from photon_ml_tpu.game.random_effect import RandomEffectOptimizationProblem
from photon_ml_tpu.game.random_effect_data import build_random_effect_dataset
from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.optim.config import GLMOptimizationConfiguration
from photon_ml_tpu.optim.problem import create_glm_problem, resolve_kernel
from photon_ml_tpu.task import TaskType
from photon_ml_tpu.utils.backend import enable_compilation_cache
from photon_ml_tpu.utils.logging_util import PhotonLogger, Timer
from photon_ml_tpu.utils.profiling import profile_trace


def parse_keyed_map(s: str) -> Dict[str, str]:
    """``key1:value1|key2:value2`` -> dict (the per-coordinate DSL)."""
    out: Dict[str, str] = {}
    for part in s.split("|"):
        part = part.strip()
        if not part:
            continue
        key, _, value = part.partition(":")
        out[key.strip()] = value.strip()
    return out


def parse_shard_map(s: str) -> List[FeatureShardConfiguration]:
    """``shard1:bag1,bag2|shard2:bag3`` -> shard configs."""
    return [
        FeatureShardConfiguration(k, [b.strip() for b in v.split(",") if b.strip()])
        for k, v in parse_keyed_map(s).items()
    ]


def apply_intercept_map(
    shards: List[FeatureShardConfiguration], intercept_map: Optional[str]
) -> List[FeatureShardConfiguration]:
    """``shardId1:true|shardId2:false`` -> per-shard add_intercept
    (featureShardIdToInterceptMap, Params.scala:289-300; default true,
    a bare ``shardId`` also means true)."""
    if not intercept_map:
        return shards
    import dataclasses

    flags = {}
    for k, v in parse_keyed_map(intercept_map).items():
        s = v.strip().lower()
        if s in ("", "true", "1", "yes"):
            flags[k] = True
        elif s in ("false", "0", "no"):
            flags[k] = False
        else:
            # a typo like "ture" must not silently drop the intercept
            # (the reference's .toBoolean throws the same way)
            raise ValueError(
                f"intercept map value for {k!r} must be true/false, got {v!r}"
            )
    unknown = set(flags) - {s.shard_id for s in shards}
    if unknown:
        raise ValueError(
            f"intercept map references unknown feature shards {sorted(unknown)}"
        )
    return [
        dataclasses.replace(
            s, add_intercept=flags.get(s.shard_id, s.add_intercept)
        )
        for s in shards
    ]


def _ensure_manifest(directory: str, manifest: Dict[str, object]) -> None:
    """Refuse to reuse a checkpoint directory produced by a different run
    configuration — resuming foreign weights would silently corrupt the
    result; a changed config must get a fresh --checkpoint-dir."""
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, "manifest.json")
    if os.path.isfile(path):
        with open(path) as f:
            existing = json.load(f)
        if existing != manifest:
            raise ValueError(
                f"checkpoint directory {directory} was created by a "
                "different run configuration (inputs, shards, or update "
                "sequence changed); point --checkpoint-dir somewhere fresh "
                f"or delete it. Recorded config: {path}"
            )
        return
    # atomic write: concurrent processes sharing the directory either see
    # no file (and write identical content) or a complete one — never a
    # partial JSON
    from photon_ml_tpu.reliability import atomic_write_json

    atomic_write_json(path, manifest)


def expand_config_grid(
    opt_configs: Dict[str, str]
) -> List[Dict[str, GLMOptimizationConfiguration]]:
    """Per-coordinate strings may carry comma-grids in regWeight via ';'
    separated alternatives; the reference expands the cross-product of
    per-coordinate config lists into one training run each
    (cli/game/training/Driver.scala:329-347)."""
    names = list(opt_configs)
    alternatives: List[List[GLMOptimizationConfiguration]] = []
    for name in names:
        opts = [
            GLMOptimizationConfiguration.parse(alt)
            for alt in opt_configs[name].split(";")
            if alt.strip()
        ]
        alternatives.append(opts)
    return [dict(zip(names, combo)) for combo in product(*alternatives)]


@dataclass
class GameTrainingParams:
    train_input_dirs: List[str] = field(default_factory=list)
    validate_input_dirs: Optional[List[str]] = None
    output_dir: str = ""
    # Dated-input coordinates (Params.scala:44-82): with a range set, each
    # input dir is expected in daily format <dir>/daily/yyyy/MM/dd.
    train_date_range: Optional[str] = None
    train_date_range_days_ago: Optional[str] = None
    validate_date_range: Optional[str] = None
    validate_date_range_days_ago: Optional[str] = None
    task_type: TaskType = TaskType.LOGISTIC_REGRESSION
    feature_shards: List[FeatureShardConfiguration] = field(default_factory=list)
    fixed_effect_data_configs: Dict[str, FixedEffectDataConfiguration] = field(
        default_factory=dict
    )
    fixed_effect_opt_configs: Dict[str, str] = field(default_factory=dict)
    random_effect_data_configs: Dict[str, RandomEffectDataConfiguration] = field(
        default_factory=dict
    )
    random_effect_opt_configs: Dict[str, str] = field(default_factory=dict)
    factored_re_configs: Dict[str, FactoredRandomEffectConfiguration] = field(
        default_factory=dict
    )
    # Matrix-factorization coordinates (name -> row/col effect types, rank,
    # ALS sweeps a pass); each one's optimizer and L2 weight are its entry
    # in random_effect_opt_configs
    mf_configs: Dict[str, MatrixFactorizationConfiguration] = field(
        default_factory=dict
    )
    updating_sequence: Optional[List[str]] = None
    num_iterations: int = 1
    evaluator_types: List[EvaluatorType] = field(default_factory=list)
    compute_variance: bool = False
    # ALL: best-model plus every combo's final model under all/<index>
    # (ModelOutputMode.scala, cli/game/training/Driver.scala:620-635);
    # BEST: best-model only; NONE: no model output.
    model_output_mode: str = "ALL"
    # Split each random-effect coordinate's per-entity model records
    # across N Avro part files (numberOfOutputFilesForRandomEffectModel,
    # Params.scala:387-391); <=0 writes one file.
    num_output_files_for_random_effect_model: int = 1
    application_name: str = "photon-ml-tpu-game-training"
    # Prebuilt per-shard partitioned feature-index stores (the reference's
    # offheap-indexmap-dir, prepareFeatureMaps at
    # cli/game/GAMEDriver.scala:89-97): a directory with one store
    # subdirectory per feature shard id, as written by the
    # feature-indexing job with --shard-name.
    offheap_indexmap_dir: Optional[str] = None
    offheap_indexmap_num_partitions: Optional[int] = None
    # Feature name-and-term list files (the reference's default feature-map
    # source, GAMEDriver.prepareFeatureMapsDefault +
    # NameAndTermFeatureSetContainer.scala): <path>/<sectionKey>/ text
    # files of name TAB term lines; a shard's vocabulary is the union of
    # its section keys' lists. Ignored when offheap_indexmap_dir is set
    # (same precedence as the reference's prepareFeatureMaps dispatch).
    feature_name_and_term_set_path: Optional[str] = None
    delete_output_dir_if_exists: bool = False
    # "auto": fixed-effect solves run data-parallel under shard_map and
    # random-effect banks shard their entity axis whenever >1 device is
    # visible (cli/game/training/Driver.scala is cluster-by-construction);
    # "off": single-device; "feature": the fixed effect runs
    # FEATURE-SHARDED over a 2-D (data, model) mesh — the reference's
    # huge-dimension GAME fixed effect (treeAggregate depth valve at
    # >=200k features, Driver.scala:357-363,717-719; "hundreds of
    # billions of coefficients", README.md:73) — while random-effect
    # banks keep sharding entities over a 1-D mesh
    distributed: str = "auto"
    model_shards: Optional[int] = None  # model-axis size for "feature"
    # Pod-scale GAME (game/pod.py): shard every random-effect bank —
    # plus its optimizer/tracker state and per-entity data — over an
    # N-device "entity" mesh by entity hash, with two-hop all_to_all
    # residual routing. 0/None keeps the replicated banks; -1 uses
    # every visible device; N uses the first N. Composes with
    # --streaming (each device stages only its shard of a segment).
    entity_shards: Optional[int] = None
    # Multi-host orchestration (SparkContextConfiguration analog).
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    # Step checkpoints + preemption-safe resume (upgrade over the
    # reference, which only recovers via saved models / Spark lineage):
    # when set, every coordinate-descent iteration checkpoints here, a
    # SIGTERM (spot/preemptible TPU eviction warning) stops training at
    # the next iteration boundary, and a rerun resumes from the latest
    # step.
    checkpoint_dir: Optional[str] = None
    # jax.profiler trace of the training combos into this directory
    # (SURVEY §7.11): one trace spanning the coordinate-descent fits.
    profile_dir: Optional[str] = None
    # Unified telemetry (ISSUE 13): training-span tracing + flight
    # recorder under --obs-dir (trace.json / flight.json at exit).
    obs_dir: Optional[str] = None
    # Persistent content-addressed tile-schedule cache directory
    # (ops/schedule_cache.py): GAME sweeps over the same dataset reuse
    # the tiled layout across runs. None falls back to the
    # PHOTON_TILE_CACHE_DIR env var; unset = off.
    tile_cache_dir: Optional[str] = None
    # Escape hatch for the host-device overlap layer (parallel/overlap.py):
    # True runs fully serial — eager readbacks, inline host prep,
    # synchronous checkpoint/metrics writes (the pre-overlap behavior).
    no_overlap: bool = False
    # Out-of-core GAME training (game/streaming.py): the train set streams
    # once per CD pass through spilled fixed-shape chunks, random effects
    # group into disk-backed bucket segments, scores/residuals live on
    # disk per chunk — host peak RSS is bounded by --stream-memory-budget
    # instead of the dataset. IDENTITY-projected plain coordinates only.
    streaming: bool = False
    # Byte budget for the streaming layer (chunk rows + RE segment size);
    # 0 keeps the default chunk sizing (65536 rows / 1 GiB segments).
    stream_memory_budget: int = 0
    # Streaming diagnostics reservoir (the GLM driver's byte-budgeted
    # bounded sample, extended to wide-row GAME streams): rows scale DOWN
    # when the staged row is wide so the sample cannot blow the bounded-
    # memory contract (cli.glm_driver.budgeted_reservoir_rows).
    diagnostic_reservoir_rows: int = 100_000
    diagnostic_reservoir_bytes: int = 256 << 20
    # Fixed-effect λ-tuning policy for the combo grid (the GAME analog of
    # the GLM driver's --grid-mode): when the grid is a PURE FE λ sweep
    # (one fixed-effect coordinate, no random effects, 1 CD iteration,
    # combos differing only in regWeight), "batched" solves every combo's
    # FE GLM in ONE vmapped program; "auto" does so when the G×d state
    # bank fits --grid-memory-budget; "sequential" keeps the warm-started
    # per-combo sweep. Grids that are not pure FE λ sweeps always run
    # sequential.
    grid_mode: str = "auto"
    grid_memory_budget: int = 1 << 30
    # Deterministic fault plan (reliability.faults), e.g.
    # "spill_write:2:EIO,ckpt_save:1:ENOSPC"; also via PHOTON_FAULT_PLAN.
    fault_plan: Optional[str] = None
    # Continuous retraining (registry/): --retrain-from warm-starts the
    # FE coefficient vectors AND the per-entity RE banks from the latest
    # committed generation with drift-safe alignment (new vocab terms
    # zero-init, removed terms dropped with accounting, churned entities
    # prior-mean-initialized; bitwise pass-through when nothing
    # drifted); --publish-registry publishes best-model as the next
    # generation, gated against the parent on the validation data.
    retrain_from: Optional[str] = None
    publish_registry: Optional[str] = None
    gate_max_auc_drop: float = 0.005
    gate_max_rmse_increase: float = 0.01
    gate_max_coef_norm_ratio: float = 10.0
    gate_max_prediction_drift: Optional[float] = None

    def validate(self) -> None:
        if not self.train_input_dirs:
            raise ValueError("train-input-dirs is required")
        if not self.output_dir:
            raise ValueError("output-dir is required")
        if self.distributed not in ("auto", "off", "feature"):
            raise ValueError(f"unknown distributed mode {self.distributed!r}")
        if self.model_output_mode not in ("ALL", "BEST", "NONE"):
            raise ValueError(
                f"unknown model output mode {self.model_output_mode!r}"
            )
        # Exclusivity AND range-string format validated up front.
        from photon_ml_tpu.utils.date_range import resolve_date_range

        resolve_date_range(self.train_date_range, self.train_date_range_days_ago)
        resolve_date_range(
            self.validate_date_range, self.validate_date_range_days_ago
        )
        coords = set(self.fixed_effect_data_configs) | set(
            self.random_effect_data_configs
        )
        if not coords and not self.mf_configs:
            raise ValueError("at least one coordinate configuration required")
        for name in self.fixed_effect_data_configs:
            if name not in self.fixed_effect_opt_configs:
                raise ValueError(f"missing optimization config for {name}")
            # Down-sampling composes with --distributed feature since the
            # sampler became pure row re-weighting on the cached sharded
            # layout (the per-draw weights are traced arguments —
            # FixedEffectCoordinate._update_model_feature_sharded); the
            # round-5 parse-time rejection is gone with the limitation.
        for name in self.random_effect_data_configs:
            if name not in self.random_effect_opt_configs:
                raise ValueError(f"missing optimization config for {name}")
        for name in self.mf_configs:
            if name in coords:
                raise ValueError(
                    f"matrix-factorization coordinate {name} shares its "
                    "name with another coordinate"
                )
            if name not in self.random_effect_opt_configs:
                raise ValueError(
                    f"missing optimization config for {name}: a "
                    "matrix-factorization coordinate reads its optimizer "
                    "and L2 weight from "
                    "--random-effect-optimization-configurations"
                )
            # what has not run with the ALS half-steps yet, refused by name
            unsupported = [
                what for what, on in (
                    ("--entity-shards", self.entity_shards not in (None, 0)),
                    ("--streaming", self.streaming),
                    ("a regularization-weight grid (';' alternatives)", any(
                        ";" in v for v in (
                            *self.fixed_effect_opt_configs.values(),
                            *self.random_effect_opt_configs.values(),
                        )
                    )),
                ) if on
            ]
            if unsupported:
                raise ValueError(
                    f"matrix-factorization coordinate {name} does not "
                    "support: " + ", ".join(unsupported)
                )
        if self.diagnostic_reservoir_rows < 1:
            raise ValueError("diagnostic-reservoir-rows must be >= 1")
        if self.diagnostic_reservoir_bytes < 1:
            raise ValueError("diagnostic-reservoir-bytes must be >= 1")
        if self.grid_mode not in ("batched", "sequential", "auto"):
            raise ValueError(
                f"unknown grid mode {self.grid_mode!r}; expected "
                "batched | sequential | auto"
            )
        if self.entity_shards is not None and self.entity_shards not in (
            0, -1
        ) and self.entity_shards < 1:
            raise ValueError(
                f"entity-shards must be -1, 0 or >= 1, got "
                f"{self.entity_shards}"
            )
        if self.entity_shards not in (None, 0):
            if self.factored_re_configs:
                raise ValueError(
                    "--entity-shards supports plain random-effect "
                    "coordinates only (factored REs re-project rows "
                    "through a replicated latent view)"
                )
            if self.compute_variance and self.streaming:
                raise ValueError(
                    "--entity-shards with --streaming does not support "
                    "--compute-variance yet"
                )
        if self.grid_memory_budget < 1:
            raise ValueError("grid-memory-budget must be >= 1")
        if self.streaming:
            # the streaming layer's structural gates; everything else the
            # in-memory path supports is a bounded pass over staged chunks
            unsupported = []
            if self.factored_re_configs:
                unsupported.append(
                    "factored random effects (latent re-projection "
                    "re-materializes every row per inner iteration)"
                )
            if self.distributed == "feature":
                unsupported.append(
                    "a feature-sharded fixed effect (use the GLM driver's "
                    "--streaming --distributed feature for that "
                    "composition)"
                )
            if self.coordinator_address is not None:
                unsupported.append("multi-process training")
            for et in self.evaluator_types:
                if et.is_sharded:
                    unsupported.append(
                        f"the sharded evaluator {et.render()}"
                    )
            if unsupported:
                raise ValueError(
                    "streaming GAME training does not support: "
                    + ", ".join(unsupported)
                )
            from photon_ml_tpu.game.streaming import (
                validate_streaming_game_configs,
            )

            validate_streaming_game_configs(self.random_effect_data_configs)
        if self.retrain_from:
            unsupported = []
            if self.streaming:
                unsupported.append(
                    "--streaming (the out-of-core CD builds its banks "
                    "from disk segments; warm-starting them is not "
                    "wired yet)"
                )
            if self.entity_shards not in (None, 0):
                unsupported.append(
                    "--entity-shards (the pod coordinates own their "
                    "sharded bank layout)"
                )
            if unsupported:
                raise ValueError(
                    "--retrain-from does not support: "
                    + ", ".join(unsupported)
                )
        if (
            self.retrain_from
            and self.publish_registry
            and not self.validate_input_dirs
        ):
            raise ValueError(
                "validation-gated promotion (--retrain-from + "
                "--publish-registry) requires validate-input-dirs: the "
                "gates compare candidate vs parent on held-out data"
            )
        if self.publish_registry and self.model_output_mode == "NONE":
            raise ValueError(
                "--publish-registry publishes the saved best-model; "
                "model-output-mode NONE writes none"
            )


class GameTrainingDriver:
    def __init__(self, params: GameTrainingParams, logger=None):
        params.validate()
        self.params = params
        from photon_ml_tpu.parallel.multihost import (
            initialize_multihost,
            is_coordinator,
            prepare_output_dir,
        )

        initialize_multihost(
            params.coordinator_address, params.num_processes, params.process_id
        )
        enable_compilation_cache()
        if params.tile_cache_dir is not None:
            # process-wide: every coordinate's tiled conversion (FE solves
            # across all combos) shares the persistent tier
            from photon_ml_tpu.ops.schedule_cache import configure

            configure(params.tile_cache_dir)
        if params.no_overlap:
            from photon_ml_tpu.parallel import overlap

            overlap.set_overlap(False)
        if params.fault_plan:
            from photon_ml_tpu.reliability import install_plan

            install_plan(params.fault_plan)
        prepare_output_dir(
            params.output_dir,
            delete_if_exists=params.delete_output_dir_if_exists,
        )
        self.logger = logger or PhotonLogger(
            params.output_dir if is_coordinator() else None
        )
        self.timer = Timer()
        from photon_ml_tpu.obs import ObsSession

        self.obs = ObsSession(params.obs_dir, signal_dump=False)
        self.results = []
        self.best_result = None
        self.best_config = None
        # continuous retraining state (--retrain-from / --publish-registry)
        self._parent_generation = None   # registry.GenerationInfo
        self._parent_loaded = None       # game.model_io.LoadedGameModel
        self._drift_reports = {}
        self._published_generation = None
        self._gate_report = None

    # -- data --------------------------------------------------------------

    def _expand_dated(self, dirs, date_range, days_ago):
        from photon_ml_tpu.utils.date_range import expand_dated_paths

        return expand_dated_paths(dirs, date_range, days_ago, self.logger)

    def _load_dataset(self, dirs: Sequence[str], index_maps=None) -> GameDataset:
        re_types = [
            c.random_effect_type
            for c in self.params.random_effect_data_configs.values()
        ]
        # a matrix-factorization coordinate's two id columns
        for c in self.params.mf_configs.values():
            for id_type in (c.row_effect_type, c.col_effect_type):
                if id_type not in re_types:
                    re_types.append(id_type)
        # sharded evaluators need their id columns too
        for et in self.params.evaluator_types:
            if et.id_type and et.id_type not in re_types:
                re_types.append(et.id_type)
        # native column decode when available; Python codec fallback inside
        return build_game_dataset_from_files(
            list(dirs),
            self.params.feature_shards,
            re_types,
            index_maps=index_maps,
            is_response_required=True,
        )

    # -- coordinates -------------------------------------------------------

    def _mesh(self):
        """Data-parallel/entity-parallel mesh; None when single-device or
        --distributed off. In "feature" mode this is the 1-D mesh the
        RANDOM-EFFECT banks shard over; the fixed effect gets its own
        2-D mesh from _fe_mesh.

        A PARTIAL pod entity mesh (--entity-shards N < visible devices)
        restricts the data mesh to the same N devices: CD row currency
        (scores, residuals) is committed to the entity device set, and
        jit refuses `residual + new_score` across two device sets."""
        from photon_ml_tpu.parallel.mesh import (
            DATA_AXIS,
            make_mesh,
            maybe_make_mesh,
        )

        mode = self.params.distributed
        mesh = maybe_make_mesh("auto" if mode == "feature" else mode)
        pod = self._entity_mesh()
        if (
            mesh is None
            or pod is None
            or pod.devices.size >= mesh.devices.size
        ):
            return mesh
        devs = list(pod.devices.flat)
        if len(devs) < 2:
            return None
        return make_mesh((len(devs),), (DATA_AXIS,), devs)

    def _entity_mesh(self):
        """Pod-scale entity mesh (--entity-shards), or None for the
        replicated random-effect banks."""
        from photon_ml_tpu.parallel.mesh import entity_mesh
        from photon_ml_tpu.training import resolve_entity_shards

        n = resolve_entity_shards(self.params.entity_shards)
        return entity_mesh(n) if n is not None else None

    def _fe_mesh(self):
        """Mesh for the fixed-effect solves: the 2-D (data, model) mesh in
        "feature" mode (feature-sharded coefficients inside the GAME CD),
        the shared 1-D data mesh otherwise. Like _mesh, a partial pod
        entity mesh restricts the device set (the FE's row scores feed
        the pod residual)."""
        from photon_ml_tpu.parallel.mesh import (
            DATA_AXIS,
            MODEL_AXIS,
            make_mesh,
            maybe_make_mesh,
        )

        p = self.params
        if p.distributed != "feature":
            return self._mesh()
        mesh = maybe_make_mesh("feature", p.model_shards)
        pod = self._entity_mesh()
        if (
            mesh is None
            or pod is None
            or pod.devices.size >= mesh.devices.size
        ):
            return mesh
        devs = list(pod.devices.flat)
        m = p.model_shards if p.model_shards is not None else 2
        if len(devs) % m != 0:
            raise ValueError(
                f"model_shards={m} does not divide the {len(devs)}-device "
                "entity mesh (--entity-shards restricts the fixed "
                "effect's (data, model) mesh to the pod device set)"
            )
        return make_mesh(
            (len(devs) // m, m), (DATA_AXIS, MODEL_AXIS), devs
        )

    def _build_coordinates(
        self,
        dataset: GameDataset,
        re_datasets,
        opt_combo: Dict[str, GLMOptimizationConfiguration],
        fe_kernel: str = "auto",
    ):
        """One coordinate per configured effect. A fixed effect's objective
        is resolved as ``glm_driver`` resolves its own: the tiled Pallas
        pair on a TPU, the scatter objective elsewhere. The feature-sharded
        (data, model) mesh keeps the scatter layout until its tiled one has
        run on the chip through this driver (PERF.md section 7), and
        ``fe_kernel`` is the batched lambda grid's seam to do the same."""
        p = self.params
        with obs_span(
            "game.build_coordinates",
            coordinates=len(p.fixed_effect_data_configs)
            + len(p.random_effect_data_configs) + len(p.mf_configs),
        ):
            return self._coordinates_of(
                dataset, re_datasets, opt_combo, fe_kernel
            )

    def _coordinates_of(self, dataset, re_datasets, opt_combo, fe_kernel):
        from photon_ml_tpu.parallel.mesh import MODEL_AXIS

        p = self.params
        mesh = self._mesh()
        fe_mesh = self._fe_mesh()
        pod_mesh = self._entity_mesh()
        if MODEL_AXIS in getattr(fe_mesh, "axis_names", ()):
            fe_kernel = "scatter"
        coords = {}
        for name, dcfg in p.fixed_effect_data_configs.items():
            ocfg = opt_combo[name]
            shard = dataset.shards[dcfg.feature_shard_id]
            kernel = resolve_kernel(
                fe_kernel, dataset.batch_for_shard(dcfg.feature_shard_id)
            )
            problem = create_glm_problem(
                p.task_type,
                shard.dim,
                config=ocfg.optimizer_config,
                regularization=ocfg.regularization,
                compute_variances=p.compute_variance,
                intercept_index=shard.intercept_index,
                kernel=kernel,
            )
            coords[name] = FixedEffectCoordinate(
                name=name,
                dataset=dataset,
                problem=problem,
                feature_shard_id=dcfg.feature_shard_id,
                reg_weight=ocfg.reg_weight,
                down_sampling_rate=ocfg.down_sampling_rate,
                mesh=fe_mesh,
            )
        loss = loss_for_task(p.task_type)
        for name, dcfg in p.random_effect_data_configs.items():
            ocfg = opt_combo[name]
            red = re_datasets[name]
            factored = name in p.factored_re_configs
            problem = RandomEffectOptimizationProblem(
                loss,
                ocfg.optimizer_config,
                ocfg.regularization,
                reg_weight=ocfg.reg_weight,
                # the pod layer owns placement on the entity-sharded path;
                # a factored random effect runs on the replicated bank (its
                # projection fit reads the blocks one device holds)
                mesh=None if pod_mesh is not None or factored else mesh,
                # plain RE coordinates attach per-entity variances; the
                # factored path persists in the ORIGINAL space where the
                # latent-space Hdiag does not transform diagonally
                compute_variances=p.compute_variance and not factored,
            )
            if factored:
                fcfg = p.factored_re_configs[name]
                coords[name] = FactoredRandomEffectCoordinate(
                    name=name,
                    dataset=dataset,
                    re_dataset=red,
                    problem=problem,
                    projection_problem=create_glm_problem(
                        p.task_type,
                        red.local_dim * fcfg.latent_space_dimension,
                        config=ocfg.optimizer_config,
                        regularization=ocfg.regularization,
                    ),
                    config=fcfg,
                    reg_weight_projection=ocfg.reg_weight,
                )
            elif pod_mesh is not None:
                from photon_ml_tpu.game.coordinate import (
                    PodRandomEffectCoordinate,
                )

                coords[name] = PodRandomEffectCoordinate(
                    name=name, dataset=dataset, re_dataset=red,
                    problem=problem, mesh=pod_mesh,
                )
            else:
                coords[name] = RandomEffectCoordinate(
                    name=name, dataset=dataset, re_dataset=red, problem=problem
                )
        for name, mcfg in p.mf_configs.items():
            ocfg = opt_combo[name]
            coords[name] = MatrixFactorizationCoordinate(
                name=name,
                dataset=dataset,
                row_effect_type=mcfg.row_effect_type,
                col_effect_type=mcfg.col_effect_type,
                num_latent_factors=mcfg.num_latent_factors,
                # the half-steps are bank updates of a random-effect
                # problem: its loss, optimizer, L2 weight and block plan
                problem=RandomEffectOptimizationProblem(
                    loss, ocfg.optimizer_config, ocfg.regularization,
                    reg_weight=ocfg.reg_weight, mesh=mesh,
                ),
                num_inner_iterations=mcfg.num_inner_iterations,
            )
        return coords

    def _fe_grid_lambdas(self, combos) -> Optional[List[float]]:
        """The combo grid as a pure fixed-effect λ sweep, or None.

        Batchable when: one FE coordinate, no random effects, 1 CD
        iteration (a single-coordinate CD iteration IS one GLM solve),
        no checkpointing, and every combo identical except the FE
        regWeight. Then the whole sweep collapses into
        training.train_grid_batched's engine — one vmapped program for
        all G combos (--grid-mode; auto applies the memory-budget
        fallback). The feature-sharded FE batches too
        (feature_sharded_glm_fit(grid=True): a [G, d_pad] bank over the
        (data, model) mesh), and down-sampling composes when every combo
        shares the rate — the draw is λ-independent, so one weight
        rewrite serves the whole grid.
        """
        p = self.params
        if p.grid_mode == "sequential":
            return None
        if (
            len(p.fixed_effect_data_configs) != 1
            or p.random_effect_data_configs
            or p.factored_re_configs
            or p.num_iterations != 1
            or p.checkpoint_dir is not None
            or p.retrain_from is not None  # warm start needs the
            # sequential sweep's initial_model seam
            or len(combos) <= 1
        ):
            return None
        name = next(iter(p.fixed_effect_data_configs))
        base = combos[0][name]
        for combo in combos:
            cfg = combo[name]
            if (
                cfg.optimizer_config != base.optimizer_config
                or cfg.regularization != base.regularization
                or cfg.down_sampling_rate != base.down_sampling_rate
            ):
                return None
        lambdas = [combo[name].reg_weight for combo in combos]
        if len(set(lambdas)) != len(lambdas):
            return None
        if p.grid_mode == "auto":
            from photon_ml_tpu.training import resolve_grid_mode

            dcfg = p.fixed_effect_data_configs[name]
            shard = dcfg.feature_shard_id
            dim = None
            try:
                dim = self._dataset_dim_hint(shard)
            except Exception:
                dim = None
            if dim is not None:
                mode = resolve_grid_mode(
                    "auto",
                    num_weights=len(lambdas),
                    dim=dim,
                    optimizer_type=base.optimizer_config.optimizer_type,
                    history=base.optimizer_config.lbfgs_history,
                    memory_budget_bytes=p.grid_memory_budget,
                )
                if mode != "batched":
                    self.logger.info(
                        "grid-mode auto: FE grid bank over %d features "
                        "exceeds the %d-byte budget; sequential sweep",
                        dim, p.grid_memory_budget,
                    )
                    return None
        return lambdas

    def _dataset_dim_hint(self, shard_id: str) -> Optional[int]:
        """Coefficient dimension of a feature shard if a dataset is
        already loaded (the auto budget check); None before load."""
        ds = getattr(self, "_train_dataset", None)
        if ds is None:
            return None
        return ds.shards[shard_id].dim

    def _train_fe_grid_batched(
        self, combos, dataset, re_datasets, validation_fn, maximize
    ) -> None:
        """Pure-FE λ sweep on the batched grid engine: ONE vmapped
        program solves every combo's fixed effect; per-combo objectives
        (loss + the combo's reg term) stay device-resident and return in
        ONE batched fetch; validation/selection then runs per combo
        exactly like the sequential sweep."""
        import jax.numpy as jnp

        from photon_ml_tpu.game.coordinate_descent import (
            CoordinateDescentResult,
        )
        from photon_ml_tpu.game.model import GameModel
        from photon_ml_tpu.ops.losses import loss_for_task
        from photon_ml_tpu.parallel import overlap

        p = self.params
        name = next(iter(p.fixed_effect_data_configs))
        lambdas = [combo[name].reg_weight for combo in combos]
        self.logger.info(
            "training the %d-combo FE lambda grid BATCHED (one vmapped "
            "program; no cross-combo warm starts)", len(combos),
        )
        with self.timer.time("train-fe-grid-batched"):
            # under vmap the tiled objective falls to _grid_bilinear_pass,
            # the slow pass of ROADMAP S4: the grid keeps the scatter one
            coords = self._build_coordinates(
                dataset, re_datasets, combos[0], fe_kernel="scatter"
            )
            coord = coords[name]
            fitted = coord.update_model_grid(lambdas)

            loss = loss_for_task(p.task_type)
            offsets = jnp.asarray(dataset.offsets)
            labels = jnp.asarray(dataset.labels)
            weights = jnp.asarray(dataset.weights)
            regularization = coord.problem.regularization
            objective_ds = []
            for lam, (fe_model, _res) in zip(lambdas, fitted):
                z = coord.score(fe_model) + offsets
                value = jnp.sum(weights * loss.value(z, labels))
                l1, l2 = regularization.split(lam)
                w = fe_model.model.means
                value = value + 0.5 * l2 * jnp.vdot(w, w)
                if l1:
                    value = value + l1 * jnp.sum(jnp.abs(w))
                objective_ds.append(overlap.Deferred(value, float))
            # the grid's CD objectives materialize in ONE batched fetch
            overlap.fetch_all(objective_ds)

        best_orig_idx = None
        for ci, combo in enumerate(combos):
            fe_model, res = fitted[ci]
            game_model = GameModel({name: fe_model}, p.task_type)
            validation_history = []
            best_metric = None
            if validation_fn is not None:
                metrics = validation_fn(game_model)
                validation_history.append(metrics)
                self.logger.info(
                    "combo %d validation: %s", ci, metrics
                )
                if self._evaluators:
                    best_metric = metrics[self._evaluators[0].render()]
            result = CoordinateDescentResult(
                model=game_model,
                objective_history=[objective_ds[ci].result()],
                trackers={name: [res]},
                validation_history=validation_history,
                best_model=game_model,
                best_metric=best_metric,
            )
            self.results.append((combo, result, ci))
            metric = result.best_metric
            if metric is None:
                if self.best_result is None or (
                    self.best_result[1] is None and ci < best_orig_idx
                ):
                    self.best_result = (result, None)
                    self.best_config = combo
                    best_orig_idx = ci
            elif (
                self.best_result is None
                or self.best_result[1] is None
                or (maximize and metric > self.best_result[1])
                or (not maximize and metric < self.best_result[1])
            ):
                self.best_result = (result, metric)
                self.best_config = combo
                best_orig_idx = ci

    # -- validation --------------------------------------------------------

    def _validation_fn(self, vdata: GameDataset):
        p = self.params
        loss = loss_for_task(p.task_type)
        evaluators = p.evaluator_types or [
            EvaluatorType.parse(
                "AUC" if p.task_type == TaskType.LOGISTIC_REGRESSION else "RMSE"
            )
        ]

        def fn(game_model: GameModel) -> Dict[str, float]:
            scores = self._score_on(game_model, vdata)
            z = scores + jnp.asarray(vdata.offsets)
            lab = jnp.asarray(vdata.labels)
            w = jnp.asarray(vdata.weights)
            out = {}
            for et in evaluators:
                if et.is_sharded:
                    gids = vdata.entity_codes[et.id_type]
                    ev = Evaluator(et, num_groups=vdata.entity_indexes[et.id_type].num_entities)
                    out[et.render()] = float(
                        ev.evaluate(z, lab, w, jnp.maximum(jnp.asarray(gids), 0))
                    )
                else:
                    metric_in = loss.mean(z) if et.name == "RMSE" else z
                    out[et.render()] = float(
                        Evaluator(et).evaluate(metric_in, lab, w)
                    )
            return out

        self._evaluators = evaluators
        return fn

    def _score_on(self, game_model: GameModel, vdata: GameDataset):
        """Score a validation dataset: fixed effects score directly; RE
        coordinates need row views over the validation rows."""
        total = jnp.zeros((vdata.num_rows,), jnp.float32)
        from photon_ml_tpu.game.model import (
            FixedEffectModel,
            MatrixFactorizationModel,
            RandomEffectModel,
        )
        from photon_ml_tpu.game.coordinate import FactoredRandomEffectModel

        for name, sub in game_model.models.items():
            if isinstance(sub, (FixedEffectModel, MatrixFactorizationModel)):
                total = total + sub.score(vdata)
            elif isinstance(sub, (RandomEffectModel, FactoredRandomEffectModel)):
                view = self._re_view(sub, vdata)
                if isinstance(sub, RandomEffectModel):
                    from photon_ml_tpu.game.random_effect import score_random_effect

                    total = total + score_random_effect(sub.bank, view)
                else:
                    ix = jnp.asarray(view.row_local_indices)
                    v = jnp.asarray(view.row_local_values)
                    x_lat = jnp.einsum(
                        "nk,nkl->nl", v, jnp.take(sub.projection, ix, axis=0)
                    )
                    codes = jnp.maximum(jnp.asarray(view.row_entity_codes), 0)
                    valid = jnp.asarray(view.row_entity_codes >= 0)
                    w_rows = jnp.take(sub.bank, codes, axis=0)
                    total = total + jnp.where(
                        valid, jnp.sum(x_lat * w_rows, axis=-1), 0.0
                    )
        return total

    def _re_view(self, sub, vdata: GameDataset):
        """Project validation rows into the model's entity-local spaces.

        Entities are matched by RAW id between train and validation
        (the reference's join on idTypeToValueMap); unseen entities score 0.
        """
        from dataclasses import replace as dc_replace

        base = sub.re_dataset
        train_eindex = self._train_dataset.entity_indexes[sub.random_effect_type]
        v_eindex = vdata.entity_indexes[sub.random_effect_type]
        sd = vdata.shards[sub.feature_shard_id]
        n, k = sd.indices.shape
        codes = np.full((n,), -1, np.int32)
        v_codes = vdata.entity_codes[sub.random_effect_type]
        for i in range(n):
            c = v_codes[i]
            if c >= 0 and vdata.weights[i] > 0:
                raw = v_eindex.ids[c]
                tc = train_eindex.code_of.get(raw)
                if tc is not None:
                    codes[i] = tc
        row_ix = np.zeros((n, k), np.int32)
        row_v = np.zeros((n, k), np.float32)
        from photon_ml_tpu.game.config import ProjectorType

        ptype = base.config.projector_type
        if ptype == ProjectorType.IDENTITY:
            row_ix, row_v = sd.indices.copy(), sd.values.copy()
        elif ptype == ProjectorType.RANDOM:
            D = base.local_dim
            row_ix = np.tile(np.arange(D, dtype=np.int32)[None, :], (n, 1))
            row_v = np.zeros((n, D), np.float32)
            for i in range(n):
                if codes[i] < 0:
                    continue
                nz = sd.values[i] != 0
                row_v[i] = (
                    base.random_projection[sd.indices[i][nz]].T @ sd.values[i][nz]
                )
        else:
            lmaps = {}
            for i in range(n):
                c = int(codes[i])
                if c < 0:
                    continue
                if c not in lmaps:
                    proj = base.projection[c]
                    lmaps[c] = {int(g): l for l, g in enumerate(proj) if g >= 0}
                lm = lmaps[c]
                for s in range(k):
                    if sd.values[i, s] != 0:
                        l = lm.get(int(sd.indices[i, s]))
                        if l is not None:
                            row_ix[i, s] = l
                            row_v[i, s] = sd.values[i, s]
        return dc_replace(
            base,
            row_local_indices=row_ix,
            row_local_values=row_v,
            row_entity_codes=codes,
            buckets=[],
        )

    # -- continuous retraining (registry/) ----------------------------------

    def _load_parent(self) -> None:
        """Resolve --retrain-from to the latest committed generation's
        loaded GAME artifact (cold start when the registry is empty)."""
        p = self.params
        if not p.retrain_from:
            return
        from photon_ml_tpu.game.model_io import load_game_model
        from photon_ml_tpu.registry import ModelRegistry

        registry = ModelRegistry(p.retrain_from)
        info = registry.latest()
        if info is None:
            self.logger.info(
                "retrain-from registry %s has no committed generation; "
                "cold start", p.retrain_from,
            )
            return
        self._parent_generation = info
        with self.timer.time("load-parent"):
            self._parent_loaded = load_game_model(info.model_dir)
        self.logger.info(
            "retraining from generation %d (lineage %s, coordinates %s)",
            info.generation,
            registry.lineage(info.generation),
            self._parent_loaded.coordinate_names(),
        )

    def _warm_start_model(self, dataset, re_datasets):
        """The initial GameModel for the first combo: parent FE vectors
        and RE banks aligned to the NEW dataset (coordinates the parent
        lacks fall back to zero-init inside CoordinateDescent.run)."""
        if self._parent_loaded is None:
            return None
        from photon_ml_tpu.registry import warm_start_game_model

        model, reports = warm_start_game_model(
            self._parent_loaded, dataset, re_datasets,
            self.params.task_type,
        )
        self._drift_reports = reports
        for name, rep in reports.items():
            self.logger.info(
                "warm-start %s: %d kept, %d new, %d dropped, "
                "%d entities kept, %d churned (prior-mean), "
                "%d entities dropped%s",
                name, rep.kept, rep.new_zero_init, rep.dropped,
                rep.kept_entities, rep.churned_entities_prior_init,
                rep.dropped_entities,
                "" if rep.no_drift else " [DRIFT]",
            )
        return model

    def _model_norms(self, best_model):
        """(candidate_norm, parent_norm): FE + RE coefficient L2 norms
        for the coefficient-sanity gate, both sides over their own
        stored coefficients."""
        from photon_ml_tpu.game.model import (
            FixedEffectModel,
            RandomEffectModel,
        )
        from photon_ml_tpu.parallel import overlap

        sq_terms = []
        for sub in best_model.models.values():
            if isinstance(sub, FixedEffectModel):
                w = sub.model.means
                sq_terms.append(jnp.vdot(w, w))
            elif isinstance(sub, RandomEffectModel):
                sq_terms.append(jnp.vdot(sub.bank, sub.bank))
        cand_sq = (
            sum(float(x) for x in overlap.device_get(sq_terms))
            if sq_terms else 0.0
        )
        par_sq = 0.0
        for _name, (_sid, means) in self._parent_loaded.fixed_effects.items():
            par_sq += sum(float(v) ** 2 for v in means.values())
        for _name, (_rt, _sid, per_entity) in (
            self._parent_loaded.random_effects.items()
        ):
            for means in per_entity.values():
                par_sq += sum(float(v) ** 2 for v in means.values())
        return float(np.sqrt(cand_sq)), float(np.sqrt(par_sq))

    def _run_gates(self, best_model, vdata):
        """Candidate-vs-parent gates on the loaded validation dataset
        (both models score the SAME rows; the parent resolves features/
        entities by key, so drift costs it exactly its vanished terms)."""
        from photon_ml_tpu.parallel import overlap
        from photon_ml_tpu.registry import GateConfig, evaluate_gates

        p = self.params
        config = GateConfig(
            max_auc_drop=p.gate_max_auc_drop,
            max_rmse_increase=p.gate_max_rmse_increase,
            max_coef_norm_ratio=p.gate_max_coef_norm_ratio,
            max_prediction_drift=p.gate_max_prediction_drift,
        )
        offsets = jnp.asarray(vdata.offsets)
        cand, par, labels, weights = overlap.device_get(
            (
                self._score_on(best_model, vdata) + offsets,
                self._parent_loaded.score(vdata, p.task_type) + offsets,
                vdata.labels,
                vdata.weights,
            )
        )
        cand_norm, par_norm = self._model_norms(best_model)
        report = evaluate_gates(
            [(cand, par, labels, weights)],
            p.task_type,
            config=config,
            candidate_norm=cand_norm,
            parent_norm=par_norm,
        )
        self._gate_report = report
        self.logger.info(
            "validation gates: %s %s", report.verdict,
            {k: v.get("passed") for k, v in report.checks.items()},
        )
        return report

    def _publish_to_registry(self, vdata) -> None:
        """Publish the saved best-model directory as the next
        generation; a failed gate records its named verdict (registry
        refusal + metrics.json) and leaves the lineage unchanged."""
        p = self.params
        best = self.best_result[0] if self.best_result is not None else None
        if best is None:
            return
        gate_report = None
        if self._parent_loaded is not None and vdata is not None:
            gate_report = self._run_gates(best.best_model, vdata)
        from photon_ml_tpu.registry import ModelRegistry, RefusedCandidate

        registry = ModelRegistry(p.publish_registry)
        extra = {"task": p.task_type.name}
        if self._drift_reports:
            extra["drift"] = {
                name: rep.as_dict()
                for name, rep in self._drift_reports.items()
            }
        try:
            info = registry.publish(
                os.path.join(p.output_dir, "best-model"),
                parent=(
                    self._parent_generation.generation
                    if self._parent_generation is not None
                    else None
                ),
                data_ranges={
                    "train_input_dirs": list(p.train_input_dirs),
                    "train_date_range": p.train_date_range,
                    "train_date_range_days_ago": (
                        p.train_date_range_days_ago
                    ),
                },
                gate_report=(
                    gate_report.as_dict() if gate_report is not None
                    else None
                ),
                extra=extra,
            )
            self._published_generation = info.generation
            self.logger.info(
                "published generation %d (parent %s, signature %s)",
                info.generation, info.parent, info.signature,
            )
        except RefusedCandidate as e:
            self.logger.warning(
                "candidate REFUSED by validation gate %s; generation "
                "lineage unchanged (refusal recorded at %s)",
                e.verdict, e.refused_dir,
            )

    def _registry_metrics(self):
        p = self.params
        if not (p.retrain_from or p.publish_registry):
            return None
        return {
            "retrain_from": p.retrain_from,
            "parent_generation": (
                self._parent_generation.generation
                if self._parent_generation is not None else None
            ),
            "published_generation": self._published_generation,
            "drift": {
                name: rep.as_dict()
                for name, rep in self._drift_reports.items()
            },
            "gates": (
                self._gate_report.as_dict()
                if self._gate_report is not None else None
            ),
        }

    # -- run ---------------------------------------------------------------

    def _offheap_index_maps(self):
        """{shard_id: index map} resolved like the reference's
        prepareFeatureMaps dispatch (cli/game/GAMEDriver.scala:89-97):
        offheap stores when --offheap-indexmap-dir is set, else
        name-and-term list files when --feature-name-and-term-set-path is
        set, else None (maps built from the training data)."""
        p = self.params
        if p.offheap_indexmap_dir:
            from photon_ml_tpu.utils.native_index import (
                load_offheap_index_maps,
            )

            maps = load_offheap_index_maps(
                p.offheap_indexmap_dir,
                [cfg.shard_id for cfg in p.feature_shards],
                num_partitions=p.offheap_indexmap_num_partitions,
            )
            for sid, m in maps.items():
                self.logger.info(
                    "offheap index map %s: %d features", sid, m.size
                )
            return maps
        if p.feature_name_and_term_set_path:
            from photon_ml_tpu.io.name_term_list import (
                index_maps_from_name_term_lists,
            )

            maps = index_maps_from_name_term_lists(
                p.feature_name_and_term_set_path, p.feature_shards
            )
            for sid, m in maps.items():
                self.logger.info(
                    "name-term list index map %s: %d features", sid, m.size
                )
            return maps
        return None

    # -- streaming (out-of-core) path --------------------------------------

    def _run_streaming(self) -> None:
        """Out-of-core run: scan -> stage -> streamed CD per combo, with
        streamed validation and the model written through the standard
        save_game_model layout (the scoring driver reads it unchanged)."""
        from photon_ml_tpu.game.data import ShardData
        from photon_ml_tpu.game.streaming import train_streaming_game
        from photon_ml_tpu.utils.profiling import peak_rss_bytes

        p = self.params
        train_paths = self._expand_dated(
            p.train_input_dirs, p.train_date_range,
            p.train_date_range_days_ago,
        )
        validate_paths = None
        if p.validate_input_dirs:
            validate_paths = self._expand_dated(
                p.validate_input_dirs, p.validate_date_range,
                p.validate_date_range_days_ago,
            )
        combos = expand_config_grid(
            {**p.fixed_effect_opt_configs, **p.random_effect_opt_configs}
        )
        self.logger.info(
            "streaming GAME training: %d configuration combo(s), "
            "%d B memory budget",
            len(combos), p.stream_memory_budget,
        )
        maximize = p.task_type == TaskType.LOGISTIC_REGRESSION
        best = None
        best_extras = None
        best_orig_idx = None
        guard = None
        if p.checkpoint_dir is not None:
            from photon_ml_tpu.utils.preemption import PreemptionGuard

            guard = PreemptionGuard().install()
        preempted = False
        try:
            for ci, combo in enumerate(combos):
                if guard is not None and guard.requested:
                    self.logger.warning(
                        "preemption requested: not starting combo %d/%d",
                        ci + 1, len(combos),
                    )
                    preempted = True
                    break
                combo_ckpt_dir = None
                if p.checkpoint_dir is not None:
                    # combo-content keyed directory, like the in-memory
                    # sweep: a changed grid can never resume foreign
                    # staged chunks or CD snapshots
                    fp = hashlib.sha1(
                        "|".join(
                            f"{name}:{cfg.render()}"
                            for name, cfg in sorted(combo.items())
                        ).encode()
                    ).hexdigest()[:12]
                    combo_ckpt_dir = os.path.join(
                        p.checkpoint_dir, f"combo-{fp}"
                    )
                with (
                    self.timer.time(f"train-combo-{ci}"),
                    profile_trace(p.profile_dir if ci == 0 else None),
                    obs_span("game.train_combo", combo=ci),
                ):
                    result, extras = train_streaming_game(
                        train_paths,
                        p.feature_shards,
                        p.fixed_effect_data_configs,
                        p.random_effect_data_configs,
                        combo,
                        p.task_type,
                        num_iterations=p.num_iterations,
                        update_sequence=p.updating_sequence,
                        memory_budget_bytes=p.stream_memory_budget,
                        index_maps=self._offheap_index_maps(),
                        validate_paths=validate_paths,
                        evaluator_types=p.evaluator_types or None,
                        compute_variance=p.compute_variance,
                        diagnostic_reservoir_rows=p.diagnostic_reservoir_rows,
                        diagnostic_reservoir_bytes=p.diagnostic_reservoir_bytes,
                        logger=self.logger,
                        checkpoint_dir=combo_ckpt_dir,
                        preemption_guard=guard,
                        entity_mesh=self._entity_mesh(),
                    )
                self.results.append((combo, result, ci))
                metric = result.best_metric
                if metric is None:
                    if best is None or (
                        best[0].best_metric is None and ci < best_orig_idx
                    ):
                        best, best_extras, best_orig_idx = result, extras, ci
                        self.best_config = combo
                elif (
                    best is None
                    or best[0].best_metric is None
                    or (maximize and metric > best[0].best_metric)
                    or (not maximize and metric < best[0].best_metric)
                ):
                    best, best_extras, best_orig_idx = result, extras, ci
                    self.best_config = combo
                if result.preempted:
                    self.logger.warning(
                        "stopping streaming combo sweep after preemption "
                        "(combo %d/%d)", ci + 1, len(combos),
                    )
                    preempted = True
                    break
        finally:
            if guard is not None:
                guard.uninstall()
        if preempted:
            # best-so-far still publishes (mirroring the in-memory sweep);
            # the checkpoints carry everything needed to resume and finish
            self.logger.warning(
                "preempted: publishing best-so-far; rerun with the same "
                "args to resume the sweep from the checkpoints"
            )
        self.best_result = (best, best.best_metric if best else None)
        if p.model_output_mode != "NONE" and best is not None:
            # a shell dataset carrying ONLY what save_game_model reads:
            # per-shard index maps + entity indexes (no row data)
            shells = {
                sid: ShardData(
                    indices=np.zeros((0, 1), np.int32),
                    values=np.zeros((0, 1), np.float32),
                    index_map=imap,
                    intercept_index=None,
                )
                for sid, imap in best_extras["index_maps"].items()
            }
            shell = GameDataset(
                uids=[],
                labels=np.zeros(0, np.float32),
                offsets=np.zeros(0, np.float32),
                weights=np.zeros(0, np.float32),
                shards=shells,
                entity_codes={},
                entity_indexes=best_extras["entity_indexes"],
                num_real_rows=0,
            )
            with self.timer.time("save-model"):
                save_game_model(
                    best.game_model, shell,
                    os.path.join(p.output_dir, "best-model"),
                    model_spec="\n".join(
                        f"{name} -> {cfg.render()}"
                        for name, cfg in self.best_config.items()
                    ),
                    num_re_output_files=(
                        p.num_output_files_for_random_effect_model
                    ),
                )
        sample = best_extras["diagnostics_sample"] if best_extras else None
        diag = None
        if sample is not None and len(sample["lab"]):
            diag = {
                "reservoir_rows": int(len(sample["lab"])),
                "label_mean": float(np.mean(sample["lab"])),
                "weight_sum": float(np.sum(sample["wgt"])),
            }
        from photon_ml_tpu.reliability import (
            atomic_write_json,
            reliability_metrics,
        )

        atomic_write_json(
            os.path.join(p.output_dir, "metrics.json"),
            {
                "objective_history": (
                    best.objective_history if best else []
                ),
                "validation_history": (
                    best.validation_history if best else []
                ),
                "best_metric": best.best_metric if best else None,
                "timers": self.timer.durations,
                "streaming": {
                    "memory_budget_bytes": p.stream_memory_budget,
                    "rows_per_chunk": (
                        best_extras["rows_per_chunk"]
                        if best_extras else None
                    ),
                    "num_chunks": (
                        best_extras["store"].count
                        if best_extras else None
                    ),
                    "peak_rss_bytes": peak_rss_bytes(),
                    "diagnostics": diag,
                },
                "reliability": reliability_metrics(),
                **(
                    {"obs": self.obs.finish()}
                    if self.obs.enabled else {}
                ),
            },
        )
        self.logger.info("timers:\n%s", self.timer.summary())

    def run(self) -> None:
        p = self.params
        self.logger.info("application: %s", p.application_name)
        if p.streaming:
            self._run_streaming()
            return
        with self.timer.time("load-train"):
            dataset = self._load_dataset(
                self._expand_dated(
                    p.train_input_dirs, p.train_date_range,
                    p.train_date_range_days_ago,
                ),
                index_maps=self._offheap_index_maps(),
            )
        self._train_dataset = dataset
        self.logger.info(
            "GAME train data: %d rows, shards %s",
            dataset.num_real_rows,
            {s: d.dim for s, d in dataset.shards.items()},
        )
        with self.timer.time("re-datasets"):
            re_datasets = {
                name: build_random_effect_dataset(dataset, cfg)
                for name, cfg in p.random_effect_data_configs.items()
            }
        self._load_parent()
        warm_model = self._warm_start_model(dataset, re_datasets)
        vdata = None
        validation_fn = None
        if p.validate_input_dirs:
            with self.timer.time("load-validate"):
                index_maps = {
                    s: d.index_map for s, d in dataset.shards.items()
                }
                vdata = self._load_dataset(
                    self._expand_dated(
                        p.validate_input_dirs, p.validate_date_range,
                        p.validate_date_range_days_ago,
                    ),
                    index_maps,
                )
            validation_fn = self._validation_fn(vdata)

        combos = expand_config_grid(
            {**p.fixed_effect_opt_configs, **p.random_effect_opt_configs}
        )
        self.logger.info("training %d configuration combo(s)", len(combos))
        maximize = p.task_type == TaskType.LOGISTIC_REGRESSION
        if self._fe_grid_lambdas(combos) is not None:
            # pure FE lambda sweep: every combo's fixed effect solves in
            # ONE vmapped grid program (--grid-mode batched/auto)
            self._train_fe_grid_batched(
                combos, dataset, re_datasets, validation_fn, maximize
            )
        else:
            # Cross-combo warm start: train the most-regularized combo first
            # and seed each subsequent combo's coordinate models from the
            # previous fit — the GLM lambda-grid warm start
            # (ModelTraining.scala:183-208) lifted to the GAME grid, which the
            # reference retrains from scratch per combo. Original grid indices
            # ride along so timer labels and metric-less best selection keep
            # the user's configured order.
            order = sorted(
                range(len(combos)),
                key=lambda i: -sum(
                    cfg.reg_weight for cfg in combos[i].values()
                ),
            )
            guard = None
            run_manifest = None
            if p.checkpoint_dir is not None:
                from photon_ml_tpu.utils.preemption import PreemptionGuard

                guard = PreemptionGuard().install()
                run_manifest = {
                    "train_input_dirs": list(p.train_input_dirs),
                    "train_date_range": p.train_date_range,
                    "train_date_range_days_ago": p.train_date_range_days_ago,
                    "task_type": p.task_type.name,
                    "updating_sequence": list(p.updating_sequence or []),
                    "feature_shards": [repr(s) for s in p.feature_shards],
                    "fixed_effect_data_configs": {
                        k: repr(v)
                        for k, v in sorted(p.fixed_effect_data_configs.items())
                    },
                    "random_effect_data_configs": {
                        k: repr(v)
                        for k, v in sorted(p.random_effect_data_configs.items())
                    },
                    # the feature-map source defines the coefficient index
                    # space — a changed source must not resume old weights
                    "offheap_indexmap_dir": p.offheap_indexmap_dir,
                    "feature_name_and_term_set_path": (
                        p.feature_name_and_term_set_path
                    ),
                }
                if p.mf_configs:
                    run_manifest["mf_configs"] = {
                        k: repr(v) for k, v in sorted(p.mf_configs.items())
                    }
            # retrain warm start: the aligned parent model seeds the
            # FIRST (most-regularized) combo exactly like the cross-
            # combo warm start seeds the rest
            prev_model = warm_model
            best_orig_idx = None
            build_futures: Dict[int, object] = {}
            try:
                for ti, ci in enumerate(order):
                    combo = combos[ci]
                    if guard is not None and guard.requested:
                        self.logger.warning(
                            "preemption requested: not starting combo %d/%d",
                            ti + 1,
                            len(combos),
                        )
                        break
                    with (
                        self.timer.time(f"train-combo-{ci}"),
                        # trace the FIRST combo actually trained (combos run
                        # in warm-start order, not grid order)
                        profile_trace(p.profile_dir if ti == 0 else None),
                        obs_span("game.train_combo", combo=ci),
                    ):
                        from photon_ml_tpu.parallel import overlap

                        fut = build_futures.pop(ci, None)
                        coords = (
                            overlap.wait(fut)
                            if fut is not None
                            else self._build_coordinates(dataset, re_datasets, combo)
                        )
                        if ti + 1 < len(order):
                            # the NEXT combo's problem setup builds on the
                            # background worker UNDER this combo's training
                            # (overlap prefetched dispatch on the grid axis)
                            nci = order[ti + 1]
                            build_futures[nci] = overlap.submit(
                                self._build_coordinates,
                                dataset, re_datasets, combos[nci],
                            )
                        metric_name = None
                        if validation_fn is not None:
                            metric_name = (self._evaluators[0].render())
                        checkpointer = None
                        if p.checkpoint_dir is not None:
                            from photon_ml_tpu.utils.checkpoint import (
                                TrainingCheckpointer,
                            )

                            # key the directory by the combo's CONTENT so a
                            # changed grid cannot silently resume from another
                            # combo's weights (a different config gets a fresh
                            # directory, not a wrong restore)
                            fp = hashlib.sha1(
                                "|".join(
                                    f"{name}:{cfg.render()}"
                                    for name, cfg in sorted(combo.items())
                                ).encode()
                            ).hexdigest()[:12]
                            combo_dir = os.path.join(
                                p.checkpoint_dir, f"combo-{fp}"
                            )
                            # data/shard/sequence changes fail loudly instead
                            # of silently resuming foreign weights
                            _ensure_manifest(combo_dir, run_manifest)
                            checkpointer = TrainingCheckpointer(combo_dir)
                        cd = CoordinateDescent(
                            coords,
                            dataset,
                            p.task_type,
                            update_sequence=p.updating_sequence,
                            validation_fn=validation_fn,
                            validation_metric=metric_name,
                            validation_maximize=maximize,
                            logger=self.logger,
                            checkpointer=checkpointer,
                            preemption_guard=guard,
                        )
                        try:
                            result = cd.run(
                                p.num_iterations, initial_model=prev_model
                            )
                        finally:
                            if checkpointer is not None:
                                from photon_ml_tpu.parallel import overlap

                                # queued step writes must land before close
                                overlap.drain_io()
                                checkpointer.close()
                        prev_model = result.model
                    self.results.append((combo, result, ci))
                    metric = result.best_metric
                    if metric is None:
                        # no validation metric: selection falls back to the
                        # user's configured grid order (parity with the
                        # pre-warm-start sweep), not training order
                        if self.best_result is None or (
                            self.best_result[1] is None and ci < best_orig_idx
                        ):
                            self.best_result = (result, None)
                            self.best_config = combo
                            best_orig_idx = ci
                    elif (
                        self.best_result is None
                        or self.best_result[1] is None
                        or (maximize and metric > self.best_result[1])
                        or (not maximize and metric < self.best_result[1])
                    ):
                        self.best_result = (result, metric)
                        self.best_config = combo
                        best_orig_idx = ci
                    if result.preempted:
                        self.logger.warning(
                            "stopping combo sweep after preemption (combo %d/%d)",
                            ti + 1,
                            len(combos),
                        )
                        break
            finally:
                if guard is not None:
                    guard.uninstall()

        from photon_ml_tpu.parallel.multihost import (
            is_coordinator,
            sync_processes,
        )

        best = self.best_result[0] if self.best_result is not None else None
        if not is_coordinator():
            sync_processes("outputs-written")
            return
        if best is None:
            # preempted before any combo finished: checkpoints (if enabled)
            # carry the partial state; nothing coherent to save as best
            self.logger.warning(
                "no configuration combo completed; skipping model save"
            )
            sync_processes("outputs-written")
            return
        if p.model_output_mode != "NONE":
            with self.timer.time("save-model"):
                spec = "\n".join(
                    f"{name} -> {cfg.render()}"
                    for name, cfg in self.best_config.items()
                )
                save_game_model(
                    best.best_model, dataset,
                    os.path.join(p.output_dir, "best-model"),
                    model_spec=spec,
                    num_re_output_files=(
                        p.num_output_files_for_random_effect_model
                    ),
                )
                if p.model_output_mode == "ALL":
                    # every combo's final model under all/<original grid
                    # index> (cli/game/training/Driver.scala:620-635) —
                    # NOT warm-start training order, so config position i
                    # always maps to all/<i>
                    for combo, result, ci in self.results:
                        save_game_model(
                            result.model, dataset,
                            os.path.join(p.output_dir, "all", str(ci)),
                            model_spec="\n".join(
                                f"{name} -> {cfg.render()}"
                                for name, cfg in combo.items()
                            ),
                            num_re_output_files=(
                                p.num_output_files_for_random_effect_model
                            ),
                        )
        if p.publish_registry and p.model_output_mode != "NONE":
            with self.timer.time("publish-registry"):
                self._publish_to_registry(vdata)
        from photon_ml_tpu.reliability import (
            atomic_write_json,
            reliability_metrics,
        )

        payload = {
            "objective_history": best.objective_history,
            "validation_history": best.validation_history,
            "best_metric": best.best_metric,
            "timers": self.timer.durations,
            "reliability": reliability_metrics(),
        }
        registry_block = self._registry_metrics()
        if registry_block is not None:
            payload["registry"] = registry_block
        obs_summary = self.obs.finish()
        if obs_summary is not None:
            payload["obs"] = obs_summary
        atomic_write_json(
            os.path.join(p.output_dir, "metrics.json"), payload
        )
        sync_processes("outputs-written")
        self.logger.info("timers:\n%s", self.timer.summary())


# ---------------------------------------------------------------------------
# CLI (option names from cli/game/training/Params.scala)
# ---------------------------------------------------------------------------


def build_arg_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="photon-ml-tpu game-training")
    ap.add_argument("--train-input-dirs", required=True)
    ap.add_argument("--output-dir", required=True)
    ap.add_argument("--validate-input-dirs", default=None)
    ap.add_argument("--train-date-range", default=None,
                    help="yyyyMMdd-yyyyMMdd; expects <dir>/daily/yyyy/MM/dd")
    ap.add_argument("--train-date-range-days-ago", default=None,
                    help="start-end days ago, e.g. 90-1")
    ap.add_argument("--validate-date-range", default=None)
    ap.add_argument("--validate-date-range-days-ago", default=None)
    ap.add_argument("--task-type", default="LOGISTIC_REGRESSION")
    ap.add_argument("--feature-shard-id-to-feature-section-keys-map", required=True)
    ap.add_argument(
        "--feature-shard-id-to-intercept-map", default=None,
        help="shardId1:true|shardId2:false — whether each shard learns an "
        "intercept (default true; Params.scala:289-300)",
    )
    ap.add_argument(
        "--feature-name-and-term-set-path", default=None,
        help="directory of per-section name<TAB>term feature list files "
        "(the default prepareFeatureMaps source)",
    )
    ap.add_argument("--fixed-effect-data-configurations", default="")
    ap.add_argument("--fixed-effect-optimization-configurations", default="")
    ap.add_argument("--random-effect-data-configurations", default="")
    ap.add_argument("--random-effect-optimization-configurations", default="")
    ap.add_argument("--factored-random-effect-optimization-configurations", default="")
    ap.add_argument(
        "--matrix-factorization-configurations", default="",
        help="name:rowEffectType,colEffectType,numFactors,numInnerIterations"
        " ('|'-separated), e.g. mf:userId,itemId,64,1; the coordinate's "
        "optimizer and L2 weight are its entry in "
        "--random-effect-optimization-configurations",
    )
    ap.add_argument("--updating-sequence", default=None)
    ap.add_argument("--num-iterations", type=int, default=1)
    ap.add_argument("--evaluator-types", default=None)
    ap.add_argument("--offheap-indexmap-dir", default=None)
    ap.add_argument("--offheap-indexmap-num-partitions", type=int, default=None)
    ap.add_argument("--compute-variance", default="false")
    ap.add_argument(
        "--model-output-mode", default=None, choices=["ALL", "BEST", "NONE"],
    )
    ap.add_argument(
        "--save-models-to-hdfs", default=None,
        help="DEPRECATED -- use --model-output-mode (true -> ALL)",
    )
    ap.add_argument(
        "--num-output-files-for-random-effect-model", type=int, default=1,
    )
    ap.add_argument("--application-name", default=None)
    ap.add_argument(
        "--min-partitions-for-validation", type=int, default=None,
        help="ignored (Spark-only)",
    )
    ap.add_argument("--delete-output-dir-if-exists", default="false")
    ap.add_argument(
        "--coordinator-address", default=None,
        help="host:port of process 0 for multi-host runs (jax.distributed)",
    )
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument(
        "--distributed", default="auto", choices=["auto", "off", "feature"],
        help="shard FE data axis + RE entity axis over all devices; "
        "feature: run the fixed effect feature-sharded over a "
        "(data, model) mesh (>HBM coefficient vectors)",
    )
    ap.add_argument(
        "--model-shards", type=int, default=None,
        help="model-axis size for --distributed feature (default 2)",
    )
    ap.add_argument(
        "--entity-shards", type=int, default=None,
        help="pod-scale GAME: shard random-effect banks + their "
        "optimizer state over an N-device entity mesh by entity hash "
        "(all_to_all residual routing); -1 = all devices, 0/unset = "
        "replicated banks",
    )
    ap.add_argument(
        "--fault-plan", default=None,
        help="deterministic fault injection, e.g. "
        "'spill_write:2:EIO,ckpt_save:1:ENOSPC' (seam:nth:error[:times])"
        "; also via PHOTON_FAULT_PLAN. Chaos harness: dev-scripts/"
        "chaos.sh",
    )
    ap.add_argument(
        "--retrain-from", default=None,
        help="model-registry directory: warm-start FE vectors and "
        "per-entity RE banks from the latest committed generation with "
        "drift-safe alignment (new terms zero-init, removed terms "
        "dropped with accounting, churned entities prior-mean-init; "
        "bitwise pass-through when nothing drifted)",
    )
    ap.add_argument(
        "--publish-registry", default=None,
        help="model-registry directory: publish best-model as the next "
        "generation, gated against the parent on the validation data "
        "(a failed gate records a named verdict; the candidate is "
        "never loadable)",
    )
    ap.add_argument("--gate-max-auc-drop", type=float, default=0.005)
    ap.add_argument("--gate-max-rmse-increase", type=float, default=0.01)
    ap.add_argument(
        "--gate-max-coef-norm-ratio", type=float, default=10.0
    )
    ap.add_argument(
        "--gate-max-prediction-drift", type=float, default=None,
        help="mean |candidate - parent| holdout margin bound "
        "(default: gate off)",
    )
    ap.add_argument(
        "--checkpoint-dir", default=None,
        help="per-iteration coordinate-descent checkpoints; enables "
        "SIGTERM-safe stop and resume-from-latest on rerun",
    )
    ap.add_argument(
        "--profile-dir", default=None,
        help="write a jax.profiler trace of the first training combo here",
    )
    ap.add_argument(
        "--obs-dir", default=None,
        help="unified telemetry: training-span tracing + flight "
        "recorder; trace.json / flight.json / metrics_snapshot.json "
        "land here atomically",
    )
    ap.add_argument(
        "--tile-cache-dir", default=None,
        help="persistent content-addressed tile-schedule cache directory "
        "(warm GAME sweeps over the same dataset skip the tiled layout "
        "rebuild). Default: $PHOTON_TILE_CACHE_DIR, unset = off",
    )
    ap.add_argument(
        "--no-overlap", default="false",
        help="disable the host-device overlap layer (deferred readbacks, "
        "background host prep, async checkpoint/metrics writes) and run "
        "fully serial — the A/B escape hatch",
    )
    ap.add_argument(
        "--grid-mode", default="auto",
        choices=["batched", "sequential", "auto"],
        help="fixed-effect lambda-tuning policy: when the combo grid is "
        "a pure FE regWeight sweep (one FE coordinate, no REs, 1 CD "
        "iteration), batched solves every combo in ONE vmapped program; "
        "auto applies the --grid-memory-budget fallback; sequential "
        "keeps the warm-started per-combo sweep",
    )
    ap.add_argument(
        "--grid-memory-budget", type=int, default=1 << 30,
        help="byte budget for the batched FE grid's G x d coefficient "
        "bank + vmapped optimizer state (default 1 GiB)",
    )
    ap.add_argument(
        "--streaming", default="false",
        help="true: out-of-core GAME training — the train set streams "
        "once per CD pass through spilled chunks, random effects solve "
        "from disk-backed bucket segments, host peak RSS is bounded by "
        "--stream-memory-budget (IDENTITY-projected plain coordinates)",
    )
    ap.add_argument(
        "--stream-memory-budget", type=int, default=0,
        help="byte budget for the streaming layer (staged-chunk rows + "
        "random-effect segment size); 0 = default chunk sizing "
        "(65536 rows, 1 GiB segments)",
    )
    ap.add_argument(
        "--diagnostic-reservoir-rows", type=int, default=100_000,
        help="max rows in the streaming diagnostics reservoir sample",
    )
    ap.add_argument(
        "--diagnostic-reservoir-bytes", type=int, default=256 << 20,
        help="byte budget for the diagnostics reservoir (rows scale down "
        "for wide multi-shard rows, preserving bounded memory)",
    )
    return ap


def _model_output_mode(ns) -> str:
    """--model-output-mode, with the DEPRECATED --save-models-to-hdfs
    boolean mapping to ALL/NONE (Params.scala:379-386); both together
    conflict."""
    if ns.save_models_to_hdfs is not None:
        if ns.model_output_mode is not None:
            raise ValueError(
                "specifying both save-models-to-hdfs and model-output-mode "
                "is not supported"
            )
        save = str(ns.save_models_to_hdfs).lower() in ("true", "1", "yes")
        return "ALL" if save else "NONE"
    return ns.model_output_mode or "ALL"


def params_from_args(argv=None) -> GameTrainingParams:
    ns = build_arg_parser().parse_args(argv)

    def _bool(s):
        return str(s).lower() in ("true", "1", "yes")

    fe_data = {
        k: FixedEffectDataConfiguration.parse(v)
        for k, v in parse_keyed_map(ns.fixed_effect_data_configurations).items()
    }
    re_data = {
        k: RandomEffectDataConfiguration.parse(v)
        for k, v in parse_keyed_map(ns.random_effect_data_configurations).items()
    }
    factored = {}
    for k, v in parse_keyed_map(
        ns.factored_random_effect_optimization_configurations
    ).items():
        # format: latentDim,numInnerIterations
        parts = [x.strip() for x in v.split(",")]
        factored[k] = FactoredRandomEffectConfiguration(
            latent_space_dimension=int(parts[0]),
            num_inner_iterations=int(parts[1]) if len(parts) > 1 else 2,
        )
    return GameTrainingParams(
        train_input_dirs=ns.train_input_dirs.split(","),
        validate_input_dirs=(
            ns.validate_input_dirs.split(",") if ns.validate_input_dirs else None
        ),
        train_date_range=ns.train_date_range,
        train_date_range_days_ago=ns.train_date_range_days_ago,
        validate_date_range=ns.validate_date_range,
        validate_date_range_days_ago=ns.validate_date_range_days_ago,
        output_dir=ns.output_dir,
        task_type=TaskType.parse(ns.task_type),
        feature_shards=apply_intercept_map(
            parse_shard_map(ns.feature_shard_id_to_feature_section_keys_map),
            ns.feature_shard_id_to_intercept_map,
        ),
        feature_name_and_term_set_path=ns.feature_name_and_term_set_path,
        fixed_effect_data_configs=fe_data,
        fixed_effect_opt_configs=parse_keyed_map(
            ns.fixed_effect_optimization_configurations
        ),
        random_effect_data_configs=re_data,
        random_effect_opt_configs=parse_keyed_map(
            ns.random_effect_optimization_configurations
        ),
        factored_re_configs=factored,
        mf_configs={
            k: MatrixFactorizationConfiguration.parse(v)
            for k, v in parse_keyed_map(
                ns.matrix_factorization_configurations
            ).items()
        },
        updating_sequence=(
            ns.updating_sequence.split(",") if ns.updating_sequence else None
        ),
        num_iterations=ns.num_iterations,
        evaluator_types=(
            [EvaluatorType.parse(s) for s in ns.evaluator_types.split(",")]
            if ns.evaluator_types
            else []
        ),
        compute_variance=_bool(ns.compute_variance),
        model_output_mode=_model_output_mode(ns),
        num_output_files_for_random_effect_model=(
            ns.num_output_files_for_random_effect_model
        ),
        application_name=(
            ns.application_name or "photon-ml-tpu-game-training"
        ),
        offheap_indexmap_dir=ns.offheap_indexmap_dir,
        offheap_indexmap_num_partitions=ns.offheap_indexmap_num_partitions,
        delete_output_dir_if_exists=_bool(ns.delete_output_dir_if_exists),
        distributed=ns.distributed,
        model_shards=ns.model_shards,
        entity_shards=ns.entity_shards,
        coordinator_address=ns.coordinator_address,
        num_processes=ns.num_processes,
        process_id=ns.process_id,
        checkpoint_dir=ns.checkpoint_dir,
        fault_plan=ns.fault_plan,
        profile_dir=ns.profile_dir,
        obs_dir=ns.obs_dir,
        tile_cache_dir=ns.tile_cache_dir,
        no_overlap=_bool(ns.no_overlap),
        grid_mode=ns.grid_mode,
        grid_memory_budget=ns.grid_memory_budget,
        streaming=_bool(ns.streaming),
        stream_memory_budget=ns.stream_memory_budget,
        diagnostic_reservoir_rows=ns.diagnostic_reservoir_rows,
        diagnostic_reservoir_bytes=ns.diagnostic_reservoir_bytes,
        retrain_from=ns.retrain_from,
        publish_registry=ns.publish_registry,
        gate_max_auc_drop=ns.gate_max_auc_drop,
        gate_max_rmse_increase=ns.gate_max_rmse_increase,
        gate_max_coef_norm_ratio=ns.gate_max_coef_norm_ratio,
        gate_max_prediction_drift=ns.gate_max_prediction_drift,
    )


def main(argv=None) -> None:
    GameTrainingDriver(params_from_args(argv)).run()


if __name__ == "__main__":
    main()
