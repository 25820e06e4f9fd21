"""GLM training driver: the end-to-end single-model pipeline + CLI.

Reference: photon-ml Driver.scala — staged pipeline
INIT -> PREPROCESSED -> TRAINED -> VALIDATED -> DIAGNOSED
(DriverStage.scala:47-51; stage methods at Driver.scala:267-292 preprocess,
294-327 train, 329-413 validate, 525-552 diagnose, 618-638 report, main at
590-616), PhotonMLCmdLineParser.scala + OptionNames.scala (CLI option
names kept verbatim), Params.scala:200-222 (cross-field validation).

The Spark context is replaced by a jax device context; everything between
load and model write-out runs on device.
"""

from __future__ import annotations

import argparse
import enum
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from photon_ml_tpu.data.stats import compute_summary
from photon_ml_tpu.data.validators import DataValidationType, sanity_check_data
from photon_ml_tpu.evaluation import (
    area_under_roc_curve,
    mean_pointwise_loss,
    root_mean_squared_error,
)
from photon_ml_tpu.events import (
    EventEmitter,
    PhotonOptimizationLogEvent,
    TrainingFinishEvent,
    TrainingStartEvent,
)
from photon_ml_tpu.io import schemas
from photon_ml_tpu.io.avro_codec import write_container
from photon_ml_tpu.io.input_format import LoadedData, create_input_format
from photon_ml_tpu.io.model_io import save_glm_models_avro, write_models_in_text
from photon_ml_tpu.models.glm import compute_margins, compute_means
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.normalization import (
    NormalizationContext,
    NormalizationType,
    build_normalization,
)
from photon_ml_tpu.optim import CONVERGENCE_REASON_NAMES, OptimizerType, RegularizationType
from photon_ml_tpu.task import TaskType
from photon_ml_tpu.training import train_generalized_linear_model
from photon_ml_tpu.utils.backend import enable_compilation_cache
from photon_ml_tpu.utils.index_map import split_feature_key
from photon_ml_tpu.utils.logging_util import PhotonLogger, Timer


class DriverStage(enum.IntEnum):
    INIT = 0
    PREPROCESSED = 1
    TRAINED = 2
    VALIDATED = 3
    DIAGNOSED = 4


class DiagnosticMode(enum.Enum):
    NONE = "NONE"
    TRAIN = "TRAIN"
    VALIDATE = "VALIDATE"
    ALL = "ALL"

    @classmethod
    def parse(cls, s: str) -> "DiagnosticMode":
        return cls(s.strip().upper())


@dataclass
class GLMParams:
    """Mirror of the reference's Params bean (Params.scala)."""

    train_dir: str = ""
    output_dir: str = ""
    validate_dir: Optional[str] = None
    # Dated-input coordinates (DateRange.scala / IOUtils.scala:84+): when a
    # range is given the directory is expected in daily format
    # <dir>/daily/yyyy/MM/dd and expands to the days in range.
    train_date_range: Optional[str] = None
    train_date_range_days_ago: Optional[str] = None
    validate_date_range: Optional[str] = None
    validate_date_range_days_ago: Optional[str] = None
    # Per-iteration validation metrics (validatePerIteration,
    # Driver.scala:329-372); requires a validation directory.
    validate_per_iteration: bool = False
    task: TaskType = TaskType.LOGISTIC_REGRESSION
    input_format: str = "AVRO"  # AVRO | LIBSVM (INPUT_FILE_FORMAT)
    # Avro field-name convention (io/FieldNamesType.scala): the response
    # field is "label" for TRAINING_EXAMPLE, "response" for
    # RESPONSE_PREDICTION.
    field_names: str = "TRAINING_EXAMPLE"
    # Pre-declared LibSVM dimension (--feature-dimension,
    # LibSVMInputDataFormat.scala:32-39): indices are ids, no vocab scan.
    feature_dimension: Optional[int] = None
    # Per-iteration optimizer state logging (OPTIMIZATION_STATE_TRACKER
    # option): writes optimization-log.txt under the output directory.
    enable_optimization_tracker: bool = True
    add_intercept: bool = True
    regularization_weights: List[float] = field(default_factory=lambda: [0.0])
    regularization_type: RegularizationType = RegularizationType.L2
    elastic_net_alpha: Optional[float] = None
    optimizer_type: OptimizerType = OptimizerType.LBFGS
    max_num_iterations: Optional[int] = None
    tolerance: Optional[float] = None
    normalization_type: NormalizationType = NormalizationType.NONE
    data_validation_type: DataValidationType = DataValidationType.VALIDATE_FULL
    constraint_string: Optional[str] = None
    selected_features_file: Optional[str] = None
    summarization_output_dir: Optional[str] = None
    # Prebuilt partitioned feature-index store (OptionNames.scala:47-48,
    # PalDBIndexMapLoader analog): skip the vocabulary build and use the
    # store for name<->index lookup. Built by the feature-indexing job.
    offheap_indexmap_dir: Optional[str] = None
    offheap_indexmap_num_partitions: Optional[int] = None
    diagnostic_mode: DiagnosticMode = DiagnosticMode.NONE
    compute_variances: bool = False
    delete_output_dirs_if_exist: bool = False
    job_name: str = "photon-ml-tpu"
    event_listeners: List[str] = field(default_factory=list)
    # objective kernel: "auto" (tiled Pallas on accelerators, scatter on
    # CPU), "tiled", or "scatter" — see optim.problem.resolve_kernel
    kernel: str = "auto"
    # "auto": train data-parallel under shard_map whenever >1 device is
    # visible (the reference is distributed by construction — every Spark
    # driver runs on a cluster); "off": single-device; "feature":
    # feature-sharded coefficients over a 2-D (data, model) mesh — the
    # >HBM-coefficient path (SURVEY §2.3 coefficient parallelism)
    distributed: str = "auto"
    model_shards: Optional[int] = None  # model-axis size for "feature"
    # Stream the training data from disk per objective evaluation
    # (io/streaming.py): datasets larger than host RAM train with bounded
    # memory — the GLMSuite/Spark MEMORY_AND_DISK analog. Avro (native
    # chunked decode) or LibSVM (line-at-a-time) input; host-driven
    # L-BFGS/OWL-QN/TRON; validation data still loads in memory.
    streaming: bool = False
    # Explicit host-memory byte budget for the streaming layer: fixes the
    # staged-chunk row count (budget // bytes-per-row) AND the chunk/
    # sharded cache tiers, and is reported against the measured peak-RSS
    # high-water in metrics.json. 0 keeps the historical default sizing
    # (65536-row chunks, 2 GiB cache tiers).
    stream_memory_budget: int = 0
    # jax.profiler trace of the training stage into this directory
    # (SURVEY §7.11 upgrade over Timer-only observability); conventionally
    # <output-dir>/profile, viewable in TensorBoard/Perfetto.
    profile_dir: Optional[str] = None
    # Unified telemetry (ISSUE 13): --obs-dir enables training-span
    # tracing (CD iterations, per-lambda solves, streaming passes) +
    # the flight recorder; trace.json/flight.json land here at exit.
    obs_dir: Optional[str] = None
    # Persistent content-addressed tile-schedule cache directory
    # (ops/schedule_cache.py): warm reruns over the same dataset load the
    # tiled layout instead of paying the multi-second rebuild. None falls
    # back to the PHOTON_TILE_CACHE_DIR env var; unset = off.
    tile_cache_dir: Optional[str] = None
    # Escape hatch for the host-device overlap layer (parallel/overlap.py):
    # True runs fully serial — eager readbacks, inline host prep,
    # synchronous artifact writes (the pre-overlap behavior).
    no_overlap: bool = False
    # Diagnostics reservoir bounds for the streaming path: the sample is
    # rows x max_nnz dense (int32+float32), so wide-row datasets must not
    # blow the bounded-memory contract — rows are scaled down to fit the
    # byte budget.
    diagnostic_reservoir_rows: int = 100_000
    diagnostic_reservoir_bytes: int = 256 << 20
    # λ-grid execution policy (training.resolve_grid_mode): "batched"
    # stacks the grid into a [G, d] bank and runs ONE vmapped optimizer
    # program over a grid-fused objective (1 compile / 1 loop / 1
    # readback round for the whole grid, no cross-λ warm starts);
    # "sequential" keeps the warm-started one-solve-per-λ path; "auto"
    # picks batched when the in-memory grid has >1 member and the G×d
    # state bank fits --grid-memory-budget, and falls back to sequential
    # otherwise (streaming/out-of-core always runs sequential).
    grid_mode: str = "auto"
    grid_memory_budget: int = 1 << 30
    # Multi-host orchestration (the SparkContextConfiguration analog):
    # address of process 0's coordination service. None = single-process.
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    # Crash-safe λ-grid resume (reliability.GridCheckpointer): when set,
    # every completed λ snapshots here (warm-start means + model +
    # result), a SIGTERM stops the sweep at the next λ boundary, and a
    # rerun with the same args resumes mid-path with bitwise-identical
    # final models. Sequential, batched, and streaming grids all resume;
    # feature-sharded paths run without snapshots (warned, not failed).
    checkpoint_dir: Optional[str] = None
    # Deterministic fault plan (reliability.faults): inject transient
    # IO errors / corruption at named seams, e.g.
    # "chunk_read:3:EIO,ckpt_save:1:ENOSPC". Also via PHOTON_FAULT_PLAN.
    fault_plan: Optional[str] = None
    # Continuous retraining (registry/): --retrain-from warm-starts the
    # coefficient vector from the latest committed generation of a model
    # registry with drift-safe alignment (new vocab terms zero-init,
    # removed terms dropped with accounting — bitwise pass-through when
    # nothing drifted); --publish-registry publishes the trained best
    # model as the next generation, gated against the parent on the
    # validating directory (AUC/RMSE non-regression, coefficient-norm
    # sanity, optional prediction-drift bound). A failed gate records a
    # named terminal verdict and the candidate is never loadable.
    retrain_from: Optional[str] = None
    publish_registry: Optional[str] = None
    gate_max_auc_drop: float = 0.005
    gate_max_rmse_increase: float = 0.01
    gate_max_coef_norm_ratio: float = 10.0
    gate_max_prediction_drift: Optional[float] = None
    # Append-only per-partition scan/stats cache (registry/stats_cache):
    # the streaming preprocess scan re-reads ONLY partitions without a
    # cache entry — for an hourly retrain over appended data, exactly
    # the new ones (counted in metrics.json scan_cache).
    scan_cache_dir: Optional[str] = None

    def validate(self) -> None:
        """Cross-field checks (Params.validate, Params.scala:200-222)."""
        if not self.train_dir:
            raise ValueError("training-data-directory is required")
        if not self.output_dir:
            raise ValueError("output-directory is required")
        if self.kernel not in ("auto", "tiled", "scatter"):
            raise ValueError(f"unknown kernel {self.kernel!r}")
        if (
            self.feature_dimension is not None
            and self.input_format.strip().upper() != "LIBSVM"
        ):
            raise ValueError(
                "feature-dimension only applies to the LIBSVM input format"
            )
        if self.distributed not in ("auto", "off", "feature"):
            raise ValueError(f"unknown distributed mode {self.distributed!r}")
        if self.optimizer_type == OptimizerType.TRON and self.regularization_type in (
            RegularizationType.L1,
            RegularizationType.ELASTIC_NET,
        ):
            raise ValueError(
                f"Combination of optimizer {self.optimizer_type.value} and "
                f"regularization {self.regularization_type.value} is not allowed"
            )
        if (
            self.task == TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM
            and self.optimizer_type == OptimizerType.TRON
        ):
            raise ValueError("TRON is not supported for the smoothed hinge loss")
        if self.constraint_string is not None and self.normalization_type != NormalizationType.NONE:
            raise ValueError(
                "box constraints with normalization are not supported"
            )
        if any(w < 0 for w in self.regularization_weights):
            raise ValueError("regularization weights must be non-negative")
        if self.grid_mode not in ("batched", "sequential", "auto"):
            raise ValueError(
                f"unknown grid mode {self.grid_mode!r}; expected "
                "batched | sequential | auto"
            )
        if self.grid_mode == "batched" and self.streaming:
            # surface the incompatibility at parse time, not mid-train
            # (training.resolve_grid_mode enforces the same rule)
            raise ValueError(
                "--grid-mode batched is incompatible with --streaming: "
                "streamed objectives evaluate through host IO, which one "
                "vmapped optimizer program cannot trace; the streaming "
                "path always runs the warm-started sequential grid"
            )
        if self.grid_memory_budget < 1:
            raise ValueError("grid-memory-budget must be >= 1")
        if self.diagnostic_reservoir_rows < 1:
            raise ValueError("diagnostic-reservoir-rows must be >= 1")
        if self.diagnostic_reservoir_bytes < 1:
            raise ValueError("diagnostic-reservoir-bytes must be >= 1")
        # Exclusivity AND range-string format validated up front (a
        # malformed range should fail here, not mid-preprocess).
        from photon_ml_tpu.utils.date_range import resolve_date_range

        resolve_date_range(self.train_date_range, self.train_date_range_days_ago)
        resolve_date_range(
            self.validate_date_range, self.validate_date_range_days_ago
        )
        if self.validate_per_iteration and not self.validate_dir:
            raise ValueError(
                "validate-per-iteration requires a validating data directory"
            )
        if self.streaming:
            # Round 5 closed most of the streaming guards and round 7
            # deleted the feature-sharding exclusion: every driver stage
            # is a bounded-memory pass over staged chunks, like the
            # reference's everything-is-an-RDD-pass design
            # (Driver.scala:525-552); --distributed feature now re-stages
            # each streamed chunk per feature block on the (data, model)
            # mesh (io.streaming.FeatureShardedStreamingObjective), with
            # one streamed sharded Hv pass per TRON CG step. What remains
            # unsupported is structural:
            unsupported = []
            if self.distributed == "feature":
                if self.normalization_type != NormalizationType.NONE:
                    unsupported.append(
                        "normalization with streaming feature-sharded "
                        "training (the shift/factor extras are not "
                        "threaded through the per-chunk sharded programs)"
                    )
                if self.coordinator_address is not None:
                    unsupported.append(
                        "multi-process streaming feature-sharded training"
                    )
            if (
                self.coordinator_address is not None
                and not self.offheap_indexmap_dir
            ):
                unsupported.append(
                    "multi-process streaming without a prebuilt offheap "
                    "index map (no single process sees the vocabulary)"
                )
            if unsupported:
                raise ValueError(
                    "streaming training does not support: "
                    + ", ".join(unsupported)
                )
        if self.stream_memory_budget and not self.streaming:
            raise ValueError(
                "stream-memory-budget requires --streaming true"
            )
        if self.scan_cache_dir and not self.streaming:
            raise ValueError(
                "scan-cache-dir caches the streaming preprocess scan; "
                "it requires --streaming true"
            )
        if self.scan_cache_dir and self.input_format.strip().upper() != (
            "AVRO"
        ):
            raise ValueError(
                "scan-cache-dir requires the AVRO input format (the "
                "per-partition moment partials use the native decoder)"
            )
        if self.gate_max_coef_norm_ratio <= 0:
            raise ValueError("gate-max-coef-norm-ratio must be > 0")
        if (
            self.retrain_from
            and self.publish_registry
            and not self.validate_dir
        ):
            raise ValueError(
                "validation-gated promotion (--retrain-from + "
                "--publish-registry) requires a validating data "
                "directory: the gates compare candidate vs parent on a "
                "held-out stream"
            )
        if self.retrain_from and self.distributed == "feature":
            raise ValueError(
                "--retrain-from warm starts are not wired through the "
                "feature-sharded trainers yet; use --distributed auto|off"
            )


def budgeted_reservoir_rows(
    max_rows: int, budget_bytes: int, max_nnz: int
) -> int:
    """Diagnostics-reservoir row count under a byte budget: the sample is
    rows x max_nnz dense (int32 indices + float32 values = 8 B/slot, plus
    12 B/row of label/offset/weight), so wide-row datasets scale rows
    DOWN to fit instead of allocating multiple GB on the host — the
    streaming path's bounded-memory contract. The
    shared core lives in io.streaming.budgeted_rows; the GAME driver
    budgets its (multi-shard-wide) reservoir through the same helper."""
    from photon_ml_tpu.io.streaming import budgeted_rows, sparse_row_bytes

    return budgeted_rows(max_rows, budget_bytes, sparse_row_bytes(max_nnz))


def _glm_artifact_means(model_dir: str) -> Dict[str, float]:
    """The coefficient dict {feature key: value} of a published GLM
    generation (``model.avro``, one best-model record) — the KEY-space
    view drift-safe alignment consumes."""
    from photon_ml_tpu.io.avro_codec import read_container
    from photon_ml_tpu.utils.index_map import feature_key

    path = os.path.join(model_dir, "model.avro")
    _, records = read_container(path)
    for record in records:
        return {
            feature_key(m["name"], m["term"]): float(m["value"])
            for m in record["means"]
        }
    raise ValueError(f"no model record in {path}")


class GLMDriver:
    """Staged GLM pipeline. After run(): ``stage_history`` lists completed
    stages, ``models`` maps lambda->model, ``best_model`` /
    ``validation_metrics`` filled when a validation dir was given."""

    def __init__(
        self,
        params: GLMParams,
        logger: Optional[PhotonLogger] = None,
        emitter: Optional[EventEmitter] = None,
    ):
        params.validate()
        self.params = params
        # Join the coordination service BEFORE any other JAX use so
        # jax.devices() spans all hosts (multihost.initialize_multihost is
        # a no-op single-process). Output-dir guard must precede logger
        # creation (the logger opens photon.log inside the output dir) —
        # IOUtils.processOutputDir analog (Driver.scala:148-151).
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
            # process-wide so every stage's tiled conversion (train,
            # validation, diagnostics) shares the same persistent tier
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
            delete_if_exists=params.delete_output_dirs_if_exist,
            hint="pass --delete-output-dirs-if-exist to overwrite",
        )
        # Every process logs; only the coordinator's photon.log is the log
        # of record (the reference copies exactly one driver log to HDFS).
        self.logger = logger or PhotonLogger(
            params.output_dir if is_coordinator() else None
        )
        self.emitter = emitter or EventEmitter()
        for name in params.event_listeners:
            self.emitter.register_by_name(name)
        from photon_ml_tpu.obs import ObsSession

        self.obs = ObsSession(params.obs_dir, signal_dump=False)
        self.timer = Timer()
        self.stage = DriverStage.INIT
        self.stage_history: List[DriverStage] = []
        self.models = {}
        self.results = {}
        self.best_model = None
        self.best_lambda: Optional[float] = None
        self.validation_metrics: Dict[float, Dict[str, float]] = {}
        self.per_iteration_metrics: Dict[float, List[Dict[str, float]]] = {}
        # single-writer published references: set by the (sequential)
        # train stage before the async summary write is submitted, then
        # never reassigned while the IO worker can see them
        self._data = None  # photon: guarded-by(atomic)
        self._norm: Optional[NormalizationContext] = None
        self._summary = None  # photon: guarded-by(atomic)
        # bounded reservoir sample of a streamed train set (diagnostics)
        self._stream_sample = None
        # tile-schedule cache counters captured after the train stage
        self._schedule_cache_stats: Dict[str, float] = {}
        # per-partition scan-cache counters (--scan-cache-dir)
        self._scan_cache_stats: Dict[str, int] = {}
        # continuous retraining state (--retrain-from / --publish-registry)
        self._parent_generation = None   # registry.GenerationInfo
        self._parent_means: Optional[Dict[str, float]] = None
        self._drift_report = None        # registry.DriftReport
        self._published_generation: Optional[int] = None
        self._gate_report = None

    # -- stages ------------------------------------------------------------

    def _advance(self, stage: DriverStage) -> None:
        self.stage_history.append(stage)
        self.stage = stage

    def preprocess(self) -> None:
        p = self.params
        with self.timer.time("preprocess"):
            selected = None
            if p.selected_features_file:
                with open(p.selected_features_file) as f:
                    selected = [line.strip() for line in f if line.strip()]
            kwargs = dict(
                add_intercept=p.add_intercept, selected_features=selected
            )
            if p.input_format.strip().upper() == "AVRO":
                kwargs["field_names"] = p.field_names
            elif p.feature_dimension is not None:
                kwargs["feature_dimension"] = p.feature_dimension
            fmt = create_input_format(p.input_format, **kwargs)
            self._fmt = fmt
            train_paths = self._dated_paths(
                p.train_dir, p.train_date_range, p.train_date_range_days_ago
            )
            # Multi-host note: every process loads the SAME input (the
            # cross-process device_put contract: identical global value on
            # all hosts, each placing only its addressable shards). True
            # per-process streaming needs a pre-built shared index map
            # (the FeatureIndexingJob store) + global-array assembly via
            # jax.make_array_from_process_local_data — see
            # parallel/multihost.process_shard for the path split.
            prebuilt = None
            if p.offheap_indexmap_dir:
                from photon_ml_tpu.utils.native_index import (
                    load_offheap_index_map,
                )

                prebuilt = load_offheap_index_map(
                    p.offheap_indexmap_dir,
                    num_partitions=p.offheap_indexmap_num_partitions,
                )
                self.logger.info(
                    "offheap index map: %d features from %s",
                    prebuilt.size, p.offheap_indexmap_dir,
                )
            if p.streaming:
                # one bounded-memory pass: vocabulary + staging shape
                # (no full materialization — the train data may exceed
                # RAM); a prebuilt offheap store skips the vocabulary scan
                # (and is required for multi-process streaming)
                import jax

                from photon_ml_tpu.io.streaming import (
                    scan_stream,
                    scan_stream_with_summary,
                )
                from photon_ml_tpu.utils.index_map import intercept_key

                needs_summary = (
                    p.normalization_type != NormalizationType.NONE
                    or bool(p.summarization_output_dir)
                    or p.diagnostic_mode != DiagnosticMode.NONE
                )
                # FUSED scan: vocabulary + stats + colStats in ONE pass
                # over the train dir (stream_scan_with_summary) instead
                # of scan + streamed-summary re-reading it back to back.
                # Falls back to two passes when the summary pass must
                # ALSO draw the diagnostics reservoir (row-level sample
                # in final index space) or reduce across processes.
                fused_summary = None
                use_fused = (
                    needs_summary
                    and p.diagnostic_mode == DiagnosticMode.NONE
                    and jax.process_count() == 1
                    and hasattr(fmt, "stream_scan_with_summary")
                )
                use_scan_cache = (
                    p.scan_cache_dir is not None
                    and jax.process_count() == 1
                )
                if use_scan_cache:
                    # append-only per-partition cache: identical
                    # (index_map, stats) to the uncached scan, touching
                    # only partitions without a valid entry — the
                    # incremental-retrain contract, counted below
                    from photon_ml_tpu.registry import (
                        cached_scan_stream,
                        cached_scan_stream_with_summary,
                    )

                    if use_fused:
                        index_map, stats, fused_summary, cache_stats = (
                            cached_scan_stream_with_summary(
                                train_paths, fmt, p.scan_cache_dir,
                                index_map=prebuilt,
                            )
                        )
                    else:
                        index_map, stats, cache_stats = cached_scan_stream(
                            train_paths, fmt, p.scan_cache_dir,
                            index_map=prebuilt,
                        )
                    self._scan_cache_stats = cache_stats.as_dict()
                    self.logger.info(
                        "scan cache: %d partition(s), %d cached, "
                        "%d scanned, %d quarantined",
                        cache_stats.partitions, cache_stats.cached,
                        cache_stats.scanned, cache_stats.quarantined,
                    )
                elif use_fused:
                    index_map, stats, fused_summary = (
                        scan_stream_with_summary(
                            train_paths, fmt, index_map=prebuilt
                        )
                    )
                else:
                    index_map, stats = scan_stream(
                        train_paths, fmt, index_map=prebuilt
                    )
                icept = (
                    index_map.get_index(intercept_key())
                    if p.add_intercept else -1
                )
                from photon_ml_tpu.io.input_format import (
                    parse_constraint_string,
                )

                constraints = parse_constraint_string(
                    p.constraint_string, index_map, index_map.size,
                    icept if icept >= 0 else None,
                )
                self._data = LoadedData(
                    batch=None,
                    index_map=index_map,
                    num_features=index_map.size,
                    intercept_index=icept if icept >= 0 else None,
                    constraints=constraints,
                )
                self._stream = (train_paths, stats)
                self.logger.info(
                    "streaming scan: %d examples, %d features, "
                    "max %d nnz/row",
                    stats.num_rows, index_map.size, stats.max_nnz,
                )
                if needs_summary:
                    if fused_summary is not None:
                        # the fused pass already collected the colStats —
                        # no second read of the train dir
                        self._summary = fused_summary
                    else:
                        # one more bounded-memory pass: streamed colStats
                        # (+ a reservoir sample of rows when diagnostics
                        # will need row-level resampling).
                        # streaming_summary all-reduces moments across
                        # processes, so each process must scan only ITS
                        # file shard — passing the full set would multiply
                        # every moment by the process count.
                        from photon_ml_tpu.io.streaming import (
                            streaming_summary,
                        )

                        summary_paths = train_paths
                        if jax.process_count() > 1:
                            from photon_ml_tpu.io.streaming import (
                                shard_stream_files,
                            )

                            summary_paths = shard_stream_files(
                                train_paths, fmt
                            )
                        reservoir = 0
                        if p.diagnostic_mode != DiagnosticMode.NONE:
                            reservoir = budgeted_reservoir_rows(
                                p.diagnostic_reservoir_rows,
                                p.diagnostic_reservoir_bytes,
                                stats.max_nnz,
                            )
                            if reservoir < p.diagnostic_reservoir_rows:
                                self.logger.info(
                                    "diagnostics reservoir scaled to %d "
                                    "rows (%d B budget at %d nnz/row)",
                                    reservoir,
                                    p.diagnostic_reservoir_bytes,
                                    stats.max_nnz,
                                )
                        self._summary, self._stream_sample = (
                            streaming_summary(
                                summary_paths, fmt, index_map, stats,
                                reservoir_rows=reservoir,
                            )
                        )
                    self._norm = build_normalization(
                        p.normalization_type,
                        mean=self._summary.mean,
                        std=self._summary.std,
                        max_magnitude=self._summary.max_magnitude,
                        intercept_index=self._data.intercept_index,
                    )
                    if p.summarization_output_dir:
                        from photon_ml_tpu.parallel.multihost import (
                            is_coordinator,
                        )

                        if is_coordinator():
                            # async artifact IO (overlap): the summary
                            # write runs off the critical path; run()
                            # drains before the output barrier
                            from photon_ml_tpu.parallel import overlap

                            overlap.submit_io(  # photon: allow(undrained-io) — run() owns the drain barrier
                                self._write_summary,
                                p.summarization_output_dir,
                                artifact="feature summary",
                            )
                if p.data_validation_type != DataValidationType.VALIDATE_DISABLED:
                    # chunk-wise sanity checks — same DataValidators rules
                    # as the in-memory path, still bounded memory; each
                    # process checks only ITS file shard (the checks are
                    # per-chunk, no cross-host reduce needed)
                    import jax

                    from photon_ml_tpu.io.streaming import iter_chunks

                    check_paths = train_paths
                    if jax.process_count() > 1:
                        from photon_ml_tpu.io.streaming import (
                            shard_stream_files,
                        )

                        check_paths = shard_stream_files(train_paths, fmt)
                    for chunk in iter_chunks(
                        check_paths, fmt, index_map,
                        rows_per_chunk=65536, nnz_width=stats.max_nnz,
                    ):
                        sanity_check_data(
                            chunk, p.task, p.data_validation_type
                        )
                self._advance(DriverStage.PREPROCESSED)
                return
            data = fmt.load(
                train_paths,
                index_map=prebuilt,
                constraint_string=p.constraint_string,
            )
            self._data = data
            self.logger.info(
                "loaded %d examples, %d features",
                int(np.asarray(data.batch.weights > 0).sum()),
                data.num_features,
            )
            sanity_check_data(data.batch, p.task, p.data_validation_type)
            self._summary = compute_summary(data.batch, data.num_features)
            self._norm = build_normalization(
                p.normalization_type,
                mean=self._summary.mean,
                std=self._summary.std,
                max_magnitude=self._summary.max_magnitude,
                intercept_index=data.intercept_index,
            )
            if p.summarization_output_dir:
                from photon_ml_tpu.parallel.multihost import is_coordinator

                if is_coordinator():
                    from photon_ml_tpu.parallel import overlap

                    overlap.submit_io(  # photon: allow(undrained-io) — run() owns the drain barrier
                        self._write_summary, p.summarization_output_dir,
                        artifact="feature summary",
                    )
        self._advance(DriverStage.PREPROCESSED)

    def _dated_paths(self, base_dir, date_range, days_ago):
        """Expand a base dir to its daily paths when a date range is given
        (IOUtils.getInputPathsWithinDateRange analog); otherwise the dir
        itself."""
        from photon_ml_tpu.utils.date_range import (
            input_paths_within_date_range,
            resolve_date_range,
        )

        rng = resolve_date_range(date_range, days_ago)
        if rng is None:
            return base_dir
        paths = input_paths_within_date_range(base_dir, rng)
        self.logger.info(
            "date range %s expanded %s to %d daily paths", rng, base_dir,
            len(paths),
        )
        return paths

    def _mesh(self):
        """Data-parallel mesh over all visible devices (Driver.scala's
        cluster-by-construction analog); None when single-device or off."""
        from photon_ml_tpu.parallel.mesh import maybe_make_mesh

        return maybe_make_mesh(
            self.params.distributed, self.params.model_shards
        )

    def _grid_checkpoint_setup(self):
        """(GridCheckpointer, PreemptionGuard) for --checkpoint-dir, or
        (None, None). The run manifest fingerprints everything that
        shapes the λ iterate chain — resuming under a changed config
        fails loudly instead of mixing foreign snapshots in."""
        p = self.params
        if p.checkpoint_dir is None:
            return None, None
        if p.distributed == "feature":
            self.logger.warning(
                "--checkpoint-dir is not wired through the feature-"
                "sharded trainers yet; training without λ snapshots"
            )
            return None, None
        from photon_ml_tpu.reliability import GridCheckpointer
        from photon_ml_tpu.utils.preemption import PreemptionGuard

        run_config = {
            "train_dir": p.train_dir,
            "train_date_range": p.train_date_range,
            "train_date_range_days_ago": p.train_date_range_days_ago,
            "task": p.task.name,
            "optimizer": p.optimizer_type.value,
            "regularization_type": p.regularization_type.value,
            "regularization_weights": sorted(
                set(float(w) for w in p.regularization_weights)
            ),
            "elastic_net_alpha": p.elastic_net_alpha,
            "max_num_iterations": p.max_num_iterations,
            "tolerance": p.tolerance,
            "normalization_type": p.normalization_type.value,
            "intercept": p.add_intercept,
            "kernel": p.kernel,
            "grid_mode": p.grid_mode,
            "streaming": p.streaming,
            "constraint_string": p.constraint_string,
        }
        if p.retrain_from:
            # the warm start changes the iterate chain: a resumed sweep
            # must come from the SAME parent generation
            run_config["retrain_parent_signature"] = (
                self._parent_generation.signature
                if self._parent_generation is not None
                else None
            )
        guard = PreemptionGuard().install()
        return GridCheckpointer(p.checkpoint_dir, run_config), guard

    # -- continuous retraining (registry/) ----------------------------------

    def _load_parent(self) -> None:
        """Resolve --retrain-from to the latest committed generation and
        its coefficient dict (by feature KEY — alignment never trusts
        indices across vocabularies). A registry with no committed
        generation is a cold start, not an error: the first cron tick
        of a retrain loop trains from zeros and publishes generation 1."""
        p = self.params
        if not p.retrain_from:
            return
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
        self._parent_means = _glm_artifact_means(info.model_dir)
        self.logger.info(
            "retraining from generation %d (lineage %s, %d parent "
            "coefficients, gate verdict %s)",
            info.generation,
            registry.lineage(info.generation),
            len(self._parent_means),
            info.gate_verdict,
        )

    def _retrain_initial(self):
        """The drift-safe warm-start vector in the CURRENT index space
        (None when not retraining): new terms zero-init, removed terms
        dropped with accounting, bitwise the parent when nothing
        drifted. The report lands in metrics.json."""
        if self._parent_means is None:
            return None
        from photon_ml_tpu.registry import DriftReport, align_coefficients

        report = DriftReport()
        initial = align_coefficients(
            self._parent_means, self._data.index_map, report=report
        )
        self._drift_report = report
        self.logger.info(
            "warm-start alignment: %d kept, %d new (zero-init), "
            "%d dropped%s",
            report.kept, report.new_zero_init, report.dropped,
            "" if report.no_drift else " [DRIFT]",
        )
        return initial

    def _run_gates(self, candidate_model):
        """Candidate-vs-parent gates on the validating stream; returns
        the GateReport whose verdict decides the publish."""
        import jax.numpy as jnp

        from photon_ml_tpu.registry import (
            GateConfig,
            align_coefficients,
            evaluate_gates,
        )

        p = self.params
        config = GateConfig(
            max_auc_drop=p.gate_max_auc_drop,
            max_rmse_increase=p.gate_max_rmse_increase,
            max_coef_norm_ratio=p.gate_max_coef_norm_ratio,
            max_prediction_drift=p.gate_max_prediction_drift,
        )
        # the parent scored through TODAY's featurization: shared terms
        # contribute identically, vanished terms contribute nothing
        parent_vec = align_coefficients(
            self._parent_means, self._data.index_map
        )
        candidate_means = np.asarray(candidate_model.means)
        validate_paths = self._dated_paths(
            p.validate_dir, p.validate_date_range,
            p.validate_date_range_days_ago,
        )
        if p.streaming:
            from photon_ml_tpu.io.streaming import scan_stream
            from photon_ml_tpu.registry.gates import glm_gate_chunks

            _, vstats = scan_stream(
                validate_paths, self._fmt, index_map=self._data.index_map
            )
            chunks = glm_gate_chunks(
                jnp.asarray(candidate_means),
                jnp.asarray(parent_vec),
                validate_paths,
                self._fmt,
                self._data.index_map,
                vstats.max_nnz,
            )
        else:
            from photon_ml_tpu.parallel import overlap

            vdata = self._validation_data
            cm, pm, labels, weights = overlap.device_get(
                (
                    compute_margins(
                        jnp.asarray(candidate_means), vdata.batch
                    ),
                    compute_margins(jnp.asarray(parent_vec), vdata.batch),
                    vdata.batch.labels,
                    vdata.batch.weights,
                )
            )
            chunks = [(cm, pm, labels, weights)]
        report = evaluate_gates(
            chunks,
            p.task,
            config=config,
            candidate_norm=float(np.linalg.norm(candidate_means)),
            parent_norm=float(np.linalg.norm(parent_vec)),
        )
        self._gate_report = report
        self.logger.info(
            "validation gates: %s %s", report.verdict,
            {k: v.get("passed") for k, v in report.checks.items()},
        )
        return report

    def _publish_to_registry(self) -> None:
        """Publish the trained model as the next generation. A failed
        gate is an EXPECTED terminal outcome of the retrain loop: the
        refusal (named verdict) is recorded in the registry and in
        metrics.json, and the driver exits cleanly without a new
        generation."""
        p = self.params
        if self.best_model is not None:
            lam, model = self.best_lambda, self.best_model
        elif len(self.models) == 1:
            lam, model = next(iter(self.models.items()))
        else:
            raise ValueError(
                "publishing a multi-lambda grid requires a validating "
                "directory to select the best model"
            )
        gate_report = None
        if self._parent_generation is not None:
            gate_report = self._run_gates(model)
        candidate_dir = os.path.join(p.output_dir, "registry-candidate")
        save_glm_models_avro(
            {lam: model},
            os.path.join(candidate_dir, "model.avro"),
            self._data.index_map,
        )
        # the index map rides with the artifact so the NEXT retrain (and
        # any scorer) aligns by key without this run's output tree
        self._data.index_map.save(
            os.path.join(candidate_dir, "feature-index", "index.json")
        )
        from photon_ml_tpu.registry import ModelRegistry, RefusedCandidate

        registry = ModelRegistry(p.publish_registry)
        extra = {
            "task": p.task.name,
            "lambda": float(lam),
            "num_features": int(self._data.num_features),
        }
        if self._drift_report is not None:
            extra["drift"] = self._drift_report.as_dict()
        try:
            info = registry.publish(
                candidate_dir,
                parent=(
                    self._parent_generation.generation
                    if self._parent_generation is not None
                    else None
                ),
                data_ranges={
                    "train_dir": p.train_dir,
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

    def train(self) -> None:
        p = self.params
        self.emitter.send(TrainingStartEvent(p.job_name))
        from photon_ml_tpu.obs.trace import span as obs_span
        from photon_ml_tpu.utils.profiling import profile_trace

        self._load_parent()
        grid_ckpt, guard = self._grid_checkpoint_setup()
        self._preempted = False
        with (
            self.timer.time("train"),
            profile_trace(p.profile_dir),
            obs_span("glm.train"),
        ):
            data = self._data
            mesh = self._mesh()
            retrain_initial = self._retrain_initial()
            if p.streaming:
                from photon_ml_tpu.io.streaming import (
                    sparse_row_bytes,
                    stream_budget_rows,
                )

                train_paths, stats = self._stream
                rows_per_chunk = stream_budget_rows(
                    p.stream_memory_budget, sparse_row_bytes(stats.max_nnz)
                )
                cache_bytes = (
                    p.stream_memory_budget
                    if p.stream_memory_budget > 0
                    else 2 << 30
                )
                if p.stream_memory_budget:
                    self.logger.info(
                        "stream memory budget %d B -> %d rows/chunk, "
                        "%d B cache tiers",
                        p.stream_memory_budget, rows_per_chunk, cache_bytes,
                    )
                if p.distributed == "feature" and mesh is not None:
                    from photon_ml_tpu.training import (
                        train_streaming_feature_sharded,
                    )

                    self.logger.info(
                        "training in streaming FEATURE-SHARDED mode over "
                        "mesh %s (%d rows per full-batch pass)",
                        dict(mesh.shape), stats.num_rows,
                    )
                    self.models, self.results, _ = (
                        train_streaming_feature_sharded(
                            train_paths,
                            p.task,
                            mesh=mesh,
                            regularization_type=p.regularization_type,
                            regularization_weights=p.regularization_weights,
                            elastic_net_alpha=p.elastic_net_alpha,
                            max_iter=p.max_num_iterations,
                            tolerance=p.tolerance,
                            rows_per_chunk=rows_per_chunk,
                            cache_bytes=cache_bytes,
                            sharded_cache_bytes=cache_bytes,
                            optimizer_type=p.optimizer_type,
                            compute_variances=p.compute_variances,
                            box=data.constraints,
                            track_models=p.validate_per_iteration,
                            fmt=self._fmt,
                            index_map=data.index_map,
                            stats=stats,
                        )
                    )
                else:
                    if mesh is not None:
                        self.logger.warning(
                            "streaming training computes on one device "
                            "per process (the %d-device mesh is not used "
                            "for the chunk passes); across PROCESSES the "
                            "input files shard and gradients reduce "
                            "automatically",
                            mesh.devices.size,
                        )
                    self.logger.info(
                        "training in streaming mode (%d rows per "
                        "full-batch pass)",
                        stats.num_rows,
                    )
                    from photon_ml_tpu.training import train_streaming_glm

                    self.models, self.results, _ = train_streaming_glm(
                        train_paths,
                        p.task,
                        regularization_type=p.regularization_type,
                        regularization_weights=p.regularization_weights,
                        elastic_net_alpha=p.elastic_net_alpha,
                        max_iter=p.max_num_iterations,
                        tolerance=p.tolerance,
                        rows_per_chunk=rows_per_chunk,
                        cache_bytes=cache_bytes,
                        kernel=p.kernel,
                        optimizer_type=p.optimizer_type,
                        normalization=self._norm,
                        compute_variances=p.compute_variances,
                        box=data.constraints,
                        track_models=p.validate_per_iteration,
                        fmt=self._fmt,
                        index_map=data.index_map,
                        stats=stats,
                        tile_cache_dir=p.tile_cache_dir,
                        grid_checkpointer=grid_ckpt,
                        preemption_guard=guard,
                        initial=retrain_initial,
                    )
            elif p.distributed == "feature" and mesh is not None:
                grid_mode = self._resolved_grid_mode(data.num_features)
                if grid_mode == "batched":
                    from photon_ml_tpu.training import (
                        train_grid_batched_feature_sharded,
                    )

                    self.logger.info(
                        "training feature-sharded over mesh %s with a "
                        "BATCHED %d-member lambda grid (one vmapped "
                        "program)",
                        dict(mesh.shape),
                        len(set(p.regularization_weights)),
                    )
                    self.models, self.results = (
                        train_grid_batched_feature_sharded(
                            data.batch,
                            p.task,
                            data.num_features,
                            mesh=mesh,
                            regularization_type=p.regularization_type,
                            regularization_weights=p.regularization_weights,
                            elastic_net_alpha=p.elastic_net_alpha,
                            max_iter=p.max_num_iterations,
                            tolerance=p.tolerance,
                            normalization=self._norm,
                            compute_variances=p.compute_variances,
                            box=data.constraints,
                            intercept_index=data.intercept_index,
                            kernel=p.kernel,
                            optimizer_type=p.optimizer_type,
                            track_models=p.validate_per_iteration,
                            tile_cache_dir=p.tile_cache_dir,
                        )
                    )
                else:
                    from photon_ml_tpu.training import train_feature_sharded

                    self.logger.info(
                        "training feature-sharded over mesh %s",
                        dict(mesh.shape),
                    )
                    self.models, self.results = train_feature_sharded(
                        data.batch,
                        p.task,
                        data.num_features,
                        mesh=mesh,
                        regularization_type=p.regularization_type,
                        regularization_weights=p.regularization_weights,
                        elastic_net_alpha=p.elastic_net_alpha,
                        max_iter=p.max_num_iterations,
                        tolerance=p.tolerance,
                        normalization=self._norm,
                        compute_variances=p.compute_variances,
                        box=data.constraints,
                        intercept_index=data.intercept_index,
                        kernel=p.kernel,
                        optimizer_type=p.optimizer_type,
                        track_models=p.validate_per_iteration,
                        tile_cache_dir=p.tile_cache_dir,
                    )
            else:
                if mesh is not None:
                    self.logger.info(
                        "training data-parallel over %d devices",
                        mesh.devices.size,
                    )
                grid_mode = self._resolved_grid_mode(data.num_features)
                if grid_mode == "batched":
                    from photon_ml_tpu.training import train_grid_batched

                    self.logger.info(
                        "training a BATCHED %d-member lambda grid (one "
                        "vmapped optimizer program; no cross-lambda warm "
                        "starts)",
                        len(set(p.regularization_weights)),
                    )
                    self.models, self.results = train_grid_batched(
                        data.batch,
                        p.task,
                        data.num_features,
                        optimizer_type=p.optimizer_type,
                        regularization_type=p.regularization_type,
                        regularization_weights=p.regularization_weights,
                        elastic_net_alpha=p.elastic_net_alpha,
                        max_iter=p.max_num_iterations,
                        tolerance=p.tolerance,
                        normalization=self._norm,
                        compute_variances=p.compute_variances,
                        box=data.constraints,
                        intercept_index=data.intercept_index,
                        kernel=p.kernel,
                        mesh=mesh,
                        track_models=p.validate_per_iteration,
                        tile_cache_dir=p.tile_cache_dir,
                        grid_checkpointer=grid_ckpt,
                        initial=retrain_initial,
                    )
                else:
                    self.models, self.results = train_generalized_linear_model(
                        data.batch,
                        p.task,
                        data.num_features,
                        optimizer_type=p.optimizer_type,
                        regularization_type=p.regularization_type,
                        regularization_weights=p.regularization_weights,
                        elastic_net_alpha=p.elastic_net_alpha,
                        max_iter=p.max_num_iterations,
                        tolerance=p.tolerance,
                        normalization=self._norm,
                        compute_variances=p.compute_variances,
                        box=data.constraints,
                        intercept_index=data.intercept_index,
                        kernel=p.kernel,
                        mesh=mesh,
                        track_models=p.validate_per_iteration,
                        tile_cache_dir=p.tile_cache_dir,
                        grid_checkpointer=grid_ckpt,
                        preemption_guard=guard,
                        initial=retrain_initial,
                    )
            self._log_results()
        if guard is not None:
            self._preempted = guard.requested
            guard.uninstall()
            if self._preempted:
                self.logger.warning(
                    "preemption requested: lambda sweep stopped at a "
                    "lambda boundary (%d snapshot(s) on disk); rerun "
                    "with the same args to resume", len(self.models),
                )
        self._log_schedule_cache()
        self.emitter.send(TrainingFinishEvent(p.job_name))
        self._advance(DriverStage.TRAINED)

    def _resolved_grid_mode(self, dim: int) -> str:
        """Resolve --grid-mode for the in-memory training stage (the
        streaming branches never call this — out-of-core always runs the
        warm-started sequential path)."""
        from photon_ml_tpu.training import resolve_grid_mode

        p = self.params
        mode = resolve_grid_mode(
            p.grid_mode,
            num_weights=len(set(p.regularization_weights)),
            dim=dim,
            optimizer_type=p.optimizer_type,
            memory_budget_bytes=p.grid_memory_budget,
            streaming=False,
        )
        if p.grid_mode == "auto" and mode == "sequential" and (
            len(set(p.regularization_weights)) > 1
        ):
            self.logger.info(
                "grid-mode auto: %d-member grid over %d features does "
                "not fit the %d-byte bank budget; using the warm-started "
                "sequential path",
                len(set(p.regularization_weights)), dim,
                p.grid_memory_budget,
            )
        return mode

    def _log_schedule_cache(self) -> None:
        """Surface the tile-schedule cache outcome of the training stage
        (build/load/hit-miss timers) to the log and the event stream."""
        from photon_ml_tpu.events import ScheduleCacheEvent
        from photon_ml_tpu.ops.schedule_cache import stats

        s = stats()
        if not (s.builds or s.hits or s.misses):
            return  # scatter kernel / no tiled conversion this run
        self._schedule_cache_stats = s.as_dict()
        self.emitter.send(ScheduleCacheEvent(stats=self._schedule_cache_stats))
        self.logger.info(
            "tile-schedule cache: %d hit(s), %d miss(es), %d build(s) "
            "(build %.2fs, load %.3fs, store %.2fs, hash %.2fs)",
            s.hits, s.misses, s.builds,
            s.build_s, s.load_s, s.store_s, s.hash_s,
        )

    def _log_results(self) -> None:
        # The lambda grid's (iterations, value, reason) scalars live on
        # device; ONE batched fetch materializes the whole grid instead
        # of three scalar pulls per lambda (deferred-readback discipline,
        # parallel/overlap.py via training.grid_result_scalars).
        from photon_ml_tpu.training import grid_result_scalars

        for lam, (iters, value, reason, evaluations) in grid_result_scalars(
            self.results
        ).items():
            self.emitter.send(
                PhotonOptimizationLogEvent(
                    reg_weight=lam,
                    iterations=iters,
                    convergence_reason=CONVERGENCE_REASON_NAMES.get(
                        reason, "?"
                    ),
                    final_value=value,
                )
            )
            self.logger.info(
                "lambda=%g: %d iters, %d evaluations, f=%g, reason=%s",
                lam,
                iters,
                evaluations,
                value,
                CONVERGENCE_REASON_NAMES.get(reason, "?"),
            )

    def _metrics_for(self, model, batch) -> Dict[str, float]:
        task = self.params.task
        margins = compute_margins(model.means, batch)
        loss = loss_for_task(task)
        metrics = {
            f"{loss.name}_loss": float(
                mean_pointwise_loss(loss, margins, batch.labels, batch.weights)
            )
        }
        if task == TaskType.LOGISTIC_REGRESSION or (
            task == TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM
        ):
            metrics["AUC"] = float(
                area_under_roc_curve(margins, batch.labels, batch.weights)
            )
        if task in (TaskType.LINEAR_REGRESSION, TaskType.POISSON_REGRESSION):
            means = compute_means(task, model.means, batch)
            metrics["RMSE"] = float(
                root_mean_squared_error(means, batch.labels, batch.weights)
            )
        return metrics

    def _streamed_metrics_for(self, means, validate_paths, vstats) -> Dict[str, float]:
        """One bounded pass over the validate stream for ONE model: the
        driver's metric set via streaming accumulators (AUC fixed-bin
        histogram, RMSE/losses exact) — evaluation/streaming.py."""
        import jax

        from photon_ml_tpu.evaluation.streaming import (
            finalize_metrics,
            glm_streaming_metrics,
            update_glm_metrics,
        )
        from photon_ml_tpu.io.streaming import iter_chunks

        p = self.params
        loss = loss_for_task(p.task)
        accs = glm_streaming_metrics(p.task, loss)
        margins_fn = self.__dict__.get("_stream_margins_fn")
        if margins_fn is None:
            # jit the named def directly: a jit(lambda ...) here would
            # mint a fresh compile cache per driver instance for nothing
            margins_fn = jax.jit(compute_margins)
            self._stream_margins_fn = margins_fn
        for chunk in iter_chunks(
            validate_paths, self._fmt, self._data.index_map,
            rows_per_chunk=65536, nnz_width=vstats.max_nnz,
        ):
            update_glm_metrics(
                accs, loss, margins_fn(means, chunk),
                chunk.labels, chunk.weights,
            )
        return finalize_metrics(accs)

    def _validate_streaming(self, validate_paths) -> None:
        """Streamed validation (one pass per model over the validate dir,
        never materialized): per-lambda metrics, best-model selection,
        and --validate-per-iteration metrics all consume the stream
        through iter_chunks — the reference's evaluate-as-one-more-
        RDD-pass shape (Driver.scala:329-413)."""
        from photon_ml_tpu.io.streaming import iter_chunks, scan_stream

        p = self.params
        _, vstats = scan_stream(
            validate_paths, self._fmt, index_map=self._data.index_map
        )
        self.logger.info(
            "streamed validation scan: %d examples, max %d nnz/row",
            vstats.num_rows, vstats.max_nnz,
        )
        if p.data_validation_type != DataValidationType.VALIDATE_DISABLED:
            for chunk in iter_chunks(
                validate_paths, self._fmt, self._data.index_map,
                rows_per_chunk=65536, nnz_width=vstats.max_nnz,
            ):
                sanity_check_data(chunk, p.task, p.data_validation_type)
        if p.validate_per_iteration:
            from photon_ml_tpu.training import iteration_models

            for lam, result in self.results.items():
                models = iteration_models(
                    result, p.task, self._norm, self._data.intercept_index
                )
                per_iter = [
                    self._streamed_metrics_for(
                        m.means, validate_paths, vstats
                    )
                    for m in models
                ]
                self.per_iteration_metrics[lam] = per_iter
                msg = "\n".join(
                    f"Iteration: [{i:6d}] " + " ".join(
                        f"Metric: [{k}] value: {v}"
                        for k, v in sorted(metrics.items())
                    )
                    for i, metrics in enumerate(per_iter)
                )
                self.logger.info("Model with lambda = %g:\n%s", lam, msg)
        maximize = p.task == TaskType.LOGISTIC_REGRESSION
        best = None
        for lam, model in self.models.items():
            metrics = self._streamed_metrics_for(
                model.means, validate_paths, vstats
            )
            self.validation_metrics[lam] = metrics
            key = (
                "AUC"
                if maximize
                else ("RMSE" if "RMSE" in metrics else next(iter(metrics)))
            )
            score = metrics[key]
            self.logger.info("lambda=%g validation %s", lam, metrics)
            if (
                best is None
                or (maximize and score > best[2])
                or (not maximize and score < best[2])
            ):
                best = (lam, model, score)
        self.best_lambda, self.best_model, _ = best

    def validate(self) -> None:
        p = self.params
        with self.timer.time("validate"):
            validate_paths = self._dated_paths(
                p.validate_dir, p.validate_date_range,
                p.validate_date_range_days_ago,
            )
            if p.streaming:
                # bounded-memory validation: the validate dir streams
                # through iter_chunks per model instead of loading whole
                self._validate_streaming(validate_paths)
                self._advance(DriverStage.VALIDATED)
                return
            vdata = self._fmt.load(
                validate_paths, index_map=self._data.index_map
            )
            sanity_check_data(vdata.batch, p.task, p.data_validation_type)
            self._validation_data = vdata
            if p.validate_per_iteration:
                self._validate_per_iteration(vdata)
            # Select by AUC for classification, RMSE/loss otherwise
            # (ModelSelection.scala:36-63).
            maximize = p.task == TaskType.LOGISTIC_REGRESSION
            best = None
            for lam, model in self.models.items():
                metrics = self._metrics_for(model, vdata.batch)
                self.validation_metrics[lam] = metrics
                key = (
                    "AUC"
                    if maximize
                    else ("RMSE" if "RMSE" in metrics else next(iter(metrics)))
                )
                score = metrics[key]
                self.logger.info("lambda=%g validation %s", lam, metrics)
                if (
                    best is None
                    or (maximize and score > best[2])
                    or (not maximize and score < best[2])
                ):
                    best = (lam, model, score)
            self.best_lambda, self.best_model, _ = best
        self._advance(DriverStage.VALIDATED)

    def _validate_per_iteration(self, vdata) -> None:
        """Metrics for every (lambda, iteration) model
        (computeAndLogModelMetrics, Driver.scala:330-349)."""
        from photon_ml_tpu.training import iteration_models

        p = self.params
        for lam, result in self.results.items():
            models = iteration_models(
                result, p.task, self._norm, self._data.intercept_index
            )
            per_iter = [self._metrics_for(m, vdata.batch) for m in models]
            self.per_iteration_metrics[lam] = per_iter
            msg = "\n".join(
                f"Iteration: [{i:6d}] " + " ".join(
                    f"Metric: [{k}] value: {v}"
                    for k, v in sorted(metrics.items())
                )
                for i, metrics in enumerate(per_iter)
            )
            self.logger.info("Model with lambda = %g:\n%s", lam, msg)

    def diagnose(self) -> None:
        """Model diagnostics + HTML report (Driver.scala:525-552, 618-638)."""
        from photon_ml_tpu.diagnostics.report import run_glm_diagnostics

        with self.timer.time("diagnose"):
            run_glm_diagnostics(self)
        self._advance(DriverStage.DIAGNOSED)

    # -- outputs -----------------------------------------------------------

    def _write_summary(self, out_dir: str) -> None:
        os.makedirs(out_dir, exist_ok=True)
        s = self._summary
        records = []
        for key, i in self._data.index_map.items():
            name, term = split_feature_key(key)
            records.append(
                {
                    "featureName": name,
                    "featureTerm": term,
                    "metrics": {
                        "mean": float(s.mean[i]),
                        "variance": float(s.variance[i]),
                        "numNonzeros": float(s.num_nonzeros[i]),
                        "max": float(s.max[i]),
                        "min": float(s.min[i]),
                        "normL1": float(s.norm_l1[i]),
                        "normL2": float(s.norm_l2[i]),
                        "meanAbs": float(s.mean_abs[i]),
                    },
                }
            )
        write_container(
            os.path.join(out_dir, "part-00000.avro"),
            schemas.FEATURE_SUMMARIZATION_RESULT_AVRO,
            records,
        )

    def _write_outputs(self) -> None:
        p = self.params
        out = p.output_dir
        os.makedirs(out, exist_ok=True)
        self._data.index_map.save(os.path.join(out, "feature-index", "index.json"))
        write_models_in_text(
            self.models, os.path.join(out, "models-text"), self._data.index_map
        )
        save_glm_models_avro(
            self.models, os.path.join(out, "models", "models.avro"),
            self._data.index_map,
        )
        if self.best_model is not None:
            save_glm_models_avro(
                {self.best_lambda: self.best_model},
                os.path.join(out, "best-model", "model.avro"),
                self._data.index_map,
            )
        if p.enable_optimization_tracker:
            from photon_ml_tpu.reliability import atomic_writer

            with atomic_writer(os.path.join(out, "optimization-log.txt")) as f:
                for lam, res in sorted(self.results.items()):
                    t = res.tracker
                    n = int(t.count)
                    f.write(
                        f"lambda={lam} iterations={int(res.iterations)} "
                        f"converged={res.reason_name}\n"
                    )
                    # slot 0 is the pre-optimization initial point
                    for i in range(n):
                        f.write(
                            f"  iter={i} value={float(t.values[i]):.8g} "
                            f"|grad|={float(t.grad_norms[i]):.8g}\n"
                        )
        from photon_ml_tpu.utils.profiling import peak_rss_bytes

        payload = {
            "validation": {
                str(k): v for k, v in self.validation_metrics.items()
            },
            "per_iteration_validation": {
                str(k): v
                for k, v in self.per_iteration_metrics.items()
            },
            "best_lambda": self.best_lambda,
            "timers": self.timer.durations,
            "schedule_cache": self._schedule_cache_stats,
        }
        if self._scan_cache_stats:
            # the "touched only new partitions" counters (scan cache)
            payload["scan_cache"] = self._scan_cache_stats
        if p.retrain_from or p.publish_registry:
            payload["registry"] = {
                "retrain_from": p.retrain_from,
                "parent_generation": (
                    self._parent_generation.generation
                    if self._parent_generation is not None else None
                ),
                "published_generation": self._published_generation,
                "drift": (
                    self._drift_report.as_dict()
                    if self._drift_report is not None else None
                ),
                "gates": (
                    self._gate_report.as_dict()
                    if self._gate_report is not None else None
                ),
            }
        if p.streaming:
            # the out-of-core contract made observable: configured budget
            # vs the measured host high-water
            payload["streaming"] = {
                "memory_budget_bytes": p.stream_memory_budget,
                "peak_rss_bytes": peak_rss_bytes(),
            }
        # fault/retry/quarantine accounting: every injected fault, retry
        # and quarantined artifact this run performed, by seam
        from photon_ml_tpu.reliability import (
            atomic_write_json,
            reliability_metrics,
        )

        payload["reliability"] = reliability_metrics()
        atomic_write_json(os.path.join(out, "metrics.json"), payload)

    def run(self) -> None:
        from photon_ml_tpu.parallel.multihost import (
            is_coordinator,
            sync_processes,
        )

        p = self.params
        self.preprocess()
        self.train()
        if getattr(self, "_preempted", False):
            # stopped mid-sweep on SIGTERM: the λ snapshots carry the
            # partial state; publishing models/metrics from a partial
            # grid would let a half-result masquerade as a full one
            from photon_ml_tpu.parallel import overlap

            overlap.drain_io()
            sync_processes("outputs-written")
            self.logger.info("preempted: outputs withheld; resume to finish")
            self.obs.finish(reason="preempted")
            self.emitter.close()
            return
        if p.validate_dir:
            self.validate()
        if p.diagnostic_mode != DiagnosticMode.NONE and is_coordinator():
            self.diagnose()
        if is_coordinator():
            if p.publish_registry:
                # gates + publish run BEFORE metrics so the verdict and
                # the published generation land in metrics.json
                with self.timer.time("publish-registry"):
                    self._publish_to_registry()
            self._write_outputs()
        from photon_ml_tpu.parallel import overlap

        overlap.drain_io()  # queued artifact writes land before the barrier
        sync_processes("outputs-written")
        self.logger.info("stages: %s", [s.name for s in self.stage_history])
        self.logger.info("timers:\n%s", self.timer.summary())
        self.obs.finish()
        self.emitter.close()


# ---------------------------------------------------------------------------
# CLI (option names from OptionNames.scala)
# ---------------------------------------------------------------------------


def build_arg_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="photon-ml-tpu glm",
        description="TPU-native GLM training driver (Photon ML parity)",
    )
    ap.add_argument("--training-data-directory", required=True)
    ap.add_argument("--output-directory", required=True)
    ap.add_argument("--validating-data-directory", default=None)
    ap.add_argument("--train-date-range", default=None,
                    help="yyyyMMdd-yyyyMMdd; expects <dir>/daily/yyyy/MM/dd")
    ap.add_argument("--train-date-range-days-ago", default=None,
                    help="start-end days ago, e.g. 90-1")
    ap.add_argument("--validate-date-range", default=None)
    ap.add_argument("--validate-date-range-days-ago", default=None)
    ap.add_argument("--validate-per-iteration", default="false")
    ap.add_argument("--task", default="LOGISTIC_REGRESSION")
    ap.add_argument(
        "--format", default="TRAINING_EXAMPLE",
        help="Avro field-name convention: TRAINING_EXAMPLE | "
        "RESPONSE_PREDICTION (FieldNamesType). Legacy values AVRO|LIBSVM "
        "are accepted as --input-file-format.",
    )
    ap.add_argument(
        "--input-file-format", default=None, help="AVRO | LIBSVM"
    )
    ap.add_argument("--feature-dimension", type=int, default=None)
    ap.add_argument("--optimization-tracker", default="true")
    ap.add_argument(
        "--training-diagnostics", default=None,
        help="DEPRECATED -- use --diagnostic-mode (true -> ALL)",
    )
    # Spark-runtime tuning knobs, accepted for invocation compatibility
    # and ignored: serialization, input splits and treeAggregate depth
    # have no analog under XLA (psum replaces treeAggregate).
    ap.add_argument("--kryo", default=None, help="ignored (Spark-only)")
    ap.add_argument(
        "--min-partitions", type=int, default=None,
        help="ignored (Spark-only)",
    )
    ap.add_argument(
        "--tree-aggregate-depth", type=int, default=None,
        help="ignored (psum replaces treeAggregate)",
    )
    ap.add_argument("--intercept", default="true")
    ap.add_argument("--regularization-weights", default="0")
    ap.add_argument("--regularization-type", default="L2")
    ap.add_argument("--elastic-net-alpha", type=float, default=None)
    ap.add_argument("--optimizer", default="LBFGS")
    ap.add_argument("--num-iterations", type=int, default=None)
    ap.add_argument("--convergence-tolerance", type=float, default=None)
    ap.add_argument("--normalization-type", default="NONE")
    ap.add_argument("--data-validation-type", default="VALIDATE_FULL")
    ap.add_argument("--coefficient-box-constraints", default=None)
    ap.add_argument("--selected-features-file", default=None)
    ap.add_argument("--summarization-output-dir", default=None)
    ap.add_argument("--offheap-indexmap-dir", default=None)
    ap.add_argument("--offheap-indexmap-num-partitions", type=int, default=None)
    ap.add_argument("--diagnostic-mode", default=None)
    ap.add_argument("--compute-variances", default="false")
    ap.add_argument("--delete-output-dirs-if-exist", default="false")
    ap.add_argument("--job-name", default="photon-ml-tpu")
    ap.add_argument("--event-listeners", default=None)
    ap.add_argument(
        "--kernel", default="auto", choices=["auto", "tiled", "scatter"],
        help="objective kernel (auto: tiled Pallas on accelerators)",
    )
    ap.add_argument(
        "--distributed", default="auto",
        choices=["auto", "off", "feature"],
        help="auto: data-parallel when >1 device; feature: feature-sharded "
        "coefficients over a (data, model) mesh (>HBM models)",
    )
    ap.add_argument(
        "--model-shards", type=int, default=None,
        help="model-axis size for --distributed feature (default 2)",
    )
    ap.add_argument(
        "--streaming", default="false",
        help="true: stream the training data from disk per evaluation "
        "(bounded memory for >RAM datasets; Avro + L-BFGS/OWL-QN; "
        "composes with --distributed feature for >HBM models)",
    )
    ap.add_argument(
        "--stream-memory-budget", type=int, default=0,
        help="host-memory byte budget for the streaming layer: fixes "
        "the staged-chunk rows and cache tiers; peak RSS is reported "
        "against it in metrics.json. 0 = default sizing",
    )
    ap.add_argument(
        "--profile-dir", default=None,
        help="write a jax.profiler trace of the training stage here "
        "(TensorBoard/Perfetto-viewable)",
    )
    ap.add_argument(
        "--obs-dir", default=None,
        help="unified telemetry: training-span tracing + flight "
        "recorder; trace.json (Chrome trace-event), flight.json and "
        "metrics_snapshot.json land here atomically",
    )
    ap.add_argument(
        "--tile-cache-dir", default=None,
        help="persistent content-addressed tile-schedule cache directory: "
        "warm reruns over the same dataset load the tiled layout instead "
        "of rebuilding it (multi-host: process 0 writes, others read). "
        "Default: $PHOTON_TILE_CACHE_DIR, unset = off",
    )
    ap.add_argument(
        "--diagnostic-reservoir-rows", type=int, default=100_000,
        help="max rows in the streaming diagnostics reservoir sample",
    )
    ap.add_argument(
        "--diagnostic-reservoir-bytes", type=int, default=256 << 20,
        help="byte budget for the diagnostics reservoir (rows scale down "
        "when max nnz/row is large, preserving bounded memory)",
    )
    ap.add_argument(
        "--coordinator-address", default=None,
        help="host:port of process 0 for multi-host runs (jax.distributed)",
    )
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument(
        "--no-overlap", default="false",
        help="disable the host-device overlap layer (deferred readbacks, "
        "background host prep, async artifact writes) and run fully "
        "serial — the A/B escape hatch",
    )
    ap.add_argument(
        "--grid-mode", default="auto",
        choices=["batched", "sequential", "auto"],
        help="lambda-grid execution: batched = ONE vmapped optimizer "
        "program over a [G, d] coefficient bank (1 compile / 1 loop / 1 "
        "readback round, no cross-lambda warm starts); sequential = "
        "warm-started one-solve-per-lambda; auto = batched when the "
        "in-memory grid has >1 member and the bank fits "
        "--grid-memory-budget (streaming always runs sequential)",
    )
    ap.add_argument(
        "--grid-memory-budget", type=int, default=1 << 30,
        help="byte budget for the batched grid's G x d coefficient bank "
        "+ vmapped optimizer state; auto falls back to sequential above "
        "it (default 1 GiB)",
    )
    ap.add_argument(
        "--checkpoint-dir", default=None,
        help="crash-safe lambda-grid resume: completed lambdas snapshot "
        "here, SIGTERM stops at the next lambda boundary, and a rerun "
        "with the same args resumes mid-path (bitwise-identical final "
        "models)",
    )
    ap.add_argument(
        "--fault-plan", default=None,
        help="deterministic fault injection, e.g. "
        "'chunk_read:3:EIO,ckpt_save:1:ENOSPC:2' (seam:nth:error[:times]"
        "); also via PHOTON_FAULT_PLAN. Chaos harness: dev-scripts/"
        "chaos.sh",
    )
    ap.add_argument(
        "--retrain-from", default=None,
        help="model-registry directory: warm-start the coefficients "
        "from the latest committed generation with drift-safe "
        "alignment (new terms zero-init, removed terms dropped with "
        "accounting; bitwise pass-through when nothing drifted)",
    )
    ap.add_argument(
        "--publish-registry", default=None,
        help="model-registry directory: publish the trained best model "
        "as the next generation — gated against the parent on the "
        "validating directory when --retrain-from resolved one (a "
        "failed gate records a named verdict; the candidate is never "
        "loadable)",
    )
    ap.add_argument(
        "--scan-cache-dir", default=None,
        help="append-only per-partition scan/stats cache: the "
        "streaming preprocess re-reads ONLY partitions without a "
        "cache entry (the incremental-retrain fast path; counters in "
        "metrics.json)",
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
    return ap


def _bool(s) -> bool:
    return str(s).strip().lower() in ("true", "1", "yes")


def params_from_args(argv=None) -> GLMParams:
    ns = build_arg_parser().parse_args(argv)
    # --format carries the FieldNamesType (reference semantics); legacy
    # invocations that passed AVRO|LIBSVM there are routed to
    # --input-file-format instead.
    fmt = (ns.format or "TRAINING_EXAMPLE").strip().upper()
    file_format = ns.input_file_format
    field_names = "TRAINING_EXAMPLE"
    if fmt in ("AVRO", "LIBSVM"):
        file_format = file_format or fmt
    elif fmt in ("TRAINING_EXAMPLE", "RESPONSE_PREDICTION", "NONE"):
        field_names = fmt
    else:
        raise ValueError(f"unknown --format {ns.format!r}")
    if ns.training_diagnostics is not None:
        # deprecated boolean (PhotonMLCmdLineParser.scala:68-69,184-186):
        # exclusive with --diagnostic-mode, maps to ALL/NONE
        if ns.diagnostic_mode is not None:
            raise ValueError(
                "specifying both training-diagnostics and diagnostic-mode "
                "is not supported"
            )
        ns.diagnostic_mode = (
            "ALL" if _bool(ns.training_diagnostics) else "NONE"
        )
    return GLMParams(
        train_dir=ns.training_data_directory,
        output_dir=ns.output_directory,
        validate_dir=ns.validating_data_directory,
        train_date_range=ns.train_date_range,
        train_date_range_days_ago=ns.train_date_range_days_ago,
        validate_date_range=ns.validate_date_range,
        validate_date_range_days_ago=ns.validate_date_range_days_ago,
        validate_per_iteration=_bool(ns.validate_per_iteration),
        task=TaskType.parse(ns.task),
        input_format=file_format or "AVRO",
        field_names=field_names,
        feature_dimension=ns.feature_dimension,
        enable_optimization_tracker=_bool(ns.optimization_tracker),
        add_intercept=_bool(ns.intercept),
        regularization_weights=[
            float(x) for x in ns.regularization_weights.split(",") if x
        ],
        regularization_type=RegularizationType.parse(ns.regularization_type),
        elastic_net_alpha=ns.elastic_net_alpha,
        optimizer_type=OptimizerType.parse(ns.optimizer),
        max_num_iterations=ns.num_iterations,
        tolerance=ns.convergence_tolerance,
        normalization_type=NormalizationType(ns.normalization_type.strip().upper()),
        data_validation_type=DataValidationType.parse(ns.data_validation_type),
        constraint_string=ns.coefficient_box_constraints,
        selected_features_file=ns.selected_features_file,
        summarization_output_dir=ns.summarization_output_dir,
        offheap_indexmap_dir=ns.offheap_indexmap_dir,
        offheap_indexmap_num_partitions=ns.offheap_indexmap_num_partitions,
        diagnostic_mode=DiagnosticMode.parse(ns.diagnostic_mode or "NONE"),
        compute_variances=_bool(ns.compute_variances),
        delete_output_dirs_if_exist=_bool(ns.delete_output_dirs_if_exist),
        job_name=ns.job_name,
        kernel=ns.kernel,
        distributed=ns.distributed,
        streaming=_bool(ns.streaming),
        stream_memory_budget=ns.stream_memory_budget,
        profile_dir=ns.profile_dir,
        obs_dir=ns.obs_dir,
        tile_cache_dir=ns.tile_cache_dir,
        no_overlap=_bool(ns.no_overlap),
        grid_mode=ns.grid_mode,
        grid_memory_budget=ns.grid_memory_budget,
        diagnostic_reservoir_rows=ns.diagnostic_reservoir_rows,
        diagnostic_reservoir_bytes=ns.diagnostic_reservoir_bytes,
        model_shards=ns.model_shards,
        coordinator_address=ns.coordinator_address,
        num_processes=ns.num_processes,
        process_id=ns.process_id,
        checkpoint_dir=ns.checkpoint_dir,
        fault_plan=ns.fault_plan,
        retrain_from=ns.retrain_from,
        publish_registry=ns.publish_registry,
        scan_cache_dir=ns.scan_cache_dir,
        gate_max_auc_drop=ns.gate_max_auc_drop,
        gate_max_rmse_increase=ns.gate_max_rmse_increase,
        gate_max_coef_norm_ratio=ns.gate_max_coef_norm_ratio,
        gate_max_prediction_drift=ns.gate_max_prediction_drift,
        event_listeners=(
            ns.event_listeners.split(",") if ns.event_listeners else []
        ),
    )


def main(argv=None) -> None:
    params = params_from_args(argv)
    driver = GLMDriver(params)
    driver.run()


if __name__ == "__main__":
    main()
