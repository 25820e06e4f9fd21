"""GAME scoring driver: load model dir -> score Avro data -> write
ScoringResultAvro -> optional evaluation.

Reference: photon-ml .../cli/game/scoring/Driver.scala:171-204 (run:
prepareFeatureMaps -> prepareGameDataSet(isResponseRequired=false) ->
loadGameModelFromHDFS -> score -> saveScoresToHDFS -> evaluateScores) and
cli/game/scoring/Params.scala (option names kept), ScoredItem.scala.
"""

from __future__ import annotations

import argparse
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np
import jax.numpy as jnp

from photon_ml_tpu.evaluation import Evaluator, EvaluatorType
from photon_ml_tpu.game.data import build_game_dataset_from_files
from photon_ml_tpu.game.config import FeatureShardConfiguration
from photon_ml_tpu.game.model_io import load_game_model
from photon_ml_tpu.io import schemas
from photon_ml_tpu.io.avro_codec import write_container
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.task import TaskType
from photon_ml_tpu.utils.backend import enable_compilation_cache
from photon_ml_tpu.utils.logging_util import PhotonLogger, Timer


@dataclass
class GameScoringParams:
    input_dirs: List[str] = field(default_factory=list)
    game_model_input_dir: str = ""
    output_dir: str = ""
    # Dated-input expansion over the input dirs (scoring Params
    # date-range / date-range-days-ago).
    date_range: Optional[str] = None
    date_range_days_ago: Optional[str] = None
    # Extra entity-id columns to extract and write with each score
    # (randomEffectTypeSet: ScoredItem carries idTypeToValueMap,
    # cli/game/scoring/Driver.scala:42,152).
    random_effect_id_set: List[str] = field(default_factory=list)
    # Split the scores output across N part files (numOutputFilesForScores).
    num_files: int = 1
    delete_output_dir_if_exists: bool = False
    application_name: str = "photon-ml-tpu-game-scoring"
    task_type: TaskType = TaskType.LOGISTIC_REGRESSION
    feature_shards: List[FeatureShardConfiguration] = field(default_factory=list)
    evaluator_types: List[EvaluatorType] = field(default_factory=list)
    model_id: str = ""
    has_response: bool = True
    # Feature-map sources (prepareFeatureMaps analog, shared with the
    # training driver; cli/game/GAMEDriver.scala:89-97): offheap stores
    # take precedence, then name-and-term list files, then maps built
    # from the scoring data.
    offheap_indexmap_dir: Optional[str] = None
    offheap_indexmap_num_partitions: Optional[int] = None
    feature_name_and_term_set_path: Optional[str] = None
    # jax.profiler trace of the scoring pass (SURVEY §7.11)
    profile_dir: Optional[str] = None
    # Unified telemetry (ISSUE 13): span tracing + flight recorder
    # under --obs-dir (trace.json / flight.json at exit).
    obs_dir: Optional[str] = None
    # Persistent content-addressed tile-schedule cache directory
    # (ops/schedule_cache.py), shared with the training drivers so a
    # scoring run over an already-trained dataset reuses its tiled
    # layout. None falls back to PHOTON_TILE_CACHE_DIR; unset = off.
    tile_cache_dir: Optional[str] = None
    # Escape hatch for the host-device overlap layer (parallel/overlap.py):
    # True writes score part files synchronously (serial A/B baseline).
    no_overlap: bool = False
    # Chunked scoring for inputs larger than memory (the reference scores
    # RDD partitions without collecting — Spark's memory profile by
    # construction); requires prebuilt feature maps, pointwise/global
    # evaluators only.
    streaming: bool = False
    rows_per_chunk: int = 100_000
    # Optional byte budget (the training drivers' --stream-memory-budget):
    # caps rows_per_chunk by the scored row's staged bytes so one flag
    # bounds the whole pipeline's host memory consistently.
    stream_memory_budget: int = 0
    # Deterministic fault plan (reliability.faults); also via
    # PHOTON_FAULT_PLAN. Chaos harness: dev-scripts/chaos.sh.
    fault_plan: Optional[str] = None

    def validate(self):
        if not self.input_dirs:
            raise ValueError("input-data-dirs is required")
        if self.stream_memory_budget and not self.streaming:
            raise ValueError(
                "stream-memory-budget requires --streaming true"
            )
        if self.streaming:
            # all param-detectable streaming misconfigurations fail HERE,
            # before __init__ touches (or deletes) the output directory
            if self.rows_per_chunk < 1:
                raise ValueError("rows-per-chunk must be >= 1")
            if not (
                self.offheap_indexmap_dir
                or self.feature_name_and_term_set_path
            ):
                raise ValueError(
                    "streaming scoring requires prebuilt feature maps "
                    "(--offheap-indexmap-dir or "
                    "--feature-name-and-term-set-path): no single chunk "
                    "sees the whole vocabulary"
                )
            for et in self.evaluator_types:
                if et.is_sharded:
                    raise ValueError(
                        f"sharded evaluator {et.render()!r} needs global "
                        "per-group data; use in-memory scoring"
                    )
        if not self.game_model_input_dir:
            raise ValueError("game-model-input-dir is required")
        if not self.output_dir:
            raise ValueError("output-dir is required")


class _ScoreRecordRows:
    """Sliceable, re-iterable score-record sequence over column arrays.

    ``__iter__`` streams one dict per row to the Avro writer (nothing
    row-shaped is materialized up front); ``[i::n]`` — the
    ``_write_parts`` round-robin split — returns another column view;
    re-iteration rebuilds rows from the columns, which keeps retried
    async writes idempotent (a consumed generator would silently write
    an empty part on retry)."""

    def __init__(self, uids, labels, scores, weights, meta_cols, model_id):
        self._uids = uids
        self._labels = labels
        self._scores = scores
        self._weights = weights
        self._meta_cols = meta_cols
        self._model_id = model_id

    def __len__(self) -> int:
        return len(self._uids)

    def __getitem__(self, sl):
        if not isinstance(sl, slice):
            raise TypeError("row views only slice")
        return _ScoreRecordRows(
            uids=self._uids[sl],
            labels=self._labels[sl] if self._labels is not None else None,
            scores=self._scores[sl],
            weights=self._weights[sl],
            meta_cols=[
                (t, vals[sl], mask[sl]) for t, vals, mask in self._meta_cols
            ],
            model_id=self._model_id,
        )

    def __iter__(self):
        labels = self._labels
        for i, uid in enumerate(self._uids):
            meta = {
                t: vals[i]
                for t, vals, mask in self._meta_cols
                if mask[i]
            }
            yield {
                "uid": uid,
                "label": labels[i] if labels is not None else None,
                "modelId": self._model_id,
                "predictionScore": self._scores[i],
                "weight": self._weights[i],
                "metadataMap": meta or None,
            }


class GameScoringDriver:
    def __init__(self, params: GameScoringParams, logger=None):
        params.validate()
        self.params = params
        enable_compilation_cache()
        if params.tile_cache_dir is not None:
            from photon_ml_tpu.ops.schedule_cache import configure

            configure(params.tile_cache_dir)
        if params.no_overlap:
            from photon_ml_tpu.parallel import overlap

            overlap.set_overlap(False)
        if params.fault_plan:
            from photon_ml_tpu.reliability import install_plan

            install_plan(params.fault_plan)
        from photon_ml_tpu.parallel.multihost import prepare_output_dir

        prepare_output_dir(
            params.output_dir,
            delete_if_exists=params.delete_output_dir_if_exists,
        )
        self.logger = logger or PhotonLogger(params.output_dir)
        self.timer = Timer()
        from photon_ml_tpu.obs import ObsSession

        self.obs = ObsSession(params.obs_dir, signal_dump=False)
        self.metrics: Dict[str, float] = {}

    def run(self) -> None:
        p = self.params
        self.logger.info("application: %s", p.application_name)
        with self.timer.time("load-model"):
            model = load_game_model(p.game_model_input_dir)
        self.logger.info("loaded coordinates: %s", model.coordinate_names())

        # id columns needed: RE types + MF types + sharded evaluator ids
        # + explicitly requested pass-through ids
        id_types = set(p.random_effect_id_set)
        for _, (re_type, _, _) in model.random_effects.items():
            id_types.add(re_type)
        for _, (rt, ct, _, _) in model.matrix_factorizations.items():
            id_types.update((rt, ct))
        for et in p.evaluator_types:
            if et.id_type:
                id_types.add(et.id_type)

        index_maps = None
        if p.offheap_indexmap_dir:
            from photon_ml_tpu.utils.native_index import load_offheap_index_maps

            index_maps = load_offheap_index_maps(
                p.offheap_indexmap_dir,
                [cfg.shard_id for cfg in p.feature_shards],
                num_partitions=p.offheap_indexmap_num_partitions,
            )
        elif p.feature_name_and_term_set_path:
            from photon_ml_tpu.io.name_term_list import (
                index_maps_from_name_term_lists,
            )

            index_maps = index_maps_from_name_term_lists(
                p.feature_name_and_term_set_path, p.feature_shards
            )
        from photon_ml_tpu.utils.date_range import expand_dated_paths

        input_paths = expand_dated_paths(
            p.input_dirs, p.date_range, p.date_range_days_ago, self.logger
        )
        from photon_ml_tpu.parallel.multihost import (
            is_coordinator,
            sync_processes,
        )
        from photon_ml_tpu.utils.profiling import profile_trace

        if p.streaming:
            self._run_streaming(model, sorted(id_types), index_maps, input_paths)
            self.obs.finish()
            sync_processes("scores-written")
            self.logger.info("timers:\n%s", self.timer.summary())
            return
        with self.timer.time("load-data"):
            dataset = build_game_dataset_from_files(
                input_paths,
                p.feature_shards,
                sorted(id_types),
                index_maps=index_maps,
                is_response_required=p.has_response,
            )
        with self.timer.time("score"), profile_trace(p.profile_dir):
            raw_scores = model.score(dataset, p.task_type)
            scores = raw_scores + jnp.asarray(dataset.offsets)

        if is_coordinator():
            with self.timer.time("write-scores"):
                from photon_ml_tpu.parallel import overlap

                # counted seam instead of a raw np.asarray readback
                self._write_scores(dataset, overlap.device_get(scores))
        if p.evaluator_types and p.has_response:
            with self.timer.time("evaluate"):
                self._evaluate(dataset, scores)
            if is_coordinator():
                from photon_ml_tpu.reliability import (
                    atomic_write_json,
                    reliability_metrics,
                )

                atomic_write_json(
                    os.path.join(p.output_dir, "metrics.json"),
                    {**self.metrics,
                     "reliability": reliability_metrics()},
                )
        self.obs.finish()
        sync_processes("scores-written")
        self.logger.info("timers:\n%s", self.timer.summary())

    def _run_streaming(self, model, id_types, index_maps, input_paths) -> None:
        """Chunked scoring: ONE input file loads at a time (through the
        native column decoder — the file is the natural partition unit,
        exactly like io/streaming.py's >RAM training path), then scores
        and writes in ``rows_per_chunk`` row slices. Peak memory is one
        file's features — the partition-streamed profile the reference
        gets from Spark by construction (cli/game/scoring/
        Driver.scala:171-204 scores RDD partitions without collecting).
        Pointwise + global-rank metrics accumulate on [n] float arrays;
        param-level guards (prebuilt maps, no sharded evaluators) live
        in GameScoringParams.validate."""
        from photon_ml_tpu.game.data import slice_game_dataset
        from photon_ml_tpu.io.paths import expand_input_paths
        from photon_ml_tpu.parallel import overlap
        from photon_ml_tpu.parallel.multihost import is_coordinator
        from photon_ml_tpu.utils.profiling import profile_trace

        p = self.params
        if p.num_files != 1:
            self.logger.warning(
                "--num-files is ignored in streaming mode: one scores "
                "part file is written per %d-row chunk", p.rows_per_chunk
            )
        # expand sorts within each directory and preserves the caller's
        # dir order — identical global order to the in-memory path (a
        # global re-sort would reassign fallback uids across dirs)
        files = expand_input_paths(
            input_paths, lambda fn: fn.endswith(".avro")
        )
        all_scores: List[np.ndarray] = []
        all_labels: List[np.ndarray] = []
        all_weights: List[np.ndarray] = []
        n_rows = 0
        part = 0
        rows_per_chunk = p.rows_per_chunk
        with self.timer.time("score-stream"), profile_trace(p.profile_dir):
            for path in files:
                try:
                    ds_file = build_game_dataset_from_files(
                        [path], p.feature_shards, id_types,
                        index_maps=index_maps,
                        is_response_required=p.has_response,
                        row_offset=n_rows,
                    )
                except ValueError as e:
                    if "empty GAME dataset" in str(e):
                        continue  # zero-record part file
                    raise
                if p.stream_memory_budget and n_rows == 0:
                    # one budget flag bounds the whole pipeline: cap the
                    # chunk rows by the scored row's staged bytes (every
                    # shard's padded slots + the scalar columns), like
                    # the training drivers' --stream-memory-budget
                    from photon_ml_tpu.game.streaming import game_row_bytes
                    from photon_ml_tpu.io.streaming import (
                        stream_budget_rows,
                    )

                    row_bytes = game_row_bytes(
                        {
                            sid: sd.indices.shape[1]
                            for sid, sd in ds_file.shards.items()
                        },
                        len(id_types),
                    )
                    rows_per_chunk = min(
                        rows_per_chunk,
                        stream_budget_rows(
                            p.stream_memory_budget, row_bytes,
                            default_rows=rows_per_chunk,
                        ),
                    )
                    self.logger.info(
                        "stream memory budget %d B -> %d rows/chunk",
                        p.stream_memory_budget, rows_per_chunk,
                    )
                for a in range(0, ds_file.num_real_rows, rows_per_chunk):
                    ds = slice_game_dataset(
                        ds_file, a, a + rows_per_chunk
                    )
                    scores = overlap.device_get(
                        model.score(ds, p.task_type)
                        + jnp.asarray(ds.offsets)
                    )[: ds.num_real_rows]
                    if is_coordinator():
                        # async artifact IO (overlap): chunk i's part
                        # file writes while chunk i+1 loads and scores;
                        # drained before the completion log/barrier
                        overlap.submit_io(
                            write_container,
                            os.path.join(
                                p.output_dir, "scores",
                                f"part-{part:05d}.avro",
                            ),
                            schemas.SCORING_RESULT_AVRO,
                            self._score_records(ds, scores),
                            artifact=f"scores/part-{part:05d}.avro",
                        )
                    part += 1
                    n_rows += ds.num_real_rows
                    if p.evaluator_types and p.has_response:
                        all_scores.append(scores)
                        all_labels.append(
                            np.asarray(ds.labels[: ds.num_real_rows])
                        )
                        all_weights.append(
                            np.asarray(ds.weights[: ds.num_real_rows])
                        )
        overlap.drain_io()  # every queued part file is on disk
        if n_rows == 0:
            raise ValueError("empty GAME dataset")  # in-memory parity
        self.logger.info(
            "streamed %d rows in %d chunk(s) from %d file(s)",
            n_rows, part, len(files),
        )
        if p.evaluator_types and p.has_response:
            with self.timer.time("evaluate"):
                self._evaluate_pointwise(
                    jnp.asarray(np.concatenate(all_scores)),
                    jnp.asarray(np.concatenate(all_labels)),
                    jnp.asarray(np.concatenate(all_weights)),
                )
            if is_coordinator():
                from photon_ml_tpu.reliability import (
                    atomic_write_json,
                    reliability_metrics,
                )

                atomic_write_json(
                    os.path.join(p.output_dir, "metrics.json"),
                    {**self.metrics,
                     "reliability": reliability_metrics()},
                )

    def _score_records(self, dataset, scores: np.ndarray) -> "_ScoreRecordRows":
        """Score records as a lazy column view: the scalar columns are
        materialized ONCE with vectorized numpy ops (`.tolist()` instead
        of a per-row/per-cell `float()`/`int()` cascade — the old hot
        path cost ~10us/row of Python casts) and each record dict is
        built only as the Avro writer consumes it. The view re-iterates
        from the columns, so async-write retries (reliability io_worker
        seam) replay it safely, and `_write_parts`' ``[i::n]`` split
        slices columns, not dicts."""
        n = dataset.num_real_rows
        id_types = sorted(dataset.entity_indexes)
        meta_cols = []
        for t in id_types:
            codes = np.asarray(dataset.entity_codes[t][:n])
            ids_arr = np.asarray(dataset.entity_indexes[t].ids, dtype=object)
            vals = (
                ids_arr[np.maximum(codes, 0)]
                if ids_arr.size
                else np.empty((n,), dtype=object)
            )
            meta_cols.append((t, vals, codes >= 0))
        return _ScoreRecordRows(
            uids=list(dataset.uids[:n]),
            labels=(
                np.asarray(dataset.labels[:n]).tolist()
                if self.params.has_response
                else None
            ),
            scores=np.asarray(scores[:n]).tolist(),
            weights=np.asarray(dataset.weights[:n]).tolist(),
            meta_cols=meta_cols,
            model_id=self.params.model_id or "game-model",
        )

    def _write_scores(self, dataset, scores: np.ndarray) -> None:
        from photon_ml_tpu.game.model_io import _write_parts

        _write_parts(
            os.path.join(self.params.output_dir, "scores"),
            schemas.SCORING_RESULT_AVRO,
            self._score_records(dataset, scores),
            self.params.num_files,
        )

    def _evaluate(self, dataset, scores) -> None:
        p = self.params
        lab = jnp.asarray(dataset.labels)
        w = jnp.asarray(dataset.weights)
        for et in p.evaluator_types:
            if et.is_sharded:
                gids = dataset.entity_codes[et.id_type]
                ev = Evaluator(
                    et, num_groups=dataset.entity_indexes[et.id_type].num_entities
                )
                value = float(
                    ev.evaluate(scores, lab, w, jnp.maximum(jnp.asarray(gids), 0))
                )
                self.metrics[et.render()] = value
                self.logger.info("%s = %g", et.render(), value)
            else:
                self._evaluate_pointwise(scores, lab, w, evaluators=[et])

    def _evaluate_pointwise(self, scores, lab, w, evaluators=None) -> None:
        """Non-sharded metrics — ONE definition shared by the in-memory
        and streaming paths so a metric change cannot diverge them."""
        p = self.params
        loss = loss_for_task(p.task_type)
        for et in evaluators if evaluators is not None else p.evaluator_types:
            metric_in = loss.mean(scores) if et.name == "RMSE" else scores
            value = float(Evaluator(et).evaluate(metric_in, lab, w))
            self.metrics[et.render()] = value
            self.logger.info("%s = %g", et.render(), value)


def build_arg_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="photon-ml-tpu game-scoring")
    ap.add_argument("--input-data-dirs", required=True)
    ap.add_argument("--game-model-input-dir", required=True)
    ap.add_argument("--output-dir", required=True)
    ap.add_argument("--task-type", default="LOGISTIC_REGRESSION")
    ap.add_argument("--feature-shard-id-to-feature-section-keys-map", required=True)
    ap.add_argument("--evaluator-types", default=None)
    ap.add_argument("--game-model-id", default=None)
    ap.add_argument("--model-id", default=None, help="alias of --game-model-id")
    ap.add_argument("--has-response", default="true")
    ap.add_argument("--offheap-indexmap-dir", default=None)
    ap.add_argument("--offheap-indexmap-num-partitions", type=int, default=None)
    ap.add_argument("--feature-name-and-term-set-path", default=None)
    ap.add_argument("--feature-shard-id-to-intercept-map", default=None)
    ap.add_argument("--date-range", default=None)
    ap.add_argument("--date-range-days-ago", default=None)
    ap.add_argument("--random-effect-id-set", default=None)
    ap.add_argument("--num-files", type=int, default=1)
    ap.add_argument("--delete-output-dir-if-exists", default="false")
    ap.add_argument("--application-name", default=None)
    ap.add_argument(
        "--obs-dir", default=None,
        help="unified telemetry: span tracing + flight recorder; "
        "trace.json / flight.json land here atomically",
    )
    ap.add_argument(
        "--profile-dir", default=None,
        help="write a jax.profiler trace of the scoring pass here",
    )
    ap.add_argument(
        "--tile-cache-dir", default=None,
        help="persistent tile-schedule cache directory shared with the "
        "training drivers. Default: $PHOTON_TILE_CACHE_DIR, unset = off",
    )
    ap.add_argument(
        "--streaming", default="false",
        help="true: score in bounded-memory chunks (needs prebuilt "
        "feature maps; sharded evaluators unsupported)",
    )
    ap.add_argument("--rows-per-chunk", type=int, default=100_000)
    ap.add_argument(
        "--stream-memory-budget", type=int, default=0,
        help="byte budget capping --rows-per-chunk by the scored row's "
        "staged bytes (one flag bounds the whole pipeline's host "
        "memory); 0 = use --rows-per-chunk as-is",
    )
    ap.add_argument(
        "--no-overlap", default="false",
        help="disable the host-device overlap layer (async score-part "
        "writes) and run fully serial",
    )
    ap.add_argument(
        "--fault-plan", default=None,
        help="deterministic fault injection "
        "(seam:nth:error[:times], comma-separated); also via "
        "PHOTON_FAULT_PLAN",
    )
    return ap


def params_from_args(argv=None) -> GameScoringParams:
    from photon_ml_tpu.cli.game_training_driver import (
        apply_intercept_map,
        parse_shard_map,
    )

    ns = build_arg_parser().parse_args(argv)
    return GameScoringParams(
        input_dirs=ns.input_data_dirs.split(","),
        game_model_input_dir=ns.game_model_input_dir,
        output_dir=ns.output_dir,
        task_type=TaskType.parse(ns.task_type),
        feature_shards=apply_intercept_map(
            parse_shard_map(ns.feature_shard_id_to_feature_section_keys_map),
            ns.feature_shard_id_to_intercept_map,
        ),
        evaluator_types=(
            [EvaluatorType.parse(s) for s in ns.evaluator_types.split(",")]
            if ns.evaluator_types
            else []
        ),
        model_id=ns.game_model_id or ns.model_id or "",
        profile_dir=ns.profile_dir,
        obs_dir=ns.obs_dir,
        tile_cache_dir=ns.tile_cache_dir,
        no_overlap=str(ns.no_overlap).lower() in ("true", "1", "yes"),
        streaming=str(ns.streaming).lower() in ("true", "1", "yes"),
        rows_per_chunk=ns.rows_per_chunk,
        stream_memory_budget=ns.stream_memory_budget,
        fault_plan=ns.fault_plan,
        has_response=str(ns.has_response).lower() in ("true", "1", "yes"),
        date_range=ns.date_range,
        date_range_days_ago=ns.date_range_days_ago,
        random_effect_id_set=(
            [s for s in ns.random_effect_id_set.split(",") if s]
            if ns.random_effect_id_set
            else []
        ),
        num_files=ns.num_files,
        delete_output_dir_if_exists=(
            str(ns.delete_output_dir_if_exists).lower()
            in ("true", "1", "yes")
        ),
        application_name=ns.application_name or "photon-ml-tpu-game-scoring",
        offheap_indexmap_dir=ns.offheap_indexmap_dir,
        offheap_indexmap_num_partitions=ns.offheap_indexmap_num_partitions,
        feature_name_and_term_set_path=ns.feature_name_and_term_set_path,
    )


def main(argv=None) -> None:
    GameScoringDriver(params_from_args(argv)).run()


if __name__ == "__main__":
    main()
