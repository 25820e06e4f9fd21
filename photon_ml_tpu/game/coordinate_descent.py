"""Block coordinate descent over GAME coordinates.

Reference: photon-ml .../algorithm/CoordinateDescent.scala:50-262 —
init models + scores per coordinate (:82-119); per iteration, per
coordinate: residual = sum of OTHER coordinates' scores -> updateModel ->
rescore -> objective = loss(sum scores) + sum regTerms -> optional
per-iteration validation; tracks the best full model by the first
validation evaluator (:130-262). `run(numIterations, gameModel)` accepts a
warm-start model (:82-87).

The fullOuterJoin score algebra (KeyValueScore.scala:62-82) is plain
row-aligned vector arithmetic on device.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.game.coordinate import Coordinate
from photon_ml_tpu.game.data import GameDataset
from photon_ml_tpu.game.model import GameModel
from photon_ml_tpu.obs.registry import default_registry
from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.optim.common import CONVERGENCE_REASON_NAMES, OptResult
from photon_ml_tpu.parallel import overlap
from photon_ml_tpu.task import TaskType
from photon_ml_tpu.utils import profiling  # noqa: F401  obs spans -> profiler
from photon_ml_tpu.utils.logging_util import PhotonLogger

Array = jnp.ndarray


def _deferreds(tracker) -> list:
    """The device-resident halves of a coordinate's tracker: one
    ``deferred``, or (a factored random effect's) several ``deferreds``."""
    many = getattr(tracker, "deferreds", None)
    return many if many is not None else [getattr(tracker, "deferred", None)]


# The CD loop's own device arithmetic, as named programs (the function
# handed to jax.jit names the XLA module) with a scope each, so that a
# device trace places it in this layer.


@jax.jit
def cd_residual(total: Array, own_score: Array) -> Array:
    """What the OTHER coordinates leave: total - own score (the
    KeyValueScore `-` of the reference)."""
    with jax.named_scope("cd.residual"):
        return total - own_score


@jax.jit
def cd_total(partial_total: Array, score: Array) -> Array:
    with jax.named_scope("cd.residual"):
        return partial_total + score


@partial(jax.jit, static_argnums=0)
def cd_objective(loss, total_score, offsets, labels, weights, reg_terms):
    """The rows' weighted loss at (sum of scores + offsets) + the
    coordinates' reg terms."""
    with jax.named_scope("cd.objective"):
        value = jnp.sum(weights * loss.value(total_score + offsets, labels))
        for term in reg_terms:
            value = value + term
        return value


@dataclass
class CoordinateDescentResult:
    model: GameModel
    objective_history: List[float]
    trackers: Dict[str, List[object]]
    validation_history: List[Dict[str, float]] = field(default_factory=list)
    best_model: Optional[GameModel] = None
    best_metric: Optional[float] = None
    # True when the run stopped early on a preemption signal; the last
    # completed iteration is checkpointed, so a restarted job resumes.
    preempted: bool = False


class CoordinateDescent:
    """run() drives the blocks in `update_sequence` order."""

    def __init__(
        self,
        coordinates: Dict[str, Coordinate],
        dataset: GameDataset,
        task: TaskType,
        *,
        update_sequence: Optional[List[str]] = None,
        validation_fn: Optional[Callable[[GameModel], Dict[str, float]]] = None,
        validation_metric: Optional[str] = None,
        validation_maximize: bool = True,
        logger: Optional[PhotonLogger] = None,
        checkpointer=None,  # photon_ml_tpu.utils.checkpoint.TrainingCheckpointer
        preemption_guard=None,  # photon_ml_tpu.utils.preemption.PreemptionGuard
    ):
        self.coordinates = coordinates
        self.dataset = dataset
        self.task = task
        self.update_sequence = update_sequence or list(coordinates)
        unknown = set(self.update_sequence) - set(coordinates)
        if unknown:
            raise ValueError(f"update sequence references unknown coordinates {unknown}")
        self.validation_fn = validation_fn
        self.validation_metric = validation_metric
        self.validation_maximize = validation_maximize
        self.logger = logger or PhotonLogger()
        self.checkpointer = checkpointer
        self.preemption_guard = preemption_guard

    def _preemption_agreed(self) -> bool:
        """Whether to stop for preemption — agreed ACROSS processes.

        Eviction may deliver SIGTERM to only some hosts; a per-process
        decision would desync the next iteration's collectives (stopped
        hosts leave the others blocking in psum forever). Every process
        polls at the same iteration boundary and an any-process OR via
        allgather makes the stop unanimous. Single-process runs skip the
        collective.
        """
        if self.preemption_guard is None:
            return False
        requested = self.preemption_guard.requested
        import jax

        if jax.process_count() == 1:
            return requested
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray([requested], dtype=np.int32)
        )
        return bool(np.max(flags))

    def _objective(self, total_score: Array, models: Dict[str, object]) -> float:
        return self._objective_deferred(total_score, models).result()

    def _objective_deferred(
        self, total_score: Array, models: Dict[str, object]
    ) -> overlap.Deferred:
        """loss(sum of scores + offsets) + sum of reg terms
        (CoordinateDescent.scala:196-243) as a DEFERRED device scalar:
        the loss term and every coordinate's regularization term stay on
        device and the value joins the iteration's single batched
        readback (overlap.fetch_all) instead of 1 + 2-per-coordinate
        scalar pulls."""
        loss = loss_for_task(self.task)
        cached = self.__dict__.get("_device_cols")
        if cached is None:
            cached = (
                jnp.asarray(self.dataset.offsets),
                jnp.asarray(self.dataset.labels),
                jnp.asarray(self.dataset.weights),
            )
            self._device_cols = cached
        off, lab, w = cached
        reg_terms = [
            coord.regularization_term_device(models[name])
            for name, coord in self.coordinates.items()
        ]
        return overlap.Deferred(
            cd_objective(loss, total_score, off, lab, w, reg_terms), float
        )

    def _score(self, name: str, model) -> Array:
        """One scoring pass under its ``cd.score`` span, which says how a
        coordinate scored (``kernel``: tiled | gather for a fixed effect,
        on the kernel its ``mxu`` variant; blocks | chunks | gather
        joined by ``+`` for a random effect, with the coordinate's
        ``score_attrs``)."""
        coord = self.coordinates[name]
        with obs_span("cd.score", coordinate=name) as sp:
            score = coord.score(model)
            kernel = getattr(coord, "score_kernel", None)
            if kernel:
                sp.set(kernel=kernel)
            if kernel == "tiled":
                sp.set(mxu=coord.mxu)
            attrs = getattr(coord, "score_attrs", None)
            if attrs:
                sp.set(**attrs)
        return score

    def run(
        self,
        num_iterations: int,
        initial_model: Optional[GameModel] = None,
    ) -> CoordinateDescentResult:
        seq = self.update_sequence
        models: Dict[str, object] = {}
        scores: Dict[str, Array] = {}
        for name in seq:
            coord = self.coordinates[name]
            if initial_model is not None and initial_model.get_model(name) is not None:
                models[name] = initial_model.get_model(name)
            else:
                models[name] = coord.initialize_model()

        start_iteration = 0
        restored_meta = None
        if self.checkpointer is not None:
            latest = self.checkpointer.latest_step()
            if latest is not None:
                models = self.checkpointer.restore(latest, models)
                start_iteration = latest
                restored_meta = self.checkpointer.load_meta()
                self.logger.info(
                    "resumed coordinate descent from checkpoint step %d", latest
                )
        # Prefetched dispatch (overlap lever 3) starts before the first
        # update: the first coordinate's host prep (a fixed effect's tile
        # schedules: seconds when cold) runs on the worker UNDER the other
        # coordinates' scoring passes and their compiles, and its own
        # starting model is scored last, once the prep is done: a fixed
        # effect on the tiled objective scores on the schedules too. As
        # below, the worker only touches the coordinate the main thread
        # is not working on.
        pending: Dict[str, object] = {}
        order = list(seq)
        if (
            len(seq) > 1 and overlap.overlap_enabled()
            and start_iteration < num_iterations
        ):
            first = seq[0]
            pending[first] = overlap.submit(
                self.coordinates[first].prepare, models[first]
            )
            order = [n for n in seq if n != first] + [first]
        for name in order:
            if name in pending:
                with obs_span("cd.prefetch_wait", coordinate=name):
                    overlap.wait(pending.pop(name))
            scores[name] = self._score(name, models[name])

        objective_history: List[float] = []
        trackers: Dict[str, List[object]] = {name: [] for name in seq}
        validation_history: List[Dict[str, float]] = []
        best_model = None
        best_metric = None
        best_step = None
        preempted = False

        if (
            restored_meta is not None
            and restored_meta.get("best_step")
            and restored_meta.get("metric_name") == self.validation_metric
        ):
            # Resume keeps the ORIGINAL run's best-iteration selection
            # instead of silently re-judging the final model: metric from
            # the sidecar; weights from that step's checkpoint when orbax
            # still retains it (max_to_keep window). The sidecar is only
            # trusted when it tracked the SAME validation metric. If the
            # best step was pruned, the metric is dropped too — a stale
            # metric paired with different weights would corrupt both grid
            # selection and later best-iteration comparisons.
            step = int(restored_meta["best_step"])
            if step == start_iteration:
                best_model = GameModel(dict(models), self.task)
                best_metric = restored_meta.get("best_metric")
                best_step = step
            elif step in self.checkpointer.available_steps():
                best_model = GameModel(
                    self.checkpointer.restore(step, models), self.task
                )
                best_metric = restored_meta.get("best_metric")
                best_step = step
            else:
                self.logger.warning(
                    "best iteration %d checkpoint was pruned; re-judging "
                    "from the restored final model",
                    step,
                )

        counters = {
            what: default_registry().counter(
                f"photon_optim_{what}_total",
                f"optimizer {what} of the coordinate solves, by coordinate",
            )
            for what in ("solves", "iterations", "evaluations")
        }
        for it in range(start_iteration, num_iterations):
            # one span per CD iteration, its children below (cd.update ->
            # fit.dispatch / bank.dispatch, cd.score, ...): host wall of
            # the async dispatch, filed in the ring when tracing is on
            # and written beside the device lines under --profile-dir
            with obs_span("cd.iteration", iteration=it + 1) as it_span:
                # Fresh O(C) score sum once per iteration; inside the sweep
                # the residual for each coordinate is total - own score
                # (the KeyValueScore `-` of the reference) and the total is
                # patched incrementally — O(1) adds per coordinate instead
                # of the O(C^2) sum-of-others join chain.
                total = jnp.zeros((self.dataset.num_rows,), jnp.float32)
                for name in seq:
                    total = cd_total(total, scores[name])
                # Prefetched dispatch (overlap lever 3): coordinate k+1's
                # host prep — bucket stacking/device transfer, layout
                # builds, AOT warming — runs on the background worker UNDER
                # coordinate k's device solves instead of as a serial gap
                # between their dispatches. The worker only ever touches
                # the coordinate being prefetched; the main thread wait()s
                # before updating it, so cache mutations never race.
                prefetched, pending = pending, {}
                for j, name in enumerate(seq):
                    coord = self.coordinates[name]
                    if name in prefetched:
                        with obs_span("cd.prefetch_wait", coordinate=name):
                            overlap.wait(prefetched.pop(name))
                    if overlap.overlap_enabled() and j + 1 < len(seq):
                        nxt = seq[j + 1]
                        if nxt != name and nxt not in prefetched:
                            prefetched[nxt] = overlap.submit(
                                self.coordinates[nxt].prepare, models[nxt]
                            )
                    residual = (
                        cd_residual(total, scores[name])
                        if len(seq) > 1 else None
                    )
                    # which objective a fixed effect runs (tiled | scatter)
                    # and, on the kernel, its MXU variant
                    kernel = getattr(coord, "kernel", None)
                    ran = {"kernel": kernel} if kernel else {}
                    if kernel == "tiled":
                        ran["mxu"] = coord.mxu
                    with obs_span("cd.update", coordinate=name, **ran):
                        models[name], tracker = coord.update_model(
                            models[name], residual
                        )
                    trackers[name].append(tracker)
                    new_score = self._score(name, models[name])
                    total = (
                        cd_total(residual, new_score)
                        if residual is not None
                        else new_score
                    )
                    scores[name] = new_score
                for fut in prefetched.values():  # surface prep failures
                    overlap.wait(fut)

                # Deferred-readback discipline: the objective (loss + every
                # reg term), every coordinate's tracker stats and the
                # optimizer counts of every solve come back in ONE batched
                # device_get per iteration — not per-bucket, not
                # per-coordinate (each pull is a synchronous round trip
                # that stalls the dispatches queued behind it).
                with obs_span("cd.objective"):
                    objective_d = self._objective_deferred(total, models)
                solved = {
                    name: overlap.Deferred(
                        (r.iterations, r.evaluations, r.reason)
                    )
                    for name, r in ((n, trackers[n][-1]) for n in seq)
                    if isinstance(r, OptResult)
                }
                with obs_span("cd.readback"):
                    overlap.fetch_all(
                        [objective_d]
                        + [
                            d for name in seq
                            for d in _deferreds(trackers[name][-1])
                        ]
                        + list(solved.values())
                    )
                objective = objective_d.result()
                it_span.set(objective=objective)
                for name, fetched in solved.items():
                    iterations, evaluations, reason = (
                        int(v) for v in fetched.result()
                    )
                    it_span.set(**{
                        f"{name}.iterations": iterations,
                        f"{name}.evaluations": evaluations,
                    })
                    counters["solves"].inc(1, coordinate=name)
                    counters["iterations"].inc(iterations, coordinate=name)
                    counters["evaluations"].inc(
                        evaluations, coordinate=name
                    )
                    kernel = getattr(self.coordinates[name], "kernel", None)
                    self.logger.info(
                        "coordinate %s: %d iterations, %d evaluations, %s%s",
                        name, iterations, evaluations,
                        CONVERGENCE_REASON_NAMES.get(reason, "?"),
                        f", kernel={kernel}" if kernel else "",
                    )
            objective_history.append(objective)
            self.logger.info(
                "coordinate descent iter %d: objective=%g", it + 1, objective
            )
            if self.checkpointer is not None:
                # async artifact IO: the write leaves the critical path;
                # drain_io() below is the barrier before any stop
                overlap.submit_io(
                    self.checkpointer.save, it + 1, dict(models),
                    artifact=f"checkpoint step {it + 1}",
                )

            if self.validation_fn is not None:
                game_model = GameModel(
                    {name: models[name] for name in seq}, self.task
                )
                metrics = self.validation_fn(game_model)
                validation_history.append(metrics)
                self.logger.info("iter %d validation: %s", it + 1, metrics)
                if self.validation_metric is not None:
                    m = metrics[self.validation_metric]
                    better = (
                        best_metric is None
                        or (self.validation_maximize and m > best_metric)
                        or (not self.validation_maximize and m < best_metric)
                    )
                    if better:
                        best_metric = m
                        best_model = game_model
                        best_step = it + 1

            if self.checkpointer is not None:
                overlap.submit_io(
                    self.checkpointer.save_meta,
                    {
                        "best_step": best_step,
                        "best_metric": best_metric,
                        "metric_name": self.validation_metric,
                    },
                    artifact="checkpoint meta",
                )

            if self._preemption_agreed():
                # Iteration it+1 is complete (and checkpointed above when a
                # checkpointer is set) — stop at the safe boundary; a
                # restarted run resumes from this step. Flag even on the
                # final iteration so a multi-run caller (the grid sweep)
                # stops instead of starting more work in the grace window.
                preempted = True
                self.logger.warning(
                    "preemption requested: stopping after iteration %d/%d",
                    it + 1,
                    num_iterations,
                )
                break

        # IO barrier: every queued checkpoint/meta write is on disk before
        # the run returns — a preempted (or completed) run's restart
        # contract must not depend on a still-in-flight write.
        overlap.drain_io()

        if (
            self.validation_fn is not None
            and not validation_history
            and best_metric is None
            and start_iteration >= num_iterations
        ):
            # Fast-forwarded resume with no best-iteration sidecar (legacy
            # checkpoint): re-establish the restored model's validation
            # metrics so grid selection doesn't treat the combo as
            # metric-less.
            game_model = GameModel(
                {name: models[name] for name in seq}, self.task
            )
            metrics = self.validation_fn(game_model)
            validation_history.append(metrics)
            if self.validation_metric is not None:
                best_metric = metrics[self.validation_metric]
                best_model = game_model

        final = GameModel({name: models[name] for name in seq}, self.task)
        return CoordinateDescentResult(
            model=final,
            objective_history=objective_history,
            trackers=trackers,
            validation_history=validation_history,
            best_model=best_model if best_model is not None else final,
            best_metric=best_metric,
            preempted=preempted,
        )
