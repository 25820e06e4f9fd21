"""Entry ``game_cd_mf``: whole coordinate-descent iterations of a biased
matrix factorization stated as GAME: a fixed effect (the movie's genres
and mu), a bias per user and per movie (random effects over an
intercept-only shard) and a rank-K factor pair (the matrix-factorization
coordinate), squared loss, one descent.

Set-up makes the ratings in memory (``benchmark/data_ratings.py``), wraps
them in the program's plain ``GameDataset``, runs the program's own
``build_random_effect_dataset`` for the two biases and
``GameTrainingDriver._build_coordinates``, and builds ONE
``CoordinateDescent`` over ``--updating-sequence``. A step is ``run(1)``
from the initial model (zero fixed effect and biases, the factors the
coordinate's own ``initialize_model()`` seeds: the same on every step of
every run), closed on every coordinate's model and the objective. No
coordinate is wrapped, traced or not.

The check is stage by stage, each stage fed what the PROGRAM produced:
the fixed effect's four numbers against ``reference.lbfgs`` under the
residual of the STARTING factors' scores; each bias against the exact
ridge solution under the residual of the program's models before it;
the row factors against the exact solve given the starting column
factors; the column factors given the program's NEW row factors (on
``SAMPLED`` users and all movies, the heaviest among them); the
objective over all four scores and penalties.
"""

from __future__ import annotations

import gc
import os
import sys
import time
from contextlib import contextmanager
from typing import Dict, List

import numpy as np

from benchmark import data_ratings, faults, reference, work, work_ratings
from benchmark import reference_ratings as ref
from benchmark.compare import max_gap, rel_gap

SAMPLED = 32768  # entities a side is judged on (all, where it has fewer)


@contextmanager
def _told(what: str):
    """How long a stage of the check took, to standard error."""
    t0 = time.perf_counter()
    yield
    print(f"check stage {what}: {time.perf_counter() - t0:.1f} s", file=sys.stderr)


def _tell_long_pauses(least: float = 0.02):
    """A ``gc.callbacks`` entry that tells, on standard error, every
    collection that took ``least`` seconds or more."""
    started = [0.0]

    def told(phase, info):
        if phase == "start":
            started[0] = time.perf_counter()
        elif time.perf_counter() - started[0] >= least:
            print(
                f"gc: generation {info['generation']} took "
                f"{time.perf_counter() - started[0]:.3f} s", file=sys.stderr)

    return told


def _means_of(model):
    return model.model.coefficients.means


def bank_gaps(got: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    """The median entity's worst coordinate (a share of the side's
    largest) and all entities together (norm of the difference over the
    norm); the worst entity is told."""
    scale = max(float(np.max(np.abs(want))), 1e-30)
    per_entity = np.max(np.abs(got - want), axis=1) / scale
    return {
        "median_gap": float(np.median(per_entity)),
        "rms_gap": float(
            np.linalg.norm((got - want).astype(np.float64))
            / max(np.linalg.norm(want.astype(np.float64)), 1e-30)
        ),
        "gap": max_gap(got, want),
    }


class Cell:
    def __init__(self, ctx):
        from photon_ml_tpu.cli import game_training_driver as gtd
        from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
        from photon_ml_tpu.game.data import EntityIndex, GameDataset, ShardData
        from photon_ml_tpu.game.random_effect_data import (
            build_random_effect_dataset,
        )
        from photon_ml_tpu.utils.index_map import IdentityIndexMap

        cfg, wl = ctx.config, ctx.workload
        self.wl = wl
        # the driver first: a program without the cell's options says so
        # before a rating is made
        self.driver = gtd.GameTrainingDriver(gtd.params_from_args(
            list(wl["driver_args"]) + [
                "--train-input-dirs", os.path.join(ctx.work_dir, "unused"),
                "--output-dir", os.path.join(ctx.work_dir, "driver-out"),
                "--delete-output-dir-if-exists", "true",
            ]
        ))
        with ctx.span("bench.setup.generate"):
            d = data_ratings.generate(cfg, ctx.seed)
        self.data = d
        n = d.labels.shape[0]
        p = self.driver.params
        (fe_name, fe_cfg), = p.fixed_effect_data_configs.items()
        (mf_name, mf_cfg), = p.mf_configs.items()
        self.fe_name, self.mf_name = fe_name, mf_name
        self.rank = mf_cfg.num_latent_factors
        # the biases in the order they are updated in, and the side of
        # the table each is over
        self.re_names = [
            name for name in p.updating_sequence
            if name in p.random_effect_data_configs
        ]
        sides = cfg["entity_types"]
        self.side = {
            name: sides[p.random_effect_data_configs[name].random_effect_type]
            for name in self.re_names
        }
        self.row_side = sides[mf_cfg.row_effect_type]
        self.col_side = sides[mf_cfg.col_effect_type]
        shards = {
            fe_cfg.feature_shard_id: ShardData(
                d.fixed.indices, d.fixed.values,
                IdentityIndexMap(d.fixed.dim - 1, add_intercept=True),
                d.fixed.intercept_index,
            ),
        }
        for name in self.re_names:
            # an intercept-only shard: one column of ones
            shards.setdefault(
                p.random_effect_data_configs[name].feature_shard_id,
                ShardData(
                    np.zeros((n, 1), np.int32), np.ones((n, 1), np.float32),
                    IdentityIndexMap(0, add_intercept=True), 0,
                ),
            )
        entity_codes = {
            etype: d.entity_of_row[side] for etype, side in sides.items()
        }
        entity_indexes = {
            etype: EntityIndex.build(etype, [
                f"{side}{e:07d}" for e in range(d.num_entities[side])
            ])
            for etype, side in sides.items()
        }
        dataset = GameDataset(
            uids=[],  # nothing in a fit reads them
            labels=d.labels,
            offsets=np.zeros(n, np.float32),
            weights=self._row_weights(n),
            shards=shards,
            entity_codes=entity_codes,
            entity_indexes=entity_indexes,
            num_real_rows=n,
        )
        with ctx.span("bench.setup.re_dataset"):
            re_datasets = {
                name: build_random_effect_dataset(
                    dataset, p.random_effect_data_configs[name])
                for name in self.re_names
            }
        combo = gtd.expand_config_grid(
            {**p.fixed_effect_opt_configs, **p.random_effect_opt_configs}
        )[0]
        self.combo = combo
        coords = self.driver._build_coordinates(dataset, re_datasets, combo)
        with ctx.span("bench.setup.mf_structure"):
            mf = coords[mf_name]
            views = {
                side: mf._side_structure(side, solve, fixed, num)
                for side, solve, fixed, num in mf._sides()
            }
        self.schedule_shapes = {
            name: self._bank_shapes(
                coords[name].problem, re_datasets[name], re_datasets[name].local_dim)
            for name in self.re_names
        }
        for side, view in views.items():
            self.schedule_shapes[f"{mf_name}_{side}"] = self._bank_shapes(
                mf.problem, view, self.rank)
        self.dataset, self.reds = dataset, re_datasets
        # the same starting factors on every step of every run: the
        # coordinate's own constant seed
        start = mf.initialize_model()
        self.start_factors = (
            np.asarray(start.row_latent), np.asarray(start.col_latent))
        self.cd = CoordinateDescent(
            coords, dataset, p.task_type,
            update_sequence=p.updating_sequence, logger=self.driver.logger,
        )
        self.last = None
        self._fe_iters: List[int] = []
        self.step_objectives: List[float] = []
        self._problems: Dict[tuple, ref.SquaredProblem] = {}
        self._fixed_reference = None
        rng = np.random.default_rng(int(cfg["shape_seed"]))
        counts = {
            side: np.bincount(d.entity_of_row[side], minlength=d.num_entities[side])
            for side in (self.row_side, self.col_side)
        }
        self.counts = counts
        for side in (self.row_side, self.col_side):
            drawn, law = np.sort(counts[side])[::-1], d.counts[side]
            print(
                f"ratings drawn, {side}s: heaviest {drawn[0]} (law {law[0]}), "
                f"{np.count_nonzero(drawn)} with a rating (law {len(law)}), "
                f"median {int(np.median(drawn[:len(law)]))} (law "
                f"{int(np.median(law))}), off the law by "
                f"{int(np.abs(drawn[:len(law)] - law).sum())} ratings in all",
                file=sys.stderr,
            )
        self.sample = {}
        for side in (self.row_side, self.col_side):
            rated = np.nonzero(counts[side] > 0)[0]
            pick = rng.permutation(rated)[:SAMPLED]
            # the heaviest entity of a side is always judged
            pick = np.union1d(pick, [int(np.argmax(counts[side]))])
            self.sample[side] = np.sort(pick)

    @staticmethod
    def _bank_shapes(problem, red, d_local: int) -> Dict:
        """The blocks a bank's solver programs run."""
        blocks = problem._solver_blocks(red, d_local, split=True)
        return {
            "buckets": [list(b.row_index.shape) for b in red.buckets],
            "blocks": [list(b.bucket.row_index.shape) for b in blocks],
            "block_kinds": [b.kind for b in blocks],
            "sub_blocks": [b.sub_blocks for b in blocks],
        }

    @staticmethod
    def _row_weights(n: int) -> np.ndarray:
        """The weights the PROGRAM's rows get: all ones. (The seam where
        ``benchmark/tests`` leaves half of the batch out.)"""
        return np.ones(n, np.float32)

    # -- the timed path ----------------------------------------------------

    def _parts(self, model):
        mf = model.get_model(self.mf_name)
        return (
            [_means_of(model.get_model(self.fe_name))]
            + [model.get_model(n).bank for n in self.re_names]
            + [mf.row_latent, mf.col_latent]
        )

    def step(self) -> Dict:
        import jax

        result = self.cd.run(1)
        jax.block_until_ready(self._parts(result.model))
        self.last = result
        objective = float(result.objective_history[-1])
        self.step_objectives.append(objective)
        self._fe_iters.append(
            int(result.trackers[self.fe_name][-1].iterations))
        if len(self.step_objectives) == 1:
            # the first (warm-up) step is done. What set-up left (the
            # jaxprs of some 440 compiled programs: millions of
            # containers) stays for the life of the process: the collector
            # need not walk it again inside the window, where a full pass
            # costs 0.2 s. A pause of 20 ms or more that still happens is
            # told.
            gc.collect()
            gc.freeze()
            self._pauses = _tell_long_pauses()
            gc.callbacks.append(self._pauses)
        return {"units": 1, "ok": bool(np.isfinite(objective))}

    def array_shapes(self) -> Dict[str, List[int]]:
        shapes = {}
        for sid, sd in self.dataset.shards.items():
            shapes[f"shard.{sid}.indices"] = list(sd.indices.shape)
        for name, red in self.reds.items():
            for i, b in enumerate(red.buckets):
                shapes[f"{name}.bucket.{i}.rows"] = list(b.row_index.shape)
            shapes[f"{name}.bank"] = [red.num_entities, red.local_dim]
        for key in (f"{self.mf_name}_row", f"{self.mf_name}_col"):
            for i, shape in enumerate(self.schedule_shapes[key]["buckets"]):
                shapes[f"{key}.bucket.{i}.rows"] = shape
        shapes[f"{self.mf_name}.row_latent"] = list(self.start_factors[0].shape)
        shapes[f"{self.mf_name}.col_latent"] = list(self.start_factors[1].shape)
        return shapes

    def half_step_work(self) -> Dict[str, float]:
        """Needed work of the two ALS half-steps of one CD step."""
        n = int(self.data.labels.shape[0])
        return work.add(*(
            work_ratings.als_half_step(
                ratings=n, entities=int(np.count_nonzero(self.counts[side])),
                rank=self.rank)
            for side in (self.row_side, self.col_side)
        ))

    def work_per_unit(self) -> Dict[str, float]:
        """Needed work of one CD step over ALL four coordinates: one
        value+gradient per fixed-effect iteration and its scoring pass,
        each bias's exact solve and scoring pass, the two half-steps and
        the factor score."""
        d = self.data
        n = int(d.labels.shape[0])
        entries = int(np.count_nonzero(d.fixed.values))
        fe_iters = float(np.mean(self._fe_iters)) if self._fe_iters else 0.0
        parts = [
            work.scale(work.glm_value_and_gradient(
                entries=entries, rows=n, dim=d.fixed.dim), fe_iters),
            work.sparse_score(entries=entries, rows=n, dim=d.fixed.dim),
            self.half_step_work(),
            work_ratings.factor_score(ratings=n, rank=self.rank),
        ]
        for name in self.re_names:
            entities = d.num_entities[self.side[name]]
            parts += [
                work_ratings.bias_update(ratings=n, entities=entities),
                work_ratings.bias_score(ratings=n, entities=entities),
            ]
        return work.add(*parts)

    # -- after the window --------------------------------------------------

    def take_outputs(self) -> Dict:
        result = self.last
        model = result.model
        fixed_result = result.trackers[self.fe_name][-1]
        tracker = fixed_result.tracker
        count = int(tracker.count)
        mf = model.get_model(self.mf_name)
        out = {
            "fixed": np.asarray(_means_of(model.get_model(self.fe_name))),
            "banks": {
                name: np.asarray(model.get_model(name).bank)
                for name in self.re_names
            },
            "row_latent": np.asarray(mf.row_latent),
            "col_latent": np.asarray(mf.col_latent),
            "objective": float(result.objective_history[-1]),
            "step_objectives": list(self.step_objectives),
            "fixed_values": np.asarray(tracker.values)[:count],
            "fixed_grad_norm": float(fixed_result.grad_norm),
            "rows": int(self.data.labels.shape[0]),
            # for the planted faults that have to solve (FAULTS)
            "cell": self,
        }
        self.last = self.cd = self.dataset = self.reds = None
        if getattr(self, "_pauses", None) in gc.callbacks:
            gc.callbacks.remove(self._pauses)
            gc.unfreeze()
        return out

    def _l2(self, name: str) -> float:
        return float(self.combo[name].reg_weight)

    def _codes(self, side: str) -> np.ndarray:
        return self.data.entity_of_row[side]

    def _start_scores(self, precision="f32") -> np.ndarray:
        """The factor coordinate's score at the STARTING factors: what
        every coordinate before it is solved under."""
        return ref.factor_scores(
            *self.start_factors, self._codes(self.row_side),
            self._codes(self.col_side), precision)

    def _fixed_problem(self, precision="f32", weights=None):
        d = self.data
        key = (precision, weights is None)
        if weights is None and key in self._problems:
            return self._problems[key]  # its rows are on the device
        problem = ref.SquaredProblem(
            d.fixed.indices, d.fixed.values, d.labels, d.fixed.dim,
            l2=self._l2(self.fe_name), offsets=self._start_scores(precision),
            weights=weights, precision=precision,
        )
        if weights is None:
            self._problems[key] = problem
        return problem

    def _reference_fixed(self, precision="f32", weights=None) -> reference.LbfgsTrace:
        """The reference's own L-BFGS on the fixed effect, from zero,
        under the residual of the starting factors' scores."""
        cached = weights is None and precision == "f32"
        if cached and self._fixed_reference is not None:
            return self._fixed_reference  # the same whatever the outputs are
        oc = self.combo[self.fe_name].optimizer_config
        prob = self._fixed_problem(precision, weights)
        trace = reference.lbfgs(
            prob.value_and_gradient, np.zeros(prob.dim, np.float32),
            max_iter=int(oc.max_iter), tol=float(oc.tolerance),
            history=int(oc.lbfgs_history),
        )
        if cached:
            self._fixed_reference = trace
        return trace

    def _weights(self, weights=None) -> np.ndarray:
        n = self.data.labels.shape[0]
        return np.ones(n, np.float32) if weights is None else weights

    def _bias(self, name, off, precision="f32", weights=None) -> np.ndarray:
        """The exact ridge solution of one bias bank under the residual
        ``off`` of the other coordinates."""
        side = self.side[name]
        return ref.bias_solve(
            self._codes(side), self.data.num_entities[side],
            self.data.labels - off, self._weights(weights), self._l2(name),
            precision)

    def _half_step(self, side, partner_latent, off, entities,
                   precision="f32", weights=None) -> np.ndarray:
        """The exact solution of one ALS half-step for ``entities`` of
        ``side`` given the partner side's factors."""
        other = self.col_side if side == self.row_side else self.row_side
        return ref.factor_solve(
            partner_latent, self._codes(side), self._codes(other),
            self.data.labels - off, self._weights(weights),
            self._l2(self.mf_name), entities, precision=precision)

    def _reference_objective(self, out, precision="f32") -> float:
        z = self._fixed_problem(precision).margins(out["fixed"])
        value = ref.penalty(self._l2(self.fe_name), out["fixed"])
        for name in self.re_names:
            z = z + ref.bias_scores(
                out["banks"][name], self._codes(self.side[name]), precision)
            value += ref.penalty(self._l2(name), out["banks"][name])
        z = z + ref.factor_scores(
            out["row_latent"], out["col_latent"], self._codes(self.row_side),
            self._codes(self.col_side), precision)
        value += ref.penalty(
            self._l2(self.mf_name), out["row_latent"], out["col_latent"])
        return value + ref.squared_total(z, self.data.labels)

    def _all(self, side) -> np.ndarray:
        return np.arange(self.data.num_entities[side])

    def reference_outputs(self, precision: str = "f32", weights=None) -> Dict:
        """The reference put in the program's place (the control and the
        planted faults; never a benchmark run): every coordinate in the
        order of the sequence, each under the residual of the others."""
        trace = self._reference_fixed(precision, weights)
        fixed = trace.coefficients[-1]
        _, g = self._fixed_problem(precision, weights).value_and_gradient(fixed)
        start = self._start_scores(precision)
        off = self._fixed_problem(precision).margins(fixed)
        banks = {}
        for name in self.re_names:
            banks[name] = self._bias(name, off + start, precision, weights)
            off = off + ref.bias_scores(
                banks[name], self._codes(self.side[name]), precision)
        p0, q0 = self.start_factors
        row = self._half_step(
            self.row_side, q0, off, self._all(self.row_side), precision, weights)
        col = self._half_step(
            self.col_side, row, off, self._all(self.col_side), precision, weights)
        out = {
            "fixed": fixed, "banks": banks, "row_latent": row, "col_latent": col,
            "fixed_values": np.asarray(trace.values, np.float32),
            "fixed_grad_norm": float(np.linalg.norm(np.asarray(g))),
            "rows": int(self.data.labels.shape[0]), "cell": self,
        }
        objective = self._reference_objective(out, precision)
        out["objective"] = objective
        out["step_objectives"] = [objective]
        return out

    def check(self, out: Dict) -> Dict[str, float]:
        fixed, banks = out["fixed"], out["banks"]
        with _told("fixed effect"):
            trace = self._reference_fixed()
            prob = self._fixed_problem()
            reached, grad = prob.value_and_gradient(fixed)
            reached = float(reached)
        with _told("objective"):
            objective = self._reference_objective(out)
        readings = {
            # the fixed effect's four, as game_cd.check reads them
            "fixed_first_gap": max(
                rel_gap(out["fixed_values"][i], trace.values[i]) for i in (0, 1)
            ),
            "fixed_value_gap": rel_gap(out["fixed_values"][-1], reached),
            "fixed_grad_gap": rel_gap(
                out["fixed_grad_norm"], float(np.linalg.norm(np.asarray(grad)))
            ),
            "fixed_descent_gap": max(
                0.0, (reached - trace.values[-1]) / abs(trace.values[-1])
            ),
            "objective_gap": rel_gap(out["objective"], objective),
            # every step of the window starts from the same model
            "repeat_gap": max(
                rel_gap(v, out["objective"]) for v in out["step_objectives"]
            ),
            # told, not judged
            "fixed_gap": max_gap(fixed, trace.coefficients[-1]),
            "fixed_reached_gap": rel_gap(reached, trace.values[-1]),
        }
        # each bias under the residual of the PROGRAM's models before it
        # and the starting factors' scores
        start = self._start_scores()
        off = prob.margins(fixed)
        for name in self.re_names:
            got = banks[name]
            want = self._bias(name, off + start)
            rated = self.counts[self.side[name]] > 0
            for k, v in bank_gaps(got[rated], want[rated]).items():
                readings[f"{self.side[name]}_bias_{k}"] = v
            off = off + ref.bias_scores(got, self._codes(self.side[name]))
        # the row factors given the STARTING column factors, the column
        # factors given the program's NEW row factors
        p0, q0 = self.start_factors
        for key, side, partner, got in (
            ("mf_row", self.row_side, q0, out["row_latent"]),
            ("mf_col", self.col_side, out["row_latent"], out["col_latent"]),
        ):
            sample = self.sample[side]
            with _told(key):
                want = self._half_step(side, partner, off, sample)
            for k, v in bank_gaps(got[sample], want).items():
                readings[f"{key}_{k}"] = v
        for i in range(min(len(out["fixed_values"]), len(trace.values))):
            readings[f"fixed_loss_gap.{i}"] = rel_gap(
                out["fixed_values"][i], trace.values[i]
            )
        return readings


def _with_objective(new: Dict) -> Dict:
    """What a program that ends on this model would report of it."""
    objective = new["cell"]._reference_objective(new)
    new["objective"] = objective
    new["step_objectives"] = [objective] * len(new["step_objectives"])
    return new


def _unchanged(out: Dict) -> Dict:
    """Every coordinate returns the model it was given: zero fixed effect
    and biases, the starting factors."""
    cell = out["cell"]
    new = dict(out)
    new["fixed"] = np.zeros_like(out["fixed"])
    new["banks"] = {n: np.zeros_like(b) for n, b in out["banks"].items()}
    new["row_latent"], new["col_latent"] = cell.start_factors
    new = _with_objective(new)
    new["fixed_values"] = np.full_like(out["fixed_values"], new["objective"])
    new["fixed_grad_norm"] = 0.0  # not told by an unchanged state; reads 1
    return new


def _altered(out: Dict) -> Dict:
    """The fixed effect's largest coefficient wrong."""
    new = dict(out)
    j = int(np.argmax(np.abs(out["fixed"])))
    new["fixed"] = out["fixed"].copy()
    new["fixed"][j] *= 1.0 + faults.ALTERED_BY
    return new


def _col_skipped(out: Dict) -> Dict:
    """The column half-step SKIPPED: the column factors the starting
    ones, the objective what the program would report of that model."""
    new = dict(out)
    new["col_latent"] = out["cell"].start_factors[1]
    return _with_objective(new)


def _col_stale(out: Dict) -> Dict:
    """The column half-step solved against the STALE row factors (the
    starting ones, not the ones the same step just made): the configured
    solve on the wrong features, and the objective of its own model."""
    cell = out["cell"]
    new = dict(out)
    off = cell._fixed_problem().margins(out["fixed"])
    for name in cell.re_names:
        off = off + ref.bias_scores(out["banks"][name], cell._codes(cell.side[name]))
    new["col_latent"] = cell._half_step(
        cell.col_side, cell.start_factors[0], off, cell._all(cell.col_side))
    return _with_objective(new)


FAULTS = {
    "unchanged": _unchanged, "altered": _altered,
    "col_skipped": _col_skipped, "col_stale": _col_stale,
}


def setup(ctx) -> Cell:
    return Cell(ctx)
