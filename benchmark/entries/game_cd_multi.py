"""Entry ``game_cd_multi``: whole coordinate-descent iterations of a full
GAME model: a fixed effect and ANY number of random effects over one
table of rows, as the cell's ``driver_args`` name them.

Set-up makes the rows in memory (``benchmark/data_game.py``), wraps them
in the program's plain ``GameDataset``, runs the program's own
``build_random_effect_dataset`` for every random effect and
``GameTrainingDriver._build_coordinates``, and builds ONE
``CoordinateDescent`` over ``--updating-sequence``. A step is ``run(1)``
on it from the zero model, closed on every coordinate's model and the
objective. The program's coordinates are never wrapped, traced or not (a
synced span around ``update_model`` would file queued scoring under the
next coordinate and stall the prefetch): the per-layer metrics read the
program's own module names, spans and counters.

The check is ``game_cd``'s, stage by stage, each stage fed what the
program itself produced: the fixed effect's four numbers against the
reference's L-BFGS from zero; then, in the order of the updating
sequence, each bank against the reference's damped Newton under the
residual the PROGRAM's models before it leave (the fixed effect's score;
for the second bank the first bank's score too: the hand-off), on
``SAMPLED`` entities drawn from the configuration's ``shape_seed``, an
entity on a level overshoot held to either stop
(``game_cd_pod.either_stop``); the objective against the reference's
scores, losses and penalties over all coordinates at the program's model.
The reference groups a bank's rows by entity at the entity's own row
count (24 an item, where the program pads to 32).
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

from benchmark import data_game, faults, reference, work
from benchmark.compare import max_gap, rel_gap
from benchmark.entries.game_cd_pod import either_stop

SAMPLED = 32768  # entities a bank is judged on


def _bank_of(model):
    return model.bank


def _means_of(model):
    return model.model.coefficients.means


def short(name: str) -> str:
    """``per-user`` -> ``user``: what a bank's readings are named by."""
    return name.split("-")[-1]


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
        with ctx.span("bench.setup.generate"):
            d = data_game.generate(cfg, ctx.seed)
        self.data = d
        n = d.labels.shape[0]
        self.driver = gtd.GameTrainingDriver(gtd.params_from_args(
            list(wl["driver_args"]) + [
                "--train-input-dirs", os.path.join(ctx.work_dir, "unused"),
                "--output-dir", os.path.join(ctx.work_dir, "driver-out"),
                "--delete-output-dir-if-exists", "true",
            ]
        ))
        p = self.driver.params
        (fe_name, fe_cfg), = p.fixed_effect_data_configs.items()
        self.fe_name = fe_name
        # the random effects in the order they are updated in
        self.re_names = [
            name for name in p.updating_sequence
            if name in p.random_effect_data_configs
        ]
        # which side of the table a random effect is over: the
        # configuration names each entity type's side
        self.side = {
            name: cfg["entity_types"][
                p.random_effect_data_configs[name].random_effect_type]
            for name in self.re_names
        }
        shards = {
            fe_cfg.feature_shard_id: ShardData(
                d.fixed.indices, d.fixed.values,
                IdentityIndexMap(d.fixed.dim - 1, add_intercept=True),
                d.fixed.intercept_index,
            ),
        }
        entity_codes, entity_indexes = {}, {}
        for name in self.re_names:
            re_cfg = p.random_effect_data_configs[name]
            rows = d.sides[self.side[name]]
            shards[re_cfg.feature_shard_id] = ShardData(
                rows.indices, rows.values, IdentityIndexMap(rows.dim), None,
            )
            etype = re_cfg.random_effect_type
            entity_codes[etype] = d.entity_of_row[self.side[name]]
            entity_indexes[etype] = EntityIndex.build(etype, [
                f"{self.side[name]}{e:07d}"
                for e in range(d.num_entities[self.side[name]])
            ])
        dataset = GameDataset(
            uids=[str(i) for i in range(n)],
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
        self.schedule_shapes = {
            name: self._bank_shapes(coords[name].problem, re_datasets[name])
            for name in self.re_names
        }
        self.dataset, self.reds = dataset, re_datasets
        self.cd = CoordinateDescent(
            coords, dataset, p.task_type,
            update_sequence=p.updating_sequence, logger=self.driver.logger,
        )
        self.last = None
        self._iters = {name: [] for name in [fe_name] + self.re_names}
        self.step_objectives: List[float] = []
        self._problems: Dict[str, reference.SparseProblem] = {}
        self._fixed_reference = None
        rng = np.random.default_rng(int(cfg["shape_seed"]))
        self.sample = {
            name: np.sort(rng.permutation(
                d.num_entities[self.side[name]])[:SAMPLED])
            for name in self.re_names
        }
        self._apart: Dict[str, float] = {}

    @staticmethod
    def _bank_shapes(problem, red) -> Dict:
        """The blocks a bank's solver programs run. (A program that splits
        no bucket tells the kind each bucket runs whole.)"""
        shapes = {"buckets": [list(b.indices.shape) for b in red.buckets]}
        blocks_of = getattr(problem, "_solver_blocks", None)
        if blocks_of is None:
            shapes["bucket_kinds"] = [
                problem._bucket_kind(b, red.local_dim) for b in red.buckets
            ]
            return shapes
        blocks = blocks_of(red, red.local_dim, split=True)
        shapes["blocks"] = [list(b.bucket.indices.shape) for b in blocks]
        shapes["block_kinds"] = [b.kind for b in blocks]
        shapes["sub_blocks"] = [b.sub_blocks for b in blocks]
        return shapes

    @staticmethod
    def _row_weights(n: int) -> np.ndarray:
        """The weights the PROGRAM's rows get: all ones. (The seam where
        ``benchmark/tests`` leaves half of the batch out.)"""
        return np.ones(n, np.float32)

    # -- the timed path ----------------------------------------------------

    def step(self) -> Dict:
        import jax

        result = self.cd.run(1)
        model = result.model
        jax.block_until_ready(
            [_means_of(model.get_model(self.fe_name))]
            + [_bank_of(model.get_model(n)) for n in self.re_names]
        )
        self.last = result
        objective = float(result.objective_history[-1])
        self.step_objectives.append(objective)
        self._iters[self.fe_name].append(
            int(result.trackers[self.fe_name][-1].iterations))
        for name in self.re_names:
            self._iters[name].append(
                float(result.trackers[name][-1].iterations_mean))
        return {"units": 1, "ok": bool(np.isfinite(objective))}

    def array_shapes(self) -> Dict[str, List[int]]:
        shapes = {}
        for sid, sd in self.dataset.shards.items():
            shapes[f"shard.{sid}.indices"] = list(sd.indices.shape)
        for name, red in self.reds.items():
            for i, b in enumerate(red.buckets):
                shapes[f"{name}.bucket.{i}.indices"] = list(b.indices.shape)
            shapes[f"{name}.bank"] = [red.num_entities, red.local_dim]
        return shapes

    def work_per_unit(self) -> Dict[str, float]:
        """Needed work of one CD step over ALL its coordinates: one
        value+gradient per fixed-effect iteration, one pass over a bank's
        rows per bank iteration (the tracker's mean), and one scoring pass
        a coordinate."""
        d = self.data
        n = d.labels.shape[0]

        def mean(name):
            return float(np.mean(self._iters[name])) if self._iters[name] else 0.0

        entries = int(np.count_nonzero(d.fixed.values))
        parts = [
            work.scale(work.glm_value_and_gradient(
                entries=entries, rows=n, dim=d.fixed.dim), mean(self.fe_name)),
            work.sparse_score(entries=entries, rows=n, dim=d.fixed.dim),
        ]
        for name in self.re_names:
            rows = d.sides[self.side[name]]
            entries = int(np.count_nonzero(rows.values))
            bank = d.num_entities[self.side[name]] * rows.dim
            parts += [
                work.scale(work.glm_value_and_gradient(
                    entries=entries, rows=n, dim=bank), mean(name)),
                work.sparse_score(entries=entries, rows=n, dim=bank),
            ]
        return work.add(*parts)

    # -- after the window --------------------------------------------------

    def take_outputs(self) -> Dict:
        result = self.last
        model = result.model
        fixed_result = result.trackers[self.fe_name][-1]
        tracker = fixed_result.tracker
        count = int(tracker.count)
        out = {
            "fixed": np.asarray(_means_of(model.get_model(self.fe_name))),
            "banks": {
                name: np.asarray(_bank_of(model.get_model(name)))
                for name in self.re_names
            },
            "objective": float(result.objective_history[-1]),
            "step_objectives": list(self.step_objectives),
            "fixed_values": np.asarray(tracker.values)[:count],
            "fixed_grad_norm": float(fixed_result.grad_norm),
            "rows": int(self.data.labels.shape[0]),
            # for the planted faults that have to solve (FAULTS)
            "cell": self,
        }
        self.last = self.cd = self.dataset = self.reds = None
        return out

    def _l2(self, name: str) -> float:
        return float(self.combo[name].reg_weight)

    def _fixed_problem(self, precision="f32", weights=None):
        d = self.data
        if weights is None and precision in self._problems:
            return self._problems[precision]  # its rows are on the device
        problem = reference.SparseProblem(
            d.fixed.indices, d.fixed.values, d.labels, d.fixed.dim,
            l2=self._l2(self.fe_name), weights=weights, precision=precision,
        )
        if weights is None:
            self._problems[precision] = problem
        return problem

    def _reference_fixed(self, precision="f32", weights=None) -> reference.LbfgsTrace:
        """The reference's own L-BFGS on the fixed effect, from zero."""
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

    def _scores(self, name: str, bank: np.ndarray, precision="f32") -> np.ndarray:
        """The reference's scores of one bank over all rows."""
        side = self.side[name]
        rows = self.data.sides[side]
        return reference.user_scores(
            bank, rows.indices, rows.values, self.data.entity_of_row[side],
            precision=precision,
        )

    def _entity_rows(self, name: str, off: np.ndarray, entities=None):
        """(indices, values, labels, offsets) grouped [E, rows an entity,
        ...] at the entity's OWN row count (every entity of a side has the
        same), for all entities or the given ones."""
        side = self.side[name]
        d = self.data
        rows = d.sides[side]
        order = np.argsort(d.entity_of_row[side], kind="stable")
        order = order.reshape(d.num_entities[side], -1)
        if entities is not None:
            order = order[entities]
        return tuple(
            a[order] for a in (rows.indices, rows.values, d.labels, off)
        )

    def _reference_bank(self, name, off, precision="f32", against=None):
        """The entities' solves under the offsets ``off``: every entity's,
        or with ``against`` (the program's sampled rows) the sampled
        ones', an entity on a level overshoot held to either stop."""
        oc = self.combo[name].optimizer_config
        l2, max_iter, tol = self._l2(name), int(oc.max_iter), float(oc.tolerance)
        dim = self.data.sides[self.side[name]].dim
        rows = self._entity_rows(
            name, off, None if against is None else self.sample[name])

        def solve(entities, max_iter, tol):
            return reference.solve_users(
                *(a[entities] for a in rows), dim, l2,
                max_iter=max_iter, tol=tol, precision=precision,
            )

        ref = solve(slice(None), max_iter, tol)
        if against is None:
            return ref
        ref, apart, held = either_stop(
            ref, against, rows, solve, l2, max_iter, tol)
        self._apart[f"{short(name)}_bank_apart"] = apart
        self._apart[f"{short(name)}_bank_either_stop"] = held
        return ref

    def _reference_objective(self, fixed, banks, precision="f32") -> float:
        z = self._fixed_problem(precision).margins(fixed)
        penalty = 0.5 * self._l2(self.fe_name) * float(
            np.sum(fixed.astype(np.float64) ** 2))
        for name in self.re_names:
            z = z + self._scores(name, banks[name], precision)
            penalty += 0.5 * self._l2(name) * float(
                np.sum(banks[name].astype(np.float64) ** 2))
        return reference.logistic_total(z, self.data.labels) + penalty

    def reference_outputs(self, precision: str = "f32", weights=None) -> Dict:
        """The reference put in the program's place (the control and the
        planted faults; never a benchmark run)."""
        trace = self._reference_fixed(precision, weights)
        fixed = trace.coefficients[-1]
        _, g = self._fixed_problem(precision, weights).value_and_gradient(fixed)
        off = self._fixed_problem(precision).margins(fixed)
        banks = {}
        for name in self.re_names:
            banks[name] = self._reference_bank(name, off, precision)
            off = off + self._scores(name, banks[name], precision)
        objective = self._reference_objective(fixed, banks, precision)
        return {
            "fixed": fixed, "banks": banks, "objective": objective,
            "step_objectives": [objective],
            "fixed_values": np.asarray(trace.values, np.float32),
            "fixed_grad_norm": float(np.linalg.norm(np.asarray(g))),
            "rows": int(self.data.labels.shape[0]), "cell": self,
        }

    def check(self, out: Dict) -> Dict[str, float]:
        fixed, banks = out["fixed"], out["banks"]
        ref = self._reference_fixed()
        ref_fixed = ref.coefficients[-1]
        prob = self._fixed_problem()
        reached, grad = prob.value_and_gradient(fixed)
        reached = float(reached)
        readings = {
            # the fixed effect's four, as game_cd.check reads them
            "fixed_first_gap": max(
                rel_gap(out["fixed_values"][i], ref.values[i]) for i in (0, 1)
            ),
            "fixed_value_gap": rel_gap(out["fixed_values"][-1], reached),
            "fixed_grad_gap": rel_gap(
                out["fixed_grad_norm"], float(np.linalg.norm(np.asarray(grad)))
            ),
            "fixed_descent_gap": max(
                0.0, (reached - ref.values[-1]) / abs(ref.values[-1])
            ),
            "objective_gap": rel_gap(
                out["objective"], self._reference_objective(fixed, banks)
            ),
            # every step of the window starts from zero on the same rows
            "repeat_gap": max(
                rel_gap(v, out["objective"]) for v in out["step_objectives"]
            ),
            # told, not judged
            "fixed_gap": max_gap(fixed, ref_fixed),
            "fixed_reached_gap": rel_gap(reached, ref.values[-1]),
        }
        # each bank under the residual of the PROGRAM's models before it
        off = prob.margins(fixed)
        self._apart = {}
        for name in self.re_names:
            got = banks[name][self.sample[name]]
            want = self._reference_bank(name, off, against=got)
            per_entity = np.max(np.abs(got - want), axis=1) / max(
                float(np.max(np.abs(want))), 1e-30
            )
            key = short(name)
            # the median entity and all sampled entities together: an
            # entity whose stopping test sits on its threshold may stop an
            # iteration apart, so the worst swings and the median does not
            readings[f"{key}_bank_median_gap"] = float(np.median(per_entity))
            readings[f"{key}_bank_rms_gap"] = float(
                np.linalg.norm((got - want).astype(np.float64))
                / max(np.linalg.norm(want.astype(np.float64)), 1e-30)
            )
            readings[f"{key}_bank_gap"] = max_gap(got, want)  # told
            readings[f"{key}_bank_gap.p99"] = float(np.quantile(per_entity, 0.99))
            off = off + self._scores(name, banks[name])
        readings.update(self._apart)
        for i in range(min(len(out["fixed_values"]), len(ref.values))):
            readings[f"fixed_loss_gap.{i}"] = rel_gap(
                out["fixed_values"][i], ref.values[i]
            )
        return readings


def _with_objective(new: Dict) -> Dict:
    """What a program that ends on this model would report of it."""
    objective = new["cell"]._reference_objective(new["fixed"], new["banks"])
    new["objective"] = objective
    new["step_objectives"] = [objective] * len(new["step_objectives"])
    return new


def _unchanged(out: Dict) -> Dict:
    """Every coordinate returns the zero model it was given."""
    new = dict(out)
    new["fixed"] = np.zeros_like(out["fixed"])
    new["banks"] = {n: np.zeros_like(b) for n, b in out["banks"].items()}
    objective = float(out["rows"] * np.log(2.0))
    new["objective"] = objective
    new["step_objectives"] = [objective] * len(out["step_objectives"])
    new["fixed_values"] = np.full_like(out["fixed_values"], objective)
    new["fixed_grad_norm"] = 0.0  # not told by an unchanged state; reads 1
    return new


def _altered(out: Dict) -> Dict:
    """The fixed effect's largest coefficient wrong."""
    new = dict(out)
    j = int(np.argmax(np.abs(out["fixed"])))
    new["fixed"] = out["fixed"].copy()
    new["fixed"][j] *= 1.0 + faults.ALTERED_BY
    return new


def _last_left_at_zero(out: Dict) -> Dict:
    """The LAST random effect of the sequence (the per-item coordinate)
    never updated: its bank the zero one, the objective what the program
    would report of that model."""
    cell = out["cell"]
    new = dict(out)
    last = cell.re_names[-1]
    new["banks"] = dict(out["banks"], **{last: np.zeros_like(out["banks"][last])})
    return _with_objective(new)


def _handoff_skipped(out: Dict) -> Dict:
    """The last random effect solved against the residual of the fixed
    effect ALONE, the scores of the random effects before it not handed
    over: the configured solve on the wrong offsets, and the objective
    what the program would report of that model."""
    cell = out["cell"]
    new = dict(out)
    last = cell.re_names[-1]
    off = cell._fixed_problem().margins(out["fixed"])
    new["banks"] = dict(out["banks"], **{last: cell._reference_bank(last, off)})
    return _with_objective(new)


FAULTS = {
    "unchanged": _unchanged, "altered": _altered,
    "last_left_at_zero": _last_left_at_zero,
    "handoff_skipped": _handoff_skipped,
}


def setup(ctx) -> Cell:
    return Cell(ctx)
