"""Entry ``game_cd``: whole coordinate-descent iterations of a GLMix model.

Set-up makes the rows in memory (``benchmark/data.py``), wraps them in the
program's plain ``GameDataset``, runs the program's own
``build_random_effect_dataset`` and ``GameTrainingDriver._build_coordinates``
and builds ONE ``CoordinateDescent``. A step is ``run(1)`` on it from the
zero model, closed on the returned model and objective.

In a traced run each coordinate sits behind a thin proxy that puts a host
span, synced at its end, around ``update_model``; untraced runs hand the
program its own coordinates untouched.

The check is stage by stage, each stage fed what the program itself
produced, so that no stage's rounding is amplified through the next: the
fixed effect against the reference's own L-BFGS from zero (its first
iterations' losses, the value it says it reached against the reference's
objective there, and how far down it got); the user bank against the
reference's damped Newton, the algorithm the configuration states, under
the residual the PROGRAM's fixed effect leaves; the objective against the
reference's, evaluated at the program's model.
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

from benchmark import data as bench_data
from benchmark import faults, reference, work
from benchmark.compare import max_gap, rel_gap


def _model_arrays(model):
    bank = getattr(model, "bank", None)
    return bank if bank is not None else model.model.coefficients.means


class _SpannedCoordinate:
    """A coordinate whose ``update_model`` runs inside a synced host span."""

    def __init__(self, inner, span, name: str):
        self.__dict__.update(_inner=inner, _span=span, _name=name)

    def __getattr__(self, attr):
        return getattr(self._inner, attr)

    def update_model(self, model, residual=None):
        import jax

        with self._span(self._name):
            new, tracker = self._inner.update_model(model, residual)
            jax.block_until_ready(_model_arrays(new))
        return new, tracker


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
            d = bench_data.generate(cfg, ctx.seed)
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
        (re_name, re_cfg), = p.random_effect_data_configs.items()
        self.fe_name, self.re_name = fe_name, re_name
        ids = [f"user{u:07d}" for u in range(d.num_users)]
        dataset = GameDataset(
            uids=[str(i) for i in range(n)],
            labels=d.labels,
            offsets=np.zeros(n, np.float32),
            weights=self._row_weights(n),
            shards={
                fe_cfg.feature_shard_id: ShardData(
                    d.fixed.indices, d.fixed.values,
                    IdentityIndexMap(d.fixed.dim - 1, add_intercept=True),
                    d.fixed.intercept_index,
                ),
                re_cfg.feature_shard_id: ShardData(
                    d.user.indices, d.user.values,
                    IdentityIndexMap(d.user.dim), None,
                ),
            },
            entity_codes={re_cfg.random_effect_type: d.user_of_row},
            entity_indexes={
                re_cfg.random_effect_type: EntityIndex.build(
                    re_cfg.random_effect_type, ids
                )
            },
            num_real_rows=n,
        )
        with ctx.span("bench.setup.re_dataset"):
            re_datasets = {re_name: build_random_effect_dataset(dataset, re_cfg)}
        red = re_datasets[re_name]
        combo = gtd.expand_config_grid(
            {**p.fixed_effect_opt_configs, **p.random_effect_opt_configs}
        )[0]
        self.combo = combo
        coords = self.driver._build_coordinates(dataset, re_datasets, combo)
        self.bucket_kinds = [
            coords[re_name].problem._bucket_kind(b, red.local_dim)
            for b in red.buckets
        ]
        self.schedule_shapes = {
            "buckets": [list(b.indices.shape) for b in red.buckets],
            "bucket_kinds": self.bucket_kinds,
        }
        if ctx.traced:
            coords = {
                fe_name: _SpannedCoordinate(
                    coords[fe_name], ctx.span, "bench.cd.fe_update"),
                re_name: _SpannedCoordinate(
                    coords[re_name], ctx.span, "bench.cd.re_update"),
            }
        self.dataset, self.red = dataset, red
        self.cd = CoordinateDescent(
            coords, dataset, p.task_type,
            update_sequence=p.updating_sequence, logger=self.driver.logger,
        )
        self.last = None
        self._iters = {"fe": [], "re": []}
        self.step_objectives: List[float] = []
        self._problems: Dict[str, reference.SparseProblem] = {}
        self._fixed_reference = None

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
        jax.block_until_ready([
            _model_arrays(model.get_model(n)) for n in (self.fe_name, self.re_name)
        ])
        self.last = result
        objective = float(result.objective_history[-1])
        self.step_objectives.append(objective)
        self._iters["fe"].append(int(result.trackers[self.fe_name][-1].iterations))
        self._iters["re"].append(
            float(result.trackers[self.re_name][-1].iterations_mean)
        )
        return {"units": 1, "ok": bool(np.isfinite(objective))}

    def array_shapes(self) -> Dict[str, List[int]]:
        shapes = {}
        for sid, sd in self.dataset.shards.items():
            shapes[f"shard.{sid}.indices"] = list(sd.indices.shape)
        for i, b in enumerate(self.red.buckets):
            shapes[f"bucket.{i}.indices"] = list(b.indices.shape)
        shapes["bank"] = [self.red.num_entities, self.red.local_dim]
        return shapes

    def work_per_unit(self) -> Dict[str, float]:
        """Needed work of one CD step: one value+gradient per fixed-effect
        iteration, one pass over the users' rows per bank iteration (the
        tracker's mean), and the two scoring passes."""
        d = self.data
        n = d.labels.shape[0]
        fe_entries = int(np.count_nonzero(d.fixed.values))
        re_entries = int(np.count_nonzero(d.user.values))
        bank = d.num_users * d.user.dim
        fe_it = float(np.mean(self._iters["fe"])) if self._iters["fe"] else 0.0
        re_it = float(np.mean(self._iters["re"])) if self._iters["re"] else 0.0
        return work.add(
            work.scale(work.glm_value_and_gradient(
                entries=fe_entries, rows=n, dim=d.fixed.dim), fe_it),
            work.scale(work.glm_value_and_gradient(
                entries=re_entries, rows=n, dim=bank), re_it),
            work.sparse_score(entries=fe_entries, rows=n, dim=d.fixed.dim),
            work.sparse_score(entries=re_entries, rows=n, dim=bank),
        )

    # -- after the window --------------------------------------------------

    def take_outputs(self) -> Dict:
        result = self.last
        model = result.model
        fixed_result = result.trackers[self.fe_name][-1]
        tracker = fixed_result.tracker
        count = int(tracker.count)
        out = {
            "fixed": np.asarray(_model_arrays(model.get_model(self.fe_name))),
            "bank": np.asarray(_model_arrays(model.get_model(self.re_name))),
            "objective": float(result.objective_history[-1]),
            "step_objectives": list(self.step_objectives),
            "fixed_values": np.asarray(tracker.values)[:count],
            "fixed_grad_norm": float(fixed_result.grad_norm),
            "rows": int(self.data.labels.shape[0]),
        }
        self.last = self.cd = self.dataset = self.red = None
        return out

    def _lambdas(self):
        return (
            float(self.combo[self.fe_name].reg_weight),
            float(self.combo[self.re_name].reg_weight),
        )

    def _fixed_problem(self, precision="f32", weights=None):
        d = self.data
        if weights is None and precision in self._problems:
            return self._problems[precision]  # its rows are on the device
        problem = reference.SparseProblem(
            d.fixed.indices, d.fixed.values, d.labels, d.fixed.dim,
            l2=self._lambdas()[0], weights=weights, precision=precision,
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

    def _by_user(self, a: np.ndarray) -> np.ndarray:
        """[n, ...] rows -> [users, rows_per_user, ...]."""
        d = self.data
        order = np.argsort(d.user_of_row, kind="stable")
        return a[order].reshape((d.num_users, -1) + a.shape[1:])

    def _reference_bank(self, fixed: np.ndarray, precision="f32") -> np.ndarray:
        """The users' solves under the residual ``fixed`` leaves."""
        d = self.data
        oc = self.combo[self.re_name].optimizer_config
        off = self._fixed_problem(precision).margins(fixed)
        return reference.solve_users(
            self._by_user(d.user.indices), self._by_user(d.user.values),
            self._by_user(d.labels), self._by_user(off),
            d.user.dim, self._lambdas()[1], max_iter=int(oc.max_iter),
            tol=float(oc.tolerance), precision=precision,
        )

    def _reference_objective(self, fixed, bank, precision="f32") -> float:
        d = self.data
        l_fe, l_re = self._lambdas()
        z = self._fixed_problem(precision).margins(fixed) + reference.user_scores(
            bank, d.user.indices, d.user.values, d.user_of_row, precision=precision
        )
        return (
            reference.logistic_total(z, d.labels)
            + 0.5 * l_fe * float(np.sum(fixed.astype(np.float64) ** 2))
            + 0.5 * l_re * float(np.sum(bank.astype(np.float64) ** 2))
        )

    def reference_outputs(self, precision: str = "f32", weights=None) -> Dict:
        """The reference put in the program's place (the control and the
        planted faults; never a benchmark run)."""
        trace = self._reference_fixed(precision, weights)
        fixed = trace.coefficients[-1]
        _, g = self._fixed_problem(precision, weights).value_and_gradient(fixed)
        bank = self._reference_bank(fixed, precision)
        objective = self._reference_objective(fixed, bank, precision)
        return {
            "fixed": fixed, "bank": bank, "objective": objective,
            "step_objectives": [objective],
            "fixed_values": np.asarray(trace.values, np.float32),
            "fixed_grad_norm": float(np.linalg.norm(np.asarray(g))),
            "rows": int(self.data.labels.shape[0]),
        }

    def check(self, out: Dict) -> Dict[str, float]:
        fixed, bank = out["fixed"], out["bank"]
        ref = self._reference_fixed()
        ref_fixed = ref.coefficients[-1]
        ref_bank = self._reference_bank(fixed)
        # the fixed effect by what it says and reaches, not coordinate by
        # coordinate: ten L-BFGS iterations do not repeat to the coordinate
        # once one line-search decision falls the other way (PERF.md section 2)
        prob = self._fixed_problem()
        reached, grad = prob.value_and_gradient(fixed)
        reached = float(reached)
        per_user = np.max(np.abs(bank - ref_bank), axis=1) / max(
            float(np.max(np.abs(ref_bank))), 1e-30
        )
        readings = {
            # the first step: the loss at zero and after -t * g(0)
            "fixed_first_gap": max(
                rel_gap(out["fixed_values"][i], ref.values[i]) for i in (0, 1)
            ),
            # what the solve says of its last iterate, against the reference
            # AT that iterate: the value, then the gradient's norm
            "fixed_value_gap": rel_gap(out["fixed_values"][-1], reached),
            "fixed_grad_gap": rel_gap(
                out["fixed_grad_norm"], float(np.linalg.norm(np.asarray(grad)))
            ),
            # it got at least as far down as the reference's solve did
            "fixed_descent_gap": max(
                0.0, (reached - ref.values[-1]) / abs(ref.values[-1])
            ),
            # the worst user, the median user, and all users together: a
            # user whose stopping test sits on its threshold may stop an
            # iteration apart, so the worst swings and the median does not
            "bank_median_gap": float(np.median(per_user)),
            "bank_rms_gap": float(
                np.linalg.norm((bank - ref_bank).astype(np.float64))
                / max(np.linalg.norm(ref_bank.astype(np.float64)), 1e-30)
            ),
            "objective_gap": rel_gap(
                out["objective"], self._reference_objective(fixed, bank)
            ),
            # every step of the window starts from zero on the same rows
            "repeat_gap": max(
                rel_gap(v, out["objective"]) for v in out["step_objectives"]
            ),
            # told, not judged: the worst user swings with the few users
            # whose search stalls, the fixed effect's coordinates with the path
            "fixed_gap": max_gap(fixed, ref_fixed),
            "bank_gap": max_gap(bank, ref_bank),
            "fixed_reached_gap": rel_gap(reached, ref.values[-1]),
            "bank_gap.p99": float(np.quantile(per_user, 0.99)),
        }
        for i in range(min(len(out["fixed_values"]), len(ref.values))):
            readings[f"fixed_loss_gap.{i}"] = rel_gap(
                out["fixed_values"][i], ref.values[i]
            )
        return readings


def _game_cd_unchanged(out: Dict) -> Dict:
    """Both coordinates return the zero model they were given."""
    new = dict(out)
    new["fixed"] = np.zeros_like(out["fixed"])
    new["bank"] = np.zeros_like(out["bank"])
    objective = float(out["rows"] * np.log(2.0))
    new["objective"] = objective
    new["step_objectives"] = [objective] * len(out["step_objectives"])
    new["fixed_values"] = np.full_like(out["fixed_values"], objective)
    new["fixed_grad_norm"] = 0.0  # not told by an unchanged state; reads 1
    return new


def _game_cd_altered(out: Dict) -> Dict:
    """The fixed effect's largest coefficient wrong."""
    new = dict(out)
    j = int(np.argmax(np.abs(out["fixed"])))
    new["fixed"] = out["fixed"].copy()
    new["fixed"][j] *= 1.0 + faults.ALTERED_BY
    return new


FAULTS = {"unchanged": _game_cd_unchanged, "altered": _game_cd_altered}


def setup(ctx) -> Cell:
    return Cell(ctx)
