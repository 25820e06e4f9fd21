"""Entry ``game_cd_indexmap``: whole coordinate-descent iterations of GLMix
on the reference's default random-effect path: each member's model in its
own index map (``INDEX_MAP``), at most ``activeDataUpperBound`` active rows
a member drawn by the program's reservoir and weighted ``count / cap``, the
rest of a member's rows passive (scored, not trained).

Set-up is ``game_cd``'s: the rows in memory (``benchmark/data_indexmap.py``),
the program's plain ``GameDataset``, ``build_random_effect_dataset`` with
the cell's ``per-member:...,32,none,none,INDEX_MAP`` and
``GameTrainingDriver._build_coordinates``, ONE ``CoordinateDescent``; a step
is ``run(1)`` from the zero model, closed on the models and the objective.
The random effect's coordinate sits behind a proxy that keeps a reference
to the scores its last ``score`` returned (no copy, no sync): the scores
the timed step itself produced, which the check reads.

The check is stage by stage, each stage fed what the program itself
produced: the fixed effect as ``game_cd`` reads it, against the
reference's L-BFGS with its gradient summed in float64; then, on a sample of
the members that holds EVERY member over the cap and the rest drawn from
``shape_seed``, against ``benchmark/reference_indexmap.py``: the program's
active set held to the cap rule, each member's model in global feature
space (the program's bank through its projection, the reference's on its
own map) under the residual the PROGRAM's fixed effect leaves, a member
left on another stop of the same solve held as ``game_cd_pod.either_stop``
holds it; the scores of the sampled members' active rows and passive rows
apart; the objective at the program's model, every row.
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

from benchmark import data_indexmap  # noqa: F401  registers the generator
from benchmark import faults, reference, work, work_indexmap
from benchmark import reference_indexmap as ri
from benchmark.compare import max_gap, rel_gap
from benchmark.entries import game_cd
from benchmark.entries.game_cd_pod import APART, APART_USERS, INDIFFERENT

SAMPLED_MEMBERS = 32768


class _KeptScores:
    """A coordinate whose ``score`` keeps a reference to what it returned."""

    def __init__(self, inner):
        self.__dict__.update(_inner=inner, last_score=None)

    def __getattr__(self, attr):
        return getattr(self._inner, attr)

    def score(self, model):
        scores = self._inner.score(model)
        self.__dict__["last_score"] = scores
        return scores


def _rms_gap(got: np.ndarray, want: np.ndarray, extra: float = 0.0) -> float:
    """|got - want| over |want| (``extra``: squared mass ``got`` holds
    where ``want`` has no entry)."""
    diff = float(np.sum((got - want).astype(np.float64) ** 2)) + extra
    return float(np.sqrt(diff) / max(
        np.linalg.norm(want.astype(np.float64)), 1e-30))


def either_stop(ref, got, problem, solve, l2, max_iter, tol):
    """``game_cd_pod.either_stop`` for members on their own maps with a
    weight a row: ``ref`` [M, W] with the row of each member the program
    left more than ``APART`` of the largest coefficient away (at most
    ``APART_USERS`` of them) put on the stop of the same solve nearest the
    program's answer ``got``, where the stop rule is indifferent to it:
    the path's end with both stopping tests off, or a trial within
    ``INDIFFERENT * tol * |f0|`` of the iterate it started from.
    ``problem`` is :func:`reference_indexmap.member_rows`' for the
    members, ``solve(members, max_iter, tol)`` the reference's solve of
    some. Returns the reference, the members apart and those held."""
    scale = max(float(np.max(np.abs(ref))), 1e-30)
    apart = np.nonzero(np.max(np.abs(got - ref), axis=1) > APART * scale)[0]
    if not 0 < apart.size <= APART_USERS:
        return ref, int(apart.size), 0
    path = np.stack([solve(apart, k, -1.0) for k in range(max_iter + 1)])
    feats, ix, v, y, off, w = problem
    ref, held = ref.copy(), 0
    for n, m in enumerate(apart):
        pos = np.searchsorted(feats[m], ix[m])
        X = np.zeros((ix.shape[1], feats.shape[1]))
        np.add.at(X, (np.arange(ix.shape[1])[:, None], np.minimum(
            pos, feats.shape[1] - 1)), np.where(
                feats[m][np.minimum(pos, feats.shape[1] - 1)] == ix[m],
                v[m], 0.0))

        def objective(c, m=m, X=X):
            c = c.astype(np.float64)
            z = X @ c + off[m]
            return float(np.sum(w[m] * (
                np.maximum(z, 0.0) + np.log1p(np.exp(-np.abs(z))) - y[m] * z
            ))) + 0.5 * l2 * float(c @ c)

        band = INDIFFERENT * tol * abs(objective(path[0, n]))
        stops = [path[-1, n]]
        for before, after in zip(path[:-1, n], path[1:, n]):
            f_before = objective(before)
            for doubled in 2.0 ** np.arange(8):
                trial = before + np.float32(doubled) * (after - before)
                if abs(objective(trial) - f_before) <= band:
                    stops.append(trial)
        nearest = min(stops, key=lambda s: float(np.max(np.abs(got[m] - s))))
        if np.max(np.abs(got[m] - nearest)) <= APART * scale:
            ref[m], held = nearest, held + 1
    return ref, int(apart.size), held


class Cell(game_cd.Cell):
    def __init__(self, ctx):
        from photon_ml_tpu.cli import game_training_driver as gtd
        from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
        from photon_ml_tpu.game.data import EntityIndex, GameDataset, ShardData
        from photon_ml_tpu.game.random_effect_data import (
            build_random_effect_dataset,
        )
        from photon_ml_tpu.utils.index_map import IdentityIndexMap

        cfg, wl = ctx.config, ctx.workload
        self.wl, self.cfg = wl, cfg
        with ctx.span("bench.setup.generate"):
            d = data_indexmap.generate(cfg, ctx.seed)
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
        self.cap = int(re_cfg.active_data_upper_bound)
        ids = [f"member{u:07d}" for u in range(d.num_members)]
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
                    d.member.indices, d.member.values,
                    IdentityIndexMap(d.member.dim - 1, add_intercept=True),
                    d.member.intercept_index,
                ),
            },
            entity_codes={re_cfg.random_effect_type: d.member_of_row},
            entity_indexes={
                re_cfg.random_effect_type: EntityIndex.build(
                    re_cfg.random_effect_type, ids
                )
            },
            num_real_rows=n,
        )
        with ctx.span("bench.setup.re_dataset"):
            red = build_random_effect_dataset(dataset, re_cfg)
        combo = gtd.expand_config_grid(
            {**p.fixed_effect_opt_configs, **p.random_effect_opt_configs}
        )[0]
        self.combo = combo
        coords = self.driver._build_coordinates(dataset, {re_name: red}, combo)
        self.bucket_kinds = [
            coords[re_name].problem._bucket_kind(b, red.local_dim)
            for b in red.buckets
        ]
        self.schedule_shapes = {
            "buckets": [list(b.indices.shape) for b in red.buckets],
            "bucket_kinds": self.bucket_kinds,
            "local_dim": int(red.local_dim),
        }
        self.kept = coords[re_name] = _KeptScores(coords[re_name])
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
        # the program's active set, [members, capacity]: the rows its
        # buckets hold and their weights
        width = max(b.capacity for b in red.buckets)
        self.active = np.full((d.num_members, width), -1, np.int32)
        self.active_weights = np.zeros((d.num_members, width), np.float32)
        for b in red.buckets:
            self.active[b.entity_codes, :b.capacity] = b.row_index
            self.active_weights[b.entity_codes, :b.capacity] = b.weights
        self.local_dims = np.count_nonzero(red.projection >= 0, axis=1)
        self.row_entries = np.count_nonzero(d.member.values, axis=1)
        self.counts = np.bincount(d.member_of_row, minlength=d.num_members)
        over = np.nonzero(self.counts > self.cap)[0]
        under = np.nonzero(self.counts <= self.cap)[0]
        rng = np.random.default_rng(int(cfg["shape_seed"]))
        self.sample = np.sort(np.concatenate([
            over, rng.permutation(under)[:max(SAMPLED_MEMBERS - len(over), 0)],
        ]))

    # -- the timed path ----------------------------------------------------

    def step(self) -> Dict:
        import jax

        result = self.cd.run(1)
        model = result.model
        jax.block_until_ready([
            model.get_model(self.fe_name).model.coefficients.means,
            model.get_model(self.re_name).bank, self.kept.last_score,
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

    def bank_work(self) -> Dict[str, float]:
        """Needed work of a step's bank update: every member's dual Newton
        on its own rows and map (``benchmark/work_indexmap.py``), the
        iterations the step's solves took a member."""
        held = self.active >= 0
        entries = int(self.row_entries[self.active[held]].sum())
        re_it = float(np.mean(self._iters["re"])) if self._iters["re"] else 0.0
        return work_indexmap.bank_update(
            active=held.sum(axis=1), dims=self.local_dims, entries=entries,
            iterations=re_it,
        )

    def work_per_unit(self) -> Dict[str, float]:
        """Needed work of one CD step: the fixed effect's solve and
        scoring (``game_cd``'s), the bank update, and one scoring pass of
        the member model over every row, active and passive."""
        d = self.data
        n = d.labels.shape[0]
        fe_entries = int(np.count_nonzero(d.fixed.values))
        fe_it = float(np.mean(self._iters["fe"])) if self._iters["fe"] else 0.0
        return work.add(
            work.scale(work.glm_value_and_gradient(
                entries=fe_entries, rows=n, dim=d.fixed.dim), fe_it),
            work.sparse_score(entries=fe_entries, rows=n, dim=d.fixed.dim),
            self.bank_work(),
            work.sparse_score(
                entries=int(np.count_nonzero(d.member.values)), rows=n,
                dim=int(self.local_dims.sum())),
        )

    # -- after the window --------------------------------------------------

    def take_outputs(self) -> Dict:
        result = self.last
        model = result.model
        fixed_result = result.trackers[self.fe_name][-1]
        tracker = fixed_result.tracker
        count = int(tracker.count)
        bank = np.asarray(model.get_model(self.re_name).bank)
        feats, coefs = ri.global_form(self.red.projection, bank)
        out = {
            "fixed": np.asarray(model.get_model(self.fe_name).model.coefficients.means),
            "feats": feats,
            "coefs": coefs,
            "re_scores": np.asarray(self.kept.last_score),
            "objective": float(result.objective_history[-1]),
            "step_objectives": list(self.step_objectives),
            "fixed_values": np.asarray(tracker.values)[:count],
            "fixed_grad_norm": float(fixed_result.grad_norm),
            "rows": int(self.data.labels.shape[0]),
            "cell": self,
        }
        self.last = self.cd = self.dataset = self.red = self.kept = None
        return out

    def _fixed_problem(self, precision="f32", weights=None):
        """``game_cd``'s, its float32 gradient summed in float64
        (:class:`reference_indexmap.Float64Gradient`); the control's
        bfloat16 problem as it is."""
        problem = super()._fixed_problem(precision, weights)
        return ri.Float64Gradient(problem) if precision == "f32" else problem

    def _members_problem(self, fixed, members, precision="f32",
                         use_weights=True):
        """The sampled members' problems on their own maps under the
        residual ``fixed`` leaves (:func:`reference_indexmap.member_rows`)."""
        d = self.data
        off = self._fixed_problem(precision).margins(fixed)
        active = self.active[members]
        feats = ri.index_maps(
            active, d.member.indices, d.member.values, d.member.intercept_index)
        return ri.member_rows(
            feats, active, self.active_weights[members], d.member.indices,
            d.member.values, d.labels, off, use_weights=use_weights,
        )

    def _solve(self, problem, precision="f32", max_iter=None, tol=None):
        oc = self.combo[self.re_name].optimizer_config
        return ri.solve_members(
            problem, self._lambdas()[1],
            max_iter=int(oc.max_iter if max_iter is None else max_iter),
            tol=float(oc.tolerance if tol is None else tol), precision=precision,
        )

    def _rows_of(self, members):
        """The members' rows, their place among ``members``, and which of
        them are active."""
        d = self.data
        place = np.full(d.num_members, -1, np.int64)
        place[members] = np.arange(len(members))
        rows = np.nonzero(place[d.member_of_row] >= 0)[0]
        active = np.zeros(d.labels.shape[0], bool)
        held = self.active[members]
        active[held[held >= 0]] = True
        return rows, place[d.member_of_row[rows]], active[rows]

    def _reference_objective(self, fixed, feats, coefs, precision="f32") -> float:
        d = self.data
        l_fe, l_re = self._lambdas()
        z = self._fixed_problem(precision).margins(fixed) + ri.map_scores(
            feats, coefs, d.member_of_row, d.member.indices, d.member.values,
            precision=precision,
        )
        return (
            reference.logistic_total(z, d.labels)
            + 0.5 * l_fe * float(np.sum(fixed.astype(np.float64) ** 2))
            + 0.5 * l_re * float(np.sum(coefs.astype(np.float64) ** 2))
        )

    def reference_outputs(self, precision: str = "f32", weights=None,
                          use_weights: bool = True) -> Dict:
        """The reference put in the program's place (the controls and the
        planted faults; never a benchmark run): the sampled members'
        models, every other member's zero."""
        d = self.data
        trace = self._reference_fixed(precision, weights)
        fixed = trace.coefficients[-1]
        _, g = self._fixed_problem(precision, weights).value_and_gradient(fixed)
        problem = self._members_problem(
            fixed, self.sample, precision, use_weights)
        feats = np.full((d.num_members, problem[0].shape[1]), ri.PAD, np.int32)
        coefs = np.zeros(feats.shape, np.float32)
        feats[self.sample], coefs[self.sample] = problem[0], self._solve(
            problem, precision)
        objective = self._reference_objective(fixed, feats, coefs, precision)
        return {
            "fixed": fixed, "feats": feats, "coefs": coefs,
            "re_scores": ri.map_scores(
                feats, coefs, d.member_of_row, d.member.indices,
                d.member.values, precision=precision),
            "objective": objective, "step_objectives": [objective],
            "fixed_values": np.asarray(trace.values, np.float32),
            "fixed_grad_norm": float(np.linalg.norm(np.asarray(g))),
            "rows": int(d.labels.shape[0]), "cell": self,
        }

    def check(self, out: Dict) -> Dict[str, float]:
        d = self.data
        fixed = out["fixed"]
        ref = self._reference_fixed()
        prob = self._fixed_problem()
        reached, grad = prob.value_and_gradient(fixed)
        reached = float(reached)
        members = self.sample
        oc = self.combo[self.re_name].optimizer_config
        problem = self._members_problem(fixed, members)
        got = ri.lookup(out["feats"][members], out["coefs"][members], problem[0])
        held_sq = float(np.sum(got.astype(np.float64) ** 2))
        all_sq = float(np.sum(out["coefs"][members].astype(np.float64) ** 2))

        def solve(some, max_iter, tol):
            return self._solve(
                tuple(a[some] for a in problem), max_iter=max_iter, tol=tol)

        want, apart, held = either_stop(
            self._solve(problem), got, problem, solve, self._lambdas()[1],
            int(oc.max_iter), float(oc.tolerance),
        )
        per_member = np.max(np.abs(got - want), axis=1) / max(
            float(np.max(np.abs(want))), 1e-30)
        rows, place, is_active = self._rows_of(members)
        want_scores = ri.map_scores(
            problem[0], want, place, d.member.indices[rows],
            d.member.values[rows])
        got_scores = out["re_scores"][rows]
        readings = {
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
            # the program's active set against the cap rule: members off it
            "cap_rule_breaks": ri.cap_rule_breaks(
                members, self.active[members], self.active_weights[members],
                d.member_of_row, self.counts, self.cap,
            ),
            # the sampled members' models in global feature space; a
            # coefficient the program holds off the reference's map counts
            "bank_rms_gap": _rms_gap(got, want, max(all_sq - held_sq, 0.0)),
            "active_score_rms_gap": _rms_gap(
                got_scores[is_active], want_scores[is_active]),
            "passive_score_rms_gap": _rms_gap(
                got_scores[~is_active], want_scores[~is_active]),
            "objective_gap": rel_gap(
                out["objective"],
                self._reference_objective(fixed, out["feats"], out["coefs"]),
            ),
            "repeat_gap": max(
                rel_gap(v, out["objective"]) for v in out["step_objectives"]
            ),
            # told, not judged
            "fixed_gap": max_gap(fixed, ref.coefficients[-1]),
            "fixed_reached_gap": rel_gap(reached, ref.values[-1]),
            "bank_gap": float(np.max(per_member)),
            "bank_median_gap": float(np.median(per_member)),
            "bank_apart_members": apart,
            "bank_either_stop_members": held,
            "sampled_members": len(members),
            "sampled_over_cap": int(np.count_nonzero(self.counts[members] > self.cap)),
            "sampled_passive_rows": int(np.count_nonzero(~is_active)),
        }
        for i in range(min(len(out["fixed_values"]), len(ref.values))):
            readings[f"fixed_loss_gap.{i}"] = rel_gap(
                out["fixed_values"][i], ref.values[i]
            )
        return readings


def _unchanged(out: Dict) -> Dict:
    """Both coordinates return the zero model they were given."""
    new = dict(out)
    new["fixed"] = np.zeros_like(out["fixed"])
    new["coefs"] = np.zeros_like(out["coefs"])
    new["re_scores"] = np.zeros_like(out["re_scores"])
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


def _passive_full_row(out: Dict) -> Dict:
    """Passive rows scored with their full global row instead of through
    the member's map: an entry whose feature the map lacks keeps its
    value and reads the member's local slot 0 (the index a remap fills
    in), as a remap that forgot to drop it would."""
    cell = out["cell"]
    d = cell.data
    held = np.zeros(d.labels.shape[0], bool)
    held[cell.active[cell.active >= 0]] = True
    rows = np.nonzero(~held)[0]
    new = dict(out)
    new["re_scores"] = out["re_scores"].copy()
    new["re_scores"][rows] = ri.map_scores(
        out["feats"], out["coefs"], d.member_of_row[rows],
        d.member.indices[rows], d.member.values[rows],
        miss=out["coefs"][:, 0],
    )
    return new


def _cap_weight_dropped(out: Dict) -> Dict:
    """The sampled members' models solved with every active row weighted
    1, the cap's ``count / cap`` dropped, and the rows scored by them."""
    cell = out["cell"]
    d = cell.data
    problem = cell._members_problem(out["fixed"], cell.sample, use_weights=False)
    coefs = np.zeros((d.num_members, max(
        out["feats"].shape[1], problem[0].shape[1])), np.float32)
    feats = np.full(coefs.shape, ri.PAD, np.int32)
    feats[:, :out["feats"].shape[1]] = out["feats"]
    coefs[:, :out["coefs"].shape[1]] = out["coefs"]
    feats[cell.sample] = ri.PAD
    coefs[cell.sample] = 0.0
    feats[cell.sample, :problem[0].shape[1]] = problem[0]
    coefs[cell.sample, :problem[0].shape[1]] = cell._solve(problem)
    new = dict(out, feats=feats, coefs=coefs)
    new["re_scores"] = ri.map_scores(
        feats, coefs, d.member_of_row, d.member.indices, d.member.values)
    return new


FAULTS = {
    "unchanged": _unchanged,
    "altered": _altered,
    "passive_full_row": _passive_full_row,
    "cap_weight_dropped": _cap_weight_dropped,
}


def setup(ctx) -> Cell:
    return Cell(ctx)
