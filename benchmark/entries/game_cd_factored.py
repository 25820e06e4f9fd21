"""Entry ``game_cd_factored``: whole coordinate-descent iterations of a
GLMix model whose per-user random effect is FACTORED: each user's model
is ``B gamma_u``, a learned ``[1000, L]`` projection shared by all users
times the user's ``L`` latent coefficients, trained by alternating the
users' latent solves and the fit of ``B`` (the driver's
``--factored-random-effect-optimization-configurations``).

Set-up is ``game_cd``'s (the rows from ``benchmark/data_factored.py``,
the program's ``GameDataset``, ``build_random_effect_dataset``,
``GameTrainingDriver._build_coordinates``, ONE ``CoordinateDescent``); a
step is ``run(1)`` from the initial model (zero fixed effect and latent
bank, the coordinate's own seeded starting ``B``), closed on every model
and the objective.

The check is stage by stage: the fixed effect's four numbers as
``game_cd`` reads them; then the factored coordinate against
``benchmark/reference_factored.py``'s alternation from the same starting
``B`` under the residual the PROGRAM's fixed effect leaves, through each
row's factored score ``z_i' B gamma_u``: ``B`` and ``gamma`` alone are
defined only up to ``B R``, ``R^-1 gamma`` and the scores are not; a user
the program left apart from the reference's last latent solve is held to
either stop of that solve (``game_cd_pod.either_stop``); the objective at
the program's model. ``B``'s and the bank's own gaps are told, not
judged.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmark import data_factored  # noqa: F401  registers the generator
from benchmark import reference, work, work_factored
from benchmark import reference_factored as ref
from benchmark.compare import rel_gap
from benchmark.entries import game_cd
from benchmark.entries.game_cd_pod import either_stop
from photon_ml_tpu.game.factored import latent_view


def _score_gaps(got: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    """Per-row scores: the median row's gap as a share of the largest
    reference score, and all rows together (norm of the difference over
    the norm); the worst row is told."""
    scale = max(float(np.max(np.abs(want))), 1e-30)
    per_row = np.abs(got - want).reshape(-1) / scale
    return {
        "score_median_gap": float(np.median(per_row)),
        "score_rms_gap": float(
            np.linalg.norm((got - want).astype(np.float64))
            / max(np.linalg.norm(want.astype(np.float64)), 1e-30)
        ),
        "score_gap": float(np.max(per_row)),
    }


class Cell(game_cd.Cell):
    def __init__(self, ctx):
        super().__init__(ctx)
        coord = self.cd.coordinates[self.re_name]
        self.fcfg = coord.config
        view = latent_view(self.red, self.fcfg.latent_space_dimension)
        blocks = coord.problem._solver_blocks(
            view, view.local_dim, split=True, staged=self.red.local_dim
        )
        self.schedule_shapes = {
            "buckets": [list(b.indices.shape) for b in self.red.buckets],
            "blocks": [list(b.bucket.indices.shape) for b in blocks],
            "block_kinds": [b.kind for b in blocks],
        }
        self._evals: List[int] = []
        self._factored = {}

    # -- the timed path ----------------------------------------------------

    def step(self) -> Dict:
        import jax

        result = self.cd.run(1)
        model = result.model
        fre = model.get_model(self.re_name)
        jax.block_until_ready([
            game_cd._model_arrays(model.get_model(self.fe_name)),
            fre.bank, fre.projection,
        ])
        self.last = result
        objective = float(result.objective_history[-1])
        self.step_objectives.append(objective)
        self._iters["fe"].append(int(result.trackers[self.fe_name][-1].iterations))
        tracker = result.trackers[self.re_name][-1]
        self._evals.append(sum(f["evaluations"] for f in tracker.projection))
        return {"units": 1, "ok": bool(np.isfinite(objective))}

    def array_shapes(self) -> Dict[str, List[int]]:
        shapes = super().array_shapes()
        L = self.fcfg.latent_space_dimension
        shapes["bank"] = [self.red.num_entities, L]
        shapes["projection"] = [self.red.local_dim, L]
        return shapes

    def _sizes(self) -> Dict[str, int]:
        d = self.data
        return dict(
            rows=int(d.labels.shape[0]), entries=int(d.user.indices.shape[1]),
            users=int(d.num_users), dim=int(d.user.dim),
            latent=int(self.fcfg.latent_space_dimension),
        )

    def projection_work(self) -> Dict[str, float]:
        """Needed work of a step's projection fits: two passes an
        evaluation (margins, gradient), the evaluations the last step's
        fits counted."""
        s = self._sizes()
        evals = float(np.mean(self._evals)) if self._evals else 0.0
        return work.scale(work_factored.projection_pass(
            rows=s["rows"], entries=s["entries"], latent=s["latent"]), 2.0 * evals)

    def latent_bank_work(self) -> Dict[str, float]:
        """Needed work of a step's latent bank updates: one a inner
        iteration."""
        s = self._sizes()
        return work.scale(work_factored.latent_update(
            users=s["users"], rows_per_user=s["rows"] // s["users"],
            entries=s["entries"], latent=s["latent"],
        ), float(self.fcfg.num_inner_iterations))

    def work_per_unit(self) -> Dict[str, float]:
        """The fixed effect's solve and scoring (``game_cd``'s), the
        latent bank updates, the projection fits and two factored
        scoring passes."""
        d = self.data
        n = d.labels.shape[0]
        fe_entries = int(np.count_nonzero(d.fixed.values))
        fe_it = float(np.mean(self._iters["fe"])) if self._iters["fe"] else 0.0
        s = self._sizes()
        return work.add(
            work.scale(work.glm_value_and_gradient(
                entries=fe_entries, rows=n, dim=d.fixed.dim), fe_it),
            work.sparse_score(entries=fe_entries, rows=n, dim=d.fixed.dim),
            self.latent_bank_work(),
            self.projection_work(),
            work.scale(work_factored.projection_pass(
                rows=s["rows"], entries=s["entries"], latent=s["latent"]), 2.0),
        )

    # -- after the window --------------------------------------------------

    def take_outputs(self) -> Dict:
        fre = self.last.model.get_model(self.re_name)
        projection = np.asarray(fre.projection)
        out = super().take_outputs()
        out["projection"] = projection
        return out

    def _user_problem(self, off, precision="f32", weights=None):
        d = self.data
        return ref.FactoredProblem(
            self._by_user(d.user.indices), self._by_user(d.user.values),
            self._by_user(d.labels), self._by_user(off), d.user.dim,
            weights=None if weights is None else self._by_user(weights),
            precision=precision,
        )

    def _reference_factored(self, fixed, precision="f32", weights=None):
        """The reference's alternation under the residual ``fixed``
        leaves, from the coordinate's starting ``B`` (drawn by the
        reference's own rule)."""
        key = (precision, weights is None, hash(fixed.tobytes()))
        if key in self._factored:
            return self._factored[key]
        oc = self.combo[self.re_name].optimizer_config
        l2 = self._lambdas()[1]
        prob = self._user_problem(
            self._fixed_problem(precision).margins(fixed), precision, weights
        )
        L = self.fcfg.latent_space_dimension
        fit = prob.fit(
            ref.starting_projection(self.data.user.dim, L),
            inner=self.fcfg.num_inner_iterations, l2=l2, l2_projection=l2,
            latent_max_iter=int(oc.max_iter), latent_tol=float(oc.tolerance),
            projection_max_iter=int(oc.max_iter),
            projection_tol=float(oc.tolerance),
            history=int(oc.lbfgs_history),
        )
        self._factored = {key: (prob, fit)}  # one at a time on the device
        return prob, fit

    def _held(self, prob, fit, bank):
        """The reference's latent bank with each user the program left
        elsewhere put on the nearest stop of the reference's own last
        latent solve that the stopping rule is indifferent to
        (``game_cd_pod.either_stop``: a user on a level overshoot), and
        the users found apart and held."""
        oc = self.combo[self.re_name].optimizer_config
        l2 = self._lambdas()[1]

        def solve(users, max_iter, tol):
            return prob.subset(users).solve_latent(
                fit.last_projection, fit.last_gamma[users], l2=l2,
                max_iter=max_iter, tol=tol,
            )

        return either_stop(
            fit.gamma, bank, prob.latent_rows(fit.last_projection), solve,
            l2, int(oc.max_iter), float(oc.tolerance),
        )

    def _objective_at(self, fixed, bank, projection, prob, precision="f32"):
        d = self.data
        l_fe, l_re = self._lambdas()
        z = self._fixed_problem(precision).margins(fixed) + self._by_row(
            prob.scores(projection, bank))
        return (
            reference.logistic_total(z, d.labels)
            + 0.5 * l_fe * float(np.sum(fixed.astype(np.float64) ** 2))
            + 0.5 * l_re * float(np.sum(bank.astype(np.float64) ** 2))
            + 0.5 * l_re * float(np.sum(projection.astype(np.float64) ** 2))
        )

    def _by_row(self, a: np.ndarray) -> np.ndarray:
        """[users, rows_per_user] -> [n] in the rows' order (the inverse
        of ``_by_user``)."""
        order = np.argsort(self.data.user_of_row, kind="stable")
        out = np.empty(order.shape[0], a.dtype)
        out[order] = a.reshape(-1)
        return out

    def reference_outputs(self, precision: str = "f32", weights=None) -> Dict:
        trace = self._reference_fixed(precision, weights)
        fixed = trace.coefficients[-1]
        _, g = self._fixed_problem(precision, weights).value_and_gradient(fixed)
        prob, fit = self._reference_factored(fixed, precision, weights)
        objective = self._objective_at(
            fixed, fit.gamma, fit.projection, prob, precision)
        return {
            "fixed": fixed, "bank": fit.gamma, "projection": fit.projection,
            "objective": objective, "step_objectives": [objective],
            "fixed_values": np.asarray(trace.values, np.float32),
            "fixed_grad_norm": float(np.linalg.norm(np.asarray(g))),
            "rows": int(self.data.labels.shape[0]),
        }

    def check(self, out: Dict) -> Dict[str, float]:
        fixed, bank, projection = out["fixed"], out["bank"], out["projection"]
        ref_fixed = self._reference_fixed()
        prob = self._fixed_problem()
        reached, grad = prob.value_and_gradient(fixed)
        reached = float(reached)
        user_prob, fit = self._reference_factored(fixed)
        gamma, apart, held = self._held(user_prob, fit, bank)
        ref_scores = (
            user_prob.scores(fit.projection, gamma) if held else fit.scores
        )
        readings = {
            "fixed_first_gap": max(
                rel_gap(out["fixed_values"][i], ref_fixed.values[i]) for i in (0, 1)
            ),
            "fixed_value_gap": rel_gap(out["fixed_values"][-1], reached),
            "fixed_grad_gap": rel_gap(
                out["fixed_grad_norm"], float(np.linalg.norm(np.asarray(grad)))
            ),
            "fixed_descent_gap": max(
                0.0, (reached - ref_fixed.values[-1]) / abs(ref_fixed.values[-1])
            ),
            **_score_gaps(user_prob.scores(projection, bank), ref_scores),
            "objective_gap": rel_gap(
                out["objective"],
                self._objective_at(fixed, bank, projection, user_prob),
            ),
            "repeat_gap": max(
                rel_gap(v, out["objective"]) for v in out["step_objectives"]
            ),
            # told, not judged: B and gamma are defined up to B R, R^-1 gamma
            "projection_gap": float(
                np.linalg.norm(projection - fit.projection)
                / max(np.linalg.norm(fit.projection), 1e-30)
            ),
            "bank_gap": float(
                np.linalg.norm(bank - fit.gamma)
                / max(np.linalg.norm(fit.gamma), 1e-30)
            ),
            "fixed_reached_gap": rel_gap(reached, ref_fixed.values[-1]),
            "latent_apart_users": apart,
            "latent_either_stop_users": held,
        }
        return readings


def _projection_unfitted(out: Dict) -> Dict:
    """The projection left where it started (the fit skipped), the latent
    bank as solved."""
    new = dict(out)
    new["projection"] = ref.starting_projection(*out["projection"].shape)
    return new


def _one_user_off(out: Dict) -> Dict:
    """The user with the largest latent row solved to twice its answer:
    apart from the reference, and on no stop of its path."""
    new = dict(out)
    u = int(np.argmax(np.linalg.norm(out["bank"], axis=1)))
    new["bank"] = out["bank"].copy()
    new["bank"][u] *= 2.0
    return new


FAULTS = {
    **game_cd.FAULTS, "projection_unfitted": _projection_unfitted,
    "one_user_off": _one_user_off,
}


def setup(ctx) -> Cell:
    return Cell(ctx)
