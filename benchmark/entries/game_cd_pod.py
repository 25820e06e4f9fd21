"""Entry ``game_cd_pod``: ``game_cd`` with the users' bank hash-partitioned
over the chips of one host (the driver's ``--entity-shards N``, which the
cell's ``driver_args`` carry).

The same step, the same eight judged numbers and the same unsharded
reference as ``game_cd``; what differs is what a sharded bank asks of the
harness:

- a step is closed on the bank's SHARDS. ``PodRandomEffectModel.bank`` is
  the export path: it gathers the whole bank onto every chip, which no
  training step does;
- the program's coordinates are never wrapped (a synced span around
  ``update_model`` would read that replicated bank): this cell's per-layer
  metrics read the program's own names;
- the needed work of a step is told per chip, so that the whole step's
  share is of all the chips' peaks;
- the bank's two numbers are judged on a sample of the users, drawn from
  the configuration's ``shape_seed`` in equal parts from every owner; the
  objective covers every user;
- a user whose solve comes to a trial that the stop rule is indifferent
  to is held to either stop (:func:`either_stop`). The fixed effect the
  solve reaches differs from seed to seed in its third digit (``fixed_gap``
  2.9e-3 to 4.2e-3 over this cell's runs), so every run solves the users
  under a slightly different residual and draws anew which of them sit on
  such a trial.
"""

from __future__ import annotations

import copy
from typing import Dict

import numpy as np

from benchmark import reference, work
from benchmark.entries import game_cd

FAULTS = game_cd.FAULTS
SAMPLED_USERS = 32768  # the one-chip cell's whole population
# A user is looked at again past this share of the bank's largest
# coefficient (the worst ordinary user reads 1e-3), and no more than this
# many are: more users apart than that is a fault, not a draw.
APART = 5e-3
APART_USERS = 8
INDIFFERENT = 2.0  # a trial within this many tolerances of where it started


def either_stop(ref, bank, rows, solve, l2, max_iter, tol):
    """``ref`` with the row of each user the program left elsewhere put
    on the stop of the same solve that lies nearest the program's answer.

    The configured solve takes the first of a Newton step's halves that
    does not raise the objective, and stops once a step moves the
    objective by ``tol * |f0|`` or less. Where a step overshoots and lands
    level with where it started, the same two tests send one float32
    evaluation on (it takes the half step and reaches the optimum) and
    stop another on the spot, most of the bank's scale apart, and both
    ran the configured algorithm. So such a user's reference is any
    stop of the reference's OWN path that the rule is indifferent to: the
    path's end with both stopping tests off, or a trial (an iterate, or
    the step to it doubled back up to the full step) whose objective, in
    float64, lies within ``INDIFFERENT * tol * |f0|`` of the iterate it
    started from. An answer that is neither stays as far off as it was.

    ``rows`` are the sampled users' (indices, values, labels, offsets);
    ``solve(users, max_iter, tol)`` is the reference's solve of some.
    Returns the reference, the users found apart and those held to a stop."""
    scale = max(float(np.max(np.abs(ref))), 1e-30)
    apart = np.nonzero(np.max(np.abs(bank - ref), axis=1) > APART * scale)[0]
    if not 0 < apart.size <= APART_USERS:
        return ref, int(apart.size), 0
    # iterate k of every such user, neither stopping test armed
    path = np.stack([solve(apart, k, -1.0) for k in range(max_iter + 1)])
    ref, held = ref.copy(), 0
    for n, u in enumerate(apart):
        ix = rows[0][u]
        v, y, off = (a[u].astype(np.float64) for a in rows[1:])

        def objective(c):
            c = c.astype(np.float64)
            z = np.sum(v * c[ix], axis=1) + off
            return reference.logistic_total(z, y) + 0.5 * l2 * float(c @ c)

        band = INDIFFERENT * tol * abs(objective(path[0, n]))
        stops = [path[-1, n]]
        for before, after in zip(path[:-1, n], path[1:, n]):
            f_before = objective(before)
            for doubled in 2.0 ** np.arange(8):
                trial = before + np.float32(doubled) * (after - before)
                if abs(objective(trial) - f_before) <= band:
                    stops.append(trial)
        nearest = min(stops, key=lambda s: float(np.max(np.abs(bank[u] - s))))
        if np.max(np.abs(bank[u] - nearest)) <= APART * scale:
            ref[u], held = nearest, held + 1
    return ref, int(apart.size), held


class Cell(game_cd.Cell):
    def __init__(self, ctx):
        plain = copy.copy(ctx)
        plain.traced = False
        super().__init__(plain)
        self.chips = int(self.driver.params.entity_shards)
        view = self.cd.coordinates[self.re_name].pod.pod_view(self.red)
        blocks = view.blocks
        self.schedule_shapes = {
            "buckets": self.schedule_shapes["buckets"],
            # one entry a solver block, each [chips * entities, capacity, k]
            "blocks": [list(b.ix.shape) for b in blocks],
            "block_kinds": [b.kind for b in blocks],
            "slots": int(view.router.num_slots),
        }
        rng = np.random.default_rng(int(ctx.config["shape_seed"]))
        users = np.arange(self.data.num_users)
        per_owner = -(-SAMPLED_USERS // self.chips)
        self.sample = np.sort(np.concatenate([
            rng.permutation(users[users % self.chips == o])[:per_owner]
            for o in range(self.chips)
        ]))
        self._whole_bank = None  # set while check() judges the sample

    # -- the timed path ----------------------------------------------------

    def step(self) -> Dict:
        import jax

        result = self.cd.run(1)
        model = result.model
        jax.block_until_ready([
            model.get_model(self.fe_name).model.coefficients.means,
            model.get_model(self.re_name).sharded_bank.data,
        ])
        self.last = result
        objective = float(result.objective_history[-1])
        self.step_objectives.append(objective)
        self._iters["fe"].append(int(result.trackers[self.fe_name][-1].iterations))
        self._iters["re"].append(
            float(result.trackers[self.re_name][-1].iterations_mean)
        )
        return {"units": 1, "ok": bool(np.isfinite(objective))}

    def work_per_unit(self) -> Dict[str, float]:
        """A chip's share of the step's needed work: ``step_mfu`` holds it
        against ONE chip's peaks over the step's wall."""
        return work.scale(super().work_per_unit(), 1.0 / self.chips)

    # -- after the window --------------------------------------------------

    def check(self, out: Dict) -> Dict[str, float]:
        self._whole_bank = out["bank"]
        try:
            readings = super().check(dict(out, bank=out["bank"][self.sample]))
        finally:
            self._whole_bank = None
        # told, not judged: sampled users far from the configured solve, and
        # those of them found on another stop of it
        readings.update(self._apart)
        return readings

    def _reference_bank(self, fixed: np.ndarray, precision="f32") -> np.ndarray:
        """Every user's solve for ``reference_outputs``; the sampled users'
        where ``check`` compares banks."""
        if self._whole_bank is None:
            return super()._reference_bank(fixed, precision)
        d = self.data
        oc = self.combo[self.re_name].optimizer_config
        off = self._fixed_problem(precision).margins(fixed)
        rows = tuple(
            self._by_user(a)[self.sample]
            for a in (d.user.indices, d.user.values, d.labels, off)
        )
        l2, max_iter, tol = self._lambdas()[1], int(oc.max_iter), float(oc.tolerance)

        def solve(users, max_iter, tol):
            return reference.solve_users(
                *(a[users] for a in rows), d.user.dim, l2,
                max_iter=max_iter, tol=tol, precision=precision,
            )

        ref, apart, held = either_stop(
            solve(slice(None), max_iter, tol), self._whole_bank[self.sample],
            rows, solve, l2, max_iter, tol,
        )
        self._apart = {"bank_apart_users": apart, "bank_either_stop_users": held}
        return ref

    def _reference_objective(self, fixed, bank, precision="f32") -> float:
        if self._whole_bank is not None:
            bank = self._whole_bank  # check() handed the sampled rows
        return super()._reference_objective(fixed, bank, precision)


def setup(ctx) -> Cell:
    return Cell(ctx)
