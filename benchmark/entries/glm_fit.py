"""Entry ``glm_fit``: whole fits of ``GLMDriver.train()``.

Set-up makes the rows in memory (``benchmark/data.py``), tiles them ONCE
with the program's own ``tiled_batch_from_sparse`` and hands the driver a
``LoadedData`` that holds the tiled batch; only ``preprocess()``'s ingest
is bypassed. A step is one ``train()`` call from the zero model, closed by
``block_until_ready`` on the coefficients, and counts the optimizer
iterations the program reports.

The check is of what the LAST timed fit produced, from the program's own
per-iteration record (values, gradient norms and coefficients, kept by
``--validate-per-iteration``), against the plain reference's own L-BFGS
from zero: the first gradient and the first iterate (the gradient pass at
zero), the second iterate (the gradient pass at a non-zero model and the
first use of the L-BFGS memory), the value and the gradient norm the fit
reports at its last iterate against the reference's objective THERE (the
margin pass and the margin-to-gradient coupling at the model the fit ends
on), and how far down its 15 iterations got against the reference's
``check_iterations`` (one-sided: the loop kept descending). From the third
iterate on two float32 paths part for good (PERF.md section 7), so later
coordinates are told and not judged.
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

from benchmark import data as bench_data
from benchmark import faults, reference, work
from benchmark.compare import max_gap, rel_gap


JUDGED_ITERATES = 2  # iterates compared coordinate by coordinate


class Cell:
    def __init__(self, ctx):
        from photon_ml_tpu.cli import glm_driver
        from photon_ml_tpu.data.batch import SparseBatch
        from photon_ml_tpu.io.input_format import LoadedData
        from photon_ml_tpu.ops.tiled_sparse import tiled_batch_from_sparse
        from photon_ml_tpu.utils.index_map import IdentityIndexMap

        cfg, wl = ctx.config, ctx.workload
        self.wl = wl
        with ctx.span("bench.setup.generate"):
            self.data = bench_data.generate(cfg, ctx.seed)
        rows = self.data.rows
        n = rows.indices.shape[0]
        out_dir = os.path.join(ctx.work_dir, "driver-out")
        self.driver = glm_driver.GLMDriver(glm_driver.params_from_args(
            list(wl["driver_args"]) + [
                "--training-data-directory", os.path.join(ctx.work_dir, "unused"),
                "--validating-data-directory", os.path.join(ctx.work_dir, "unused"),
                "--output-directory", out_dir,
                "--delete-output-dirs-if-exist", "true",
            ]
        ))
        self.lam = float(self.driver.params.regularization_weights[0])
        host_batch = SparseBatch(
            indices=rows.indices, values=rows.values, labels=self.data.labels,
            offsets=np.zeros(n, np.float32), weights=self._row_weights(n),
        )
        with ctx.span("bench.setup.tile"):
            batch = tiled_batch_from_sparse(host_batch, rows.dim)
        self.schedule_shapes = {
            "z_steps": int(batch.z_sched.num_steps),
            "g_steps": int(batch.g_sched.num_steps),
            "chunk": int(batch.params.chunk),
            "z_spill": int(batch.z_sched.spill_vals.shape[0]),
            "g_spill": int(batch.g_sched.spill_vals.shape[0]),
        }
        self.driver._data = LoadedData(
            batch=batch,
            index_map=IdentityIndexMap(rows.dim - 1, add_intercept=True),
            num_features=rows.dim,
            intercept_index=rows.intercept_index,
        )
        self.last = None
        self.step_values: List[float] = []  # every step's final value
        self._reference = None

    @staticmethod
    def _row_weights(n: int) -> np.ndarray:
        """The weights the PROGRAM's rows get: all ones. (The seam where
        ``benchmark/tests`` leaves half of the batch out.)"""
        return np.ones(n, np.float32)

    # -- the timed path ----------------------------------------------------

    def step(self) -> Dict:
        import jax

        self.driver.train()
        result = self.driver.results[self.lam]
        means = self.driver.models[self.lam].coefficients.means
        jax.block_until_ready((means, result))
        self.last = (means, result)
        iterations = int(result.iterations)
        value = float(result.value)
        self.step_values.append(value)
        return {"units": iterations, "ok": bool(np.isfinite(value)) and iterations > 0}

    def array_shapes(self) -> Dict[str, List[int]]:
        """Shapes of every device array the timed path holds."""
        import jax

        leaves = jax.tree_util.tree_flatten_with_path(self.driver._data.batch)[0]
        return {
            jax.tree_util.keystr(p): list(np.shape(a)) for p, a in leaves
        }

    def work_per_unit(self) -> Dict[str, float]:
        """Needed work of one optimizer iteration: one value+gradient (the
        kernel's margin launch and gradient launch together)."""
        rows = self.data.rows
        nnz = int(np.count_nonzero(rows.values))
        return work.glm_value_and_gradient(
            entries=nnz, rows=rows.indices.shape[0], dim=rows.dim
        )

    # -- after the window --------------------------------------------------

    def take_outputs(self) -> Dict:
        """Host copies of what the last timed fit produced; then the
        program's device state is dropped so the reference has room."""
        means, result = self.last
        count = int(result.tracker.count)
        out = {
            "final": np.asarray(means),
            "final_value": float(result.value),
            "final_grad_norm": float(result.grad_norm),
            "iterations": int(result.iterations),
            "values": np.asarray(result.tracker.values)[:count],
            "grad_norms": np.asarray(result.tracker.grad_norms)[:count],
            "coefs": np.asarray(result.tracker.coefs[: JUDGED_ITERATES + 1]),
            "step_values": list(self.step_values),
        }
        self.last = None
        self.driver._data = None
        self.driver.models, self.driver.results = {}, {}
        return out

    def reference_problem(self, precision: str = "f32", weights=None):
        rows = self.data.rows
        return reference.SparseProblem(
            rows.indices, rows.values, self.data.labels, rows.dim,
            l2=self.lam, weights=weights, precision=precision,
        )

    def _reference_lbfgs(self, prob) -> reference.LbfgsTrace:
        """The reference's first ``check_iterations`` iterations from zero."""
        return reference.lbfgs(
            prob.value_and_gradient, np.zeros(prob.dim, np.float32),
            max_iter=int(self.wl["check_iterations"]),
            tol=float(self.driver.params.tolerance),
        )

    def reference_outputs(self, precision: str = "f32", weights=None) -> Dict:
        """The reference put in the program's place: the same record a
        fit leaves, from the reference's own L-BFGS (used by the control
        and the planted faults; never by a benchmark run)."""
        prob = self.reference_problem(precision, weights)
        tr = self._reference_lbfgs(prob)
        f, g = prob.value_and_gradient(tr.coefficients[-1])
        return {
            "final": tr.coefficients[-1],
            "final_value": float(f),
            "final_grad_norm": float(np.linalg.norm(np.asarray(g))),
            "iterations": len(tr.values) - 1,
            "values": np.asarray(tr.values, np.float32),
            "grad_norms": np.asarray(tr.grad_norms, np.float32),
            "coefs": np.stack(tr.coefficients[: JUDGED_ITERATES + 1]),
            "step_values": [float(f)],
        }

    def check(self, out: Dict) -> Dict[str, float]:
        """name -> reading; the limits live in the workload file."""
        if self._reference is None:  # the same whatever the outputs are
            prob = self.reference_problem()
            self._reference = prob, self._reference_lbfgs(prob)
        prob, ref = self._reference
        f, g = prob.value_and_gradient(out["final"])
        by_iteration = [
            max_gap(out["coefs"][i], ref.coefficients[i])
            for i in range(1, JUDGED_ITERATES + 1)
        ]
        readings = {
            "first_grad_gap": rel_gap(out["grad_norms"][0], ref.grad_norms[0]),
            # the first iterate is -t * g(0): the kernel's gradient pass read
            # coordinate by coordinate, before a later iteration amplifies it
            "first_step_gap": by_iteration[0],
            # the second iterate: the gradient pass at a non-zero model, its
            # difference from g(0) and the first use of the L-BFGS memory
            "second_step_gap": by_iteration[1],
            # what the fit says of its last iterate, against the reference AT
            # that iterate: the margin pass and the loss, then the gradient
            # those margins give
            "final_value_gap": rel_gap(out["final_value"], float(f)),
            "final_grad_gap": rel_gap(
                out["final_grad_norm"], float(np.linalg.norm(np.asarray(g)))
            ),
            # the whole fit got at least as far down as the reference's own
            # L-BFGS does in ``check_iterations``
            "descent_gap": max(
                0.0, (out["final_value"] - ref.values[-1]) / abs(ref.values[-1])
            ),
            # every fit of the window starts from zero on the same rows
            "repeat_gap": max(
                rel_gap(v, out["final_value"]) for v in out["step_values"]
            ),
        }
        # told, not judged: the path iteration by iteration (from the third
        # iterate on two float32 paths part for good, PERF.md section 7)
        for i in range(1, min(len(out["values"]), len(ref.values))):
            readings[f"loss_gap.{i}"] = rel_gap(out["values"][i], ref.values[i])
            readings[f"grad_norm_gap.{i}"] = rel_gap(
                out["grad_norms"][i], ref.grad_norms[i]
            )
        readings["reference_descent"] = float(ref.values[-1] / ref.values[0])
        readings["fit_descent"] = float(out["final_value"] / ref.values[0])
        return readings


def _glm_fit_unchanged(out: Dict) -> Dict:
    """Every iteration returns the state it was given: the zero model."""
    new = dict(out)
    new["final"] = np.zeros_like(out["final"])
    new["coefs"] = np.zeros_like(out["coefs"])
    new["values"] = np.full_like(out["values"], out["values"][0])
    new["grad_norms"] = np.full_like(out["grad_norms"], out["grad_norms"][0])
    new["final_value"] = float(out["values"][0])
    new["final_grad_norm"] = float(out["grad_norms"][0])
    new["step_values"] = [float(out["values"][0])] * len(out["step_values"])
    return new


def _glm_fit_stalled(out: Dict) -> Dict:
    """The loop stops moving after its second iteration and goes on
    reporting that state."""
    new = dict(out)
    k = JUDGED_ITERATES
    new["final"] = out["coefs"][k].copy()
    new["final_value"] = float(out["values"][k])
    new["final_grad_norm"] = float(out["grad_norms"][k])
    new["values"] = np.concatenate(
        [out["values"][: k + 1], np.full_like(out["values"][k + 1 :], out["values"][k])])
    new["grad_norms"] = np.concatenate(
        [out["grad_norms"][: k + 1],
         np.full_like(out["grad_norms"][k + 1 :], out["grad_norms"][k])])
    new["step_values"] = [float(out["values"][k])] * len(out["step_values"])
    return new


def _glm_fit_altered(out: Dict) -> Dict:
    """One coefficient wrong from the first iteration on."""
    new = dict(out)
    j = int(np.argmax(np.abs(out["coefs"][1])))
    new["coefs"] = out["coefs"].copy()
    new["coefs"][1:, j] *= 1.0 + faults.ALTERED_BY
    new["final"] = out["final"].copy()
    new["final"][j] *= 1.0 + faults.ALTERED_BY
    return new


FAULTS = {
    "unchanged": _glm_fit_unchanged, "stalled": _glm_fit_stalled,
    "altered": _glm_fit_altered,
}


def setup(ctx) -> Cell:
    return Cell(ctx)
