"""GLM objective kernels: value / gradient / Hessian-vector / Hessian-diagonal.

This is the TPU-native replacement for the reference's aggregator layer
(photon-ml .../function/ValueAndGradientAggregator.scala:133-250,
HessianVectorAggregator.scala:137-152, HessianDiagonalAggregator.scala) and
its Distributed/SingleNode objective wrappers
(DistributedGLMLossFunction.scala:63-136, SingleNodeGLMLossFunction.scala).

Design:
- One fused pass per evaluation: margins (gather or matmul) -> pointwise loss
  derivatives -> weighted reductions (scatter-add or matmul). XLA fuses the
  elementwise work into the reductions; no per-datum loop exists.
- Distribution is a *parameter*, not a subclass: if ``axis_name`` is set the
  per-shard partial sums are combined with ``jax.lax.psum`` — run the same
  method under ``shard_map`` over a mesh and it becomes the treeAggregate
  analog (partials ride ICI instead of netty).
- Normalization is applied algebraically via NormalizationContext (shift /
  factor), never materialized (reference trick, ValueAndGradientAggregator.
  scala:36-80).
- Objective semantics match the reference: total = sum_i weight_i * loss_i
  (no 1/n), L2 term = lambda/2 * ||w||^2 added once after the psum.
  L1 is NOT part of the objective — it lives in OWLQN (reference:
  function/L2Regularization.scala comment; OWLQN.scala).

Everything here is jit-, grad-, vmap- and shard_map-safe; ``l2_weight`` is a
dynamic argument so a whole regularization path reuses one compilation.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import (
    Batch,
    SparseBatch,
    sparse_dot,
    sparse_scatter_add,
)
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.normalization import NormalizationContext, identity_context

Array = jnp.ndarray


@dataclass(frozen=True)
class GLMObjective:
    """A (possibly distributed) weighted GLM objective over one batch type.

    Attributes:
      loss: pointwise loss kernel triple.
      dim: coefficient dimension.
      norm: normalization context (shift/factor), identity by default.
      axis_name: if set, reductions are psum'ed over this mesh axis
        (use inside shard_map / pjit with a sharded batch).
    """

    loss: PointwiseLoss
    dim: int
    norm: NormalizationContext = field(default_factory=identity_context)
    axis_name: Optional[str] = None

    # -- reductions --------------------------------------------------------

    def _psum(self, x):
        if self.axis_name is None:
            return x
        return jax.lax.psum(x, self.axis_name)

    # -- margins -----------------------------------------------------------

    def margins(self, coef: Array, batch: Batch) -> Array:
        """z_i = x_eff_i . w_eff + offset_i (normalized-space margin)."""
        with jax.named_scope("objective.margins"):
            w_eff = self.norm.effective_coefficients(coef)
            if isinstance(batch, SparseBatch):
                raw = sparse_dot(batch, w_eff)
            else:
                raw = batch.features @ w_eff
            return raw - self.norm.shift_dot(w_eff) + batch.offsets

    # -- scatter helpers ---------------------------------------------------

    def _weighted_feature_sum(self, batch: Batch, row_coef: Array) -> Array:
        """sum_i row_coef[i] * x_i  as a dense [dim] vector."""
        with jax.named_scope("objective.gradient"):
            if isinstance(batch, SparseBatch):
                return sparse_scatter_add(batch, row_coef, self.dim)
            return batch.features.T @ row_coef

    # -- value / gradient --------------------------------------------------

    def value(self, coef: Array, batch: Batch, l2_weight=0.0) -> Array:
        z = self.margins(coef, batch)
        with jax.named_scope("objective.loss"):
            val = jnp.sum(batch.weights * self.loss.value(z, batch.labels))
        val = self._psum(val)
        return val + 0.5 * l2_weight * jnp.dot(coef, coef)

    def value_and_gradient(
        self, coef: Array, batch: Batch, l2_weight=0.0
    ) -> Tuple[Array, Array]:
        """One fused pass for (value, gradient) — the LBFGS hot path.

        Accumulates the reference's three partials (valueSum, vectorSum,
        vectorShiftPrefactorSum), psums them, then un-shifts:
        grad = factor * (vectorSum - shift * prefactorSum) + lambda * w.
        """
        z = self.margins(coef, batch)
        with jax.named_scope("objective.loss"):
            lv = self.loss.value(z, batch.labels)
            ld = self.loss.d1(z, batch.labels)
            c = batch.weights * ld
            value_sum = jnp.sum(batch.weights * lv)
            prefactor_sum = jnp.sum(c)
        vector_sum = self._weighted_feature_sum(batch, c)
        value_sum, vector_sum, prefactor_sum = self._psum(
            (value_sum, vector_sum, prefactor_sum)
        )
        with jax.named_scope("objective.gradient"):
            grad = self.norm.unshift_gradient(vector_sum, prefactor_sum)
            value = value_sum + 0.5 * l2_weight * jnp.dot(coef, coef)
            grad = grad + l2_weight * coef
        return value, grad

    def gradient(self, coef: Array, batch: Batch, l2_weight=0.0) -> Array:
        return self.value_and_gradient(coef, batch, l2_weight)[1]

    # -- second order ------------------------------------------------------

    def hessian_vector(
        self, coef: Array, direction: Array, batch: Batch, l2_weight=0.0
    ) -> Array:
        """H(w) @ d, one psum round — the TRON/CG hot path.

        Mirrors HessianVectorAggregator.scala:137-152:
        Hv = factor * (sum_i w_i l''_i (x_eff_i . d_eff) x_i
                       - shift * sum_i w_i l''_i (x_eff_i . d_eff)) + lambda d
        """
        w_eff = self.norm.effective_coefficients(coef)
        d_eff = self.norm.effective_coefficients(direction)
        if isinstance(batch, SparseBatch):
            z_raw = sparse_dot(batch, w_eff)
            zd_raw = sparse_dot(batch, d_eff)
        else:
            z_raw = batch.features @ w_eff
            zd_raw = batch.features @ d_eff
        z = z_raw - self.norm.shift_dot(w_eff) + batch.offsets
        zd = zd_raw - self.norm.shift_dot(d_eff)
        c = batch.weights * self.loss.d2(z, batch.labels) * zd
        vector_sum = self._weighted_feature_sum(batch, c)
        prefactor_sum = jnp.sum(c)
        vector_sum, prefactor_sum = self._psum((vector_sum, prefactor_sum))
        hv = self.norm.unshift_gradient(vector_sum, prefactor_sum)
        return hv + l2_weight * direction

    def hessian_diagonal(self, coef: Array, batch: Batch, l2_weight=0.0) -> Array:
        """diag(H), used for per-coefficient variances 1/(Hdiag + eps)
        (reference: DistributedOptimizationProblem.scala:79-93,
        HessianDiagonalAggregator.scala).

        With x_eff = (x - shift) * factor:
          diag_j = factor_j^2 * ( S2_j - 2 shift_j S1_j + shift_j^2 S0 )
        where c_i = weight_i l''_i, S2 = sum c x^2, S1 = sum c x, S0 = sum c.
        All three accumulate sparsely.
        """
        z = self.margins(coef, batch)
        c = batch.weights * self.loss.d2(z, batch.labels)
        if isinstance(batch, SparseBatch):
            flat_ix = batch.indices.reshape(-1)
            cv = (batch.values * c[:, None]).reshape(-1)
            cv2 = (batch.values**2 * c[:, None]).reshape(-1)
            s1 = jnp.zeros((self.dim,), batch.values.dtype).at[flat_ix].add(cv)
            s2 = jnp.zeros((self.dim,), batch.values.dtype).at[flat_ix].add(cv2)
        else:
            s1 = batch.features.T @ c
            s2 = (batch.features**2).T @ c
        s0 = jnp.sum(c)
        s0, s1, s2 = self._psum((s0, s1, s2))
        diag = s2
        if self.norm.shift is not None:
            diag = diag - 2.0 * self.norm.shift * s1 + (self.norm.shift**2) * s0
        if self.norm.factor is not None:
            diag = diag * self.norm.factor**2
        return diag + l2_weight

    # -- convenience -------------------------------------------------------

    def with_axis(self, axis_name: Optional[str]) -> "GLMObjective":
        return GLMObjective(self.loss, self.dim, self.norm, axis_name)


# A pytree: the normalization vectors are leaves, everything else static
# aux. The objective then passes straight through jit as an ARGUMENT, so
# the module-level partial programs below (and any future jitted
# consumer) share ONE persistent compile cache across instances — two
# streaming objectives over the same chunk shape hit the same executable
# instead of each holding a private jit(lambda).
jax.tree_util.register_dataclass(
    GLMObjective,
    data_fields=["norm"],
    meta_fields=["loss", "dim", "axis_name"],
)


# -- shared per-chunk partial programs ---------------------------------------
#
# The streaming objectives (io/streaming.py, game/streaming.py) evaluate
# l2=0 partials chunk by chunk and fold on device; these module-level jits
# replace their constructor-time ``jit(lambda)``s: one compile cache for
# the whole process,
# keyed by jit on the objective's static structure + chunk shapes.


@jax.jit
def partial_value_and_gradient(objective, coef: Array, batch: Batch):
    """(value, gradient) at l2=0 — the streamed per-chunk partial."""
    return objective.value_and_gradient(coef, batch, 0.0)


@jax.jit
def partial_hessian_vector(
    objective, coef: Array, direction: Array, batch: Batch
):
    """H(w) @ d at l2=0 — the streamed per-chunk TRON/CG partial."""
    return objective.hessian_vector(coef, direction, batch, 0.0)


@jax.jit
def partial_hessian_diagonal(objective, coef: Array, batch: Batch):
    """diag(H) at l2=0 — the streamed per-chunk variance partial."""
    return objective.hessian_diagonal(coef, batch, 0.0)
