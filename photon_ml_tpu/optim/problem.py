"""GLM optimization problems: optimizer + objective + model construction.

Reference: photon-ml .../optimization/GeneralizedLinearOptimizationProblem.
scala (run at :112-121, coefficient de-normalization at :89-95),
DistributedOptimizationProblem.scala (variance computation 1/(Hdiag+eps) at
:79-93, updateRegularizationWeight at :59-70, runWithSampling at :112-124)
and SingleNodeOptimizationProblem.scala.

The Distributed/SingleNode split disappears on TPU: the same problem object
runs single-chip or under shard_map depending on the objective's
``axis_name``; "single node" per-entity solves are the vmapped variant
(photon_ml_tpu.game.random_effect).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax.numpy as jnp

from photon_ml_tpu.data.batch import Batch
from photon_ml_tpu.data.sampler import down_sample
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.glm import GeneralizedLinearModel, create_model
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.normalization import NormalizationContext, identity_context
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim.common import BoxConstraints, OptResult
from photon_ml_tpu.optim.config import (
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
)
from photon_ml_tpu.optim.factory import make_optimizer
from photon_ml_tpu.task import TaskType

Array = jnp.ndarray

# Reference adds a small epsilon when inverting the Hessian diagonal
# (DistributedOptimizationProblem.scala:79-93).
_VARIANCE_EPSILON = 1e-12

# Jitted fit programs shared by equal problems (see _get_fit);
# FIFO-bounded so long-lived processes constructing many distinct
# problems don't pin executables forever.
_FIT_CACHE: dict = {}
_FIT_CACHE_MAX = 32


def _row_axis(mesh) -> str:
    """The mesh axis example rows shard over: the data axis when the
    mesh has one; on the unified (grid, entity) mesh rows ride the
    entity axis (the pod row convention — residual currency and the
    two-hop exchange stay entity-aligned); else the first axis."""
    from photon_ml_tpu.parallel.mesh import DATA_AXIS, ENTITY_AXIS

    names = tuple(mesh.axis_names)
    if DATA_AXIS in names:
        return DATA_AXIS
    if ENTITY_AXIS in names:
        return ENTITY_AXIS
    return names[0]


@dataclass(frozen=True)
class GLMOptimizationProblem:
    """One (task, optimizer, regularization) training problem over a
    coefficient dimension. Reusable across a whole lambda grid: the
    regularization weight is a runtime argument."""

    task: "TaskType"
    objective: GLMObjective
    config: OptimizerConfig = field(default_factory=OptimizerConfig)
    regularization: RegularizationContext = field(
        default_factory=RegularizationContext
    )
    compute_variances: bool = False
    box: Optional[BoxConstraints] = None
    intercept_index: Optional[int] = None

    def _l1_mask(self) -> Optional[Array]:
        if self.intercept_index is None:
            return None
        return jnp.ones((self.objective.dim,)).at[self.intercept_index].set(0.0)

    # photon: entropy(id(mesh)-keyed jit-program memo; in-memory only)
    def _get_fit(self, track_models: bool, mesh=None, axis: str = "",
                 grid: bool = False, with_offsets: bool = False):
        """``(fit, cache_hit)``: the jitted fit program (optionally
        shard_mapped over ``mesh``), cached so repeat `run`/`run_grid`
        calls skip re-tracing the optimizer while_loop.

        Tracing the L-BFGS while_loop over the tiled objective costs
        seconds of host time (the schedules are ~16.7M-entry pytrees);
        without caching EVERY `run` call pays it — once per lambda-grid
        entry per driver stage, and once per coordinate-descent iteration
        in GAME. Cache key: the problem's config tuple (module-level, so
        equal problems share; FIFO-bounded) with an instance-local
        fallback when a field (e.g. box-constraint arrays) is unhashable.
        reg weights stay TRACED arguments, so a whole lambda grid is one
        compile. The cache entry pins the mesh so an id-recycled mesh
        cannot alias a stale program.

        ``grid`` builds the GRID variant: ``fit(w0_bank, batch, l1_vec,
        l2_vec)`` runs ``vmap(minimize_lbfgs/owlqn/tron)`` over a [G, d]
        coefficient bank — the whole λ grid as ONE XLA program (1
        compile, 1 optimizer loop, 1 dispatch for G solves). Per-member
        convergence is active-masked by the batched ``lax.while_loop``
        itself: jax's batching rule selects each member's carry only
        while its own cond holds, so a converged λ's state
        (coefficients, reason, tracker) is frozen bit-stable while the
        loop runs on for the stragglers, and the loop exits when all G
        are done. The objective's data pass evaluates the whole bank
        fused: the scatter objective batches into one
        (n×d)@(d×G)-shaped gather/contract under vmap, and the tiled
        objective's Pallas passes swap in the flat fused grid pass via
        custom_vmap (ops.tiled_sparse._bilinear_pass_auto) — one
        schedule walk for the whole grid. With ``with_offsets`` the
        grid program takes a fifth [G, n] per-member offsets bank
        (row-sharded under a mesh) and each member solves against
        ``batch._replace(offsets=...)`` — the unified-mesh GAME trainer's
        residual currency.
        """
        import jax

        key = (
            "grid" if grid else "fit",
            with_offsets,
            self.objective,
            self.config,
            self.regularization,
            self.box,
            self.intercept_index,
            track_models,
            id(mesh) if mesh is not None else None,
            axis,
        )
        try:
            hash(key)
            cache = _FIT_CACHE
        except TypeError:
            if "_local_fit_cache" not in self.__dict__:
                object.__setattr__(self, "_local_fit_cache", {})
            cache = self._local_fit_cache
            key = (
                "grid" if grid else "fit", with_offsets, track_models,
                id(mesh) if mesh is not None else None, axis,
            )
        hit = cache.get(key)
        if hit is not None:
            return hit[0], True
        optimize = make_optimizer(
            self.config,
            self.regularization,
            loss_has_hessian=self.objective.loss.has_hessian,
            box=self.box,
            l1_mask=self._l1_mask(),
            track_coefficients=track_models,
        )
        needs_hvp = self.config.optimizer_type == OptimizerType.TRON
        objective = (
            self.objective if mesh is None else self.objective.with_axis(axis)
        )

        def solve_one(w0, batch, l1, l2):
            def vg(w):
                return objective.value_and_gradient(w, batch, l2)

            def hvp(w, d):
                return objective.hessian_vector(w, d, batch, l2)

            return optimize(
                vg, w0, l1_weight=l1, hvp_fn=hvp if needs_hvp else None
            )

        if not grid:
            fit = solve_one
        elif with_offsets:

            def fit(w0_bank, batch, l1_vec, l2_vec, off_bank):
                def run_one(w0, l1, l2, off):
                    return solve_one(
                        w0, batch._replace(offsets=off), l1, l2
                    )

                return jax.vmap(run_one)(w0_bank, l1_vec, l2_vec, off_bank)

        else:

            def fit(w0_bank, batch, l1_vec, l2_vec):
                def run_one(w0, l1, l2):
                    return solve_one(w0, batch, l1, l2)

                return jax.vmap(run_one)(w0_bank, l1_vec, l2_vec)

        if mesh is not None:
            from functools import partial as _partial

            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            in_specs = (P(), P(axis), P(), P())
            if grid and with_offsets:
                in_specs = in_specs + (P(None, axis),)
            # photon: sharding(axes=[data], in=?, out=[r])
            fit = _partial(
                shard_map,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=P(),
                check_vma=False,
            )(fit)
        # the name of the function handed to jax.jit names the XLA module
        fit.__name__ = "glm_fit_grid" if grid else "glm_fit"
        fit = jax.jit(fit)

        while len(cache) >= _FIT_CACHE_MAX:
            cache.pop(next(iter(cache)))
        cache[key] = (fit, mesh)
        return fit, False

    # photon: entropy(id(mesh)-keyed jit-program memo; in-memory only)
    def _get_hdiag(self, mesh=None, axis: str = "", grid: bool = False,
                   with_offsets: bool = False):
        """Jitted Hessian-diagonal pass (variance computation), cached
        like :meth:`_get_fit` — one builder for all four call sites
        (single/grid × replicated/sharded). Grid signature:
        ``hdiag(w_bank, batch, l2_vec[, off_bank])``."""
        import jax

        key = (
            "hdiag", grid, with_offsets, self.objective,
            id(mesh) if mesh is not None else None, axis,
        )
        try:
            hash(key)
            cache = _FIT_CACHE
        except TypeError:
            if "_local_fit_cache" not in self.__dict__:
                object.__setattr__(self, "_local_fit_cache", {})
            cache = self._local_fit_cache
            key = (
                "hdiag", grid, with_offsets,
                id(mesh) if mesh is not None else None, axis,
            )
        hit = cache.get(key)
        if hit is not None:
            return hit[0]
        objective = (
            self.objective if mesh is None else self.objective.with_axis(axis)
        )

        def one(w, batch, l2):
            return objective.hessian_diagonal(w, batch, l2)

        if not grid:
            hdiag = one
        elif with_offsets:

            def hdiag(w_bank, batch, l2_vec, off_bank):
                return jax.vmap(
                    lambda w, l2, off: one(
                        w, batch._replace(offsets=off), l2
                    )
                )(w_bank, l2_vec, off_bank)

        else:

            def hdiag(w_bank, batch, l2_vec):
                return jax.vmap(lambda w, l2: one(w, batch, l2))(
                    w_bank, l2_vec
                )

        if mesh is not None:
            from functools import partial as _partial

            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            in_specs = (P(), P(axis), P())
            if grid and with_offsets:
                in_specs = in_specs + (P(None, axis),)
            # photon: sharding(axes=[data], in=?, out=[r])
            hdiag = _partial(
                shard_map,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=P(),
                check_vma=False,
            )(hdiag)
        hdiag = jax.jit(hdiag)

        while len(cache) >= _FIT_CACHE_MAX:
            cache.pop(next(iter(cache)))
        cache[key] = (hdiag, mesh)
        return hdiag

    def run_grid(
        self,
        batch: Batch,
        reg_weights,
        initial: Optional[Array] = None,
        mesh=None,
        track_models: bool = False,
        offsets_bank: Optional[Array] = None,
    ):
        """Solve the whole λ grid in ONE batched program.

        ``reg_weights`` is the (deduplicated, ordered) λ sequence;
        ``initial`` is either a [d] vector broadcast to every member or a
        [G, d] bank. Returns ``(variances_bank, OptResult)`` where every
        OptResult field carries a leading grid axis (slice i belongs to
        reg_weights[i]); ``variances_bank`` is None unless
        ``compute_variances`` (the Hdiag pass is a second program — the
        1-compile contract covers the fit itself).

        ``offsets_bank`` ([G, n]) gives each member its OWN row offsets
        (``batch.offsets`` is ignored): the unified-mesh GAME trainer's
        per-member residual currency, where member g's fixed effect
        solves against base offsets + its own residual. Columns short of
        the (padded) batch row count are zero-extended.

        Unlike :meth:`run` driven sequentially, members do NOT warm-start
        from each other — every λ starts from ``initial`` (see the README
        "Regularization paths" discussion of when that trade wins).
        """
        weights = [float(w) for w in reg_weights]
        G = len(weights)
        splits = [self.regularization.split(w) for w in weights]
        l1_vec = jnp.asarray([s[0] for s in splits], jnp.float32)
        l2_vec = jnp.asarray([s[1] for s in splits], jnp.float32)
        if initial is None:
            w0_bank = jnp.zeros((G, self.objective.dim), jnp.float32)
        else:
            w0 = jnp.asarray(initial, jnp.float32)
            w0_bank = (
                w0 if w0.ndim == 2 else jnp.broadcast_to(
                    w0, (G, self.objective.dim)
                )
            )
        with_offsets = offsets_bank is not None

        def _pad_offsets(rows: int) -> Array:
            off = jnp.asarray(offsets_bank, jnp.float32)
            if off.shape[1] < rows:
                off = jnp.concatenate(
                    [off, jnp.zeros((off.shape[0], rows - off.shape[1]),
                                    jnp.float32)],
                    axis=1,
                )
            return off

        if mesh is None:
            from photon_ml_tpu.data.batch import SparseBatch
            from photon_ml_tpu.ops.tiled_sparse import (
                TiledGLMObjective,
                ensure_tiled,
            )

            if isinstance(self.objective, TiledGLMObjective) and isinstance(
                batch, SparseBatch
            ):
                batch = ensure_tiled(batch, self.objective.dim)
            fit, _ = self._get_fit(
                track_models, grid=True, with_offsets=with_offsets
            )
            extras = (
                (_pad_offsets(int(batch.offsets.shape[0])),)
                if with_offsets else ()
            )
            result = fit(w0_bank, batch, l1_vec, l2_vec, *extras)
            variances = None
            if self.compute_variances:
                hdiag = self._get_hdiag(
                    grid=True, with_offsets=with_offsets
                )(result.coefficients, batch, l2_vec, *extras)
                variances = 1.0 / (hdiag + _VARIANCE_EPSILON)
            return variances, result

        from photon_ml_tpu.parallel.mesh import ensure_data_sharded

        axis = _row_axis(mesh)
        from photon_ml_tpu.ops.tiled_sparse import (
            TiledGLMObjective,
            ensure_tiled_sharded,
        )

        if isinstance(self.objective, TiledGLMObjective):
            sharded = ensure_tiled_sharded(batch, self.objective.dim, mesh, axis)
        else:
            sharded = ensure_data_sharded(batch, mesh, axis)
        fit, _ = self._get_fit(
            track_models, mesh=mesh, axis=axis, grid=True,
            with_offsets=with_offsets,
        )
        extras = ()
        if with_offsets:
            from jax import device_put
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            off = _pad_offsets(int(sharded.offsets.shape[0]))
            extras = (
                device_put(off, NamedSharding(mesh, P(None, axis))),
            )
        result = fit(w0_bank, sharded, l1_vec, l2_vec, *extras)
        variances = None
        if self.compute_variances:
            hdiag = self._get_hdiag(
                mesh=mesh, axis=axis, grid=True, with_offsets=with_offsets
            )(result.coefficients, sharded, l2_vec, *extras)
            variances = 1.0 / (hdiag + _VARIANCE_EPSILON)
        return variances, result

    def run(
        self,
        batch: Batch,
        initial: Optional[Array] = None,
        reg_weight: float = 0.0,
        mesh=None,
        track_models: bool = False,
    ) -> Tuple[Coefficients, OptResult]:
        """Optimize and build coefficients (+ variances if requested).

        ``track_models`` stacks the coefficient vector per iteration into
        ``result.tracker.coefs`` (the ModelTracker analog backing
        validate-per-iteration, Driver.scala:329-372).

        Mirrors GeneralizedLinearOptimizationProblem.run:112-121.

        With ``mesh`` set, the ENTIRE optimize loop runs inside one
        shard_map program: the batch is row-padded and sharded over the
        mesh's "data" axis, coefficients are replicated, and the
        objective psums its partials — the treeAggregate analog
        (ValueAndGradientAggregator.scala:235-250), but with per-iteration
        reductions riding ICI instead of one cluster round-trip per Breeze
        evaluation.
        """
        from photon_ml_tpu.obs.trace import span
        from photon_ml_tpu.ops.tiled_sparse import (
            TiledGLMObjective,
            ensure_tiled,
            ensure_tiled_sharded,
        )

        tiled = isinstance(self.objective, TiledGLMObjective)
        l1, l2 = self.regularization.split(reg_weight)
        axis = _row_axis(mesh) if mesh is not None else ""
        with span("fit.prepare"):
            w0 = (
                jnp.zeros((self.objective.dim,), jnp.float32)
                if initial is None
                else jnp.asarray(initial)
            )
            if mesh is None:
                from photon_ml_tpu.data.batch import SparseBatch

                if tiled and isinstance(batch, SparseBatch):
                    # identity-cached conversion: a CD loop re-wrapping the
                    # same columns with fresh offsets reuses the schedules
                    batch = ensure_tiled(batch, self.objective.dim)
            elif tiled:
                # fast kernel AND mesh together: per-shard tiled schedules
                # (ValueAndGradientAggregator.scala:235-250 runs distributed
                # at full speed; so do we — no scatter fallback)
                batch = ensure_tiled_sharded(
                    batch, self.objective.dim, mesh, axis
                )
            else:
                from photon_ml_tpu.parallel.mesh import ensure_data_sharded

                batch = ensure_data_sharded(batch, mesh, axis)
        with span(
            "fit.dispatch", kernel="tiled" if tiled else "scatter"
        ) as sp:
            fit, hit = self._get_fit(track_models, mesh=mesh, axis=axis)
            sp.set(fit_cache_hit=hit)
            result = fit(w0, batch, jnp.float32(l1), jnp.float32(l2))

        variances = None
        if self.compute_variances:
            if mesh is None:
                hdiag = self.objective.hessian_diagonal(
                    result.coefficients, batch, l2
                )
            else:
                hdiag = self._get_hdiag(mesh=mesh, axis=axis)(
                    result.coefficients, batch, jnp.float32(l2)
                )
            variances = 1.0 / (hdiag + _VARIANCE_EPSILON)
        return Coefficients(result.coefficients, variances), result

    def run_with_sampling(
        self,
        batch: Batch,
        key: Array,
        down_sampling_rate: float,
        initial: Optional[Array] = None,
        reg_weight: float = 0.0,
        mesh=None,
        track_models: bool = False,
    ) -> Tuple[Coefficients, OptResult]:
        """Apply the task's down-sampler first (runWithSampling:112-124)."""
        if down_sampling_rate < 1.0:
            batch = down_sample(key, batch, down_sampling_rate, self.task)
        return self.run(
            batch, initial, reg_weight, mesh=mesh, track_models=track_models
        )

    def create_model(
        self,
        coefficients: Coefficients,
        norm: Optional[NormalizationContext] = None,
    ) -> GeneralizedLinearModel:
        """Build the model, de-normalizing coefficients back to the raw
        feature space (GeneralizedLinearOptimizationProblem.scala:89-95)."""
        norm = norm if norm is not None else identity_context()
        if not norm.is_identity:
            means = norm.model_to_original_space(coefficients.means)
            if self.intercept_index is not None:
                # The intercept absorbs -shift.(factor*w'); its own slot has
                # factor 1 / shift 0 by construction in build_normalization.
                means = means.at[self.intercept_index].add(
                    norm.intercept_adjustment(coefficients.means)
                )
            coefficients = Coefficients(means, coefficients.variances)
        return create_model(self.task, coefficients)


def resolve_kernel(kernel: str, batch=None) -> str:
    """Resolve the objective-kernel choice: "scatter" | "tiled" | "auto".

    "auto" picks the tiled Pallas kernel pair when running on TPU with
    sparse data (PERF_LEDGER.jsonl, PR 28, `glmix-ads-100m.cd`:
    `cd_fe_eval_ms` 589.13 on the scatter objective, 115.33 on the
    kernels); the kernels are Mosaic (TPU-only), so every other backend —
    CPU, GPU — gets scatter. An already tiled batch is "tiled" on any
    platform: only the tiled objective can read one (on the CPU it
    interprets the kernels).
    """
    if kernel not in ("auto", "tiled", "scatter"):
        raise ValueError(
            f"unknown kernel {kernel!r}; expected auto | tiled | scatter"
        )
    if kernel != "auto":
        return kernel
    from photon_ml_tpu.data.batch import SparseBatch
    from photon_ml_tpu.ops.tiled_sparse import TiledSparseBatch
    from photon_ml_tpu.utils.backend import effective_platform

    if isinstance(batch, TiledSparseBatch):
        return "tiled"
    on_tpu = effective_platform() == "tpu"
    sparse_ok = batch is None or isinstance(batch, SparseBatch)
    return "tiled" if (on_tpu and sparse_ok) else "scatter"


def create_glm_problem(
    task,
    dim: int,
    *,
    config: Optional[OptimizerConfig] = None,
    regularization: Optional[RegularizationContext] = None,
    norm: Optional[NormalizationContext] = None,
    axis_name: Optional[str] = None,
    compute_variances: bool = False,
    box: Optional[BoxConstraints] = None,
    intercept_index: Optional[int] = None,
    kernel: str = "scatter",
) -> GLMOptimizationProblem:
    """Convenience factory mirroring DistributedGLMLossFunction.create +
    DistributedOptimizationProblem.create (ModelTraining.scala:123-169).

    ``kernel`` selects the objective implementation: "scatter" (gather/
    scatter GLMObjective, any Batch type) or "tiled" (TiledGLMObjective
    over a TiledSparseBatch — see photon_ml_tpu.ops.tiled_sparse). Both
    share the same method contract, so the rest of the problem layer is
    agnostic.
    """
    norm_ctx = norm if norm is not None else identity_context()
    if kernel == "tiled":
        from photon_ml_tpu.ops.tiled_sparse import TiledGLMObjective
        from photon_ml_tpu.utils.backend import effective_platform

        # Mosaic kernels cannot lower to CPU: an explicit tiled request
        # there runs in interpret mode (slow, for tests/debugging).
        objective = TiledGLMObjective(
            loss_for_task(task), dim, norm_ctx, axis_name,
            interpret=effective_platform() == "cpu",
        )
    else:
        objective = GLMObjective(
            loss_for_task(task), dim, norm_ctx, axis_name
        )
    return GLMOptimizationProblem(
        task=task,
        objective=objective,
        config=config if config is not None else OptimizerConfig(),
        regularization=(
            regularization if regularization is not None else RegularizationContext()
        ),
        compute_variances=compute_variances,
        box=box,
        intercept_index=intercept_index,
    )
