"""GAME coordinates: fixed effect, random effect, factored RE, MF.

Reference: photon-ml .../algorithm/Coordinate.scala:82 (score /
initializeModel / updateModel / regTerm over its dataset),
FixedEffectCoordinate.scala:137-164, RandomEffectCoordinate.scala:104-199,
RandomEffectCoordinateInProjectedSpace.scala:30-140,
FactoredRandomEffectCoordinate.scala:99-289 (alternating latent-space RE
solves and a distributed projection-matrix fit).

The KeyValueScore residual currency is a row-aligned [n] array here; every
``updateModel(model, partialScore)`` first folds the residual into offsets
(dataSet.addScoresToOffsets analog) by passing ``offsets + residual``.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, replace
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.batch import SparseBatch
from photon_ml_tpu.game import factored
from photon_ml_tpu.game.config import FactoredRandomEffectConfiguration
from photon_ml_tpu.game.data import GameDataset
from photon_ml_tpu.game.model import (
    DatumScoringModel,
    FixedEffectModel,
    MatrixFactorizationModel,
    RandomEffectModel,
)
from photon_ml_tpu.game.random_effect import (
    RandomEffectOptimizationProblem,
    ValuesOverride,
    score_kernel_name,
    score_places,
    score_plan,
    score_random_effect,
    slots_by_path,
)
from photon_ml_tpu.game.random_effect_data import RandomEffectDataset
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.glm import compute_scores, create_model
from photon_ml_tpu.obs.registry import default_registry
from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.optim.problem import GLMOptimizationProblem

Array = jnp.ndarray


class Coordinate:
    """One block of the coordinate descent (Coordinate.scala)."""

    name: str

    def initialize_model(self) -> DatumScoringModel:
        raise NotImplementedError

    def update_model(
        self, model: DatumScoringModel, residual: Optional[Array]
    ) -> Tuple[DatumScoringModel, object]:
        raise NotImplementedError

    def score(self, model: DatumScoringModel) -> Array:
        raise NotImplementedError

    def regularization_term(self, model: DatumScoringModel) -> float:
        raise NotImplementedError

    def regularization_term_device(self, model: DatumScoringModel) -> Array:
        """The reg term as a DEVICE scalar: the CD loop sums these into
        its one batched readback per iteration (parallel/overlap) instead
        of pulling 1-2 host floats per coordinate. Default falls back to
        the host implementation for coordinate types with no device
        expression."""
        return jnp.float32(self.regularization_term(model))

    def prepare(self, model: Optional[DatumScoringModel] = None) -> None:
        """Host-side staging for this coordinate's NEXT update (device
        transfers, layout builds, AOT warming) — idempotent, and safe to
        run on a background thread while another coordinate's solves
        occupy the device (overlap prefetched dispatch). Default: no-op."""


@partial(jax.jit, static_argnames=("mesh", "axis"))
def fe_score(means: Array, batch, objective=None, *, mesh=None, axis=None) -> Array:
    """The fixed effect's scoring pass as one named program (module
    ``fe_score``, scope ``cd.score``) a device trace can place:
    ``compute_scores``' numbers in dataset row order, by one of two
    implementations.

    Given a ``SparseBatch`` it is the gather (``compute_scores``). Given
    the coordinate's ``TiledSparseBatch`` and its ``TiledGLMObjective`` it
    rides the margin pass (``objective.scores``: kernel
    ``photon_tiled_margin`` plus the spilled entries and a dense column's
    side term) and cuts the padded row space back to the dataset's rows;
    both tiled layouts keep global
    row r at position r and pad only at the end. Under ``mesh`` the pass
    runs under ``shard_map`` over ``axis``, each device over its own
    schedule with no ``psum``, and the vector leaves this program
    replicated over the mesh: the layout the CD score algebra carries
    (what the pod's scores have and its ``route_in`` takes)."""
    with jax.named_scope("cd.score"):
        if objective is None:
            return compute_scores(means, batch)
        rows = batch.meta.num_real_rows
        if mesh is None:
            return objective.scores(means, batch)[:rows]
        from jax.sharding import PartitionSpec as P

        from photon_ml_tpu.parallel.mesh import replicated

        # photon: sharding(axes=[data], in=[r,data], out=[r])
        padded = jax.shard_map(
            objective.scores, mesh=mesh, in_specs=(P(), P(axis)),
            out_specs=P(axis), check_vma=False,
        )(means, batch)
        # (gathered whole, then cut: the cut of a row-sharded vector at a
        # row count the mesh does not divide is a halo exchange)
        return jax.lax.with_sharding_constraint(
            padded, replicated(mesh)
        )[:rows]


# One build of a coordinate's tile schedules at a time: ``score()`` on the
# main thread and a prefetched ``prepare()`` on the worker may both ask for
# them; the first builds, the other waits and finds them.
_TILED_BUILD_LOCK = threading.Lock()


@dataclass
class FixedEffectCoordinate(Coordinate):
    """Global GLM block (FixedEffectCoordinate.scala:137-164).

    With a 2-D (data, model) ``mesh`` the solve runs FEATURE-SHARDED:
    the coefficient vector splits over the model axis and the existing
    sparse/tiled feature-sharded fits (incl. TRON) run inside the GAME
    coordinate descent — the reference's whole scale story is the GAME
    fixed effect at huge dimension (treeAggregate depth valve at >=200k
    features, cli/game/training/Driver.scala:357-363,717-719; "hundreds
    of billions of coefficients", README.md:73). The sharded layout is
    built once and reused across CD iterations — only the row vectors a
    sweep changes (offsets, the residual currency, and the down-sampling
    draw's weights) are re-placed per update.

    A problem built on the tiled objective (``kernel == "tiled"``: what
    the GAME driver resolves on a TPU) follows the same rule on one
    device and on the data-parallel mesh: the coordinate holds the shard
    as a ``TiledSparseBatch``, its schedules built once (by whichever of
    ``prepare()``, ``score()`` and the first update comes first), and
    every update swaps in the row vectors on the device. Such a coordinate
    SCORES on the margin kernel too (``fe_score`` over the same tiled
    batch, row-sharded under the mesh), as long as the schedules hold
    every entry that scores: ``_sparse_coo`` builds weight-0 rows out, so
    a shard with features on such a row keeps the gather over the
    dataset's ``SparseBatch``, as the scatter objective and the
    feature-sharded (data, model) mesh do. ``score_kernel`` says which.
    """

    name: str
    dataset: GameDataset
    problem: GLMOptimizationProblem
    feature_shard_id: str
    reg_weight: float = 0.0
    down_sampling_rate: float = 1.0
    sampler_seed: int = 0
    # data-parallel mesh for the global solve (FixedEffectCoordinate runs
    # distributed by construction in the reference; None = single device).
    # A mesh carrying a "model" axis selects the feature-sharded solve.
    mesh: Optional[object] = None

    def initialize_model(self) -> FixedEffectModel:
        dim = self.dataset.shards[self.feature_shard_id].dim
        return FixedEffectModel(
            create_model(self.problem.task, Coefficients.zeros(dim)),
            self.feature_shard_id,
        )

    def _batch(self, residual: Optional[Array]) -> SparseBatch:
        offsets = self.dataset.offsets
        if residual is not None:
            # residual algebra stays on device (SURVEY §7.9: KeyValueScore
            # is a device-resident [n] array; no host round trip)
            offsets = jnp.asarray(offsets) + residual
        return self.dataset.batch_for_shard(self.feature_shard_id, offsets)

    def _is_feature_sharded(self) -> bool:
        from photon_ml_tpu.parallel.mesh import MODEL_AXIS

        return (
            self.mesh is not None
            and MODEL_AXIS in getattr(self.mesh, "axis_names", ())
        )

    @property
    def kernel(self) -> str:
        """Which objective the solves run, "tiled" | "scatter": what
        ``fit.dispatch`` reports, for ``cd.update`` and the CD log line."""
        from photon_ml_tpu.ops.tiled_sparse import TiledGLMObjective

        tiled = isinstance(self.problem.objective, TiledGLMObjective)
        return "tiled" if tiled else "scatter"

    @property
    def mxu(self) -> Optional[str]:
        """The tiled kernel's MXU variant (``TiledGLMObjective.mxu``), None
        on the scatter objective: beside ``kernel`` on ``cd.update`` and
        ``cd.score``."""
        return getattr(self.problem.objective, "mxu", None)

    def _tiled_base(self):
        """The shard as a ``TiledSparseBatch`` (mesh layout under a data
        mesh), held on the coordinate: a driver with more fixed-effect
        shards than ``ensure_tiled``'s LRU has entries must not rebuild
        its schedules every sweep. The LRU behind it is keyed on the
        dataset's cached device columns, so the coordinates a combo grid
        builds afresh share one build."""
        base = self.__dict__.get("_tiled")
        if base is not None:
            return base
        from photon_ml_tpu.ops.tiled_sparse import (
            bucket_spill,
            ensure_tiled,
            ensure_tiled_sharded,
        )
        from photon_ml_tpu.optim.problem import _row_axis

        with _TILED_BUILD_LOCK:
            base = self.__dict__.get("_tiled")
            if base is not None:  # built while this thread waited
                return base
            sparse = self.dataset.batch_for_shard(self.feature_shard_id)
            dim = self.problem.objective.dim
            if self.mesh is None:
                # (bucketed: a refit on the same rows in another order
                # finds its compiled solve in the persistent cache)
                base = bucket_spill(ensure_tiled(sparse, dim))
            else:
                base = ensure_tiled_sharded(
                    sparse, dim, self.mesh, _row_axis(self.mesh)
                )
            # noted once, with the build: does the tiled batch hold every
            # entry that scores? (the build drops weight-0 rows; one with
            # no feature scores 0 either way, as the row padding does. A
            # dense column's entries are held: beside the schedules)
            dead = ~(np.asarray(self.dataset.weights) > 0)
            values = self.dataset.shards[self.feature_shard_id].values
            self.__dict__["_tiled_scores"] = not (
                dead.any() and np.any(np.asarray(values)[dead])
            )
            self.__dict__["_tiled"] = base
        return base

    def _scoring_batch(self):
        """The tiled batch when scoring rides the margin kernel, None when
        it takes the gather: decided on the objective's type, the mesh's
        axes and whether the schedules hold every entry that scores."""
        if self.kernel != "tiled" or self._is_feature_sharded():
            return None
        base = self._tiled_base()
        return base if self.__dict__["_tiled_scores"] else None

    @property
    def score_kernel(self) -> str:
        """How ``score()`` computes, "tiled" | "gather": for ``cd.score``
        and ``photon_fe_scores_total``."""
        return "gather" if self._scoring_batch() is None else "tiled"

    def _refresh_rows(self, cached, row_sharding, residual):
        """``cached`` (a tiled or feature-sharded layout) with this
        update's row vectors: offsets, the residual currency, and, when
        down-sampling, the draw's weights, padded to the layout's rows and
        placed ON THE DEVICE (the residual never visits the host). The
        schedules and entry routing only depend on indices, values and the
        BUILD-time weight mask; a sampled weight only ever ZEROES a row
        that was live at build time (inert through c = w * l'(z)), never
        revives a built-out one, so the cached layout stays exact under
        every draw."""
        from photon_ml_tpu.ops.tiled_sparse import pad_row_vector

        rows_total = cached.labels.shape[0]

        def _place_rows(vec):
            vec = pad_row_vector(vec, rows_total)
            if row_sharding is None:
                return vec
            return jax.device_put(vec, row_sharding)

        # the dataset's cached device copies of its row columns
        rows = self.dataset.batch_for_shard(self.feature_shard_id)
        offsets = rows.offsets
        if residual is not None:
            offsets = offsets + residual
        out = cached._replace(offsets=_place_rows(offsets))
        if self.down_sampling_rate < 1.0:
            # Down-sampling is pure row re-weighting (data/sampler.py):
            # the per-draw weights ride the SAME re-pad-and-place path as
            # the residual offsets — traced arguments against the cached
            # layout, so the entry routing, tile schedules and compiled
            # fit all survive sampling (padding rows keep weight 0 and
            # stay inert). Same PRNG key and row count as the scatter
            # path's ``down_sample``, so the draws agree row for row.
            from photon_ml_tpu.data.sampler import down_sample_weights

            w_new = down_sample_weights(
                jax.random.PRNGKey(self.sampler_seed),
                rows.labels,
                rows.weights,
                self.down_sampling_rate,
                self.problem.task,
            )
            out = out._replace(weights=_place_rows(w_new))
        return out

    def _tiled_batch(self, residual):
        base = self._tiled_base()
        return self._refresh_rows(
            base, base.labels.sharding if self.mesh is not None else None,
            residual,
        )

    def update_model(self, model, residual=None):
        if self._is_feature_sharded():
            return self._update_model_feature_sharded(model, residual)
        initial = model.model.means if model is not None else None
        if self.kernel == "tiled":
            # the draw, if any, is already in the batch's row weights
            coefficients, result = self.problem.run(
                self._tiled_batch(residual), initial=initial,
                reg_weight=self.reg_weight, mesh=self.mesh,
            )
        elif self.down_sampling_rate < 1.0:
            coefficients, result = self.problem.run_with_sampling(
                self._batch(residual),
                jax.random.PRNGKey(self.sampler_seed),
                self.down_sampling_rate,
                initial=initial,
                reg_weight=self.reg_weight,
                mesh=self.mesh,
            )
        else:
            coefficients, result = self.problem.run(
                self._batch(residual), initial=initial,
                reg_weight=self.reg_weight, mesh=self.mesh,
            )
        return (
            FixedEffectModel(
                self.problem.create_model(coefficients), self.feature_shard_id
            ),
            result,
        )

    # -- feature-sharded solve (2-D mesh) ----------------------------------

    def _feature_sharded_state(self):
        """Build-once layout + jitted fit for the (data, model) mesh.

        The sharded batch STRUCTURE (entry routing, tile schedules) only
        depends on indices/values and the BUILD-time weight mask — fixed
        across CD iterations — so it is cached on the coordinate; per
        update only the row vectors (offsets — the residual currency —
        and, when down-sampling, the draw's weights) are re-padded and
        re-placed. A sampled weight only ever ZEROES a row that was live
        at build time (inert through c = w * l'(z)), never revives a
        built-out one, so the cached layout stays exact under every
        draw."""
        state = self.__dict__.get("_fs_state")
        if state is not None:
            return state
        from photon_ml_tpu.ops.tiled_sparse import (
            TiledGLMObjective,
            feature_shard_tiled_batch,
        )
        from photon_ml_tpu.optim.config import OptimizerType
        from photon_ml_tpu.parallel import overlap
        from photon_ml_tpu.parallel.distributed import (
            feature_shard_sparse_batch,
            feature_sharded_glm_fit,
            feature_sharded_hessian_diagonal,
        )
        from photon_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

        problem = self.problem
        dim = self.dataset.shards[self.feature_shard_id].dim
        data_shards = int(self.mesh.shape[DATA_AXIS])
        model_shards = int(self.mesh.shape[MODEL_AXIS])
        tiled = isinstance(problem.objective, TiledGLMObjective)
        # The LAYOUT only depends on the shard + mesh CONTENT + kernel,
        # not on the optimizer config — cache it on the dataset so a grid
        # of combos (each building fresh coordinates AND a fresh,
        # content-identical mesh) pays the multi-second re-layout once,
        # like batch_for_shard's device cache on the replicated path.
        # Keyed by mesh content (axes + device ids), not object identity:
        # shardings over content-equal meshes are interchangeable. The
        # sparse layout never touches the mesh, so its key omits it.
        # Bounded to ONE entry per feature shard: a sweep that varies the
        # mesh shape or kernel must not accumulate device-pinned layouts.
        layout_cache = self.dataset.__dict__.setdefault(
            "_fs_layout_cache", {}
        )
        mesh_key = (
            (
                tuple(self.mesh.axis_names),
                tuple(int(n) for n in self.mesh.devices.shape),
                tuple(d.id for d in self.mesh.devices.flat),
            )
            if tiled
            else None
        )
        layout_key = (
            self.feature_shard_id, data_shards, model_shards, tiled,
            mesh_key,
        )
        hit = layout_cache.get(layout_key)
        if hit is not None:
            sharded, block_dim, meta, layout = hit
        else:
            base = self.dataset.batch_for_shard(self.feature_shard_id)
            # counted seam: a one-time layout-build fetch, but still a
            # device->host round trip the discipline tests should see
            host = overlap.device_get(base)
            if tiled:
                sharded, block_dim = feature_shard_tiled_batch(
                    host, dim, data_shards, model_shards, mesh=self.mesh
                )
                meta, layout = sharded.meta, "tiled"
            else:
                sharded, block_dim = feature_shard_sparse_batch(
                    host, dim, model_shards, rows_multiple=data_shards
                )
                meta, layout = None, "sparse"
            for k in [
                k for k in layout_cache if k[0] == self.feature_shard_id
            ]:
                del layout_cache[k]
            layout_cache[layout_key] = (sharded, block_dim, meta, layout)
        use_tron = problem.config.optimizer_type == OptimizerType.TRON
        use_owlqn = problem.regularization.has_l1
        norm = problem.objective.norm
        d_pad = model_shards * block_dim
        from photon_ml_tpu.parallel.distributed import (
            feature_sharded_extras,
        )

        extras_tail, l1_mask, with_norm = feature_sharded_extras(
            dim, d_pad, normalization=norm, box=problem.box,
            use_owlqn=use_owlqn, intercept_index=problem.intercept_index,
        )
        fit = feature_sharded_glm_fit(
            problem.objective, self.mesh, meta, layout=layout,
            optimizer=(
                "tron" if use_tron else ("owlqn" if use_owlqn else "lbfgs")
            ),
            max_iter=problem.config.max_iter,
            tol=problem.config.tolerance,
            history=problem.config.lbfgs_history,
            max_cg=problem.config.tron_max_cg,
            with_norm=with_norm, with_box=problem.box is not None,
        )
        hdiag = None
        if problem.compute_variances:
            hdiag = feature_sharded_hessian_diagonal(
                problem.objective, self.mesh, meta, layout=layout,
                with_norm=with_norm,
            )
        state = dict(
            sharded=sharded, fit=fit, hdiag=hdiag, dim=dim, d_pad=d_pad,
            use_owlqn=use_owlqn, l1_mask=l1_mask,
            extras_tail=extras_tail, with_norm=with_norm,
            meta=meta, layout=layout,
        )
        self.__dict__["_fs_state"] = state
        return state

    def _refresh_sharded_rows(self, residual):
        """The cached feature-sharded layout under this update's row
        vectors (``_refresh_rows``). Shared by the sequential update and
        the λ-grid solve so the two row paths cannot diverge."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_ml_tpu.parallel.mesh import DATA_AXIS

        st = self._feature_sharded_state()
        sharded = self._refresh_rows(
            st["sharded"], NamedSharding(self.mesh, P(DATA_AXIS)), residual
        )
        st["sharded"] = sharded  # keep the freshest placement cached
        return sharded

    def _update_model_feature_sharded(self, model, residual):
        st = self._feature_sharded_state()
        sharded = self._refresh_sharded_rows(residual)

        initial = model.model.means if model is not None else None
        w0 = jnp.zeros((st["d_pad"],), jnp.float32)
        if initial is not None:
            w0 = w0.at[: initial.shape[0]].set(initial)
        l1, l2 = self.problem.regularization.split(self.reg_weight)
        extras = (
            [jnp.float32(l1), st["l1_mask"]] if st["use_owlqn"] else []
        ) + st["extras_tail"]
        result = st["fit"](w0, sharded, jnp.float32(l2), *extras)
        variances = None
        if st["hdiag"] is not None:
            from photon_ml_tpu.optim.problem import _VARIANCE_EPSILON

            norm_extras = st["extras_tail"][:2] if st["with_norm"] else []
            hd = st["hdiag"](
                result.coefficients, sharded, jnp.float32(l2), *norm_extras
            )
            variances = (1.0 / (hd + _VARIANCE_EPSILON))[: st["dim"]]
        coefficients = Coefficients(
            result.coefficients[: st["dim"]], variances
        )
        result = result._replace(coefficients=coefficients.means)
        return (
            FixedEffectModel(
                self.problem.create_model(coefficients), self.feature_shard_id
            ),
            result,
        )

    def update_model_grid(self, reg_weights):
        """Batched λ tuning for this fixed effect: solve EVERY grid
        weight in ONE vmapped program (training.train_grid_batched's
        engine — GLMOptimizationProblem.run_grid on the replicated and
        data-parallel layouts, feature_sharded_glm_fit(grid=True) on the
        feature-sharded (data, model) mesh) instead of one warm-started
        solve per combo — the GAME grid sweep's FE λ axis collapses to 1
        compile / 1 optimizer loop / 1 dispatch. Down-sampling composes:
        the draw is λ-independent (one shared weight rewrite, same PRNG
        stream as the sequential path), so the whole grid solves against
        the same sampled batch. Cold starts per member.

        Returns ``[(FixedEffectModel, OptResult), ...]`` aligned with
        ``reg_weights``; result scalars stay device-resident for the
        caller's batched fetch.
        """
        if self._is_feature_sharded():
            return self._update_model_grid_feature_sharded(reg_weights)
        from photon_ml_tpu.models.coefficients import Coefficients
        from photon_ml_tpu.optim.common import grid_member

        batch = self._batch(None)
        if self.down_sampling_rate < 1.0:
            from photon_ml_tpu.data.sampler import down_sample

            batch = down_sample(
                jax.random.PRNGKey(self.sampler_seed), batch,
                self.down_sampling_rate, self.problem.task,
            )
        variances, result = self.problem.run_grid(
            batch, [float(w) for w in reg_weights], mesh=self.mesh
        )
        out = []
        for i in range(len(reg_weights)):
            var_i = variances[i] if variances is not None else None
            coefficients = Coefficients(result.coefficients[i], var_i)
            out.append((
                FixedEffectModel(
                    self.problem.create_model(coefficients),
                    self.feature_shard_id,
                ),
                grid_member(result, i),
            ))
        return out

    def _update_model_grid_feature_sharded(self, reg_weights):
        """The λ-grid solve on the (data, model) mesh: ONE
        feature_sharded_glm_fit(grid=True) dispatch covers every member
        — a [G, d_pad] coefficient bank (replicated grid axis, feature
        blocks sharded over "model"), [G] l1/l2 vectors, and the cached
        tile/entry layout walked once per data pass for the whole grid."""
        from photon_ml_tpu.models.coefficients import Coefficients
        from photon_ml_tpu.optim.common import grid_member
        from photon_ml_tpu.optim.config import OptimizerType
        from photon_ml_tpu.optim.problem import _VARIANCE_EPSILON
        from photon_ml_tpu.parallel.distributed import (
            feature_sharded_glm_fit,
        )

        st = self._feature_sharded_state()
        problem = self.problem
        use_tron = problem.config.optimizer_type == OptimizerType.TRON
        grid_fit = feature_sharded_glm_fit(
            problem.objective, self.mesh, st["meta"], layout=st["layout"],
            optimizer=(
                "tron" if use_tron
                else ("owlqn" if st["use_owlqn"] else "lbfgs")
            ),
            max_iter=problem.config.max_iter,
            tol=problem.config.tolerance,
            history=problem.config.lbfgs_history,
            max_cg=problem.config.tron_max_cg,
            with_norm=st["with_norm"], with_box=problem.box is not None,
            grid=True,
        )
        weights = [float(w) for w in reg_weights]
        G = len(weights)
        splits = [problem.regularization.split(w) for w in weights]
        l1_vec = jnp.asarray([s[0] for s in splits], jnp.float32)
        l2_vec = jnp.asarray([s[1] for s in splits], jnp.float32)
        # same row currency as the sequential sharded update: dataset
        # offsets (no residual at grid-tuning time) + the sampled draw
        sharded = self._refresh_sharded_rows(None)
        w0_bank = jnp.zeros((G, st["d_pad"]), jnp.float32)
        extras = (
            [l1_vec, st["l1_mask"]] if st["use_owlqn"] else []
        ) + st["extras_tail"]
        result = grid_fit(w0_bank, sharded, l2_vec, *extras)
        out = []
        norm_extras = st["extras_tail"][:2] if st["with_norm"] else []
        for i in range(G):
            var_i = None
            if st["hdiag"] is not None:
                hd = st["hdiag"](
                    result.coefficients[i], sharded,
                    jnp.float32(splits[i][1]), *norm_extras
                )
                var_i = (1.0 / (hd + _VARIANCE_EPSILON))[: st["dim"]]
            coef_i = result.coefficients[i][: st["dim"]]
            coefficients = Coefficients(coef_i, var_i)
            out.append((
                FixedEffectModel(
                    problem.create_model(coefficients),
                    self.feature_shard_id,
                ),
                grid_member(result, i)._replace(coefficients=coef_i),
            ))
        return out

    def score(self, model: FixedEffectModel) -> Array:
        means = model.model.means
        tiled = self._scoring_batch()
        default_registry().counter(
            "photon_fe_scores_total",
            "scoring passes of the fixed effects, by coordinate and "
            "kernel (tiled | gather)",
        ).inc(
            1, coordinate=self.name,
            kernel="gather" if tiled is None else "tiled",
        )
        if tiled is None:
            return fe_score(
                means, self.dataset.batch_for_shard(self.feature_shard_id)
            )
        axis = None
        if self.mesh is not None:
            from photon_ml_tpu.optim.problem import _row_axis
            from photon_ml_tpu.parallel.mesh import replicated

            axis = _row_axis(self.mesh)
            # (the zero model is one device's array, a solved one the
            # mesh's: placed alike, they share one compiled program)
            means = jax.device_put(means, replicated(self.mesh))
        return fe_score(
            means, tiled, self.problem.objective, mesh=self.mesh, axis=axis
        )

    def regularization_term(self, model: FixedEffectModel) -> float:
        from photon_ml_tpu.parallel import overlap

        return float(
            overlap.device_get(self.regularization_term_device(model))
        )

    def regularization_term_device(self, model: FixedEffectModel) -> Array:
        l1, l2 = self.problem.regularization.split(self.reg_weight)
        w = model.model.means
        term = 0.5 * l2 * jnp.vdot(w, w)
        if l1:
            term = term + l1 * jnp.sum(jnp.abs(w))
        return term

    def prepare(self, model=None) -> None:
        """Stage the solve's static inputs ahead of update_model: the
        feature-sharded layout or the tiled schedules (each built once,
        multi-second cold; ``score()`` rides the schedules too and waits
        for a build under way here), else the scatter path's device
        copies of the shard columns."""
        if self._is_feature_sharded():
            self._feature_sharded_state()
        elif self.kernel == "tiled":
            self._tiled_base()
        else:
            self.dataset.batch_for_shard(self.feature_shard_id)


def _count_scored_rows(
    coordinate: str, block_rows: int, gather_rows: int, chunk_rows: int = 0
):
    """One scoring pass of a random effect into the registry: the rows
    scored from the solver's blocks, the passive rows scored from their
    entity chunks, and those left to the gather (host arithmetic on the
    scoring plan; nothing is read from the device)."""
    counter = default_registry().counter(
        "photon_re_score_rows_total",
        "rows the random effects scored, by coordinate and path "
        "(blocks | chunks | gather)",
    )
    for path, rows in (
        ("blocks", block_rows), ("chunks", chunk_rows),
        ("gather", gather_rows),
    ):
        if rows:
            counter.inc(rows, coordinate=coordinate, path=path)


def _placed(re_dataset, problem) -> Dict[str, int]:
    """The slots of the blocks a replicated bank scores from, by the path
    their scores are placed on, a path with none left out (host
    arithmetic on the scoring plan and the paths it observes)."""
    plan = score_plan(re_dataset, problem)
    _, _, paths = score_places(problem, re_dataset, plan)
    taken = slots_by_path(plan.groups, paths)
    return {path: slots for path, slots in taken.items() if slots}


def _score_replicated_bank(coordinate, bank, re_dataset, problem) -> Array:
    plan = score_plan(re_dataset, problem)
    _count_scored_rows(
        coordinate, plan.block_rows, plan.gather_rows, plan.chunk_rows
    )
    counter = default_registry().counter(
        "photon_re_score_placed_slots_total",
        "slots of the blocks the random effects scored from, by "
        "coordinate and the path their scores were placed on (windows: an "
        "entity's rows are a run; sorted: they are a run of the dataset's "
        "entity order, sorted back; slots: the element scatter)",
    )
    for path, slots in _placed(re_dataset, problem).items():
        counter.inc(slots, coordinate=coordinate, path=path)
    return score_random_effect(bank, re_dataset, problem)


@dataclass
class RandomEffectCoordinate(Coordinate):
    """Per-entity block (RandomEffectCoordinate[InProjectedSpace])."""

    name: str
    dataset: GameDataset
    re_dataset: RandomEffectDataset
    problem: RandomEffectOptimizationProblem

    def initialize_model(self) -> RandomEffectModel:
        bank = jnp.zeros(
            (self.re_dataset.num_entities, self.re_dataset.local_dim),
            jnp.float32,
        )
        return RandomEffectModel(
            bank,
            self.re_dataset,
            self.re_dataset.config.random_effect_type,
            self.re_dataset.config.feature_shard_id,
        )

    def update_model(self, model, residual=None):
        offsets = self.dataset.offsets
        if residual is not None:
            offsets = jnp.asarray(offsets) + residual  # device-resident
        variances = None
        if self.problem.compute_variances:
            bank, tracker, variances = self.problem.update_bank(
                model.bank, self.re_dataset, residual_offsets=offsets,
                with_variances=True, defer_tracker=True,
                coordinate=self.name,
            )
        else:
            bank, tracker = self.problem.update_bank(
                model.bank, self.re_dataset, residual_offsets=offsets,
                defer_tracker=True, coordinate=self.name,
            )
        return replace(model, bank=bank, variances=variances), tracker

    @property
    def score_kernel(self) -> str:
        """How ``score()`` computes, "blocks" | "gather" |
        "blocks+chunks" | ...: for ``cd.score``."""
        return score_plan(self.re_dataset, self.problem).kernel

    @property
    def score_attrs(self) -> dict:
        """The paths the blocks' scores were placed on, joined by ``+``,
        and the passive rows' chunks and their padding slots, where the
        plan scores them from chunks: for ``cd.score``."""
        plan = score_plan(self.re_dataset, self.problem)
        attrs = {}
        if plan.groups:
            attrs["placed"] = "+".join(
                _placed(self.re_dataset, self.problem)
            )
        if plan.chunk_rows:
            attrs.update(
                passive_chunks=plan.passive_chunks,
                passive_padding=plan.passive_padding,
            )
        return attrs

    def score(self, model: RandomEffectModel) -> Array:
        return _score_replicated_bank(
            self.name, model.bank, self.re_dataset, self.problem
        )

    def regularization_term(self, model: RandomEffectModel) -> float:
        return self.problem.regularization_term(model.bank)

    def regularization_term_device(self, model: RandomEffectModel) -> Array:
        return self.problem.regularization_term_device(model.bank)

    def prepare(self, model=None) -> None:
        """Stage bucket device transfers / stacked group args / AOT
        programs + the row view and the placement specs the score pass
        reads."""
        bank = (
            model.bank
            if model is not None
            else jnp.zeros(
                (self.re_dataset.num_entities, self.re_dataset.local_dim),
                jnp.float32,
            )
        )
        self.problem.prepare(bank, self.re_dataset, coordinate=self.name)
        score_places(
            self.problem, self.re_dataset,
            score_plan(self.re_dataset, self.problem),
        )


@dataclass
class PodRandomEffectCoordinate(Coordinate):
    """Entity-sharded random-effect block (pod-scale GAME, game/pod.py):
    the bank, variances and per-entity data shard over the ``entity``
    mesh axis by entity hash, each replica solves only its own entities
    (cross-replica sharded update), and the residual currency rides a
    two-hop all_to_all — residuals in, scores out — instead of any
    host gather. Model state is a PodRandomEffectModel whose replicated
    ``bank`` view materializes lazily (export/validation only)."""

    name: str
    dataset: GameDataset
    re_dataset: RandomEffectDataset
    problem: RandomEffectOptimizationProblem  # mesh-less base
    mesh: object = None  # 1-D entity mesh (required)

    def __post_init__(self):
        from photon_ml_tpu.game.pod import PodRandomEffectProblem

        if self.mesh is None:
            raise ValueError("PodRandomEffectCoordinate requires an entity mesh")
        self.pod = PodRandomEffectProblem(self.problem, self.mesh)

    def _count_hop(self, hop: str):
        """One exchange hop of this coordinate's rows, into the registry:
        the rows routed, and those whose owner is another device than the
        one that holds the row (host arithmetic on the router's static
        tables; nothing is read from the device). Returns the pod view."""
        view = self.pod.pod_view(self.re_dataset)
        registry = default_registry()
        registry.counter(
            "photon_pod_routed_rows_total",
            "rows the pod exchange carried, by coordinate and hop (in | out)",
        ).inc(view.router.num_routed_rows, coordinate=self.name, hop=hop)
        registry.counter(
            "photon_pod_cross_shard_rows_total",
            "routed rows whose owner is another device than the row's",
        ).inc(view.router.cross_shard_rows, coordinate=self.name)
        return view

    def initialize_model(self):
        from photon_ml_tpu.game.pod import PodRandomEffectModel

        return PodRandomEffectModel(
            self.pod.init_bank(self.re_dataset),
            self.re_dataset,
            self.re_dataset.config.random_effect_type,
            self.re_dataset.config.feature_shard_id,
        )

    def update_model(self, model, residual=None):
        from photon_ml_tpu.game.pod import PodRandomEffectModel

        offsets = self.dataset.offsets
        if residual is not None:
            offsets = jnp.asarray(offsets) + residual  # device-resident
        bank = getattr(model, "sharded_bank", None)
        if bank is None and model is not None:
            bank = model.bank  # warm start from a replicated model
        variances = None
        if self.problem.compute_variances:
            bank, tracker, variances = self.pod.update_bank(
                bank, self.re_dataset, residual_offsets=offsets,
                with_variances=True, defer_tracker=True,
            )
        else:
            bank, tracker = self.pod.update_bank(
                bank, self.re_dataset, residual_offsets=offsets,
                defer_tracker=True,
            )
        view = self._count_hop("in")
        solved = default_registry().counter(
            "photon_pod_entities_total",
            "entities the pod bank updates solved, by coordinate and "
            "solver kind",
        )
        for kind, entities in view.entities_by_kind().items():
            solved.inc(entities, coordinate=self.name, kind=kind)
        return (
            PodRandomEffectModel(
                bank,
                self.re_dataset,
                self.re_dataset.config.random_effect_type,
                self.re_dataset.config.feature_shard_id,
                variances_sharded=variances,
            ),
            tracker,
        )

    @property
    def score_kernel(self) -> str:
        """As :attr:`RandomEffectCoordinate.score_kernel`, of the pod
        view's blocks (a sharded model's scoring)."""
        return self.pod.pod_view(self.re_dataset).score_kernel

    def score(self, model) -> Array:
        if getattr(model, "sharded_bank", None) is None:
            return _score_replicated_bank(
                self.name, model.bank, self.re_dataset, self.problem
            )
        view = self._count_hop("out")
        _count_scored_rows(
            self.name, view.score_block_rows, view.score_gather_rows
        )
        return self.pod.score(model.sharded_bank, self.re_dataset)

    def regularization_term(self, model) -> float:
        from photon_ml_tpu.parallel import overlap

        return float(
            overlap.device_get(self.regularization_term_device(model))
        )

    def regularization_term_device(self, model) -> Array:
        bank = getattr(model, "sharded_bank", None)
        if bank is None:
            bank = model.bank
        return self.pod.regularization_term_device(bank)

    def prepare(self, model=None) -> None:
        self.pod.prepare(self.re_dataset)


class FactoredRandomEffectTracker:
    """What one factored update did, inner iteration by inner iteration:
    the latent bank update's tracker (``latent``, device-resident until
    read) and the projection fit's iterations, evaluations and stop
    reason (``projection``). Everything stays on the device until the
    coordinate descent's one batched readback (``deferreds``); the
    readback fills the ``fre.projection`` spans' attrs and counts the
    fits' evaluations into ``photon_fre_projection_evals_total``."""

    def __init__(self, coordinate: str, latent: list, fits: list, spans: list):
        from photon_ml_tpu.parallel import overlap

        self.coordinate = coordinate
        self.latent = latent
        self._spans = spans
        self._fits = overlap.Deferred(
            [(r.iterations, r.evaluations, r.reason) for r in fits],
            self._finalize,
        )

    def _finalize(self, fetched) -> list:
        from photon_ml_tpu.optim.common import CONVERGENCE_REASON_NAMES

        fits = [
            {
                "iterations": int(it), "evaluations": int(ev),
                "reason": CONVERGENCE_REASON_NAMES.get(int(reason), "?"),
            }
            for it, ev, reason in fetched
        ]
        for sp, fit in zip(self._spans, fits):
            sp.set_after(**fit)
        default_registry().counter(
            "photon_fre_projection_evals_total",
            "objective evaluations of the factored random effects' "
            "projection fits, by coordinate",
        ).inc(sum(f["evaluations"] for f in fits), coordinate=self.coordinate)
        return fits

    @property
    def deferreds(self) -> list:
        return [getattr(t, "deferred", None) for t in self.latent] + [
            self._fits
        ]

    @property
    def projection(self) -> list:
        return self._fits.result()


@dataclass
class FactoredRandomEffectCoordinate(Coordinate):
    """Random effects in a LEARNED latent projection
    (FactoredRandomEffectCoordinate.scala:99-289): ``num_inner_iterations``
    times, (1) every entity's latent coefficients with ``z' B`` as its
    features, one ``update_bank`` under a values override that makes
    them inside each block's program (:mod:`photon_ml_tpu.game.factored`),
    then (2) the shared projection ``B`` fitted with them held, over the
    same blocks. ``projection_problem`` is the projection's GLM problem
    over ``vec(B)``: its optimizer configuration and regularization (at
    ``reg_weight_projection``) are the fit's; its objective is not run.

    Model state: a :class:`FactoredRandomEffectModel` (latent bank
    ``[E, L]`` and projection ``[d, L]``), both on the device throughout.
    """

    name: str
    dataset: GameDataset
    re_dataset: RandomEffectDataset  # IDENTITY-projected base view
    problem: RandomEffectOptimizationProblem
    projection_problem: GLMOptimizationProblem  # over flattened B
    config: FactoredRandomEffectConfiguration
    reg_weight_projection: float = 0.0
    seed: int = 0

    def initialize_model(self) -> "FactoredRandomEffectModel":
        d = self.re_dataset.local_dim
        L = self.config.latent_space_dimension
        rng = np.random.default_rng(self.seed)
        projection = jnp.asarray(
            rng.normal(0.0, 1.0 / np.sqrt(L), size=(d, L)).astype(np.float32)
        )
        bank = jnp.zeros((self.re_dataset.num_entities, L), jnp.float32)
        return FactoredRandomEffectModel(
            bank=bank,
            projection=projection,
            re_dataset=self.re_dataset,
            random_effect_type=self.re_dataset.config.random_effect_type,
            feature_shard_id=self.re_dataset.config.feature_shard_id,
        )

    def _view(self) -> RandomEffectDataset:
        return factored.latent_view(
            self.re_dataset, self.config.latent_space_dimension
        )

    def update_model(self, model, residual=None):
        offsets = jnp.asarray(self.dataset.offsets)
        if residual is not None:
            if len(residual.sharding.device_set) > 1:
                # a data mesh's residual, replicated over its devices: the
                # bank here is one device's (its programs are compiled for
                # one), so the vector comes to it through the host, once
                # an update; on one device it never leaves
                from photon_ml_tpu.parallel import overlap

                residual = jnp.asarray(overlap.device_get(residual))
            offsets = offsets + residual  # device-resident
        view = self._view()
        bank, projection = model.bank, model.projection
        inner = self.config.num_inner_iterations
        default_registry().counter(
            "photon_fre_inner_iterations_total",
            "inner iterations (latent bank update + projection fit) of the "
            "factored random effects, by coordinate",
        ).inc(inner, coordinate=self.name)
        # the residual does not change across inner iterations: every
        # block's offsets are made from it once
        groups, offsets = self.problem.group_offsets(
            view, offsets, override=factored.latent_override(projection),
            coordinate=self.name,
        )
        arrays = factored.group_arrays(self.problem, view, groups)
        opt = self.projection_problem
        l1, l2 = opt.regularization.split(self.reg_weight_projection)
        latent, fits, spans = [], [], []
        for i in range(inner):
            with obs_span("fre.latent", coordinate=self.name, inner=i + 1):
                bank, tracker = self.problem.update_bank(
                    bank, view,
                    values_override=factored.latent_override(projection),
                    defer_tracker=True, coordinate=self.name,
                    group_offsets=offsets,
                )
            with obs_span(
                "fre.projection", coordinate=self.name, inner=i + 1
            ) as sp:
                fit = factored.fit_projection(
                    projection, bank, arrays, offsets,
                    loss=self.problem.loss, config=opt.config, l1=l1, l2=l2,
                    coordinate=self.name,
                )
            projection = fit.coefficients.reshape(projection.shape)
            latent.append(tracker)
            fits.append(fit)
            spans.append(sp)
        return (
            replace(model, bank=bank, projection=projection),
            FactoredRandomEffectTracker(self.name, latent, fits, spans),
        )

    @property
    def score_kernel(self) -> str:
        """How ``score()`` computes: for ``cd.score``."""
        plan = score_plan(
            self._view(), self.problem, staged=self.re_dataset.local_dim,
            passive_apart=False,
        )
        return score_kernel_name(plan.block_rows, plan.gather_rows)

    def score(self, model) -> Array:
        scores, plan = factored.score_factored(
            model.bank, model.projection, self.re_dataset, self.problem
        )
        _count_scored_rows(self.name, plan.block_rows, plan.gather_rows)
        return scores

    def regularization_term(self, model) -> float:
        from photon_ml_tpu.parallel import overlap

        return float(
            overlap.device_get(self.regularization_term_device(model))
        )

    def regularization_term_device(self, model) -> Array:
        """The latent bank's term and the projection's own ``l2/2 |B|^2
        (+ l1 |B|_1)`` at ``reg_weight_projection``."""
        B = model.projection
        l1, l2 = self.projection_problem.regularization.split(
            self.reg_weight_projection
        )
        term = self.problem.regularization_term_device(model.bank)
        term = term + 0.5 * l2 * jnp.sum(B * B)
        if l1:
            term = term + l1 * jnp.sum(jnp.abs(B))
        return term

    def prepare(self, model=None) -> None:
        """The latent bank's :meth:`RandomEffectOptimizationProblem.prepare`
        (block copies, residual arrays, AOT solver programs) under its
        values override, and the scoring plan."""
        model = model if model is not None else self.initialize_model()
        view = self._view()
        override = factored.latent_override(model.projection)
        self.problem.prepare(
            model.bank, view, coordinate=self.name, override=override
        )
        score_plan(
            view, self.problem, staged=override.staged, passive_apart=False
        )


@dataclass
class FactoredRandomEffectModel(DatumScoringModel):
    """Latent bank [E, L] + shared projection [d, L]
    (FactoredRandomEffectModel.scala:75)."""

    bank: Array
    projection: Array
    re_dataset: RandomEffectDataset
    random_effect_type: str
    feature_shard_id: str

    def score(self, dataset: GameDataset) -> Array:
        return factored.score_factored(
            self.bank, self.projection, self.re_dataset
        )[0]


def partner_factors(latent: Array, keys: Array) -> Array:
    """The values of an ALS half-step's block (a :class:`ValuesOverride`
    ``fn``): each rating's partner-side factor row, ``latent[keys]``
    ``[E, S, K]``, zero on a padding slot (key -1). Runs inside the
    block's solver program."""
    return jnp.where(
        (keys >= 0)[..., None],
        jnp.take(latent, jnp.maximum(keys, 0), axis=0),
        0.0,
    )


@dataclass
class MatrixFactorizationCoordinate(Coordinate):
    """MF block trained by alternating least squares on residuals: row
    factors solve a K-dim GLM with features = colLatent[col_i] (a
    random-effect solve in disguise), then columns symmetrically, against
    the row factors the same step just made.

    The reference trains factored models via FactoredRandomEffect and
    scores external MF models (MatrixFactorizationModel.scala); training
    in-tree here completes the GAME loop for MovieLens-style benchmarks.
    The two half-steps run as ``update_bank(coordinate="<name>_row" |
    "<name>_col")``: their programs, spans and counters carry those names.
    """

    name: str
    dataset: GameDataset
    row_effect_type: str
    col_effect_type: str
    num_latent_factors: int
    problem: RandomEffectOptimizationProblem
    num_inner_iterations: int = 1
    seed: int = 0

    def initialize_model(self) -> MatrixFactorizationModel:
        """The starting factors: N(0, 0.1^2) from the coordinate's own
        constant ``seed``, so the same on every call; drawn and uploaded
        once (10.6M normals at MovieLens-20M's counts and rank 64), and
        never written to (``update_bank`` copies a bank before it donates
        it)."""
        model = self.__dict__.get("_initial_model")
        if model is None:
            rng = np.random.default_rng(self.seed)
            R = self.dataset.entity_indexes[self.row_effect_type].num_entities
            C = self.dataset.entity_indexes[self.col_effect_type].num_entities
            K = self.num_latent_factors
            model = MatrixFactorizationModel(
                self.row_effect_type,
                self.col_effect_type,
                jnp.asarray(rng.normal(0, 0.1, size=(R, K)).astype(np.float32)),
                jnp.asarray(rng.normal(0, 0.1, size=(C, K)).astype(np.float32)),
            )
            self.__dict__["_initial_model"] = model
        return model

    def _sides(self):
        """(side, solved codes, partner codes, entities solved) of the two
        half-steps, in the order they run."""
        rows = self.dataset.entity_codes[self.row_effect_type]
        cols = self.dataset.entity_codes[self.col_effect_type]
        R = self.dataset.entity_indexes[self.row_effect_type].num_entities
        C = self.dataset.entity_indexes[self.col_effect_type].num_entities
        return (("row", rows, cols, R), ("col", cols, rows, C))

    def _side_structure(self, side: str, solve_codes, fixed_codes, num_solved):
        """Static ALS half-step structure: entity grouping, bucket
        membership and each slot's partner code (the bucket's
        ``override_keys``). Depends only on the dataset's entity codes,
        so it is built once per side and cached — per half-step only the
        partner side's factors change, and those are gathered on device,
        inside each block's solver program (:func:`partner_factors`).
        """
        cache = getattr(self, "_als_structure_cache", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_als_structure_cache", cache)
        hit = cache.get(side)
        if hit is not None:
            return hit
        with obs_span("mf.structure_build", side=side) as build_span:
            view = self._build_side_structure(
                side, solve_codes, fixed_codes, num_solved
            )
            build_span.set(
                entities=sum(b.num_entities for b in view.buckets),
                classes=len(view.buckets),
                slots=sum(b.row_index.size for b in view.buckets),
            )
        cache[side] = view
        return view

    def _build_side_structure(
        self, side: str, solve_codes, fixed_codes, num_solved
    ):
        from photon_ml_tpu.game.config import (
            ProjectorType,
            RandomEffectDataConfiguration,
        )
        from photon_ml_tpu.game.random_effect_data import (
            RandomEffectBucket,
            RandomEffectDataset,
            observe_row_runs,
        )

        K = self.num_latent_factors
        real = (
            (self.dataset.weights > 0)
            & (solve_codes >= 0)
            & (fixed_codes >= 0)
        )

        # vectorized entity grouping (a python append-per-rating loop
        # here took minutes at MovieLens scale): stable-sort rows by
        # entity, then scatter each cap-class's grouped rows into its
        # padded [E_b, S] block with one flat assignment
        real_idx = np.nonzero(real)[0]
        codes_real = solve_codes[real_idx].astype(np.int64)
        order = np.argsort(codes_real, kind="stable")
        sorted_rows = real_idx[order]
        counts = np.bincount(codes_real, minlength=num_solved)
        starts = np.cumsum(counts) - counts
        caps = np.where(
            counts > 0,
            1 << np.ceil(np.log2(np.maximum(counts, 1))).astype(np.int64),
            0,
        )
        # One class a power of two, as build_random_effect_dataset has
        # them: at heavy-tailed activity (MovieLens-20M's: 10 classes of
        # users, 18 of movies) three slots in ten hold no rating. Coarser
        # classes would spare compiles and leave seven in ten empty
        # there, and every half-step gathers a factor row for every slot,
        # empty or not; the programs compile once, concurrently
        # (:meth:`prepare`).
        buckets = []
        for S in sorted(set(int(c) for c in caps if c > 0)):
            members = np.nonzero(caps == S)[0]
            E_b = len(members)
            lens = counts[members]
            total = int(lens.sum())
            intra = np.arange(total) - np.repeat(np.cumsum(lens) - lens, lens)
            src = sorted_rows[np.repeat(starts[members], lens) + intra]
            b_rows = np.full((E_b, S), -1, np.int32)
            b_rows.flat[np.repeat(np.arange(E_b) * S, lens) + intra] = src
            safe = np.maximum(b_rows, 0)
            ok = b_rows >= 0
            buckets.append(RandomEffectBucket(
                entity_codes=members.astype(np.int32),
                row_index=b_rows,
                # an identity block made by a values override stores
                # neither indices nor values: X IS what
                # :func:`partner_factors` gathers inside the program
                indices=np.zeros((E_b, S, 0), np.int32),
                values=np.zeros((E_b, S, 0), np.float32),
                labels=np.where(ok, self.dataset.labels[safe], 0.0),
                offsets=np.where(ok, self.dataset.offsets[safe], 0.0),
                weights=np.where(ok, self.dataset.weights[safe], 0.0),
                identity_indices=True,
                override_keys=np.where(ok, fixed_codes[safe], -1).astype(
                    np.int32
                ),
                row_runs=observe_row_runs(b_rows),
            ))
        view = RandomEffectDataset(
            config=RandomEffectDataConfiguration(
                random_effect_type="__mf__",
                feature_shard_id="__latent__",
                projector_type=ProjectorType.IDENTITY,
            ),
            num_entities=num_solved,
            local_dim=K,
            projection=np.tile(
                np.arange(K, dtype=np.int32)[None, :], (num_solved, 1)
            ),
            # zero-length row-level placeholders: update_bank never
            # reads them (scoring goes through ``mf_score`` on the real
            # dataset), and [n, K] zeros would pin ~0.5 GB host RAM per
            # side for the coordinate's lifetime
            row_local_indices=np.zeros((0, K), np.int32),
            row_local_values=np.zeros((0, K), np.float32),
            row_entity_codes=np.where(real, solve_codes, -1).astype(np.int32),
            buckets=buckets,
            num_active_rows=int(counts.sum()),
            num_passive_rows=0,
        )
        # counted on the host once a structure build: the slots the
        # half-steps run, and how many of them hold no rating
        slots = default_registry().counter(
            "photon_mf_slots_total",
            "slots of the ALS half-steps' blocks, by coordinate, side and "
            "state (rating | padding)",
        )
        held = sum(b.row_index.size for b in buckets)
        ratings = int(counts.sum())
        for state, count in (("rating", ratings), ("padding", held - ratings)):
            if count:
                slots.inc(count, coordinate=self.name, side=side, state=state)
        return view

    def _als_side(
        self,
        side: str,
        solve_codes: np.ndarray,  # [n] entity codes of the side being solved
        fixed_codes: np.ndarray,
        fixed_latent: Array,  # [F, K]
        bank: Array,  # [S, K] current factors of the solved side
        offsets,
        num_solved: int,
    ) -> Array:
        view = self._side_structure(side, solve_codes, fixed_codes, num_solved)
        blocks = self.problem._solver_blocks(
            view, self.num_latent_factors, split=self.problem.mesh is None
        )
        with obs_span(
            "mf.half_step", side=side,
            entities=sum(b.num_entities for b in view.buckets),
            classes=len(view.buckets), sub_blocks=len(blocks),
            kind="+".join(sorted({b.kind for b in blocks})),
        ):
            # the partner side's CURRENT factors, gathered on device a
            # block at a time inside the block's own program
            new_bank, _ = self.problem.update_bank(
                bank, view, residual_offsets=offsets,
                values_override=ValuesOverride(partner_factors, fixed_latent),
                coordinate=f"{self.name}_{side}",
            )
        return new_bank

    def update_model(self, model, residual=None):
        # With no residual the cached bucket offsets already hold the
        # dataset offsets — passing residual_offsets would re-gather and
        # re-upload [E, S] offsets per bucket every half-step for nothing
        offsets = None
        if residual is not None:
            offsets = jnp.asarray(self.dataset.offsets) + residual
        latent = {"row": model.row_latent, "col": model.col_latent}
        self.prepare(model)
        for _ in range(self.num_inner_iterations):
            for side, solve_codes, fixed_codes, num in self._sides():
                partner = "col" if side == "row" else "row"
                latent[side] = self._als_side(
                    side, solve_codes, fixed_codes, latent[partner],
                    latent[side], offsets, num,
                )
        return replace(
            model, row_latent=latent["row"], col_latent=latent["col"]
        ), None

    def prepare(self, model=None) -> None:
        """Both sides' structures, and (once) BOTH sides' solver programs
        AOT-compiled in one threaded pool — per-side warming serialized
        the col side's compiles behind the row solves."""
        if self.__dict__.get("_als_prewarmed"):
            return
        model = model if model is not None else self.initialize_model()
        latent = {"row": model.row_latent, "col": model.col_latent}
        specs = []
        for side, solve_codes, fixed_codes, num in self._sides():
            partner = "col" if side == "row" else "row"
            specs.append((
                latent[side],
                self._side_structure(side, solve_codes, fixed_codes, num),
                ValuesOverride(partner_factors, latent[partner]),
                f"{self.name}_{side}",
            ))
        self.problem.prewarm(specs)
        self.__dict__["_als_prewarmed"] = True

    def score(self, model: MatrixFactorizationModel) -> Array:
        with obs_span("mf.score", coordinate=self.name):
            return model.score(self.dataset)

    def regularization_term(self, model: MatrixFactorizationModel) -> float:
        from photon_ml_tpu.parallel import overlap

        return float(
            overlap.device_get(self.regularization_term_device(model))
        )

    def regularization_term_device(
        self, model: MatrixFactorizationModel
    ) -> Array:
        # device scalar, like the FE/RE coordinates: the CD loop folds it
        # into its one batched readback instead of a per-coordinate pull
        l1, l2 = self.problem.regularization.split(self.problem.reg_weight)
        return 0.5 * l2 * (
            jnp.sum(model.row_latent**2) + jnp.sum(model.col_latent**2)
        )
