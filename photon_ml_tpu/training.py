"""GLM training orchestration: regularization-path with warm starts.

Reference: photon-ml ModelTraining.scala:103-215 —
``trainGeneralizedLinearModel`` builds one loss function + one optimization
problem per task (:123-169), sorts the regularization weights DESCENDING
(:172) and folds over them reusing the previous lambda's coefficients as the
warm start (:183-208). One problem object is reused across the grid; here
that means one XLA compilation serves the entire path (reg weight is a
runtime scalar).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from photon_ml_tpu.data.batch import Batch
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.optim.common import BoxConstraints, OptResult, grid_member
from photon_ml_tpu.optim.config import (
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.optim.problem import create_glm_problem, resolve_kernel
from photon_ml_tpu.task import TaskType
from photon_ml_tpu.utils import profiling  # noqa: F401  obs spans -> profiler

Array = jnp.ndarray


# -- λ-grid crash-safe snapshots (reliability.GridCheckpointer) ---------------
#
# One snapshot per COMPLETED λ: warm-start means (optimization space —
# the currency the next λ's solve starts from, so a resumed sweep walks
# bitwise the same iterate chain), the exported model (original space),
# and the OptResult arrays. kill -9 mid-λ loses only that λ's solve; the
# restart re-solves it from the SAME warm start and continues.


def _snapshot_result_arrays(result: OptResult) -> Dict[str, object]:
    import numpy as np

    t = result.tracker
    arrs = {
        "coefficients": np.asarray(result.coefficients),
        "value": np.asarray(result.value),
        "grad_norm": np.asarray(result.grad_norm),
        "iterations": np.asarray(result.iterations),
        "reason": np.asarray(result.reason),
        "evaluations": np.asarray(result.evaluations),
        "tracker_values": np.asarray(t.values),
        "tracker_grad_norms": np.asarray(t.grad_norms),
        "tracker_count": np.asarray(t.count),
    }
    if t.coefs is not None:
        arrs["tracker_coefs"] = np.asarray(t.coefs)
    return arrs


def _result_from_snapshot(d: Dict[str, object]) -> OptResult:
    from photon_ml_tpu.optim.common import Tracker

    coefs = d.get("tracker_coefs")
    return OptResult(
        coefficients=jnp.asarray(d["coefficients"]),
        value=jnp.asarray(d["value"]),
        grad_norm=jnp.asarray(d["grad_norm"]),
        iterations=jnp.asarray(d["iterations"]),
        reason=jnp.asarray(d["reason"]),
        tracker=Tracker(
            values=jnp.asarray(d["tracker_values"]),
            grad_norms=jnp.asarray(d["tracker_grad_norms"]),
            count=jnp.asarray(d["tracker_count"]),
            coefs=jnp.asarray(coefs) if coefs is not None else None,
        ),
        # a snapshot written before the count existed restores as
        # "not counted", not as an error
        evaluations=jnp.asarray(d.get("evaluations", -1), jnp.int32),
    )


def _model_from_snapshot(
    task: TaskType, snap: Dict[str, object]
) -> GeneralizedLinearModel:
    from photon_ml_tpu.models.coefficients import Coefficients

    var = snap.get("model_variances")
    return GeneralizedLinearModel(
        task,
        Coefficients(
            jnp.asarray(snap["model_means"]),
            jnp.asarray(var) if var is not None else None,
        ),
    )


def _save_lambda_snapshot(
    checkpointer, lam: float, warm_means, model, result: OptResult
) -> None:
    import numpy as np

    checkpointer.save(
        lam,
        warm_means=np.asarray(warm_means),
        model_means=np.asarray(model.means),
        model_variances=(
            np.asarray(model.coefficients.variances)
            if model.coefficients.variances is not None
            else None
        ),
        result_arrays=_snapshot_result_arrays(result),
    )


def train_generalized_linear_model(
    batch: Batch,
    task: TaskType,
    dim: int,
    *,
    optimizer_type: OptimizerType = OptimizerType.LBFGS,
    regularization_type: RegularizationType = RegularizationType.NONE,
    regularization_weights: Sequence[float] = (0.0,),
    elastic_net_alpha: Optional[float] = None,
    max_iter: Optional[int] = None,
    tolerance: Optional[float] = None,
    normalization: Optional[NormalizationContext] = None,
    warm_start: bool = True,
    compute_variances: bool = False,
    box: Optional[BoxConstraints] = None,
    intercept_index: Optional[int] = None,
    axis_name: Optional[str] = None,
    initial: Optional[Array] = None,
    kernel: str = "scatter",
    mesh=None,
    track_models: bool = False,
    tile_cache_dir: Optional[str] = None,
    grid_checkpointer=None,
    preemption_guard=None,
) -> Tuple[Dict[float, GeneralizedLinearModel], Dict[float, OptResult]]:
    """Train one model per regularization weight with warm starts.

    Returns ({lambda: model}, {lambda: OptResult}) — models are in the
    ORIGINAL feature space (normalization un-done), matching
    ModelTraining.trainGeneralizedLinearModel's contract.

    ``kernel``: "scatter" | "tiled" | "auto" — objective implementation
    (see optim.problem.resolve_kernel). The tiled schedule is built once
    here and amortized across the whole lambda grid.

    ``mesh``: a jax.sharding.Mesh for data-parallel training — the whole
    L-BFGS/OWLQN/TRON loop runs under shard_map with the batch sharded
    over the "data" axis (the treeAggregate analog). The tiled kernel
    composes: per-device-shard schedules are built once and the Pallas
    kernels run unmodified inside shard_map (no scatter fallback).

    ``track_models``: stack per-iteration coefficients into each
    OptResult's ``tracker.coefs`` (ModelTracker analog). Use
    :func:`iteration_models` to turn a result into per-iteration models
    in the original feature space.

    ``tile_cache_dir``: persistent content-addressed schedule cache
    directory (ops/schedule_cache.py) for the tiled conversion — a warm
    rerun over the same dataset loads the schedules instead of
    rebuilding. None falls back to the process configuration /
    PHOTON_TILE_CACHE_DIR env var (unset = off).

    ``grid_checkpointer`` (reliability.GridCheckpointer): per-λ
    crash-safe snapshots — completed λs load instead of re-solving, and
    the resumed sweep warm-starts from the snapshotted means, so the
    final models are bitwise what an uninterrupted run produces.
    ``preemption_guard``: a SIGTERM stops the sweep BEFORE the next λ's
    solve (the λ boundary is the safe point); already-solved λs are
    checkpointed and returned.
    """
    base = OptimizerConfig.default_for(optimizer_type)
    config = OptimizerConfig(
        optimizer_type=optimizer_type,
        max_iter=max_iter if max_iter is not None else base.max_iter,
        tolerance=tolerance if tolerance is not None else base.tolerance,
        lbfgs_history=base.lbfgs_history,
        tron_max_cg=base.tron_max_cg,
    )
    regularization = RegularizationContext(regularization_type, elastic_net_alpha)
    kernel = resolve_kernel(kernel, batch)
    if mesh is not None and kernel != "tiled":
        # shard (and row-pad) once; every lambda reuses the device copies
        from photon_ml_tpu.parallel.mesh import ensure_data_sharded

        batch = ensure_data_sharded(batch, mesh)
    if kernel == "tiled":
        from photon_ml_tpu.data.batch import SparseBatch
        from photon_ml_tpu.ops.schedule_cache import cache_scope
        from photon_ml_tpu.ops.tiled_sparse import (
            TiledSparseBatch,
            ensure_tiled_sharded,
            tiled_batch_from_sparse,
        )

        with cache_scope(tile_cache_dir):
            if mesh is not None:
                # per-device-shard schedules built once here; the whole
                # lambda grid (and problem.run's idempotent ensure) reuses
                # them — tiled and distributed compose, no scatter fallback
                if not isinstance(batch, (SparseBatch, TiledSparseBatch)):
                    raise TypeError(
                        "kernel='tiled' requires a SparseBatch or "
                        f"TiledSparseBatch, got {type(batch).__name__}; use "
                        "kernel='scatter' for dense batches"
                    )
                batch = ensure_tiled_sharded(batch, dim, mesh)
            elif isinstance(batch, SparseBatch):
                batch = tiled_batch_from_sparse(batch, dim)
            elif not isinstance(batch, TiledSparseBatch):
                raise TypeError(
                    "kernel='tiled' requires a SparseBatch or "
                    f"TiledSparseBatch, got {type(batch).__name__}; use "
                    "kernel='scatter' for dense batches"
                )
    problem = create_glm_problem(
        task,
        dim,
        config=config,
        regularization=regularization,
        norm=normalization,
        axis_name=axis_name,
        compute_variances=compute_variances,
        box=box,
        intercept_index=intercept_index,
        kernel=kernel,
    )

    # Descending order: strongest regularization first, so each warm start
    # relaxes an already-shrunk model (ModelTraining.scala:172).
    weights_desc: List[float] = sorted(set(float(w) for w in regularization_weights), reverse=True)

    models: Dict[float, GeneralizedLinearModel] = {}
    results: Dict[float, OptResult] = {}
    current = initial
    for lam in weights_desc:
        snap = (
            grid_checkpointer.load(lam)
            if grid_checkpointer is not None
            else None
        )
        if snap is not None:
            # completed in a previous (interrupted) run: restore instead
            # of re-solving; the snapshotted warm means keep the iterate
            # chain bitwise identical for the λs still to solve
            models[lam] = _model_from_snapshot(task, snap)
            results[lam] = _result_from_snapshot(snap["result"])
            if warm_start:
                current = jnp.asarray(snap["warm_means"])
            continue
        if preemption_guard is not None and preemption_guard.requested:
            # stop at the λ boundary: solved λs are snapshotted; the
            # restarted run resumes the sweep here
            break
        with obs_span("glm.lambda_solve", reg_weight=lam):
            coefficients, result = problem.run(
                batch, initial=current, reg_weight=lam, mesh=mesh,
                track_models=track_models,
            )
        models[lam] = problem.create_model(coefficients, normalization)
        results[lam] = result
        if grid_checkpointer is not None:
            _save_lambda_snapshot(
                grid_checkpointer, lam, coefficients.means,
                models[lam], result,
            )
        if warm_start:
            current = coefficients.means
    return models, results


# Default host-memory budget for the batched grid's coefficient bank +
# vmapped optimizer state ("auto" falls back to the warm-started
# sequential path above it). 1 GiB leaves the usual batch-dominated HBM
# headroom on every supported device class.
DEFAULT_GRID_MEMORY_BUDGET = 1 << 30


def grid_bank_bytes(
    num_weights: int,
    dim: int,
    optimizer_type: OptimizerType = OptimizerType.LBFGS,
    history: int = 10,
    entity_shards: int = 1,
) -> int:
    """Estimated PER-DEVICE bytes for the batched grid's [G, d]
    coefficient bank plus the vmapped optimizer's per-member state
    (L-BFGS memory is the dominant term: the [m, d] s/y buffers; TRON
    carries the CG vectors instead). Under the unified mesh's
    P(grid, entity) placement the bank rows split over ``entity_shards``
    devices, so each device holds ~1/N of the replicated-bank
    footprint; ``entity_shards=1`` is the replicated/1-D figure."""
    if optimizer_type == OptimizerType.TRON:
        vectors_per_member = 12  # w, g + CG s/r/d/hd + trial w/g + slack
    else:
        vectors_per_member = 2 * history + 8
    total = int(num_weights) * vectors_per_member * int(dim) * 4
    return -(-total // max(1, int(entity_shards)))


def resolve_grid_mode(
    mode: str,
    *,
    num_weights: int,
    dim: int,
    optimizer_type: OptimizerType = OptimizerType.LBFGS,
    history: int = 10,
    memory_budget_bytes: int = DEFAULT_GRID_MEMORY_BUDGET,
    streaming: bool = False,
    entity_shards: int = 1,
) -> str:
    """Resolve ``--grid-mode {batched,sequential,auto}`` to a concrete
    path. ``auto`` picks batched when the grid has >1 member, the data
    fits in memory (not streaming — out-of-core stays the warm-started
    sequential default), and the G×d state bank fits the budget;
    everything else falls back to sequential. An explicit ``batched``
    with streaming input is a configuration error (the host-driven
    streamed optimizers cannot vmap over disk passes).

    ``entity_shards`` feeds the unified-mesh accounting: under
    P(grid, entity) each device holds ~1/N of the bank, so the budget
    comparison uses the per-device figure (grid_bank_bytes)."""
    if mode not in ("batched", "sequential", "auto"):
        raise ValueError(
            f"unknown grid mode {mode!r}; expected batched | sequential "
            "| auto"
        )
    if mode == "sequential":
        return "sequential"
    if streaming:
        if mode == "batched":
            raise ValueError(
                "--grid-mode batched is incompatible with streaming "
                "input: the streamed objectives evaluate through host "
                "IO, which the single vmapped optimizer program cannot "
                "trace; use sequential or auto"
            )
        return "sequential"
    if mode == "batched":
        return "batched"
    if num_weights <= 1:
        return "sequential"
    bank = grid_bank_bytes(
        num_weights, dim, optimizer_type, history, entity_shards
    )
    return "batched" if bank <= memory_budget_bytes else "sequential"


def resolve_entity_shards(
    requested: Optional[int],
    *,
    num_devices: Optional[int] = None,
) -> Optional[int]:
    """Resolve the GAME driver's ``--entity-shards`` to a concrete
    entity-mesh size (pod-scale GAME, game/pod.py), or None for the
    replicated bank path.

    ``None``/``0`` keeps the replicated default (entity sharding is
    opt-in: the sharded path changes the bank's device layout, so the
    operator asks for it explicitly); ``-1`` means "every visible
    device"; an explicit N must fit the device count. N == 1 is valid —
    the single-shard pod path, the parity baseline the weak-scaling
    tests anchor on."""
    if requested is None or requested == 0:
        return None
    import jax

    n_dev = num_devices if num_devices is not None else len(jax.devices())
    if requested == -1:
        return n_dev
    if not 1 <= requested <= n_dev:
        raise ValueError(
            f"--entity-shards {requested} out of range for {n_dev} "
            "visible devices (use -1 for all devices, 0 to disable)"
        )
    return int(requested)


def train_grid_batched(
    batch: Batch,
    task: TaskType,
    dim: int,
    *,
    optimizer_type: OptimizerType = OptimizerType.LBFGS,
    regularization_type: RegularizationType = RegularizationType.NONE,
    regularization_weights: Sequence[float] = (0.0,),
    elastic_net_alpha: Optional[float] = None,
    max_iter: Optional[int] = None,
    tolerance: Optional[float] = None,
    normalization: Optional[NormalizationContext] = None,
    compute_variances: bool = False,
    box: Optional[BoxConstraints] = None,
    intercept_index: Optional[int] = None,
    initial: Optional[Array] = None,
    kernel: str = "scatter",
    mesh=None,
    track_models: bool = False,
    tile_cache_dir: Optional[str] = None,
    grid_checkpointer=None,
) -> Tuple[Dict[float, GeneralizedLinearModel], Dict[float, OptResult]]:
    """Batched λ-grid twin of :func:`train_generalized_linear_model`:
    the grid stacks into a [G, d] coefficient bank and ONE jitted
    ``vmap(minimize_lbfgs/owlqn/tron)`` over a grid-batched objective
    solves every λ simultaneously — G compiles + G optimizer loops + G
    readback rounds become 1/1/1 (the final 1 via
    :func:`grid_result_scalars`' single batched fetch).

    The data pass is fused across the grid: the scatter objective's
    sparse matvec batches into one (n×d)@(d×G)-shaped gather/contract
    under vmap, and the tiled objective reuses its tile schedule (and
    the persistent schedule cache) ONCE for the whole grid via the flat
    grid pass (ops.tiled_sparse._grid_bilinear_pass). Box constraints,
    normalization and offsets broadcast across the grid member axis.
    Per-λ convergence is active-masked inside the while_loop carry:
    converged members freeze bit-stable while stragglers run on.

    There are NO warm starts between members (each λ starts from
    ``initial``) — that is the trade against the sequential path; see
    README "Regularization paths". Returns the same
    ({lambda: model}, {lambda: OptResult}) contract as the sequential
    trainer; result scalars stay device-resident for the batched fetch.
    """
    base = OptimizerConfig.default_for(optimizer_type)
    config = OptimizerConfig(
        optimizer_type=optimizer_type,
        max_iter=max_iter if max_iter is not None else base.max_iter,
        tolerance=tolerance if tolerance is not None else base.tolerance,
        lbfgs_history=base.lbfgs_history,
        tron_max_cg=base.tron_max_cg,
    )
    regularization = RegularizationContext(regularization_type, elastic_net_alpha)
    kernel = resolve_kernel(kernel, batch)
    if mesh is not None and kernel != "tiled":
        from photon_ml_tpu.parallel.mesh import ensure_data_sharded

        batch = ensure_data_sharded(batch, mesh)
    if kernel == "tiled":
        from photon_ml_tpu.data.batch import SparseBatch
        from photon_ml_tpu.ops.schedule_cache import cache_scope
        from photon_ml_tpu.ops.tiled_sparse import (
            TiledSparseBatch,
            ensure_tiled_sharded,
            tiled_batch_from_sparse,
        )

        with cache_scope(tile_cache_dir):
            if mesh is not None:
                if not isinstance(batch, (SparseBatch, TiledSparseBatch)):
                    raise TypeError(
                        "kernel='tiled' requires a SparseBatch or "
                        f"TiledSparseBatch, got {type(batch).__name__}; use "
                        "kernel='scatter' for dense batches"
                    )
                batch = ensure_tiled_sharded(batch, dim, mesh)
            elif isinstance(batch, SparseBatch):
                batch = tiled_batch_from_sparse(batch, dim)
            elif not isinstance(batch, TiledSparseBatch):
                raise TypeError(
                    "kernel='tiled' requires a SparseBatch or "
                    f"TiledSparseBatch, got {type(batch).__name__}; use "
                    "kernel='scatter' for dense batches"
                )
    problem = create_glm_problem(
        task,
        dim,
        config=config,
        regularization=regularization,
        norm=normalization,
        compute_variances=compute_variances,
        box=box,
        intercept_index=intercept_index,
        kernel=kernel,
    )
    # Same descending order as the sequential path, so the returned dict
    # iterates identically — the order is cosmetic here (no warm starts).
    weights_desc: List[float] = sorted(
        set(float(w) for w in regularization_weights), reverse=True
    )
    if grid_checkpointer is not None and all(
        grid_checkpointer.has(lam) for lam in weights_desc
    ):
        # the whole grid solved in ONE vmapped program last run: the
        # snapshot unit is the completed grid (there is no per-λ
        # mid-solve boundary inside a single jitted while_loop), so a
        # restart after the solve skips it entirely
        models = {}
        results = {}
        for lam in weights_desc:
            snap = grid_checkpointer.load(lam)
            models[lam] = _model_from_snapshot(task, snap)
            results[lam] = _result_from_snapshot(snap["result"])
        return models, results
    with obs_span(
        "glm.grid_solve", grid=len(weights_desc), batched=True
    ):
        variances, result = problem.run_grid(
            batch, weights_desc, initial=initial, mesh=mesh,
            track_models=track_models,
        )

    from photon_ml_tpu.models.coefficients import Coefficients

    models: Dict[float, GeneralizedLinearModel] = {}
    results: Dict[float, OptResult] = {}
    for i, lam in enumerate(weights_desc):
        var_i = variances[i] if variances is not None else None
        coefficients = Coefficients(result.coefficients[i], var_i)
        models[lam] = problem.create_model(coefficients, normalization)
        results[lam] = grid_member(result, i)
        if grid_checkpointer is not None:
            _save_lambda_snapshot(
                grid_checkpointer, lam, result.coefficients[i],
                models[lam], results[lam],
            )
    return models, results


def train_feature_sharded(
    batch: Batch,
    task: TaskType,
    dim: int,
    *,
    mesh,
    regularization_type: RegularizationType = RegularizationType.NONE,
    regularization_weights: Sequence[float] = (0.0,),
    elastic_net_alpha: Optional[float] = None,
    max_iter: Optional[int] = None,
    tolerance: Optional[float] = None,
    history: int = 10,
    warm_start: bool = True,
    normalization: Optional[NormalizationContext] = None,
    compute_variances: bool = False,
    box: Optional[BoxConstraints] = None,
    intercept_index: Optional[int] = None,
    kernel: str = "scatter",
    optimizer_type: OptimizerType = OptimizerType.LBFGS,
    track_models: bool = False,
    tile_cache_dir: Optional[str] = None,
) -> Tuple[Dict[float, GeneralizedLinearModel], Dict[float, OptResult]]:
    """Lambda grid over a FEATURE-SHARDED coefficient vector (the >HBM /
    10B-coefficient path, SURVEY §2.3 "coefficient parallelism").

    The mesh must be 2-D (data, model); the sparse batch is re-laid out
    once into per-feature-block slabs and every lambda reuses it. L1 and
    elastic-net run sharded OWL-QN; L2/none run sharded L-BFGS or (with
    ``optimizer_type=TRON``) sharded trust-region Newton whose truncated
    CG psums every inner product — the reference's
    one-treeAggregate-per-CG-iteration loop (SURVEY §3.2) on ICI. TRON
    runs the tiled kernels too: its Hv pass reuses the z/g schedules
    (tiled_block_local_hvp_factory).

    The reference composes normalization, variances, box constraints and
    per-iteration model tracking freely with distribution
    (NormalizationContext.scala:119-157 inside the aggregators,
    DistributedOptimizationProblem.scala:79-93, LBFGS.scala:77); here the
    shift/factor vectors shard along the feature axis (one extra psum'd
    scalar for the margin shift), the Hessian diagonal and box projection
    are block-local/elementwise, and ``track_models`` shards the
    per-iteration coefficient stack like the coefficients themselves.

    ``kernel``: "scatter" | "tiled" | "auto" — "tiled" lays each
    (data shard x feature block) cell out as block-local Pallas tile
    schedules, so the 10B-coefficient path runs the fast kernels instead
    of serialized gather/scatter (~7ns/element).
    """
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import SparseBatch
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.models.glm import create_model
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.parallel.distributed import (
        feature_shard_sparse_batch,
        feature_sharded_glm_fit,
        feature_sharded_hessian_diagonal,
    )
    from photon_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    if not isinstance(batch, SparseBatch):
        raise TypeError(
            "feature-sharded training requires a SparseBatch, got "
            f"{type(batch).__name__}"
        )
    if MODEL_AXIS not in mesh.axis_names or DATA_AXIS not in mesh.axis_names:
        raise ValueError(
            f"feature-sharded training needs a (data, model) mesh, got "
            f"axes {mesh.axis_names}"
        )
    num_blocks = int(mesh.shape[MODEL_AXIS])
    data_shards = int(mesh.shape[DATA_AXIS])
    from photon_ml_tpu.optim.factory import validate_optimizer_choice

    regularization = RegularizationContext(regularization_type, elastic_net_alpha)
    objective = GLMObjective(loss_for_task(task), dim)
    use_tron = optimizer_type == OptimizerType.TRON
    use_owlqn = regularization.has_l1
    # shared TRON x regularization / loss-smoothness rules
    # (OptimizerFactory.scala:49-86)
    base = OptimizerConfig.default_for(optimizer_type)
    max_iter = max_iter if max_iter is not None else base.max_iter
    tolerance = tolerance if tolerance is not None else base.tolerance
    validate_optimizer_choice(
        OptimizerConfig(optimizer_type=optimizer_type),
        regularization,
        loss_has_hessian=objective.loss.has_hessian,
    )
    kernel = resolve_kernel(kernel, batch)
    with_norm = normalization is not None and not normalization.is_identity

    if kernel == "tiled":
        from photon_ml_tpu.ops.schedule_cache import cache_scope
        from photon_ml_tpu.ops.tiled_sparse import feature_shard_tiled_batch

        with cache_scope(tile_cache_dir):
            sharded, block_dim = feature_shard_tiled_batch(
                batch, dim, data_shards, num_blocks, mesh=mesh,
                data_axis=DATA_AXIS, model_axis=MODEL_AXIS,
            )
        meta = sharded.meta
    else:
        sharded, block_dim = feature_shard_sparse_batch(
            batch, dim, num_blocks, rows_multiple=data_shards
        )
        meta = None
    optimizer = "tron" if use_tron else ("owlqn" if use_owlqn else "lbfgs")
    layout = "tiled" if kernel == "tiled" else "sparse"
    fit = feature_sharded_glm_fit(
        objective, mesh, meta, layout=layout, optimizer=optimizer,
        max_iter=max_iter, tol=tolerance, history=history,
        with_norm=with_norm, with_box=box is not None,
        track_models=track_models,
    )
    d_pad = num_blocks * block_dim
    from photon_ml_tpu.parallel.distributed import feature_sharded_extras

    extras_tail, l1_mask, _ = feature_sharded_extras(
        dim, d_pad, normalization=normalization, box=box,
        use_owlqn=use_owlqn, intercept_index=intercept_index,
    )

    hdiag_fn = None
    if compute_variances:
        hdiag_fn = feature_sharded_hessian_diagonal(
            objective, mesh, meta, layout=layout, with_norm=with_norm,
        )
        norm_extras = extras_tail[:2] if with_norm else []

    def _to_original_space(means):
        """De-normalize back to the raw feature space, exactly like
        GLMOptimizationProblem.create_model
        (GeneralizedLinearOptimizationProblem.scala:89-95)."""
        if not with_norm:
            return means
        orig = normalization.model_to_original_space(means)
        if intercept_index is not None:
            orig = orig.at[intercept_index].add(
                normalization.intercept_adjustment(means)
            )
        return orig

    weights_desc = sorted(set(float(w) for w in regularization_weights), reverse=True)
    models: Dict[float, GeneralizedLinearModel] = {}
    results: Dict[float, OptResult] = {}
    current = jnp.zeros((d_pad,), jnp.float32)
    for lam in weights_desc:
        l1, l2 = regularization.split(lam)
        extras = (
            [jnp.float32(l1), l1_mask] if use_owlqn else []
        ) + extras_tail
        result = fit(current, sharded, jnp.float32(l2), *extras)
        variances = None
        if hdiag_fn is not None:
            from photon_ml_tpu.optim.problem import _VARIANCE_EPSILON

            hd = hdiag_fn(
                result.coefficients, sharded, jnp.float32(l2), *norm_extras
            )
            variances = (1.0 / (hd + _VARIANCE_EPSILON))[:dim]
        models[lam] = create_model(
            task,
            Coefficients(
                _to_original_space(result.coefficients[:dim]), variances
            ),
        )
        # Results carry REAL-dimension coefficients (and tracked models),
        # consistent with the replicated path; the padded vector is only
        # the warm-start currency.
        tracker = result.tracker
        if tracker.coefs is not None:
            tracker = tracker._replace(coefs=tracker.coefs[:, :dim])
        results[lam] = result._replace(
            coefficients=result.coefficients[:dim], tracker=tracker
        )
        if warm_start:
            current = result.coefficients
    return models, results


def train_grid_batched_feature_sharded(
    batch: Batch,
    task: TaskType,
    dim: int,
    *,
    mesh,
    regularization_type: RegularizationType = RegularizationType.NONE,
    regularization_weights: Sequence[float] = (0.0,),
    elastic_net_alpha: Optional[float] = None,
    max_iter: Optional[int] = None,
    tolerance: Optional[float] = None,
    history: int = 10,
    normalization: Optional[NormalizationContext] = None,
    compute_variances: bool = False,
    box: Optional[BoxConstraints] = None,
    intercept_index: Optional[int] = None,
    kernel: str = "scatter",
    optimizer_type: OptimizerType = OptimizerType.LBFGS,
    track_models: bool = False,
    tile_cache_dir: Optional[str] = None,
) -> Tuple[Dict[float, GeneralizedLinearModel], Dict[float, OptResult]]:
    """Batched λ-grid twin of :func:`train_feature_sharded`: the grid
    stacks into a [G, d_pad] bank whose feature axis shards over the
    (data, model) mesh while the grid axis is vmapped INSIDE the
    shard_map body — one compiled program, one optimizer loop, one
    schedule layout for every λ (sparse and tiled layouts both; the
    tiled cells ride the fused grid pass). No cross-member warm starts
    (each λ starts from zero), same trade as :func:`train_grid_batched`.
    """
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import SparseBatch
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.models.glm import create_model
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.optim.factory import validate_optimizer_choice
    from photon_ml_tpu.parallel.distributed import (
        feature_shard_sparse_batch,
        feature_sharded_extras,
        feature_sharded_glm_fit,
        feature_sharded_hessian_diagonal,
    )
    from photon_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    if not isinstance(batch, SparseBatch):
        raise TypeError(
            "feature-sharded training requires a SparseBatch, got "
            f"{type(batch).__name__}"
        )
    if MODEL_AXIS not in mesh.axis_names or DATA_AXIS not in mesh.axis_names:
        raise ValueError(
            f"feature-sharded training needs a (data, model) mesh, got "
            f"axes {mesh.axis_names}"
        )
    num_blocks = int(mesh.shape[MODEL_AXIS])
    data_shards = int(mesh.shape[DATA_AXIS])
    regularization = RegularizationContext(regularization_type, elastic_net_alpha)
    objective = GLMObjective(loss_for_task(task), dim)
    use_tron = optimizer_type == OptimizerType.TRON
    use_owlqn = regularization.has_l1
    base = OptimizerConfig.default_for(optimizer_type)
    max_iter = max_iter if max_iter is not None else base.max_iter
    tolerance = tolerance if tolerance is not None else base.tolerance
    validate_optimizer_choice(
        OptimizerConfig(optimizer_type=optimizer_type),
        regularization,
        loss_has_hessian=objective.loss.has_hessian,
    )
    kernel = resolve_kernel(kernel, batch)
    with_norm = normalization is not None and not normalization.is_identity

    if kernel == "tiled":
        from photon_ml_tpu.ops.schedule_cache import cache_scope
        from photon_ml_tpu.ops.tiled_sparse import feature_shard_tiled_batch

        with cache_scope(tile_cache_dir):
            sharded, block_dim = feature_shard_tiled_batch(
                batch, dim, data_shards, num_blocks, mesh=mesh,
                data_axis=DATA_AXIS, model_axis=MODEL_AXIS,
            )
        meta = sharded.meta
    else:
        sharded, block_dim = feature_shard_sparse_batch(
            batch, dim, num_blocks, rows_multiple=data_shards
        )
        meta = None
    optimizer = "tron" if use_tron else ("owlqn" if use_owlqn else "lbfgs")
    layout = "tiled" if kernel == "tiled" else "sparse"
    fit = feature_sharded_glm_fit(
        objective, mesh, meta, layout=layout, optimizer=optimizer,
        max_iter=max_iter, tol=tolerance, history=history,
        with_norm=with_norm, with_box=box is not None,
        track_models=track_models, grid=True,
    )
    d_pad = num_blocks * block_dim
    extras_tail, l1_mask, _ = feature_sharded_extras(
        dim, d_pad, normalization=normalization, box=box,
        use_owlqn=use_owlqn, intercept_index=intercept_index,
    )

    hdiag_fn = None
    if compute_variances:
        hdiag_fn = feature_sharded_hessian_diagonal(
            objective, mesh, meta, layout=layout, with_norm=with_norm,
        )
        norm_extras = extras_tail[:2] if with_norm else []

    def _to_original_space(means):
        if not with_norm:
            return means
        orig = normalization.model_to_original_space(means)
        if intercept_index is not None:
            orig = orig.at[intercept_index].add(
                normalization.intercept_adjustment(means)
            )
        return orig

    weights_desc = sorted(
        set(float(w) for w in regularization_weights), reverse=True
    )
    G = len(weights_desc)
    splits = [regularization.split(w) for w in weights_desc]
    l1_vec = jnp.asarray([s[0] for s in splits], jnp.float32)
    l2_vec = jnp.asarray([s[1] for s in splits], jnp.float32)
    w0_bank = jnp.zeros((G, d_pad), jnp.float32)
    extras = ([l1_vec, l1_mask] if use_owlqn else []) + extras_tail
    result = fit(w0_bank, sharded, l2_vec, *extras)

    models: Dict[float, GeneralizedLinearModel] = {}
    results: Dict[float, OptResult] = {}
    tracker = result.tracker
    for i, lam in enumerate(weights_desc):
        coefs_pad = result.coefficients[i]
        variances = None
        if hdiag_fn is not None:
            from photon_ml_tpu.optim.problem import _VARIANCE_EPSILON

            hd = hdiag_fn(coefs_pad, sharded, l2_vec[i], *norm_extras)
            variances = (1.0 / (hd + _VARIANCE_EPSILON))[:dim]
        models[lam] = create_model(
            task,
            Coefficients(_to_original_space(coefs_pad[:dim]), variances),
        )
        member = grid_member(result, i)
        results[lam] = member._replace(
            coefficients=coefs_pad[:dim],
            tracker=member.tracker._replace(
                coefs=(
                    tracker.coefs[i][:, :dim]
                    if tracker.coefs is not None else None
                ),
            ),
        )
    return models, results


def train_streaming_glm(
    paths,
    task: TaskType,
    *,
    regularization_type: RegularizationType = RegularizationType.NONE,
    regularization_weights: Sequence[float] = (0.0,),
    elastic_net_alpha: Optional[float] = None,
    max_iter: Optional[int] = None,
    tolerance: Optional[float] = None,
    history: int = 10,
    rows_per_chunk: int = 65536,
    cache_bytes: int = 2 << 30,
    prefetch: bool = True,
    kernel: str = "auto",
    tile_params=None,
    add_intercept: bool = True,
    field_names: str = "TRAINING_EXAMPLE",
    warm_start: bool = True,
    optimizer_type: OptimizerType = OptimizerType.LBFGS,
    normalization: Optional[NormalizationContext] = None,
    compute_variances: bool = False,
    box: Optional[BoxConstraints] = None,
    track_models: bool = False,
    fmt=None,
    index_map=None,
    stats=None,
    tile_cache_dir: Optional[str] = None,
    grid_checkpointer=None,
    preemption_guard=None,
    initial: Optional[Array] = None,
):
    """Train a GLM over Avro inputs LARGER than host RAM: every objective
    evaluation streams fixed-shape chunks from disk (io/streaming.py), so
    peak memory is bounded by one decoded file + one staged chunk. The
    host-driven L-BFGS (optim/host_lbfgs.py) walks the same iterate
    sequence as the in-memory path.

    The reference's analog is Spark's MEMORY_AND_DISK persist under
    GLMSuite.readLabeledPointsFromAvro (io/GLMSuite.scala:98-131): the
    first evaluation caches staged chunks — device-resident up to
    ``cache_bytes``, the remainder spilled as raw fixed-shape arrays to
    local scratch — so later evaluations never re-decode Avro;
    ``prefetch`` decode-aheads on a worker thread. L1/elastic-net run
    host-driven OWL-QN (minimize_owlqn_host) with the intercept exempt
    from the penalty, exactly like the in-memory path.

    Works over Avro (native chunked column decode) or LibSVM text
    (line-at-a-time) inputs — pass the matching ``fmt``; both formats
    implement the streaming protocol (stream_files/stream_rows/
    stream_scan), like the reference streams both through GLMSuite.

    Under ``jax.distributed`` (process_count > 1) the input FILES split
    across processes (multihost.process_shard — the executor-partition
    analog) and every evaluation's (value, gradient) partials reduce
    across hosts, so each host only ever reads its shard; this requires a
    PREBUILT shared index map (the FeatureIndexingJob store) because no
    single process sees the whole vocabulary.

    Returns ({lambda: model}, {lambda: OptResult}, index_map).
    """
    import numpy as np
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.io.input_format import AvroInputDataFormat
    from photon_ml_tpu.io.streaming import StreamingGLMObjective, scan_stream
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.models.glm import create_model
    from photon_ml_tpu.optim.factory import validate_optimizer_choice
    from photon_ml_tpu.optim.host_lbfgs import (
        minimize_lbfgs_host,
        minimize_owlqn_host,
    )
    from photon_ml_tpu.optim.host_tron import minimize_tron_host

    regularization = RegularizationContext(
        regularization_type, elastic_net_alpha
    )
    from photon_ml_tpu.ops.losses import loss_for_task as _loss_for_task

    use_tron = optimizer_type == OptimizerType.TRON
    base = OptimizerConfig.default_for(optimizer_type)
    max_iter = max_iter if max_iter is not None else base.max_iter
    tolerance = tolerance if tolerance is not None else base.tolerance
    # shared TRON x regularization / loss-smoothness rules
    validate_optimizer_choice(
        OptimizerConfig(optimizer_type=optimizer_type),
        regularization,
        loss_has_hessian=_loss_for_task(task).has_hessian,
    )
    if fmt is None:
        fmt = AvroInputDataFormat(
            add_intercept=add_intercept, field_names=field_names
        )
    multi = jax.process_count() > 1
    if multi:
        if index_map is None:
            raise ValueError(
                "multi-host streaming requires a prebuilt shared index "
                "map (build one with the feature-indexing job); no single "
                "process sees the whole vocabulary"
            )
        from photon_ml_tpu.io.streaming import shard_stream_files

        paths = shard_stream_files(paths, fmt)
        if stats is None:
            # local stats -> global agreement (max nnz must match across
            # processes: it fixes the compiled staging shape). A process
            # can own zero files when processes outnumber files — it
            # still joins every collective with empty partials. Callers
            # that already hold GLOBAL stats (the driver's preprocess
            # scan) skip this whole per-shard disk pass.
            from photon_ml_tpu.io.streaming import StreamStats

            if paths:
                _, local_stats = scan_stream(
                    paths, fmt, index_map=index_map
                )
            else:
                local_stats = StreamStats(num_rows=0, max_nnz=1)
            from jax.experimental import multihost_utils

            gathered = multihost_utils.process_allgather(
                np.asarray(
                    [local_stats.num_rows, local_stats.max_nnz], np.int64
                )
            )
            stats = StreamStats(
                num_rows=int(gathered[:, 0].sum()),
                max_nnz=int(gathered[:, 1].max()),
            )
    elif index_map is None or stats is None:
        index_map, stats = scan_stream(paths, fmt, index_map=index_map)
    objective = StreamingGLMObjective(
        paths, fmt, index_map, stats, task,
        rows_per_chunk=rows_per_chunk, cache_bytes=cache_bytes,
        prefetch=prefetch, kernel=kernel, tile_params=tile_params,
        norm=normalization, tile_cache_dir=tile_cache_dir,
    )
    from photon_ml_tpu.utils.index_map import intercept_key

    intercept_index = None
    if fmt.add_intercept:
        icept = index_map.get_index(intercept_key())
        if icept >= 0:
            intercept_index = icept
    l1_mask = None
    if regularization.has_l1 and intercept_index is not None:
        l1_mask = (
            jnp.ones((objective.dim,), jnp.float32)
            .at[intercept_index].set(0.0)
        )

    def _to_original_space(means):
        """De-normalize like GLMOptimizationProblem.create_model
        (GeneralizedLinearOptimizationProblem.scala:89-95)."""
        if normalization is None or normalization.is_identity:
            return means
        orig = normalization.model_to_original_space(means)
        if intercept_index is not None:
            orig = orig.at[intercept_index].add(
                normalization.intercept_adjustment(means)
            )
        return orig

    weights_desc = sorted(
        set(float(w) for w in regularization_weights), reverse=True
    )
    models: Dict[float, GeneralizedLinearModel] = {}
    results: Dict[float, OptResult] = {}
    # retrain warm start (registry.warm_start): the aligned parent
    # coefficients seed the FIRST λ exactly like `initial` on the
    # in-memory paths
    current = (
        jnp.asarray(initial, jnp.float32)
        if initial is not None
        else jnp.zeros((objective.dim,), jnp.float32)
    )
    for lam in weights_desc:
        snap = (
            grid_checkpointer.load(lam)
            if grid_checkpointer is not None
            else None
        )
        if snap is not None:
            # λ completed before the crash/preemption: restore model +
            # result and keep the warm-start chain bitwise intact
            models[lam] = _model_from_snapshot(task, snap)
            results[lam] = _result_from_snapshot(snap["result"])
            if warm_start:
                current = jnp.asarray(snap["warm_means"])
            continue
        if preemption_guard is not None and preemption_guard.requested:
            break
        l1, l2 = regularization.split(lam)
        if use_tron:
            # one streamed Hv pass per CG step — the reference's exact
            # second-order pattern (HessianVectorAggregator.scala:137-152)
            result = minimize_tron_host(
                lambda w: objective.value_and_gradient(w, l2),
                lambda w, d_: objective.hessian_vector(w, d_, l2),
                current, max_iter=max_iter, tol=tolerance, box=box,
                track_coefficients=track_models,
            )
        elif l1:
            result = minimize_owlqn_host(
                lambda w: objective.value_and_gradient(w, l2),
                current, l1, max_iter=max_iter, tol=tolerance,
                history=history, l1_mask=l1_mask, box=box,
                track_coefficients=track_models,
            )
        else:
            result = minimize_lbfgs_host(
                lambda w: objective.value_and_gradient(w, l2),
                current, max_iter=max_iter, tol=tolerance, history=history,
                box=box, track_coefficients=track_models,
            )
        variances = None
        if compute_variances:
            from photon_ml_tpu.optim.problem import _VARIANCE_EPSILON

            hd = objective.hessian_diagonal(result.coefficients, l2)
            variances = 1.0 / (hd + _VARIANCE_EPSILON)
        models[lam] = create_model(
            task,
            Coefficients(
                _to_original_space(result.coefficients), variances
            ),
        )
        results[lam] = result
        if grid_checkpointer is not None:
            _save_lambda_snapshot(
                grid_checkpointer, lam, result.coefficients,
                models[lam], result,
            )
        if warm_start:
            current = result.coefficients
    return models, results, index_map


def train_streaming_feature_sharded(
    paths,
    task: TaskType,
    *,
    mesh,
    regularization_type: RegularizationType = RegularizationType.NONE,
    regularization_weights: Sequence[float] = (0.0,),
    elastic_net_alpha: Optional[float] = None,
    max_iter: Optional[int] = None,
    tolerance: Optional[float] = None,
    history: int = 10,
    rows_per_chunk: int = 65536,
    cache_bytes: int = 2 << 30,
    sharded_cache_bytes: int = 2 << 30,
    prefetch: bool = True,
    add_intercept: bool = True,
    field_names: str = "TRAINING_EXAMPLE",
    warm_start: bool = True,
    optimizer_type: OptimizerType = OptimizerType.LBFGS,
    compute_variances: bool = False,
    box: Optional[BoxConstraints] = None,
    track_models: bool = False,
    fmt=None,
    index_map=None,
    stats=None,
    spill_dir=None,
):
    """Streaming x feature-sharded GLM: dataset > host RAM AND model >
    single-chip HBM at once. Rows stream through the staged-chunk
    pipeline; every chunk re-stages per feature block on the (data,
    model) mesh (io.streaming.FeatureShardedStreamingObjective); the
    host-driven L-BFGS/OWL-QN/TRON walk the same iterate sequences as
    their in-memory counterparts, with TRON paying one streamed sharded
    Hv pass per CG step (the reference's
    one-treeAggregate-per-CG-iteration loop with chunks standing in for
    executor partitions).

    Single process only (the multi-host composition would need the
    cross-host reduce inside each sharded fold); normalization is not
    supported on this path yet — the driver validates both up front.

    Returns ({lambda: model}, {lambda: OptResult}, index_map).
    """
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.io.input_format import AvroInputDataFormat
    from photon_ml_tpu.io.streaming import (
        FeatureShardedStreamingObjective,
        scan_stream,
    )
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.models.glm import create_model
    from photon_ml_tpu.optim.factory import validate_optimizer_choice
    from photon_ml_tpu.optim.host_lbfgs import (
        minimize_lbfgs_host,
        minimize_owlqn_host,
    )
    from photon_ml_tpu.optim.host_tron import minimize_tron_host

    if jax.process_count() > 1:
        raise ValueError(
            "streaming feature-sharded training is single-process"
        )
    regularization = RegularizationContext(
        regularization_type, elastic_net_alpha
    )
    from photon_ml_tpu.ops.losses import loss_for_task as _loss_for_task

    use_tron = optimizer_type == OptimizerType.TRON
    base = OptimizerConfig.default_for(optimizer_type)
    max_iter = max_iter if max_iter is not None else base.max_iter
    tolerance = tolerance if tolerance is not None else base.tolerance
    validate_optimizer_choice(
        OptimizerConfig(optimizer_type=optimizer_type),
        regularization,
        loss_has_hessian=_loss_for_task(task).has_hessian,
    )
    if fmt is None:
        fmt = AvroInputDataFormat(
            add_intercept=add_intercept, field_names=field_names
        )
    if index_map is None or stats is None:
        index_map, stats = scan_stream(paths, fmt, index_map=index_map)
    objective = FeatureShardedStreamingObjective(
        paths, fmt, index_map, stats, task, mesh,
        rows_per_chunk=rows_per_chunk, cache_bytes=cache_bytes,
        sharded_cache_bytes=sharded_cache_bytes, prefetch=prefetch,
        spill_dir=spill_dir,
    )
    dim, d_pad = objective.dim, objective.d_pad
    from photon_ml_tpu.utils.index_map import intercept_key

    intercept_index = None
    if fmt.add_intercept:
        icept = index_map.get_index(intercept_key())
        if icept >= 0:
            intercept_index = icept
    l1_mask = None
    if regularization.has_l1:
        # padded tail exempt from the penalty (its gradient is zero and
        # it must stay at exactly 0), intercept exempt like the
        # replicated path
        l1_mask = jnp.concatenate(
            [jnp.ones((dim,), jnp.float32),
             jnp.zeros((d_pad - dim,), jnp.float32)]
        )
        if intercept_index is not None:
            l1_mask = l1_mask.at[intercept_index].set(0.0)
    box_pad = box
    if box is not None:
        from photon_ml_tpu.optim.common import BoxConstraints as _Box

        # padding coordinates get (-inf, inf): projection must not move
        # them off exactly 0
        box_pad = _Box(
            lower=jnp.concatenate(
                [jnp.asarray(box.lower, jnp.float32),
                 jnp.full((d_pad - dim,), -jnp.inf, jnp.float32)]
            ),
            upper=jnp.concatenate(
                [jnp.asarray(box.upper, jnp.float32),
                 jnp.full((d_pad - dim,), jnp.inf, jnp.float32)]
            ),
        )

    weights_desc = sorted(
        set(float(w) for w in regularization_weights), reverse=True
    )
    models: Dict[float, GeneralizedLinearModel] = {}
    results: Dict[float, OptResult] = {}
    current = jnp.zeros((d_pad,), jnp.float32)
    for lam in weights_desc:
        l1, l2 = regularization.split(lam)
        if use_tron:
            result = minimize_tron_host(
                lambda w: objective.value_and_gradient(w, l2),
                lambda w, d_: objective.hessian_vector(w, d_, l2),
                current, max_iter=max_iter, tol=tolerance, box=box_pad,
                track_coefficients=track_models,
            )
        elif l1:
            result = minimize_owlqn_host(
                lambda w: objective.value_and_gradient(w, l2),
                current, l1, max_iter=max_iter, tol=tolerance,
                history=history, l1_mask=l1_mask, box=box_pad,
                track_coefficients=track_models,
            )
        else:
            result = minimize_lbfgs_host(
                lambda w: objective.value_and_gradient(w, l2),
                current, max_iter=max_iter, tol=tolerance, history=history,
                box=box_pad, track_coefficients=track_models,
            )
        variances = None
        if compute_variances:
            from photon_ml_tpu.optim.problem import _VARIANCE_EPSILON

            hd = objective.hessian_diagonal(result.coefficients, l2)
            variances = (1.0 / (hd + _VARIANCE_EPSILON))[:dim]
        models[lam] = create_model(
            task, Coefficients(result.coefficients[:dim], variances)
        )
        tracker = result.tracker
        if tracker.coefs is not None:
            tracker = tracker._replace(coefs=tracker.coefs[:, :dim])
        results[lam] = result._replace(
            coefficients=result.coefficients[:dim], tracker=tracker
        )
        if warm_start:
            current = result.coefficients
    return models, results, index_map


def grid_result_scalars(
    results: Dict[float, OptResult],
) -> Dict[float, Tuple[int, float, int, int]]:
    """{lambda: (iterations, value, reason, evaluations)} with ONE
    batched readback for the whole grid (parallel/overlap
    deferred-readback discipline); ``evaluations`` is -1 for a result
    restored from a snapshot that did not count them.

    Every OptResult's scalars are device-resident futures until someone
    forces them; the pre-overlap consumers pulled three scalars per
    lambda serially — each a synchronous host<->device round trip, paid
    once per grid entry. One device_get materializes the lot."""
    from photon_ml_tpu.parallel import overlap

    items = list(results.items())
    fetched = overlap.device_get(
        [
            (res.iterations, res.value, res.reason, res.evaluations)
            for _, res in items
        ]
    )
    return {
        lam: (int(it), float(value), int(reason), int(evaluations))
        for (lam, _), (it, value, reason, evaluations) in zip(items, fetched)
    }


def iteration_models(
    result: OptResult,
    task: TaskType,
    normalization: Optional[NormalizationContext] = None,
    intercept_index: Optional[int] = None,
) -> List[GeneralizedLinearModel]:
    """Per-iteration models from a tracked OptResult (ModelTracker.models
    analog): slot 0 is the initial point, slot i the accepted iterate i.
    Coefficients are de-normalized to the original feature space exactly
    like the final model (GeneralizedLinearOptimizationProblem.scala:89-95).
    """
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.optim.problem import create_glm_problem

    if result.tracker.coefs is None:
        raise ValueError(
            "OptResult has no coefficient history; train with "
            "track_models=True"
        )
    problem = create_glm_problem(
        task, int(result.tracker.coefs.shape[1]),
        intercept_index=intercept_index,
    )
    count = int(result.tracker.count)
    return [
        problem.create_model(
            Coefficients(result.tracker.coefs[i]), normalization
        )
        for i in range(count)
    ]
