"""Feature-sharded GLM objectives and training steps under shard_map.

Reference mapping (SURVEY §2.3/§2.4):
- P1 data parallelism (examples sharded over the "data" axis, coefficients
  replicated, (value, grad, Hv) psum'ed — DistributedGLMLossFunction +
  ValueAndGradientAggregator.treeAggregate,
  ValueAndGradientAggregator.scala:235-250) lives in
  ``optim.problem.GLMOptimizationProblem.run(mesh=)``, not here.
- Feature/coefficient parallelism ("model" axis): for coefficient vectors
  too big to replicate, margins decompose over feature blocks
  (z = sum_blocks x_b . w_b -> psum over "model"), and each device keeps
  only its gradient/optimizer-state block — the reduce-scatter/all-gather
  recipe of sequence parallelism applied to the feature axis (the 10B-coef
  design addition; no literal analog exists in the reference).

Both run the UNMODIFIED optimizers from photon_ml_tpu.optim: the objective
closure psums, so LBFGS/OWLQN/TRON never know they are distributed —
exactly how the reference reuses one Optimizer against Distributed vs
SingleNode objectives (SURVEY L2/L3).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim.common import OptResult
from photon_ml_tpu.optim.lbfgs import minimize_lbfgs
from photon_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

Array = jnp.ndarray


def _opt_result_specs(model_axis: str, track_models: bool = False) -> OptResult:
    """out_specs pytree for an OptResult whose coefficient vector is sharded
    over ``model_axis`` while every scalar/trace is replicated (scalars are
    psum'ed mesh-global inside the optimizer, so they agree on all ranks).
    With ``track_models`` the per-iteration coefficient stack is sharded
    over its feature axis like the coefficients themselves."""
    from photon_ml_tpu.optim.common import Tracker

    return OptResult(
        coefficients=P(model_axis),
        value=P(),
        grad_norm=P(),
        iterations=P(),
        reason=P(),
        tracker=Tracker(
            values=P(), grad_norms=P(), count=P(),
            coefs=P(None, model_axis) if track_models else None,
        ),
        evaluations=P(),
    )


def _opt_result_grid_specs(
    model_axis: str, track_models: bool = False
) -> OptResult:
    """Grid-batched variant of :func:`_opt_result_specs`: every field
    carries a leading [G] grid axis (replicated — the grid members live
    on every device), with the coefficient banks still sharded over
    ``model_axis`` on their feature axis."""
    from photon_ml_tpu.optim.common import Tracker

    return OptResult(
        coefficients=P(None, model_axis),
        value=P(),
        grad_norm=P(),
        iterations=P(),
        reason=P(),
        tracker=Tracker(
            values=P(), grad_norms=P(), count=P(),
            coefs=P(None, None, model_axis) if track_models else None,
        ),
        evaluations=P(),
    )


# ---------------------------------------------------------------------------
# Sparse feature sharding (the 10B-coefficient layout)
# ---------------------------------------------------------------------------


class FeatureShardedSparseBatch(NamedTuple):
    """A SparseBatch re-laid-out for 2-D (data x model) sharding.

    At the 10B-coefficient north star the data is sparse by definition
    (SURVEY §2.3 "coefficient parallelism"); the dense [n, d] layout above
    cannot even be materialized. Here each feature block owns the entries
    whose feature id falls in its slice of the (padded) vocabulary:

    - ``indices[M, n, kb]`` int32 — BLOCK-LOCAL feature ids (global id
      minus block offset); slot (m, i, :) holds row i's entries landing in
      block m, zero-padded.
    - ``values[M, n, kb]`` — matching values, zero-padded (a padded slot
      contributes 0 * w_block[0]).
    - ``labels/offsets/weights[n]`` — row metadata, sharded over "data".

    Leading axis M shards over "model", rows shard over "data", so the
    shard_map block is [1, n/Dd, kb]. kb is the max per-(row, block) entry
    count — for hashed/uniform feature ids kb ~ k/M; worst case k.
    """

    indices: Array  # int32 [M, n, kb] block-local
    values: Array  # float [M, n, kb]
    labels: Array  # [n]
    offsets: Array  # [n]
    weights: Array  # [n]

    @property
    def num_blocks(self) -> int:
        return self.indices.shape[0]

    @property
    def num_rows(self) -> int:
        return self.indices.shape[1]


def feature_shard_sparse_batch(
    batch,
    dim: int,
    num_blocks: int,
    *,
    rows_multiple: int = 1,
    pad_nnz_to: int = 8,
) -> Tuple[FeatureShardedSparseBatch, int]:
    """Host-side re-layout of a SparseBatch into per-feature-block slabs.

    Returns (sharded_batch, block_dim) with block_dim = ceil(dim /
    num_blocks) rounded so every block covers an equal slice; the sharded
    coefficient vector has length num_blocks * block_dim (callers pad /
    slice against ``dim``). The partition is the static analog of the
    reference's hash-partitioned feature vocabulary
    (FeatureIndexingJob.scala:90-136) — but by contiguous range, so a
    block's ids gather from a dense local window.
    """
    import numpy as np

    idx = np.asarray(batch.indices)
    val = np.asarray(batch.values)
    n, k = idx.shape
    n_pad = ((n + rows_multiple - 1) // rows_multiple) * rows_multiple
    block_dim = -(-dim // num_blocks)

    block_of = idx // block_dim  # [n, k]
    local = idx - block_of * block_dim
    # Entries with value exactly 0 (padding) are inert wherever they land;
    # route them to block 0 so kb reflects real entries only.
    real = val != 0.0
    block_of = np.where(real, block_of, 0)

    # Vectorized routing: rank each real entry within its (block, row)
    # group via a stable sort; one scatter builds all slabs at once.
    rows_b = np.broadcast_to(np.arange(n)[:, None], (n, k))
    flat_key = (block_of * n + rows_b).ravel()  # group id per entry
    order = np.argsort(flat_key + (~real).ravel() * (num_blocks * n), kind="stable")
    sorted_key = flat_key[order]
    n_real = int(real.sum())
    group_start = np.searchsorted(sorted_key[:n_real], sorted_key[:n_real], side="left")
    slot = np.arange(n_real) - group_start  # rank within group

    counts = np.bincount(flat_key[real.ravel()], minlength=num_blocks * n)
    kb = int(max(counts.max(initial=0), 1))
    kb = ((kb + pad_nnz_to - 1) // pad_nnz_to) * pad_nnz_to

    out_idx = np.zeros((num_blocks, n_pad, kb), np.int32)
    out_val = np.zeros((num_blocks, n_pad, kb), val.dtype)
    sel = order[:n_real]
    b_sel = block_of.ravel()[sel]
    r_sel = rows_b.ravel()[sel]
    out_idx[b_sel, r_sel, slot] = local.ravel()[sel]
    out_val[b_sel, r_sel, slot] = val.ravel()[sel]

    def pad_rows(a):
        if n_pad == n:
            return a
        return np.concatenate([a, np.zeros((n_pad - n,), a.dtype)])

    sharded = FeatureShardedSparseBatch(
        indices=jnp.asarray(out_idx),
        values=jnp.asarray(out_val),
        labels=jnp.asarray(pad_rows(np.asarray(batch.labels))),
        offsets=jnp.asarray(pad_rows(np.asarray(batch.offsets))),
        weights=jnp.asarray(pad_rows(np.asarray(batch.weights))),
    )
    return sharded, block_dim


def _sparse_shard_specs(model_axis: str, data_axis: str):
    return (
        P(model_axis),
        FeatureShardedSparseBatch(
            indices=P(model_axis, data_axis),
            values=P(model_axis, data_axis),
            labels=P(data_axis),
            offsets=P(data_axis),
            weights=P(data_axis),
        ),
        P(),
    )


def _sparse_block_vg(loss, b, l2, model_axis: str, data_axis: str,
                     shift=None, factor=None):
    """Block-local (value, grad) closure shared by the sparse-sharded
    value_and_grad and fit entry points. ``b`` is this device's shard:
    one feature block x its rows.

    ``shift``/``factor``: this block's slice of the lazy normalization
    vectors (NormalizationContext.scala:119-157) — margins use
    w_eff = factor * w and subtract the psum'd shift.w_eff scalar; the
    gradient un-shifts with the data-psum'd prefactor."""
    assert b.indices.shape[0] == 1, (
        f"got {b.indices.shape[0]} feature blocks per device; "
        "num_blocks passed to feature_shard_sparse_batch must equal the "
        "mesh's model-axis size"
    )
    idx = b.indices[0]  # [n_loc, kb] block-local
    val = b.values[0]

    def vg(w_block):
        w_eff = w_block if factor is None else w_block * factor
        raw = jnp.sum(val * w_eff[idx], axis=-1)
        if shift is not None:
            raw = raw - jnp.vdot(shift, w_eff)
        z = jax.lax.psum(raw, model_axis) + b.offsets
        c = b.weights * loss.d1(z, b.labels)
        value = jax.lax.psum(
            jnp.sum(b.weights * loss.value(z, b.labels)), data_axis
        )
        grad_block = jax.lax.psum(
            jnp.zeros_like(w_block).at[idx].add(c[:, None] * val), data_axis
        )
        if shift is not None or factor is not None:
            prefactor = jax.lax.psum(jnp.sum(c), data_axis)
            if shift is not None:
                grad_block = grad_block - shift * prefactor
            if factor is not None:
                grad_block = grad_block * factor
        w_sq = jax.lax.psum(jnp.vdot(w_block, w_block), model_axis)
        return value + 0.5 * l2 * w_sq, grad_block + l2 * w_block

    return vg


def _sparse_block_hvp_factory(loss, b, l2, model_axis: str, data_axis: str,
                              shift=None, factor=None):
    """Block-local Hessian-vector FACTORY over one device's shard — the
    distributed HessianVectorAggregator analog
    (HessianVectorAggregator.scala:137-152). The w-only pieces (margins
    psum, second-derivative coefficients) are computed once per outer
    TRON iteration; each CG step then costs one psum of the direction's
    partial margins over "model" plus one psum of the block product over
    "data"."""
    idx = b.indices[0]
    val = b.values[0]

    def _z(x_block):
        raw = jnp.sum(val * x_block[idx], axis=-1)
        if shift is not None:
            raw = raw - jnp.vdot(shift, x_block)
        return raw

    def _eff(x_block):
        return x_block if factor is None else x_block * factor

    def factory(w_block):
        z = jax.lax.psum(_z(_eff(w_block)), model_axis) + b.offsets
        d2c = b.weights * loss.d2(z, b.labels)

        def hvp(d_block):
            zd = jax.lax.psum(_z(_eff(d_block)), model_axis)
            c = d2c * zd
            h_block = jax.lax.psum(
                jnp.zeros_like(d_block).at[idx].add(c[:, None] * val),
                data_axis,
            )
            if shift is not None or factor is not None:
                prefactor = jax.lax.psum(jnp.sum(c), data_axis)
                if shift is not None:
                    h_block = h_block - shift * prefactor
                if factor is not None:
                    h_block = h_block * factor
            return h_block + l2 * d_block

        return hvp

    return factory


def _sparse_block_hdiag(loss, b, l2, model_axis: str, data_axis: str,
                        shift=None, factor=None):
    """Block-local Hessian-diagonal closure (the variance computation of
    DistributedOptimizationProblem.scala:79-93 on the sharded layout):
    diag_j only touches feature j's entries, so it shards trivially —
    one scatter of c * val^2 psum'd over "data" (plus S1/S0 terms in the
    shifted space when normalization is active)."""
    idx = b.indices[0]
    val = b.values[0]

    def hdiag(w_block):
        w_eff = w_block if factor is None else w_block * factor
        raw = jnp.sum(val * w_eff[idx], axis=-1)
        if shift is not None:
            raw = raw - jnp.vdot(shift, w_eff)
        z = jax.lax.psum(raw, model_axis) + b.offsets
        c = b.weights * loss.d2(z, b.labels)
        s2 = jax.lax.psum(
            jnp.zeros_like(w_block).at[idx].add(c[:, None] * val**2),
            data_axis,
        )
        if shift is not None:
            s1 = jax.lax.psum(
                jnp.zeros_like(w_block).at[idx].add(c[:, None] * val),
                data_axis,
            )
            s0 = jax.lax.psum(jnp.sum(c), data_axis)
            diag = s2 - 2.0 * shift * s1 + (shift**2) * s0
        else:
            diag = s2
        if factor is not None:
            diag = diag * factor**2
        return diag + l2

    return hdiag


def feature_sharded_sparse_value_and_grad(
    objective: GLMObjective,
    mesh: Mesh,
    *,
    data_axis: str = DATA_AXIS,
    model_axis: str = MODEL_AXIS,
) -> Callable:
    """(w, sharded_batch, l2) -> (value, grad) over the sparse 2-D layout;
    value replicated, grad sharded over ``model_axis``."""
    loss = objective.loss

    # photon: sharding(axes=[data,model], in=?, out=[r,model])
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=_sparse_shard_specs(model_axis, data_axis),
        out_specs=(P(), P(model_axis)),
        check_vma=False,
    )
    def vg(w_block, b, l2):
        return _sparse_block_vg(loss, b, l2, model_axis, data_axis)(w_block)

    return jax.jit(vg)


def feature_sharded_sparse_hessian_vector(
    objective: GLMObjective,
    mesh: Mesh,
    *,
    data_axis: str = DATA_AXIS,
    model_axis: str = MODEL_AXIS,
) -> Callable:
    """(w, direction, sharded_batch, l2) -> H(w) @ d over the sparse 2-D
    layout, direction/result sharded over ``model_axis`` — the per-chunk
    building block of the STREAMED feature-sharded TRON (one streamed
    pass per CG step, accumulated chunk by chunk, exactly the
    HessianVectorAggregator.scala:137-152 aggregate with the chunk loop
    standing in for the executor partitions)."""
    loss = objective.loss

    # photon: sharding(axes=[data,model], in=?, out=[model])
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(model_axis), P(model_axis),
        ) + _sparse_shard_specs(model_axis, data_axis)[1:],
        out_specs=P(model_axis),
        check_vma=False,
    )
    def hv(w_block, d_block, b, l2):
        factory = _sparse_block_hvp_factory(
            loss, b, l2, model_axis, data_axis
        )
        return factory(w_block)(d_block)

    return jax.jit(hv)


# Jitted feature-sharded fit programs shared across builder calls: a
# GAME combo grid builds fresh coordinates (and fresh fit closures) per
# combo, and without sharing each pays a multi-second re-trace of the
# optimizer while_loop over the schedule pytrees (the round-2 lesson
# problem.py's _FIT_CACHE already encodes for the replicated path).
# Keyed by mesh CONTENT — shardings over content-equal meshes are
# interchangeable. FIFO-bounded; unhashable keys (e.g. array-carrying
# normalization contexts inside the objective) skip the cache.
_FS_FIT_CACHE: dict = {}
_FS_FIT_CACHE_MAX = 16


def _mesh_content_key(mesh: Mesh):
    # platform included: device ids are only unique PER platform, and a
    # process can hold both a CPU mesh (interpret fallback) and an
    # accelerator mesh with identical axes/ids
    return (
        tuple(mesh.axis_names),
        tuple(int(n) for n in mesh.devices.shape),
        tuple((d.platform, d.id) for d in mesh.devices.flat),
    )


def feature_sharded_glm_fit(
    objective: GLMObjective,
    mesh: Mesh,
    meta=None,
    *,
    layout: str = "sparse",  # "sparse" | "tiled"
    optimizer: str = "lbfgs",  # "lbfgs" | "owlqn" | "tron"
    data_axis: str = DATA_AXIS,
    model_axis: str = MODEL_AXIS,
    max_iter: int = 50,
    tol: float = 1e-7,
    history: int = 10,
    max_cg: int = 20,
    with_norm: bool = False,
    with_box: bool = False,
    track_models: bool = False,
    interpret: Optional[bool] = None,
    grid: bool = False,
) -> Callable:
    """Unified feature-sharded fit builder: every optimizer x layout x
    feature combination the replicated path supports, on the 2-D
    (data, model) mesh. The reference composes normalization
    (NormalizationContext.scala:119-157, applied inside aggregators),
    variances (DistributedOptimizationProblem.scala:79-93), and box
    projection (LBFGS.scala:77) freely with distribution; so do we —
    Hdiag and the box projection are block-local/elementwise, and the
    lazy shift/factor algebra shards along the feature axis with one
    extra psum'd scalar.

    Returns ``fit(w0, batch, l2, *extras)`` where extras are, in order:
    ``l1, l1_mask`` (owlqn), ``shift, factor`` (with_norm; full [d_pad]
    vectors, sharded over the model axis), ``lower, upper`` (with_box;
    full [d_pad] vectors). ``meta`` is required for the tiled layout.

    ``grid=True`` builds the batched λ-grid variant: ``w0`` becomes a
    [G, d_pad] coefficient bank, ``l2`` (and owlqn's ``l1``) become [G]
    vectors, and the shard_map body runs ``vmap(optimizer)`` over the
    grid axis — every member's block solve shares ONE compiled program
    and, on the tiled layout, one fused schedule walk per data pass
    (ops.tiled_sparse._bilinear_pass_auto's custom_vmap rule). The
    returned OptResult carries a leading grid axis on every field.
    """
    if optimizer not in ("lbfgs", "owlqn", "tron"):
        raise ValueError(f"unknown optimizer {optimizer!r}")
    if layout not in ("sparse", "tiled"):
        raise ValueError(f"unknown layout {layout!r}")
    if layout == "tiled":
        if meta is None:
            raise ValueError("tiled layout requires the batch meta")
        from photon_ml_tpu.utils.backend import effective_platform

        if interpret is None:
            interpret = effective_platform() == "cpu"
    cache_key = (
        objective, _mesh_content_key(mesh), meta, layout, optimizer,
        data_axis, model_axis, max_iter, tol, history, max_cg,
        with_norm, with_box, track_models, interpret, grid,
    )
    from photon_ml_tpu.utils.memo import get_or_build

    return get_or_build(
        _FS_FIT_CACHE, _FS_FIT_CACHE_MAX, cache_key,
        lambda: _build_feature_sharded_glm_fit(
            objective, mesh, meta, layout=layout, optimizer=optimizer,
            data_axis=data_axis, model_axis=model_axis, max_iter=max_iter,
            tol=tol, history=history, max_cg=max_cg, with_norm=with_norm,
            with_box=with_box, track_models=track_models,
            interpret=interpret, grid=grid,
        ),
    )


def _build_feature_sharded_glm_fit(
    objective: GLMObjective,
    mesh: Mesh,
    meta,
    *,
    layout: str,
    optimizer: str,
    data_axis: str,
    model_axis: str,
    max_iter: int,
    tol: float,
    history: int,
    max_cg: int,
    with_norm: bool,
    with_box: bool,
    track_models: bool,
    interpret: Optional[bool],
    grid: bool = False,
) -> Callable:
    from photon_ml_tpu.optim.common import BoxConstraints
    from photon_ml_tpu.optim.lbfgs import minimize_owlqn
    from photon_ml_tpu.optim.tron import minimize_tron

    loss = objective.loss
    owlqn = optimizer == "owlqn"
    tron = optimizer == "tron"

    extra_specs = []
    if owlqn:
        extra_specs += [P(), P(model_axis)]  # l1, l1_mask
    if with_norm:
        extra_specs += [P(model_axis), P(model_axis)]  # shift, factor
    if with_box:
        extra_specs += [P(model_axis), P(model_axis)]  # lower, upper

    def _unpack(extras):
        i = 0
        l1 = l1_mask = shift = factor = box = None
        if owlqn:
            l1, l1_mask = extras[0], extras[1]
            i = 2
        if with_norm:
            shift, factor = extras[i], extras[i + 1]
            i += 2
        if with_box:
            box = BoxConstraints(lower=extras[i], upper=extras[i + 1])
        return l1, l1_mask, shift, factor, box

    def _dispatch(vg, hvp_factory, w0_block, l1, l1_mask, box):
        if tron:
            return minimize_tron(
                vg, None, w0_block, max_iter=max_iter, tol=tol,
                max_cg=max_cg, box=box, axis_name=model_axis,
                hvp_factory=hvp_factory, track_coefficients=track_models,
            )
        if owlqn:
            return minimize_owlqn(
                vg, w0_block, l1, max_iter=max_iter, tol=tol,
                history=history, l1_mask=l1_mask, box=box,
                axis_name=model_axis, track_coefficients=track_models,
            )
        return minimize_lbfgs(
            vg, w0_block, max_iter=max_iter, tol=tol, history=history,
            box=box, axis_name=model_axis, track_coefficients=track_models,
        )

    def _solve(make_vg, make_factory, w0_block, l1, l2, l1_mask, box):
        """One block solve (grid=False) or the vmapped bank of G solves
        (grid=True: w0_block is [G, d_block], l1/l2 are [G] — one
        program, per-member convergence masked by the batched
        while_loop)."""
        if not grid:
            vg = make_vg(l2)
            factory = make_factory(l2) if tron else None
            return _dispatch(vg, factory, w0_block, l1, l1_mask, box)
        l1_vec = (
            l1 if l1 is not None
            else jnp.zeros((w0_block.shape[0],), w0_block.dtype)
        )

        def run_one(w0_b, l1_, l2_):
            vg = make_vg(l2_)
            factory = make_factory(l2_) if tron else None
            return _dispatch(vg, factory, w0_b, l1_, l1_mask, box)

        return jax.vmap(run_one)(w0_block, l1_vec, l2)

    w0_spec = P(None, model_axis) if grid else P(model_axis)
    out_specs = (
        _opt_result_grid_specs(model_axis, track_models)
        if grid else _opt_result_specs(model_axis, track_models)
    )

    if layout == "tiled":
        from photon_ml_tpu.ops.tiled_sparse import (
            FeatureShardedTiledBatch,
            tiled_block_local_hvp_factory,
            tiled_block_local_vg,
        )

        sched_spec = P((data_axis, model_axis))

        # photon: sharding(axes=[data,model], in=?, out=?)
        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(
                w0_spec, sched_spec, sched_spec,
                P(data_axis), P(data_axis), P(data_axis), P(),
                tuple(extra_specs),
            ),
            out_specs=out_specs,
            check_vma=False,
        )
        def _fit(w0_block, z_sched, g_sched, labels, offsets, weights, l2,
                 extras):
            l1, l1_mask, shift, factor, box = _unpack(extras)
            cell = FeatureShardedTiledBatch(
                meta, z_sched, g_sched, labels, offsets, weights
            )

            def make_vg(l2_):
                return tiled_block_local_vg(
                    loss, cell, data_axis, model_axis, l2_,
                    shift=shift, factor=factor, interpret=interpret,
                )

            def make_factory(l2_):
                return tiled_block_local_hvp_factory(
                    loss, cell, data_axis, model_axis, l2_,
                    shift=shift, factor=factor, interpret=interpret,
                )

            return _solve(
                make_vg, make_factory, w0_block, l1, l2, l1_mask, box
            )

        def fit(w0, batch, l2, *extras):
            return _fit(
                w0, batch.z_sched, batch.g_sched, batch.labels,
                batch.offsets, batch.weights, l2, tuple(extras),
            )
    else:

        # photon: sharding(axes=[data,model], in=?, out=?)
        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(w0_spec,)
            + _sparse_shard_specs(model_axis, data_axis)[1:]
            + (tuple(extra_specs),),
            out_specs=out_specs,
            check_vma=False,
        )
        def _fit(w0_block, b, l2, extras):
            l1, l1_mask, shift, factor, box = _unpack(extras)

            def make_vg(l2_):
                return _sparse_block_vg(
                    loss, b, l2_, model_axis, data_axis,
                    shift=shift, factor=factor,
                )

            def make_factory(l2_):
                return _sparse_block_hvp_factory(
                    loss, b, l2_, model_axis, data_axis,
                    shift=shift, factor=factor,
                )

            return _solve(
                make_vg, make_factory, w0_block, l1, l2, l1_mask, box
            )

        def fit(w0, batch, l2, *extras):
            return _fit(w0, batch, l2, tuple(extras))

    return jax.jit(fit)


def feature_sharded_extras(
    dim: int,
    d_pad: int,
    *,
    normalization=None,
    box=None,
    use_owlqn: bool = False,
    intercept_index: Optional[int] = None,
):
    """Assemble feature_sharded_glm_fit's positional extras protocol in
    ONE place (fit call order: [l1, l1_mask] from the caller, then this
    tail = [shift, factor] when normalization is active, then
    [lower, upper] when a box is given — all padded to [d_pad] with inert
    fills). Returns ``(extras_tail, l1_mask, with_norm)``; ``l1_mask`` is
    None unless ``use_owlqn`` (intercept exempt, like the replicated
    GLMOptimizationProblem._l1_mask). Both train_feature_sharded and the
    GAME FixedEffectCoordinate build their calls from here so the
    protocol cannot silently diverge."""
    with_norm = normalization is not None and not normalization.is_identity

    def _pad(v, fill):
        v = jnp.asarray(v, jnp.float32)
        if v.shape[0] == d_pad:
            return v
        return jnp.concatenate(
            [v, jnp.full((d_pad - v.shape[0],), fill, jnp.float32)]
        )

    extras_tail = []
    if with_norm:
        # padded slots are inert: shift 0, factor 1
        extras_tail += [
            _pad(
                normalization.shift
                if normalization.shift is not None
                else jnp.zeros((dim,), jnp.float32),
                0.0,
            ),
            _pad(
                normalization.factor
                if normalization.factor is not None
                else jnp.ones((dim,), jnp.float32),
                1.0,
            ),
        ]
    if box is not None:
        # padded slots unconstrained so padding coefficients stay at 0
        extras_tail += [_pad(box.lower, -jnp.inf), _pad(box.upper, jnp.inf)]
    l1_mask = None
    if use_owlqn:
        l1_mask = jnp.ones((d_pad,), jnp.float32)
        if intercept_index is not None:
            l1_mask = l1_mask.at[intercept_index].set(0.0)
    return extras_tail, l1_mask, with_norm


def feature_sharded_hessian_diagonal(
    objective: GLMObjective,
    mesh: Mesh,
    meta=None,
    *,
    layout: str = "sparse",
    data_axis: str = DATA_AXIS,
    model_axis: str = MODEL_AXIS,
    with_norm: bool = False,
    interpret: Optional[bool] = None,
) -> Callable:
    """Hessian diagonal over the feature-sharded layouts — the variance
    computation (DistributedOptimizationProblem.scala:79-93) composed with
    feature sharding. Returns ``hdiag(w, batch, l2[, shift, factor])``
    producing the full [d_pad] diagonal (gathered across blocks)."""
    loss = objective.loss
    if layout == "tiled":
        if meta is None:
            raise ValueError("tiled layout requires the batch meta")
        from photon_ml_tpu.utils.backend import effective_platform

        if interpret is None:
            interpret = effective_platform() == "cpu"
    norm_specs = (P(model_axis), P(model_axis)) if with_norm else ()

    if layout == "tiled":
        from photon_ml_tpu.ops.tiled_sparse import (
            FeatureShardedTiledBatch,
            tiled_block_local_hdiag,
        )

        sched_spec = P((data_axis, model_axis))

        # photon: sharding(axes=[data,model], in=?, out=[model])
        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(
                P(model_axis), sched_spec, sched_spec,
                P(data_axis), P(data_axis), P(data_axis), P(),
                tuple(norm_specs),
            ),
            out_specs=P(model_axis),
            check_vma=False,
        )
        def _hdiag(w_block, z_sched, g_sched, labels, offsets, weights, l2,
                   extras):
            shift, factor = extras if with_norm else (None, None)
            cell = FeatureShardedTiledBatch(
                meta, z_sched, g_sched, labels, offsets, weights
            )
            return tiled_block_local_hdiag(
                loss, cell, data_axis, model_axis, l2,
                shift=shift, factor=factor, interpret=interpret,
            )(w_block)

        def hdiag(w, batch, l2, *extras):
            return _hdiag(
                w, batch.z_sched, batch.g_sched, batch.labels,
                batch.offsets, batch.weights, l2, tuple(extras),
            )
    else:

        # photon: sharding(axes=[data,model], in=?, out=[model])
        @partial(
            shard_map,
            mesh=mesh,
            in_specs=_sparse_shard_specs(model_axis, data_axis)
            + (tuple(norm_specs),),
            out_specs=P(model_axis),
            check_vma=False,
        )
        def _hdiag(w_block, b, l2, extras):
            shift, factor = extras if with_norm else (None, None)
            return _sparse_block_hdiag(
                loss, b, l2, model_axis, data_axis,
                shift=shift, factor=factor,
            )(w_block)

        def hdiag(w, batch, l2, *extras):
            return _hdiag(w, batch, l2, tuple(extras))

    return jax.jit(hdiag)
