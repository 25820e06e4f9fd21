"""Factored random effects: a per-entity model in a LEARNED latent
projection (photon-ml FactoredRandomEffectCoordinate.scala:99-289,
FactoredRandomEffectOptimizationProblem.scala:42-162).

The model's term is ``z_i' B gamma_u(i)``: ``z_i`` the row's features of
the random effect's shard (``d`` of them, sparse), ``B`` the shared
``[d, L]`` projection, ``gamma_u`` the entity's ``L`` latent
coefficients. Training alternates two solves: every entity's ``gamma_u``
with ``z_i' B`` as its features (a bank update), then ``B`` with every
``gamma`` held (a GLM whose margin is linear in ``B``).

Everything here runs over the bank's own solver blocks (an entity a block
row, its rows the block's slots), built once for the dataset: ``gamma_u``
is one row a block entity and never a per-row gather, and a block's rows
meet ``B`` as ``densify(z) @ B``, the rows densified by a compare and a
reduce (:func:`photon_ml_tpu.game.random_effect._densify`) and one matmul
on the MXU. The ``[E, S, d]`` block is the only large temporary, one block
at a time, inside the dense budget the block split keeps
(:attr:`ValuesOverride.staged`); no ``[n, k L]`` or ``[n, k, L]`` array is
made. Every matmul names ``HIGHEST``: the coordinate is float32 whatever
the process default is.
"""

from __future__ import annotations

from dataclasses import replace
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.game.random_effect import (
    RandomEffectOptimizationProblem,
    ValuesOverride,
    _default_problem,
    _densify,
    _named,
    score_blocks,
    score_plan,
)
from photon_ml_tpu.game.random_effect_data import RandomEffectDataset
from photon_ml_tpu.optim.config import OptimizerType
from photon_ml_tpu.optim.lbfgs import minimize_lbfgs, minimize_owlqn
from photon_ml_tpu.optim.tron import minimize_tron

Array = jnp.ndarray
_HI = jax.lax.Precision.HIGHEST


def factored_values(projection: Array, ix: Array, v: Array) -> Array:
    """The latent features ``z' B`` of a block's rows ``ix, v [E, S, k]``:
    ``[E, S, L]``. A :class:`ValuesOverride` function that reads the
    block's rows: the latent bank update makes its values with it, inside
    each block's solver program."""
    X = _densify(ix, v, projection.shape[0])
    return jnp.einsum("esd,dl->esl", X, projection, precision=_HI)


def latent_override(projection: Array) -> ValuesOverride:
    """The latent bank update's values: :func:`factored_values` of
    ``projection`` over each block's own rows, a densified row of
    ``d`` floats a slot staged beside them."""
    return ValuesOverride(
        factored_values, projection, reads="rows", staged=projection.shape[0]
    )


def latent_view(
    re_dataset: RandomEffectDataset, latent_dim: int
) -> RandomEffectDataset:
    """``re_dataset`` as the latent bank's dataset: the same entities,
    buckets and rows, a local space of ``latent_dim`` and blocks whose
    values a :func:`latent_override` makes (identity blocks of the
    solvers' kinds). Built once and cached on ``re_dataset``: the block
    structure, the device copies of the blocks and the solver programs
    are made once for every inner iteration of every update."""
    views = re_dataset.__dict__.setdefault("_latent_views", {})
    view = views.get(latent_dim)
    if view is None:
        view = replace(
            re_dataset,
            local_dim=latent_dim,
            buckets=[
                replace(b, identity_indices=True) for b in re_dataset.buckets
            ],
        )
        views[latent_dim] = view
    return view


def group_arrays(
    problem: RandomEffectOptimizationProblem, view: RandomEffectDataset,
    groups,
) -> tuple:
    """``(codes, ix, v, labels, weights)`` on the device for each group
    of solver blocks (``[B, E, ...]`` for a folded group): the copies the
    bank update holds, from ``problem``'s caches."""
    out = []
    for members in groups:
        if len(members) > 1:
            codes, ix, v, lab, _, w = problem._stacked_group_args(
                view, members, with_residuals=True
            )
        else:
            ix, v, lab, w, _, codes = problem._bucket_device_args(
                members[0].bucket
            )
        out.append((codes, ix, v, lab, w))
    return tuple(out)


def _block_pass(loss, B, bank, args, off, direction=None):
    """One block's share of the projection objective
    ``sum w loss(y, off + z' B gamma)`` at ``B``: ``(value, gradient)``,
    or with ``direction`` ``V`` the Hessian's product with it. ``gamma``
    is one bank row an entity (a padding lane's code lies past the bank
    and reads zero); a padding slot has weight 0."""
    codes, ix, v, lab, w = args
    gamma = jnp.take(bank, codes, axis=0, mode="fill", fill_value=0)
    X = _densify(ix, v, B.shape[0])  # [E, S, d]

    def margins(M):
        t = jnp.einsum("esd,dl->esl", X, M, precision=_HI)
        return jnp.sum(t * gamma[:, None, :], axis=-1)

    def back(c):  # sum over rows of c z gamma'
        return jnp.einsum(
            "esd,esl->dl", X, c[..., None] * gamma[:, None, :], precision=_HI
        )

    z = margins(B) + off
    if direction is not None:
        return back(w * loss.d2(z, lab) * margins(direction))
    return jnp.sum(w * loss.value(z, lab)), back(w * loss.d1(z, lab))


def _over_groups(fn, init, groups, offsets):
    """``fn(acc, args, off)`` folded over every block: a folded group's
    sub-blocks one at a time (``lax.scan``), so that one block's
    densified rows are alive at once."""
    acc = init
    for args, off in zip(groups, offsets):
        if args[0].ndim == 2:
            acc, _ = jax.lax.scan(
                lambda a, x: (fn(a, x[0], x[1]), None), acc, (args, off)
            )
        else:
            acc = fn(acc, args, off)
    return acc


@lru_cache(maxsize=None)
def _projection_program(coordinate: str, loss, config, use_l1: bool):
    """The projection fit as ONE program, module
    ``jit_fre_projection_fit_<coordinate>`` (non-word characters as
    ``_``): the coordinate's optimizer (L-BFGS; OWL-QN under an L1 term;
    TRON with the exact Hessian product) over ``vec(B)``, every
    evaluation a pass over the blocks."""

    @jax.jit
    @_named("fre_projection_fit", coordinate)
    def fit(w0, bank, groups, offsets, l1, l2):
        shape = (-1, bank.shape[1])

        def vg(flat):
            B = flat.reshape(shape)

            def add(acc, args, off):
                val, grad = _block_pass(loss, B, bank, args, off)
                return acc[0] + val, acc[1] + grad

            val, grad = _over_groups(
                add, (jnp.zeros((), jnp.float32), jnp.zeros_like(B)),
                groups, offsets,
            )
            return (
                val + 0.5 * l2 * jnp.vdot(flat, flat),
                (grad + l2 * B).reshape(-1),
            )

        def hvp(flat, d):
            B, V = flat.reshape(shape), d.reshape(shape)

            def add(acc, args, off):
                return acc + _block_pass(loss, B, bank, args, off, V)

            hv = _over_groups(add, jnp.zeros_like(B), groups, offsets)
            return (hv + l2 * V).reshape(-1)

        with jax.named_scope("fre.projection"):
            if config.optimizer_type == OptimizerType.TRON:
                return minimize_tron(
                    vg, hvp, w0, max_iter=config.max_iter,
                    tol=config.tolerance, max_cg=config.tron_max_cg,
                )
            if use_l1:
                return minimize_owlqn(
                    vg, w0, l1, max_iter=config.max_iter,
                    tol=config.tolerance, history=config.lbfgs_history,
                )
            return minimize_lbfgs(
                vg, w0, max_iter=config.max_iter, tol=config.tolerance,
                history=config.lbfgs_history,
            )

    return fit


def fit_projection(
    projection: Array, bank: Array, groups: tuple, offsets, *, loss,
    config, l1: float, l2: float, coordinate: str,
):
    """The projection fit from ``projection`` with every ``gamma`` in
    ``bank`` held: the objective ``sum_i w_i loss(y_i, off_i + z_i' B
    gamma_u(i)) + l2/2 |B|^2 (+ l1 |B|_1)`` over the blocks ``groups``
    (:func:`group_arrays`) and their ``offsets``; returns the optimizer's
    ``OptResult`` over ``vec(B)``."""
    return _projection_program(coordinate, loss, config, bool(l1))(
        projection.reshape(-1), bank, groups, tuple(offsets),
        jnp.float32(l1), jnp.float32(l2),
    )


@partial(jax.jit, static_argnames=("num_rows",))
def fre_score(bank, projection, blocks, rest, *, num_rows):
    """The factored term of every row, ``z_i' B gamma_u(i)``, as one
    named program (module ``fre_score``, scope ``cd.score``): for each
    group of solver blocks (:func:`score_blocks`; a folded group one
    sub-block at a time) the block's latent features
    (:func:`factored_values`) against its entities' bank rows, the
    ``[E, S]`` scores placed by ``rows``, the element scatter of
    ``re_score``'s ``slots`` path.
    ``rest``: rows no block holds (a passive row; none where every row is
    active), from ``B``'s rows at their feature ids. A row with no entity
    scores 0."""

    def place(out, args):
        codes, ix, v, rows = args
        gamma = jnp.take(bank, codes, axis=0, mode="fill", fill_value=0)
        score = jnp.sum(
            factored_values(projection, ix, v) * gamma[:, None, :], axis=-1
        )
        at = jnp.where(rows >= 0, rows, num_rows)
        return out.at[at.reshape(-1)].set(score.reshape(-1), mode="drop")

    with jax.named_scope("cd.score"):
        out = jnp.zeros((num_rows,), jnp.float32)
        for args in blocks:
            if args[0].ndim == 2:
                out, _ = jax.lax.scan(
                    lambda o, a: (place(o, a), None), out, args
                )
            else:
                out = place(out, args)
        if rest is not None:
            rows, codes, valid, ix, v = rest
            latent = jnp.einsum(
                "nk,nkl->nl", v, jnp.take(projection, ix, axis=0),
                precision=_HI,
            )
            score = jnp.sum(latent * jnp.take(bank, codes, axis=0), axis=-1)
            if valid is not None:
                score = jnp.where(valid, score, 0.0)
            out = score if rows is None else out.at[rows].set(score)
        return out


def score_factored(
    bank: Array, projection: Array, re_dataset: RandomEffectDataset,
    problem: Optional[RandomEffectOptimizationProblem] = None,
):
    """Row-aligned factored scores ``[n]`` and the scoring plan they took:
    the ONE scoring function of the factored coordinate and its model,
    from the blocks ``problem``'s latent bank update holds (a model's
    score with no problem splits by the default budget)."""
    problem = problem or _default_problem()
    view = latent_view(re_dataset, bank.shape[1])
    plan = score_plan(
        view, problem, staged=projection.shape[0], passive_apart=False
    )
    blocks = score_blocks(problem, view, plan)
    return fre_score(
        bank, projection, blocks, plan.rest,
        num_rows=int(view.row_entity_codes.shape[0]),
    ), plan
