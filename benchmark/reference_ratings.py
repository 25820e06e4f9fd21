"""The plain reference of a ratings deployment: float32 ``jax.numpy`` at
``highest``, no kernels, no cache. It imports nothing of the program and
takes nothing the program made but the numbers it is asked to judge.

The model (Koren, Bell, Volinsky 2009, eqs. 4-5) as GAME states it:

    r_ui ~ x_i'b + b_u + b_i + p_u . q_i

with ``x_i`` the movie's genres and an intercept (mu), fitted one
coordinate at a time under the residual of the others. The objective is

    sum_rows 1/2 w (z - r)^2 + 1/2 l2 (|b|^2 + sum b_u^2 + sum b_i^2
                                       + sum |p_u|^2 + sum |q_i|^2),

each penalty with its own coordinate's ``l2``. Departures from Koren,
both photon-ml's: the sum of squares is HALVED (its squared loss), and
the genres ride in the fixed effect beside mu, which is penalized like
any coefficient of it.

The fixed effect is fitted by ``reference.lbfgs`` (the L-BFGS the other
cells' fixed effects are judged by) on the squared-loss value and
gradient over the rows in blocks, densified (21 columns). A random effect over an
intercept-only shard and an ALS half-step are the EXACT ridge solution of
an entity's rows given the residual of the other coordinates,

    (X' W X + l2 I)^-1 X' W (r - offset),

by ``jnp.linalg.solve`` at the entity's own row count (grouped in
classes of a power of two, a padding row at weight 0): ``X`` is the
column of ones for a bias, the partner side's factor rows for a
half-step.

``precision="bf16"`` is the control: features and coefficients are
rounded to bfloat16 before every product, sums stay in float32.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import ROW_BLOCK, _round

# floats of gathered factor rows one batched ridge solve holds
SOLVE_FLOATS = 1 << 26


@partial(jax.jit, static_argnames="dim")
def _dense_rows(ix, v, dim: int):
    """[b, k] padded sparse rows -> [b, dim] dense rows (a genre space is
    a few dozen wide: every product below is then a plain matmul)."""
    return jnp.sum(
        v[:, :, None] * (ix[:, :, None] == jnp.arange(dim)[None, None, :]), axis=1)


@partial(jax.jit, static_argnames="precision")
def _vg_block(w, X, y, off, wt, grad, precision="f32"):
    hi = jax.lax.Precision.HIGHEST
    z = jnp.dot(X, w, precision=hi) + off
    value = 0.5 * jnp.sum(wt * (z - y) ** 2)
    c = _round(wt * (z - y), precision)
    return value, grad + jnp.dot(c, X, precision=hi)


@jax.jit
def _margins_block(w, X):
    return jnp.dot(X, w, precision=jax.lax.Precision.HIGHEST)


@dataclass
class SquaredProblem:
    """One squared-loss objective over padded sparse rows of a NARROW
    space (the genres and an intercept), kept on the device as dense row
    blocks."""

    indices: np.ndarray  # int32 [n, k]
    values: np.ndarray  # float32 [n, k]
    labels: np.ndarray  # float32 [n]
    dim: int
    l2: float
    offsets: Optional[np.ndarray] = None
    weights: Optional[np.ndarray] = None
    precision: str = "f32"
    _device_blocks: Optional[list] = None

    def _blocks(self):
        if self._device_blocks is None:
            n = self.indices.shape[0]
            blocks = []
            for s in range(0, n, ROW_BLOCK):
                e = min(s + ROW_BLOCK, n)
                off = (
                    np.zeros(e - s, np.float32) if self.offsets is None
                    else self.offsets[s:e]
                )
                wt = (
                    np.ones(e - s, np.float32) if self.weights is None
                    else self.weights[s:e]
                )
                X = _dense_rows(
                    jnp.asarray(self.indices[s:e]),
                    jnp.asarray(self.values[s:e]), self.dim)
                blocks.append((
                    _round(X, self.precision),
                    jnp.asarray(self.labels[s:e]), jnp.asarray(off),
                    jnp.asarray(wt),
                ))
            self._device_blocks = blocks
        return self._device_blocks

    def margins(self, w) -> np.ndarray:
        """x.w a row, WITHOUT the offsets: the coordinate's score."""
        w = _round(jnp.asarray(w, jnp.float32), self.precision)
        return np.concatenate([
            np.asarray(_margins_block(w, X)) for X, _, _, _ in self._blocks()
        ])

    def value_and_gradient(self, w) -> Tuple[jnp.ndarray, jnp.ndarray]:
        w = jnp.asarray(w, jnp.float32)
        wr = _round(w, self.precision)
        value = jnp.zeros((), jnp.float32)
        grad = jnp.zeros((self.dim,), jnp.float32)
        for X, y, off, wt in self._blocks():
            part, grad = _vg_block(
                wr, X, y, off, wt, grad, precision=self.precision
            )
            value = value + part
        value = value + 0.5 * self.l2 * jnp.vdot(w, w)
        return value, grad + self.l2 * w


def bias_solve(entity_of_row: np.ndarray, num_entities: int,
               target: np.ndarray, weights: np.ndarray, l2: float,
               precision: str = "f32") -> np.ndarray:
    """The exact ridge solution of a bias per entity, ``[E, 1]``: with
    ``X`` the column of ones, ``sum w t / (sum w + l2)`` over the entity's
    rows (``target`` = rating - offset), the sums in float64. An entity
    without rows stays at zero."""
    e = np.asarray(entity_of_row)
    above = np.bincount(e, weights=(weights * target).astype(np.float64),
                        minlength=num_entities)
    below = np.bincount(e, weights=weights.astype(np.float64),
                        minlength=num_entities)
    b = (above / (below + l2)).astype(np.float32)[:, None]
    return np.asarray(_round(jnp.asarray(b), precision))


def bias_scores(bank: np.ndarray, entity_of_row: np.ndarray,
                precision: str = "f32") -> np.ndarray:
    b = np.asarray(_round(jnp.asarray(bank[:, 0], jnp.float32), precision))
    return b[np.asarray(entity_of_row)]


@partial(jax.jit, static_argnames="precision")
def _ridge_block(partner, keys, target, wt, l2, precision="f32"):
    """[e, S] slots (key -1: padding) -> [e, K] exact ridge solutions."""
    hi = jax.lax.Precision.HIGHEST
    X = jnp.where((keys >= 0)[..., None], partner[jnp.maximum(keys, 0)], 0.0)
    X = _round(X, precision)
    A = jnp.einsum("esk,es,esl->ekl", X, wt, X, precision=hi)
    A = A + l2 * jnp.eye(X.shape[-1], dtype=jnp.float32)[None]
    b = jnp.einsum("esk,es->ek", X, _round(wt * target, precision), precision=hi)
    return jnp.linalg.solve(A, b[..., None])[..., 0]


def factor_solve(partner: np.ndarray, solved_of_row: np.ndarray,
                 partner_of_row: np.ndarray, target: np.ndarray,
                 weights: np.ndarray, l2: float, entities: np.ndarray,
                 *, precision: str = "f32") -> np.ndarray:
    """An ALS half-step's exact solution for ``entities`` (codes of the
    solved side, ascending), ``[len(entities), K]``: each entity's rows
    at its own count, ``X`` the ``partner`` side's factor rows of those
    rows' partner codes. An entity without rows is the zero vector (its
    system is ``l2 I x = 0``)."""
    partner_d = jnp.asarray(partner, jnp.float32)
    K = partner.shape[1]
    solved_of_row = np.asarray(solved_of_row)
    order = np.argsort(solved_of_row, kind="stable")
    counts = np.bincount(solved_of_row, minlength=int(entities.max()) + 1)
    starts = np.cumsum(counts) - counts
    out = np.zeros((len(entities), K), np.float32)
    mine = counts[entities]
    caps = np.where(
        mine > 0, 1 << np.ceil(np.log2(np.maximum(mine, 1))).astype(np.int64), 0)
    for S in sorted(set(caps[caps > 0].tolist())):
        members = np.nonzero(caps == S)[0]
        # one shape a class (so one compile): the last call is padded
        # with entities of no rows
        per_call = min(max(1, SOLVE_FLOATS // (S * K)), len(members))
        slot = np.arange(S)[None, :]
        for s in range(0, len(members), per_call):
            part = members[s:s + per_call]
            lens = np.zeros(per_call, np.int64)
            lens[:len(part)] = mine[part]
            first = np.zeros(per_call, np.int64)
            first[:len(part)] = starts[entities[part]]
            ok = slot < lens[:, None]
            rows = order[np.where(ok, first[:, None] + slot, 0)]
            out[part] = np.asarray(_ridge_block(
                partner_d,
                jnp.asarray(np.where(ok, partner_of_row[rows], -1).astype(np.int32)),
                jnp.asarray(np.where(ok, target[rows], 0.0).astype(np.float32)),
                jnp.asarray(np.where(ok, weights[rows], 0.0).astype(np.float32)),
                jnp.float32(l2), precision=precision,
            ))[:len(part)]
    return out


def factor_scores(row_latent: np.ndarray, col_latent: np.ndarray,
                  rows: np.ndarray, cols: np.ndarray,
                  precision: str = "f32") -> np.ndarray:
    """score_i = p[rows_i] . q[cols_i], in row blocks."""

    @jax.jit
    def block(p, q, r, c):
        return jnp.sum(p[r] * q[c], axis=1)

    p = _round(jnp.asarray(row_latent, jnp.float32), precision)
    q = _round(jnp.asarray(col_latent, jnp.float32), precision)
    parts = []
    for s in range(0, rows.shape[0], ROW_BLOCK):
        e = s + ROW_BLOCK
        parts.append(np.asarray(block(
            p, q, jnp.asarray(rows[s:e]), jnp.asarray(cols[s:e]))))
    return np.concatenate(parts)


def squared_total(scores: np.ndarray, labels: np.ndarray,
                  weights: Optional[np.ndarray] = None) -> float:
    """sum_i 1/2 w (z_i - r_i)^2, accumulated in float64 on the host."""
    d = scores.astype(np.float64) - labels
    if weights is not None:
        d = d * np.sqrt(weights.astype(np.float64))
    return float(0.5 * np.sum(d * d))


def penalty(l2: float, *arrays: np.ndarray) -> float:
    return 0.5 * l2 * float(sum(
        np.sum(a.astype(np.float64) ** 2) for a in arrays))
