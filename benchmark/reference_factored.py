"""The plain reference of a factored random effect: float32 ``jax.numpy``
at ``highest``, no kernels and no cache.

It imports nothing of the program and takes nothing the program made
(``benchmark/reference.py``'s L-BFGS and logistic helpers are the
reference's own). The model's term is ``z_i' B gamma_u(i)``: ``z_i`` the
row's sparse features over ``d`` dimensions, ``B`` the shared ``[d, L]``
projection, ``gamma_u`` the user's ``L`` latent coefficients; the
objective ``sum_i logloss(off_i + z_i' B gamma_u(i)) + l2/2 |gamma|^2 +
l2_B/2 |B|^2`` (the configurations' one L2 weight for both). Training is
``inner`` alternations from ``gamma = 0`` and the seeded starting ``B``:

1. every user's ``gamma_u`` by damped Newton from its current value with
   ``z' B`` as its ``L`` features: the full step first and then its
   halves down to 1/128, the first that does not raise the objective
   taken; the step exact through the ``L x L`` normal equations; stop on
   the change of the objective or the gradient's norm against the state
   the solve started from (``tol``), on no decrease, or at ``max_iter``;
2. ``B`` by ``reference.lbfgs`` from its current value with every
   ``gamma`` held, its value and gradient summed over blocks of users.

Departures from photon-ml's Scala (FactoredRandomEffectCoordinate.scala,
FactoredRandomEffectOptimizationProblem.scala:42-162): the per-user
latent problems are solved by damped Newton, the per-entity algorithm the
configurations state, where photon-ml runs its GLM optimizer per entity;
the projection fit is this repo's L-BFGS rule (Armijo backtracking,
memory 10) where photon-ml runs Breeze's L-BFGS on the flattened
``z_i (x) gamma_u`` features of ``d L`` dimensions: the same objective,
here summed from blocks of densified rows and never flattened; the
starting ``B`` is ``N(0, 1/L)`` from numpy's ``default_rng(seed)`` (a
Gaussian like photon-ml's random projection matrix) and ``gamma`` starts
at zero.

``precision="bf16"``: the control: feature values, ``B`` and ``gamma``
rounded to bfloat16 where they meet, sums in float32.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference

USER_BLOCK = 1024


def starting_projection(dim: int, latent_dim: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.normal(0.0, 1.0 / np.sqrt(latent_dim), size=(dim, latent_dim)).astype(
        np.float32
    )


def _round(x, precision: str):
    return reference._round(x, precision)


def _latent(ix, v, B, dim, precision):
    """The rows' latent features ``z' B`` [b, S, L] and the densified
    rows [b, S, dim]."""
    d = jnp.arange(dim, dtype=ix.dtype)
    X = jnp.sum(_round(v, precision)[..., None] * (ix[..., None] == d), axis=2)
    return jnp.einsum("bsd,dl->bsl", X, _round(B, precision)), X


@partial(jax.jit, static_argnames=("dim", "precision"))
def _vg(B, gamma, ix, v, y, off, w, *, dim, precision):
    """Value and gradient over B of the loss part, every block of users
    in turn (``lax.scan``)."""

    def block(acc, args):
        ix_b, v_b, y_b, off_b, w_b, g_b = args
        t, X = _latent(ix_b, v_b, B, dim, precision)
        g_r = _round(g_b, precision)
        z = jnp.einsum("bsl,bl->bs", t, g_r) + off_b
        val = jnp.sum(w_b * (reference._log1pexp(z) - y_b * z))
        c = _round(w_b * (reference._sigmoid(z) - y_b), precision)
        grad = jnp.einsum("bsd,bs,bl->dl", X, c, g_r)
        return (acc[0] + val, acc[1] + grad), None

    (val, grad), _ = jax.lax.scan(
        block, (jnp.zeros((), jnp.float32), jnp.zeros_like(B)),
        (ix, v, y, off, w, gamma),
    )
    return val, grad


@partial(jax.jit, static_argnames=("dim", "max_iter", "precision"))
def _solve_latent(B, gamma, ix, v, y, off, w, l2, tol, *, dim, max_iter, precision):
    """Every user's damped Newton solve, a block of users at a time."""
    halves = 0.5 ** jnp.arange(8, dtype=jnp.float32)

    def block(_, args):
        ix_b, v_b, y_b, off_b, w_b, c0 = args
        X, _ = _latent(ix_b, v_b, B, dim, precision)  # [b, S, L]
        eye = jnp.eye(X.shape[2], dtype=jnp.float32)[None]

        def value(c):
            z = jnp.einsum("bsl,bl->bs", X, _round(c, precision)) + off_b
            return jnp.sum(
                w_b * (reference._log1pexp(z) - y_b * z), axis=1
            ) + 0.5 * l2 * jnp.sum(c * c, axis=1)

        def gradient(c):
            z = jnp.einsum("bsl,bl->bs", X, _round(c, precision)) + off_b
            p = reference._sigmoid(z)
            r = _round(w_b * (p - y_b), precision)
            return jnp.einsum("bsl,bs->bl", X, r) + l2 * c, w_b * p * (1.0 - p)

        f0 = value(c0)
        g0, _ = gradient(c0)
        g0_norm = jnp.linalg.norm(g0, axis=1)

        def body(_, state):
            c, f, done = state
            g, d2 = gradient(c)
            H = jnp.einsum("bsl,bs,bsm->blm", X, d2, X) + l2 * eye
            step = -jnp.linalg.solve(H, g[:, :, None])[:, :, 0]
            trials = c[None] + halves[:, None, None] * step[None]
            f_trials = jax.vmap(value)(trials)
            ok = (f_trials <= f[None]) & jnp.isfinite(f_trials)
            first = jnp.argmax(ok, axis=0)
            moved = jnp.any(ok, axis=0)
            c_new = jnp.take_along_axis(trials, first[None, :, None], axis=0)[0]
            f_new = jnp.take_along_axis(f_trials, first[None, :], axis=0)[0]
            c_new = jnp.where(moved[:, None], c_new, c)
            f_new = jnp.where(moved, f_new, f)
            g_new, _ = gradient(c_new)
            stop = (
                ~moved
                | (jnp.abs(f_new - f) <= tol * jnp.abs(f0))
                | (jnp.linalg.norm(g_new, axis=1) <= tol * g0_norm)
            )
            return (
                jnp.where(done[:, None], c, c_new),
                jnp.where(done, f, f_new), done | stop,
            )

        c, _, _ = jax.lax.fori_loop(
            0, max_iter, body, (c0, f0, g0_norm == 0.0)
        )
        return None, c

    _, out = jax.lax.scan(block, None, (ix, v, y, off, w, gamma))
    return out


@partial(jax.jit, static_argnames=("dim", "precision"))
def _scores(B, gamma, ix, v, *, dim, precision):
    def block(_, args):
        ix_b, v_b, g_b = args
        t, _ = _latent(ix_b, v_b, B, dim, precision)
        return None, jnp.einsum("bsl,bl->bs", t, _round(g_b, precision))

    _, out = jax.lax.scan(block, None, (ix, v, gamma))
    return out


@partial(jax.jit, static_argnames=("dim",))
def _latent_rows(B, ix, v, *, dim):
    def block(_, args):
        t, _ = _latent(args[0], args[1], B, dim, "f32")
        return None, t

    _, out = jax.lax.scan(block, None, (ix, v))
    return out


@dataclass
class FactoredFit:
    projection: np.ndarray  # [d, L]
    gamma: np.ndarray  # [users, L]
    scores: np.ndarray  # [users, S]: z_i' B gamma_u of each user's rows
    # the last latent solve's projection and starting gamma
    last_projection: np.ndarray
    last_gamma: np.ndarray


class FactoredProblem:
    """One user side of a factored random effect over rows grouped by
    user (``[users, S, k]``), on the device in blocks of
    ``USER_BLOCK`` users."""

    def __init__(self, ix, v, labels, offsets, dim: int, *, weights=None,
                 precision: str = "f32", block: int = USER_BLOCK):
        users = ix.shape[0]
        self.users, self.dim, self.precision = users, dim, precision
        self.rows = (ix, v, labels, offsets, weights)
        b = min(block, users)
        pad = -users % b
        self.block = b

        def blocks(a, fill=0):
            a = np.asarray(a)
            if pad:
                a = np.concatenate([a, np.full((pad,) + a.shape[1:], fill, a.dtype)])
            return jnp.asarray(a.reshape((-1, b) + a.shape[1:]))

        w = np.ones(labels.shape, np.float32) if weights is None else weights
        self.ix, self.v = blocks(ix), blocks(v)
        self.y, self.off = blocks(labels), blocks(offsets)
        self.w = blocks(w.astype(np.float32))

    def _gamma_blocks(self, gamma):
        g = np.asarray(gamma, np.float32)
        pad = self.ix.shape[0] * self.block - g.shape[0]
        if pad:
            g = np.concatenate([g, np.zeros((pad, g.shape[1]), np.float32)])
        return jnp.asarray(g.reshape(self.ix.shape[0], self.block, -1))

    def solve_latent(self, B, gamma, *, l2, max_iter, tol) -> np.ndarray:
        with jax.default_matmul_precision("highest"):
            out = _solve_latent(
                jnp.asarray(B), self._gamma_blocks(gamma), self.ix, self.v,
                self.y, self.off, self.w, jnp.float32(l2), jnp.float32(tol),
                dim=self.dim, max_iter=int(max_iter), precision=self.precision,
            )
        return np.asarray(out).reshape(-1, out.shape[-1])[: self.users]

    def projection_vg(self, gamma, l2):
        g = self._gamma_blocks(gamma)

        def vg(flat):
            B = jnp.asarray(flat, jnp.float32).reshape(self.dim, -1)
            with jax.default_matmul_precision("highest"):
                val, grad = _vg(
                    B, g, self.ix, self.v, self.y, self.off, self.w,
                    dim=self.dim, precision=self.precision,
                )
            val = val + 0.5 * l2 * jnp.vdot(flat, flat)
            return val, (grad + l2 * B).reshape(-1)

        return vg

    def scores(self, B, gamma) -> np.ndarray:
        with jax.default_matmul_precision("highest"):
            out = _scores(
                jnp.asarray(B), self._gamma_blocks(gamma), self.ix, self.v,
                dim=self.dim, precision=self.precision,
            )
        return np.asarray(out).reshape(-1, out.shape[-1])[: self.users]

    def subset(self, users) -> "FactoredProblem":
        """The same problem over the given users only."""
        ix, v, labels, offsets, weights = self.rows
        return FactoredProblem(
            ix[users], v[users], labels[users], offsets[users], self.dim,
            weights=None if weights is None else weights[users],
            precision=self.precision,
        )

    def latent_rows(self, B) -> tuple:
        """Every user's latent problem under ``B`` as sparse rows over
        ``L`` identity ids: ``(ids, z' B, labels, offsets)``, each
        ``[users, S, ...]`` (``game_cd_pod.either_stop``'s ``rows``)."""
        with jax.default_matmul_precision("highest"):
            out = _latent_rows(jnp.asarray(B), self.ix, self.v, dim=self.dim)
        x = np.asarray(out).reshape((-1,) + out.shape[2:])[: self.users]
        ids = np.broadcast_to(np.arange(x.shape[-1], dtype=np.int32), x.shape)
        return ids, x, self.rows[2], self.rows[3]

    def fit(
        self, B0: np.ndarray, *, inner: int, l2: float, l2_projection: float,
        latent_max_iter: int, latent_tol: float, projection_max_iter: int,
        projection_tol: float, history: int = 10,
        gamma0: Optional[np.ndarray] = None,
    ) -> FactoredFit:
        B = np.asarray(B0, np.float32)
        L = B.shape[1]
        gamma = (
            np.zeros((self.users, L), np.float32) if gamma0 is None else gamma0
        )
        for _ in range(inner):
            last = (B, gamma)
            gamma = self.solve_latent(
                B, gamma, l2=l2, max_iter=latent_max_iter, tol=latent_tol
            )
            trace = reference.lbfgs(
                self.projection_vg(gamma, l2_projection), B.reshape(-1),
                max_iter=projection_max_iter, tol=projection_tol,
                history=history,
            )
            B = np.asarray(trace.coefficients[-1]).reshape(B.shape)
        return FactoredFit(B, gamma, self.scores(B, gamma), *last)
