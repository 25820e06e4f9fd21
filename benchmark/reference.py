"""The plain reference: float32 ``jax.numpy`` with no kernels and no cache.

It imports nothing of the program and takes nothing the program made. It
holds the objective the configurations state (sum of weighted logistic
losses plus ``l2/2 * |w|^2``, intercept included, no 1/n), the L-BFGS the
fixed effects are fitted with (memory 10, Armijo backtracking from step
``1/max(|d|, 1)`` on the first iteration and 1 after it, halving, c1 =
1e-4, at most 24 trials; a pair is stored only when ``y.s > 1e-10``; stop
on relative function change or gradient norm against the INITIAL state),
and the per-user solve (damped Newton from zero with the same stopping
rule, its step exact through the 16 x 16 system of a user's rows).

Rows are processed in blocks so that a cell's data never has to sit on
the device whole beside the gather temporaries.

``precision="bf16"`` is the control of ``benchmark/tests``: feature
values and coefficients are rounded to bfloat16 before every product (the
step below the float32 the configurations state), sums stay in float32.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 1 << 19


def _round(x, precision: str):
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _log1pexp(z):
    return jnp.maximum(z, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(z)))


def _sigmoid(z):
    return 1.0 / (1.0 + jnp.exp(-z))


@jax.jit
def _margins_block(w, ix, v, off):
    return jnp.sum(v * w[ix], axis=1) + off


@partial(jax.jit, static_argnames="precision")
def _vg_block(w, ix, v, y, off, wt, grad, precision="f32"):
    z = jnp.sum(v * w[ix], axis=1) + off
    value = jnp.sum(wt * (_log1pexp(z) - y * z))
    c = _round(wt * (_sigmoid(z) - y), precision)
    grad = grad.at[ix.reshape(-1)].add((v * c[:, None]).reshape(-1))
    return value, grad


@dataclass
class SparseProblem:
    """One logistic objective over padded sparse rows, kept on the host
    and sent to the device a block of rows at a time."""

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
        """Row blocks on the device, sent once (the check runs after the
        program's own state is freed, so the rows fit beside the gather
        temporaries of one block)."""
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
                blocks.append((
                    jnp.asarray(self.indices[s:e]),
                    _round(jnp.asarray(self.values[s:e]), self.precision),
                    jnp.asarray(self.labels[s:e]), jnp.asarray(off),
                    jnp.asarray(wt),
                ))
            self._device_blocks = blocks
        return self._device_blocks

    def margins(self, w) -> np.ndarray:
        w = _round(jnp.asarray(w, jnp.float32), self.precision)
        with jax.default_matmul_precision("highest"):
            parts = [
                np.asarray(_margins_block(w, ix, v, off))
                for ix, v, _, off, _ in self._blocks()
            ]
        return np.concatenate(parts)

    def value_and_gradient(self, w) -> Tuple[jnp.ndarray, jnp.ndarray]:
        w = jnp.asarray(w, jnp.float32)
        wr = _round(w, self.precision)
        value = jnp.zeros((), jnp.float32)
        grad = jnp.zeros((self.dim,), jnp.float32)
        with jax.default_matmul_precision("highest"):
            for ix, v, y, off, wt in self._blocks():
                part, grad = _vg_block(
                    wr, ix, v, y, off, wt, grad, precision=self.precision
                )
                value = value + part
        value = value + 0.5 * self.l2 * jnp.vdot(w, w)
        return value, grad + self.l2 * w


@dataclass
class LbfgsTrace:
    """What one L-BFGS run passed through: entry i is the state after i
    iterations (entry 0 the start)."""

    coefficients: List[np.ndarray]
    values: List[float]
    grad_norms: List[float]


def lbfgs(
    vg: Callable, w0, *, max_iter: int, tol: float, history: int = 10,
    c1: float = 1e-4, max_trials: int = 24,
) -> LbfgsTrace:
    w = jnp.asarray(w0, jnp.float32)
    f, g = vg(w)
    f0, g0_norm = float(f), float(jnp.linalg.norm(g))
    out = LbfgsTrace([np.asarray(w)], [f0], [g0_norm])
    if g0_norm == 0.0:
        return out
    pairs: List[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = []
    for it in range(1, max_iter + 1):
        q, alphas = g, []
        for s, y, rho in reversed(pairs):
            a = rho * jnp.vdot(s, q)
            q = q - a * y
            alphas.append(a)
        if pairs:
            s, y, _ = pairs[-1]
            q = q * (jnp.vdot(s, y) / jnp.maximum(jnp.vdot(y, y), 1e-30))
        for (s, y, rho), a in zip(pairs, reversed(alphas)):
            q = q + (a - rho * jnp.vdot(y, q)) * s
        d = -q
        if not float(jnp.vdot(d, g)) < 0:
            d = -g
        t = 1.0 if pairs else 1.0 / max(float(jnp.linalg.norm(d)), 1.0)
        ok = False
        for _ in range(max_trials + 1):
            w_t = w + t * d
            f_t, g_t = vg(w_t)
            f_tf = float(f_t)
            if np.isfinite(f_tf) and f_tf <= float(f) + c1 * float(
                jnp.vdot(g, w_t - w)
            ):
                ok = True
                break
            t *= 0.5
        if not ok:
            break  # the search stalled: keep the point
        s, y = w_t - w, g_t - g
        ys = float(jnp.vdot(y, s))
        if ys > 1e-10:
            pairs = (pairs + [(s, y, 1.0 / max(ys, 1e-30))])[-history:]
        f_prev, w, f, g = float(f), w_t, f_t, g_t
        g_norm = float(jnp.linalg.norm(g))
        out.coefficients.append(np.asarray(w))
        out.values.append(float(f))
        out.grad_norms.append(g_norm)
        if abs(float(f) - f_prev) <= tol * abs(f0) or g_norm <= tol * g0_norm:
            break
    return out


def _densify(ix, v, dim: int):
    """[B, S, k] sparse rows -> [B, S, dim] dense rows."""
    b, s, _ = ix.shape
    bi = jnp.arange(b)[:, None, None]
    si = jnp.arange(s)[None, :, None]
    return jnp.zeros((b, s, dim), jnp.float32).at[bi, si, ix].add(v)


def _user_block_solve(X, y, off, l2, max_iter: int, tol: float, round_):
    """The per-user solve the configuration states: damped Newton from the
    zero model on ``sum_s logloss(x_s.c + off_s) + l2/2 |c|^2``, the full
    step first and then its halves down to 1/128, taking the first that
    does not raise the objective; it stops as the L-BFGS above does, on
    the change of the objective or the gradient's norm against the
    INITIAL state, or at ``max_iter``. The Newton step is exact: with
    G = X X^T ([S, S]) and D = diag(p (1 - p)), Woodbury gives
    H^-1 g = (g - X^T (l2 I + D G)^-1 D X g) / l2.

    ``round_`` rounds the coefficients where they meet the features (the
    identity in float32; the control's bfloat16)."""
    G = jnp.einsum("bsd,btd->bst", X, X)
    eye = jnp.eye(G.shape[1], dtype=jnp.float32)[None]
    halves = 0.5 ** jnp.arange(8, dtype=jnp.float32)

    def margins(c):
        return jnp.einsum("bsd,bd->bs", X, round_(c)) + off

    def value(c):
        z = margins(c)
        return jnp.sum(_log1pexp(z) - y * z, axis=1) + 0.5 * l2 * jnp.sum(c * c, axis=1)

    def gradient(c):
        p = _sigmoid(margins(c))
        return jnp.einsum("bsd,bs->bd", X, round_(p - y)) + l2 * c, p

    c0 = jnp.zeros((X.shape[0], X.shape[2]), jnp.float32)
    f0 = value(c0)
    g0, _ = gradient(c0)
    g0_norm = jnp.linalg.norm(g0, axis=1)

    def body(i, state):
        c, f, done = state
        g, p = gradient(c)
        d2 = p * (1.0 - p)
        u = jnp.einsum("bsd,bd->bs", X, g)
        t = jnp.linalg.solve(
            l2 * eye + d2[:, :, None] * G, (d2 * u)[:, :, None]
        )[:, :, 0]
        step = -(g - jnp.einsum("bsd,bs->bd", X, t)) / l2
        trials = c[None] + halves[:, None, None] * step[None]
        f_trials = jax.vmap(value)(trials)  # [8, B]
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
        keep = done[:, None]
        return (
            jnp.where(keep, c, c_new), jnp.where(done, f, f_new), done | stop,
        )

    c, _, _ = jax.lax.fori_loop(0, max_iter, body, (c0, f0, g0_norm == 0.0))
    return c


def solve_users(
    ix: np.ndarray, v: np.ndarray, y: np.ndarray, off: np.ndarray,
    dim: int, l2: float, *, max_iter: int, tol: float,
    precision: str = "f32", block: int = 2048,
) -> np.ndarray:
    """Per-user coefficients, [E, dim], over ``[E, S, k]`` sparse rows."""

    @jax.jit
    def solve(ix_b, v_b, y_b, off_b):
        X = _densify(ix_b, _round(v_b, precision), dim)
        return _user_block_solve(
            X, y_b, off_b, jnp.float32(l2), max_iter, tol,
            lambda a: _round(a, precision),
        )

    out = []
    with jax.default_matmul_precision("highest"):
        for s in range(0, ix.shape[0], block):
            e = s + block
            out.append(np.asarray(solve(
                jnp.asarray(ix[s:e]), jnp.asarray(v[s:e]),
                jnp.asarray(y[s:e]), jnp.asarray(off[s:e]),
            )))
    return np.concatenate(out)


def user_scores(bank: np.ndarray, ix, v, user_of_row, *, precision="f32"):
    """score_i = sum_j v[i, j] * bank[user_i, ix[i, j]], in row blocks."""

    @jax.jit
    def block(bank_d, ix_b, v_b, u_b):
        return jnp.sum(_round(v_b, precision) * bank_d[u_b[:, None], ix_b], axis=1)

    bank_d = _round(jnp.asarray(bank, jnp.float32), precision)
    parts = []
    for s in range(0, ix.shape[0], ROW_BLOCK):
        e = s + ROW_BLOCK
        parts.append(np.asarray(block(
            bank_d, jnp.asarray(ix[s:e]), jnp.asarray(v[s:e]),
            jnp.asarray(user_of_row[s:e]),
        )))
    return np.concatenate(parts)


def logistic_total(margins: np.ndarray, labels: np.ndarray) -> float:
    """sum_i logloss(z_i, y_i), accumulated in float64 on the host."""
    z = margins.astype(np.float64)
    return float(np.sum(
        np.maximum(z, 0.0) + np.log1p(np.exp(-np.abs(z))) - labels * z
    ))
