"""The plain reference for GLMix on the reference's default random-effect
path: each member's model in its OWN index map, a capped active set
weighted ``count / cap``, passive rows scored through the map. Float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no
kernels; the fixed effect is ``reference.py``'s, its gradient summed in
float64 on the host (:class:`Float64Gradient`).

It imports nothing of the program. What it takes of the program's is the
ACTIVE SET: the reservoir draw is seeded data the program makes, as
``--seed``'s row order is the benchmark's, and :func:`cap_rule_breaks`
holds it to the rule (exactly ``min(count, cap)`` rows a member, all its
own, each weighted ``count / cap`` where ``count > cap`` and 1 where not).
From there, member by member:

- the index map is the features the member's active rows name, and its
  intercept always (IndexMapProjector.scala:83-105), sorted
  (:func:`index_maps`);
- the member's model is the damped Newton of ``reference.py``'s per-user
  solve, with each row's weight in the loss, its gradient and its
  Hessian: ``sum_s w_s logloss(x_s.c + off_s) + l2/2 |c|^2`` from zero,
  the full step and its halves down to 1/128, the first that does not
  raise the objective; stopping on the objective's change or the
  gradient's norm against the INITIAL state, or at ``max_iter``
  (:func:`solve_members`);
- a row, active or passive, scores ``sum_j v_j c[map(f_j)]``, and a
  feature outside the map contributes zero (:func:`map_scores`). That is
  the reference's own behaviour for a passive row (its features outside
  the member's active map were never fitted), not a departure from it.

Members are solved a block at a time, each on its own map: a member's
rows are padded to the cap with rows of weight zero and its map to the
widest in the block with features no row names, both of which add exact
zeros. A model is held in GLOBAL feature space: ``feats`` int32 [M, W],
each member's features ascending, :data:`PAD` after them, and ``coefs``
float32 [M, W] beside them.

``precision="bf16"``: values and coefficients rounded to bfloat16 where
they meet, as ``reference.py``'s control.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import SparseProblem, _log1pexp, _round, _sigmoid

PAD = np.iinfo(np.int32).max  # past every feature id
HOST_BLOCK = 1 << 18  # rows a float64 host pass
ROW_BLOCK = 1 << 13
MEMBER_BLOCK = 512
MAP_BLOCK = 16384  # members a host pass of index_maps


def cap_rule_breaks(
    members: np.ndarray, active: np.ndarray, weights: np.ndarray,
    member_of_row: np.ndarray, counts: np.ndarray, cap: int,
) -> int:
    """How many of ``members`` have an active set (``active`` int32
    [M, S], row ids, -1 for no row; ``weights`` beside it) that breaks the
    cap rule: other than ``min(count, cap)`` rows, a row twice or another
    member's, a weight other than ``count / cap`` over the cap and 1
    under it, or a weight on no row."""
    held = active >= 0
    c = counts[members].astype(np.float64)
    rows = np.sort(np.where(held, active, -1), axis=1)
    twice = np.any((rows[:, 1:] == rows[:, :-1]) & (rows[:, 1:] >= 0), axis=1)
    own = np.all(
        ~held | (member_of_row[np.maximum(active, 0)] == members[:, None]),
        axis=1,
    )
    want = np.where(c > cap, c / cap, 1.0)[:, None]
    weighted = np.all(
        np.where(held, np.abs(weights - want) <= 1e-6 * want, weights == 0),
        axis=1,
    )
    ok = (held.sum(axis=1) == np.minimum(c, cap)) & ~twice & own & weighted
    return int(np.count_nonzero(~ok))


def index_maps(
    active: np.ndarray, ix: np.ndarray, v: np.ndarray, intercept: int,
) -> np.ndarray:
    """Each member's own index map, ``feats`` int32 [M, W]: the features
    that the live entries (value not zero) of its active rows name, and
    ``intercept`` always, ascending, :data:`PAD` after them."""
    width = intercept + 1
    parts = []
    for s in range(0, active.shape[0], MAP_BLOCK):
        a = active[s:s + MAP_BLOCK]
        m = np.arange(a.shape[0], dtype=np.int64)
        rows = np.maximum(a, 0)
        live = (a >= 0)[:, :, None] & (v[rows] != 0)
        owner = np.broadcast_to(m[:, None, None], live.shape)[live]
        keys = np.unique(np.concatenate([
            owner * width + ix[rows][live], m * width + intercept,
        ]))
        km = keys // width
        sizes = np.bincount(km, minlength=a.shape[0])
        feats = np.full((a.shape[0], int(sizes.max())), PAD, np.int32)
        starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        feats[km, np.arange(len(keys)) - starts[km]] = keys % width
        parts.append(feats)
    out = np.full((active.shape[0], max(p.shape[1] for p in parts)), PAD, np.int32)
    at = 0
    for p in parts:
        out[at:at + p.shape[0], :p.shape[1]] = p
        at += p.shape[0]
    return out


def _positions(feats_rows, ix):
    """Where each feature of ``ix`` [r, k] sits in its row's map
    ``feats_rows`` [r, W] (the count of the map's features below it), and
    whether it is there."""
    pos = jnp.sum(feats_rows[:, None, :] < ix[:, :, None], axis=-1)
    pos = jnp.minimum(pos, feats_rows.shape[1] - 1)
    return pos, jnp.take_along_axis(feats_rows, pos, axis=1) == ix


@partial(jax.jit, static_argnames="precision")
def _map_scores_block(feats, coefs, miss, member, ix, v, precision="f32"):
    fr = feats[member]
    pos, hit = _positions(fr, ix)
    c = jnp.take_along_axis(coefs[member], pos, axis=1)
    c = jnp.where(hit, c, miss[member][:, None])
    return jnp.sum(_round(v, precision) * _round(c, precision), axis=1)


def map_scores(
    feats: np.ndarray, coefs: np.ndarray, member: np.ndarray, ix: np.ndarray,
    v: np.ndarray, *, miss: Optional[np.ndarray] = None, precision="f32",
) -> np.ndarray:
    """score_i = sum_j v[i, j] * coefs[member_i, map(ix[i, j])], a feature
    outside member_i's map contributing ``miss[member_i]`` (zero: the
    reference's rule), in row blocks."""
    feats_d, coefs_d = jnp.asarray(feats), jnp.asarray(coefs, jnp.float32)
    miss_d = jnp.zeros(feats.shape[0], jnp.float32) if miss is None else (
        jnp.asarray(miss, jnp.float32))
    parts = []
    with jax.default_matmul_precision("highest"):
        for s in range(0, len(member), ROW_BLOCK):
            e = s + ROW_BLOCK
            parts.append(np.asarray(_map_scores_block(
                feats_d, coefs_d, miss_d, jnp.asarray(member[s:e]),
                jnp.asarray(ix[s:e]), jnp.asarray(v[s:e]), precision=precision,
            )))
    return np.concatenate(parts) if parts else np.zeros(0, np.float32)


def _member_block_solve(X, y, off, wt, l2, max_iter: int, tol: float, round_):
    """``reference._user_block_solve`` with a weight a row: the Newton
    step exact through Woodbury, H^-1 g = (g - X^T (l2 I + D G)^-1 D X g)
    / l2 with G = X X^T and D = diag(w p (1 - p))."""
    G = jnp.einsum("bsd,btd->bst", X, X)
    eye = jnp.eye(G.shape[1], dtype=jnp.float32)[None]
    halves = 0.5 ** jnp.arange(8, dtype=jnp.float32)

    def margins(c):
        return jnp.einsum("bsd,bd->bs", X, round_(c)) + off

    def value(c):
        z = margins(c)
        return (jnp.sum(wt * (_log1pexp(z) - y * z), axis=1)
                + 0.5 * l2 * jnp.sum(c * c, axis=1))

    def gradient(c):
        p = _sigmoid(margins(c))
        return jnp.einsum("bsd,bs->bd", X, round_(wt * (p - y))) + l2 * c, p

    c0 = jnp.zeros((X.shape[0], X.shape[2]), jnp.float32)
    f0 = value(c0)
    g0, _ = gradient(c0)
    g0_norm = jnp.linalg.norm(g0, axis=1)

    def body(i, state):
        c, f, done = state
        g, p = gradient(c)
        d2 = wt * p * (1.0 - p)
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


def member_rows(
    feats: np.ndarray, active: np.ndarray, weights: np.ndarray, ix, v,
    labels, offsets, *, use_weights: bool = True,
) -> Tuple[np.ndarray, ...]:
    """Each member's active rows as a problem on its own map: (feats,
    ix [M, S, k], v, labels [M, S], offsets, weights), a slot of no row
    all zeros. ``use_weights`` False: every held row weighted 1 (the
    control that drops the cap's ``count / cap``)."""
    held = active >= 0
    rows = np.maximum(active, 0)
    w = np.where(held, weights if use_weights else 1.0, 0.0).astype(np.float32)
    return (
        feats, ix[rows], np.where(held[:, :, None], v[rows], 0.0),
        np.where(held, labels[rows], 0.0).astype(np.float32),
        np.where(held, offsets[rows], 0.0).astype(np.float32), w,
    )


@partial(jax.jit, static_argnames="precision")
def _solve_block(feats_b, ix_b, v_b, y_b, off_b, wt_b, l2, max_iter, tol,
                 precision="f32"):
    """One block of members on their own maps: each member's active rows
    densified over its map, then :func:`_member_block_solve`. ``max_iter``
    and ``tol`` are traced, so that every call at one shape is one
    program."""
    b, s, k = ix_b.shape
    width = feats_b.shape[1]
    pos, hit = _positions(jnp.repeat(feats_b, s, axis=0), ix_b.reshape(b * s, k))
    vals = jnp.where(hit, _round(v_b.reshape(b * s, k), precision), 0.0)
    X = jnp.zeros((b * s, width), jnp.float32).at[
        jnp.arange(b * s)[:, None], pos].add(vals).reshape(b, s, width)
    return _member_block_solve(
        X, y_b, off_b, wt_b, l2, max_iter, tol, lambda a: _round(a, precision),
    )


def solve_members(
    problem: Tuple[np.ndarray, ...], l2: float, *, max_iter: int, tol: float,
    precision: str = "f32",
) -> np.ndarray:
    """Each member's coefficients ``coefs`` [M, W] beside its map, from
    :func:`member_rows`' problem, :data:`MEMBER_BLOCK` members a call (the
    last block padded with members of no row, which solve to zero)."""
    feats = problem[0]
    out = []
    with jax.default_matmul_precision("highest"):
        for s in range(0, feats.shape[0], MEMBER_BLOCK):
            part = []
            for a in problem:
                a = a[s:s + MEMBER_BLOCK]
                pad = np.zeros(
                    (MEMBER_BLOCK - a.shape[0],) + a.shape[1:], a.dtype)
                part.append(jnp.asarray(np.concatenate([a, pad])))
            coefs = _solve_block(
                *part, jnp.float32(l2), jnp.int32(max_iter), jnp.float32(tol),
                precision=precision,
            )
            out.append(np.asarray(coefs)[:min(MEMBER_BLOCK, feats.shape[0] - s)])
    coefs = (np.concatenate(out) if out
             else np.zeros(feats.shape, np.float32))
    return np.where(feats == PAD, 0.0, coefs).astype(np.float32)


def global_form(projection: np.ndarray, bank: np.ndarray):
    """A bank in each member's local order (``projection`` [E, D]: the
    global feature of each local slot, -1 for none) as ``(feats, coefs)``
    in global feature space, each row ascending."""
    feats = np.where(projection < 0, PAD, projection).astype(np.int32)
    coefs = np.where(projection < 0, 0.0, bank).astype(np.float32)
    if not np.all(feats[:, 1:] >= feats[:, :-1]):
        order = np.argsort(feats, axis=1, kind="stable")
        feats = np.take_along_axis(feats, order, axis=1)
        coefs = np.take_along_axis(coefs, order, axis=1)
    return feats, coefs


def lookup(feats: np.ndarray, coefs: np.ndarray, want: np.ndarray) -> np.ndarray:
    """``coefs`` at the features ``want`` [M, W'] names, a member's row
    against the same member's row (0 where not in its map; :data:`PAD`
    reads 0)."""
    out = []
    for s in range(0, feats.shape[0], MEMBER_BLOCK):
        e = s + MEMBER_BLOCK
        f, c, w = (jnp.asarray(a[s:e]) for a in (feats, coefs, want))
        pos, hit = _positions(f, w)
        out.append(np.asarray(jnp.where(
            hit & (w != PAD), jnp.take_along_axis(c, pos, axis=1), 0.0)))
    return np.concatenate(out) if out else np.zeros(want.shape, np.float32)


class Float64Gradient:
    """``reference.py``'s fixed-effect objective with its GRADIENT summed in
    float64 on the host. At this cell's 2.1M rows the float32 gradient of
    ``SparseProblem`` (a scatter-add into the coefficients, 524,288 rows a
    block) reads 7e-5 off a float64 sum in norm at the tenth L-BFGS iterate
    on a TPU v5e, where the program's reads 5e-7; ten iterations of a
    descent still falling steeply carry a gap of that size to a tenth of
    the objective, so the reference sums its gradient more exactly than
    the program it judges. The value stays ``SparseProblem``'s float32 one:
    it agrees with a float64 sum to 2.5e-6, as the program's does, and the
    fixed effect's value limits are set from float32 readings."""

    def __init__(self, inner: SparseProblem):
        self.inner = inner
        self.dim = inner.dim

    def margins(self, w) -> np.ndarray:
        return self.inner.margins(w)

    def value_and_gradient(self, w):
        p = self.inner
        value, _ = p.value_and_gradient(w)
        w64 = np.asarray(w, np.float64)
        grad = p.l2 * w64
        for s in range(0, p.indices.shape[0], HOST_BLOCK):
            ix = p.indices[s:s + HOST_BLOCK]
            v = p.values[s:s + HOST_BLOCK].astype(np.float64)
            z = np.sum(w64[ix] * v, axis=1)
            if p.offsets is not None:
                z += p.offsets[s:s + HOST_BLOCK]
            r = np.exp(-np.logaddexp(0.0, -z)) - p.labels[s:s + HOST_BLOCK]
            if p.weights is not None:
                r *= p.weights[s:s + HOST_BLOCK]
            grad += np.bincount(
                ix.ravel(), weights=(v * r[:, None]).ravel(), minlength=p.dim)
        return value, jnp.asarray(grad.astype(np.float32))
