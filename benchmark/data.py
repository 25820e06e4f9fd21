"""Seeded inputs for the benchmark's cells: one general generator.

A configuration file under ``benchmark/configs/`` names a generator here
and its sizes. Every generator draws the ROWS (feature ids, feature
values, the planted model, the labels) from the configuration's own
``shape_seed`` and their ORDER from ``--seed``: every seed fits the same
rows, so every seed does the same work (an optimizer's line search
follows the numbers: with numbers drawn from ``--seed`` one L-BFGS fit
took 16 to 25 evaluations, PERF.md section 6), and the order moves rows
only inside the blocks the program tiles by (``order_block`` rows; a
user's rows among themselves), so that the shapes of every array, and
everything the program derives from the pattern (tile schedule lengths,
capacity classes), are the same on every seed.

Each generator is ONE jitted call on the default device (``jax.random``,
counter-based, so the same seed gives the same arrays on every run);
what comes back is copied to the host once, because both the program's
input builders and the plain reference start from numpy arrays. Nothing
here imports the program.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class SparseRows:
    """Padded sparse rows: row i is sum_j values[i, j] * w[indices[i, j]]."""

    indices: np.ndarray  # int32 [n, k]
    values: np.ndarray  # float32 [n, k]
    dim: int  # coefficient dimension, intercept included
    intercept_index: Optional[int]


@dataclass
class GlmData:
    rows: SparseRows
    labels: np.ndarray  # float32 [n]


@dataclass
class GlmixData:
    fixed: SparseRows  # [n, k_fixed]
    user: SparseRows  # [n, k_user], no intercept
    user_of_row: np.ndarray  # int32 [n]
    num_users: int
    labels: np.ndarray  # float32 [n]


def _key(seed: int, stream: int):
    """A key from any whole number (``--seed`` passes 2**31): the low 31
    bits seed it and the rest is folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _planted(key, shape, nnz: int, density: float, margin_std: float):
    """Normal weights on a random ``density`` share of the coordinates
    (the planted recipe of the repo's ``chip_smoke.py``), scaled so that a
    row of ``nnz`` unit-variance entries has a margin of the given
    standard deviation."""
    k_w, k_mask = jax.random.split(key)
    w = jax.random.normal(k_w, shape, jnp.float32)
    w = w * (jax.random.uniform(k_mask, shape) < density)
    return w * jnp.float32(margin_std / np.sqrt(nnz * density))


def _values(key, shape):
    """Standard normal feature values, never exactly zero: the program's
    loaders drop zero entries, and the pattern must not follow the seed."""
    v = jax.random.normal(key, shape, jnp.float32)
    return jnp.where(v == 0.0, jnp.float32(1.0), v)


def _labels(key, margins):
    return (
        jax.random.uniform(key, margins.shape) < jax.nn.sigmoid(margins)
    ).astype(jnp.float32)


def _with_intercept(ids, hashed: int, k: int):
    """[n, f] ids -> [n, k] with the intercept's id after them, zero-padded."""
    n, f = ids.shape
    out = jnp.zeros((n, k), jnp.int32)
    return out.at[:, :f].set(ids).at[:, f].set(hashed)


@partial(jax.jit, static_argnames=("n", "fields", "hashed"))
def _hashed_pattern(key, *, n: int, fields: int, hashed: int):
    return jax.random.randint(key, (n, fields), 0, hashed, dtype=jnp.int32)


def hashed_pattern(cfg: Dict):
    """Feature ids [rows, numeric + categorical] from ``shape_seed``:
    uniform over the hashed space (the configuration's ``assumed``)."""
    return _hashed_pattern(
        _key(cfg["shape_seed"], 0), n=int(cfg["rows"]),
        fields=int(cfg["numeric_features"]) + int(cfg["categorical_features"]),
        hashed=int(cfg["hashed_dim"]),
    )


def _order_in_blocks(key, n: int, block: int):
    """A permutation of ``n`` rows that moves each row only inside its
    block of ``block`` consecutive rows."""
    keys = jax.random.uniform(key, (n // block, block))
    starts = jnp.arange(n // block, dtype=jnp.int32)[:, None] * block
    return (jnp.argsort(keys, axis=1).astype(jnp.int32) + starts).reshape(-1)


@partial(jax.jit, static_argnames=(
    "num", "hashed", "density", "margin_std", "order_block"))
def _hashed_rows(ids, key, order_key, *, num, hashed, density, margin_std,
                 order_block):
    n, fields = ids.shape
    k = _round_up(fields + 1, 8)
    indices = _with_intercept(ids, hashed, k)
    k_val, k_w, k_lab = jax.random.split(key, 3)
    values = jnp.zeros((n, k), jnp.float32)
    values = values.at[:, :num].set(_values(k_val, (n, num)))
    values = values.at[:, num : fields + 1].set(1.0)
    planted = _planted(k_w, (hashed + 1,), fields, density, margin_std)
    margins = jnp.sum(planted[indices] * values, axis=1)
    order = _order_in_blocks(order_key, n, order_block)
    return indices[order], values[order], _labels(k_lab, margins)[order]


def hashed_rows(cfg: Dict, seed: int) -> GlmData:
    """Criteo-shaped rows: ``numeric_features`` real-valued entries and
    ``categorical_features`` one-hot entries hashed into ``hashed_dim``
    dimensions, plus the intercept as the last coordinate, padded to a
    multiple of 8 entries a row as the program's loader pads."""
    hashed = int(cfg["hashed_dim"])
    n = int(cfg["rows"])
    block = min(int(cfg["order_block"]), n)
    if n % block:
        raise ValueError(f"rows {n} is not a multiple of order_block {block}")
    indices, values, labels = map(np.asarray, _hashed_rows(
        hashed_pattern(cfg), _key(cfg["shape_seed"], 1), _key(seed, 2),
        num=int(cfg["numeric_features"]), hashed=hashed,
        density=float(cfg["planted"]["density"]),
        margin_std=float(cfg["planted"]["margin_std"]), order_block=block,
    ))
    return GlmData(SparseRows(indices, values, hashed + 1, hashed), labels)


@partial(jax.jit, static_argnames=("users", "n", "hashed", "fk", "d", "k"))
def _glmix_pattern(key, units, *, users, n, hashed, fk, d, k):
    k_fixed, k_start, k_stride = jax.random.split(key, 3)
    fixed_ids = jax.random.randint(k_fixed, (n, fk), 0, hashed, dtype=jnp.int32)
    start = jax.random.randint(k_start, (n, 1), 0, d, dtype=jnp.int32)
    stride = units[jax.random.randint(k_stride, (n, 1), 0, units.shape[0])]
    user_ids = (start + stride * jnp.arange(k, dtype=jnp.int32)[None, :]) % d
    user_of_row = jnp.arange(n, dtype=jnp.int32) % users
    return fixed_ids, user_ids, user_of_row


def glmix_pattern(cfg: Dict):
    """(fixed ids [n, fixed_nnz], user ids [n, user_nnz], user_of_row [n])
    from ``shape_seed``. Every user has exactly ``rows_per_user`` rows, so
    the program's random-effect data has one capacity class. A row names a
    user feature once: ids are ``(start + stride * j) mod user_dim`` with a
    stride that is a unit modulo ``user_dim``."""
    users = int(cfg["users"])
    d = int(cfg["user_dim"])
    units = np.array([s for s in range(1, d) if np.gcd(s, d) == 1], np.int32)
    return _glmix_pattern(
        _key(cfg["shape_seed"], 0), jnp.asarray(units), users=users,
        n=users * int(cfg["rows_per_user"]), hashed=int(cfg["fixed_hashed_dim"]),
        fk=int(cfg["fixed_nnz"]), d=d, k=int(cfg["user_nnz"]),
    )


@partial(jax.jit, static_argnames=(
    "users", "hashed", "d", "density", "fixed_margin_std", "user_margin_std"))
def _glmix_rows(fixed_ids, user_ids, user_of_row, key, order_key, *, users,
                hashed, d, density, fixed_margin_std, user_margin_std):
    n, fk = fixed_ids.shape
    uk = user_ids.shape[1]
    f_ix = _with_intercept(fixed_ids, hashed, _round_up(fk + 1, 8))
    k_fv, k_uv, k_wf, k_wu, k_lab = jax.random.split(key, 5)
    f_v = jnp.zeros(f_ix.shape, jnp.float32)
    f_v = f_v.at[:, :fk].set(_values(k_fv, (n, fk)))
    f_v = f_v.at[:, fk].set(1.0)
    u_v = _values(k_uv, (n, uk))
    w_fixed = _planted(k_wf, (hashed + 1,), fk, density, fixed_margin_std)
    w_user = _planted(k_wu, (users, d), uk, density, user_margin_std)
    margins = jnp.sum(w_fixed[f_ix] * f_v, axis=1) + jnp.sum(
        w_user[user_of_row[:, None], user_ids] * u_v, axis=1
    )
    # row r is (slot r // users, user r % users): each user's rows change
    # places among themselves
    slots = jnp.argsort(jax.random.uniform(order_key, (n // users, users)), axis=0)
    order = (
        slots.astype(jnp.int32) * users + jnp.arange(users, dtype=jnp.int32)[None, :]
    ).reshape(-1)
    labels = _labels(k_lab, margins)
    return f_ix[order], f_v[order], user_ids[order], u_v[order], labels[order]


def glmix_rows(cfg: Dict, seed: int) -> GlmixData:
    """GLMix rows: a hashed fixed-effect shard with an intercept and a
    per-user shard over ``user_dim`` features; logistic labels from a
    planted fixed model plus a planted model per user."""
    fixed_ids, user_ids, user_of_row = glmix_pattern(cfg)
    hashed = int(cfg["fixed_hashed_dim"])
    users = int(cfg["users"])
    d = int(cfg["user_dim"])
    p = cfg["planted"]
    f_ix, f_v, user_ids, u_v, labels = map(np.asarray, _glmix_rows(
        fixed_ids, user_ids, user_of_row, _key(cfg["shape_seed"], 1), _key(seed, 2),
        users=users, hashed=hashed, d=d, density=float(p["density"]),
        fixed_margin_std=float(p["fixed_margin_std"]),
        user_margin_std=float(p["user_margin_std"]),
    ))
    return GlmixData(
        fixed=SparseRows(f_ix, f_v, hashed + 1, hashed),
        user=SparseRows(user_ids, u_v, d, None),
        user_of_row=np.asarray(user_of_row),
        num_users=users,
        labels=labels,
    )


GENERATORS = {"hashed_rows": hashed_rows, "glmix_rows": glmix_rows}


def generate(cfg: Dict, seed: int):
    return GENERATORS[cfg["generator"]](cfg, seed)
