"""Seeded inputs for a full GAME deployment: ONE table of rows, each with
a user and an item, under a fixed effect, a per-user and a per-item
random effect (the GLMix paper's own model, section 2).

``data.py``'s rules hold here too, and its helpers are used by import:
the ROWS (feature ids, values, which item a row falls on, the planted
models, the labels) come from the configuration's ``shape_seed``, their
ORDER from ``--seed``, and the order moves a user's rows only among
themselves, so every seed fits the same rows on the same shapes. One
jitted call on the default device; what comes back is copied to the host
once. Nothing here imports the program.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import data as bench_data
from benchmark.data import (
    SparseRows,
    _key,
    _labels,
    _planted,
    _round_up,
    _values,
    _with_intercept,
)


@dataclass
class GameData:
    fixed: SparseRows  # [n, k_fixed], intercept last
    # the shard a random effect reads, by the random effect's entity type:
    # "user" holds the ITEM-side features of the row (a user's model is
    # over them), "item" the USER-side ones
    sides: Dict[str, SparseRows]
    entity_of_row: Dict[str, np.ndarray]  # int32 [n] by entity type
    num_entities: Dict[str, int]
    labels: np.ndarray  # float32 [n]


def _side_ids(key, units, n: int, d: int, k: int):
    """[n, k] feature ids, no id twice in a row: ``(start + stride * j) mod
    d`` with a stride that is a unit modulo ``d`` (``data.py``'s scheme)."""
    k_start, k_stride = jax.random.split(key)
    start = jax.random.randint(k_start, (n, 1), 0, d, dtype=jnp.int32)
    stride = units[jax.random.randint(k_stride, (n, 1), 0, units.shape[0])]
    return (start + stride * jnp.arange(k, dtype=jnp.int32)[None, :]) % d


def _units(d: int) -> np.ndarray:
    return np.array([s for s in range(1, d) if np.gcd(s, d) == 1], np.int32)


@partial(jax.jit, static_argnames=(
    "users", "items", "per_user", "hashed", "fk", "du", "ku", "di", "ki",
    "density", "fixed_std", "user_std", "item_std"))
def _game_rows(key, order_key, units_u, units_i, *, users, items, per_user,
               hashed, fk, du, ku, di, ki, density, fixed_std, user_std,
               item_std):
    n = users * per_user
    (k_fixed, k_ids_u, k_ids_i, k_sigma, k_rho, k_fv, k_uv, k_iv, k_wf,
     k_wu, k_wi, k_lab) = jax.random.split(key, 12)
    # row r is (slot r // users, user r % users)
    user_of_row = jnp.arange(n, dtype=jnp.int32) % users
    slot = jnp.arange(n, dtype=jnp.int32) // users
    # the item of a row: a permutation of the rows taken modulo ``items``,
    # built so that a user's rows fall on ``per_user`` DIFFERENT items
    # (a random place sigma(u) and then steps of items / per_user) and
    # every item gets rows / items rows exactly (game_rows checks the
    # sizes that make it so); rho relabels the items
    sigma = jax.random.permutation(k_sigma, users).astype(jnp.int32)
    rho = jax.random.permutation(k_rho, items).astype(jnp.int32)
    item_of_row = rho[(sigma[user_of_row] + (items // per_user) * slot) % items]

    f_ix = _with_intercept(
        jax.random.randint(k_fixed, (n, fk), 0, hashed, dtype=jnp.int32),
        hashed, _round_up(fk + 1, 8),
    )
    f_v = jnp.zeros(f_ix.shape, jnp.float32)
    f_v = f_v.at[:, :fk].set(_values(k_fv, (n, fk))).at[:, fk].set(1.0)
    u_ix = _side_ids(k_ids_u, units_u, n, du, ku)  # what a user's model reads
    i_ix = _side_ids(k_ids_i, units_i, n, di, ki)  # what an item's model reads
    u_v, i_v = _values(k_uv, (n, ku)), _values(k_iv, (n, ki))
    w_fixed = _planted(k_wf, (hashed + 1,), fk, density, fixed_std)
    w_user = _planted(k_wu, (users, du), ku, density, user_std)
    w_item = _planted(k_wi, (items, di), ki, density, item_std)
    margins = (
        jnp.sum(w_fixed[f_ix] * f_v, axis=1)
        + jnp.sum(w_user[user_of_row[:, None], u_ix] * u_v, axis=1)
        + jnp.sum(w_item[item_of_row[:, None], i_ix] * i_v, axis=1)
    )
    labels = _labels(k_lab, margins)
    # each user's rows change places among themselves
    slots = jnp.argsort(jax.random.uniform(order_key, (per_user, users)), axis=0)
    order = (
        slots.astype(jnp.int32) * users + jnp.arange(users, dtype=jnp.int32)[None, :]
    ).reshape(-1)
    return tuple(a[order] for a in (
        f_ix, f_v, u_ix, u_v, i_ix, i_v, item_of_row, labels))


def game_rows(cfg: Dict, seed: int) -> GameData:
    """Rows of a GAME deployment: a hashed fixed-effect shard with an
    intercept, an item-side shard a user's model reads and a user-side
    shard an item's model reads; every user has ``rows_per_user`` rows on
    as many different items, every item ``rows_per_item`` rows; logistic
    labels from a planted fixed model plus a planted model per user and
    per item."""
    users, items = int(cfg["users"]), int(cfg["items"])
    per_user, per_item = int(cfg["rows_per_user"]), int(cfg["rows_per_item"])
    n = int(cfg["rows"])
    step = items // per_user
    if (
        n != users * per_user or n != items * per_item
        or items % per_user or (users % items) % step
    ):
        raise ValueError(
            f"rows {n} must be users x rows_per_user and items x "
            f"rows_per_item, and items / rows_per_user must divide items "
            f"and users mod items: else the items' row counts are uneven"
        )
    hashed = int(cfg["fixed_hashed_dim"])
    du, di = int(cfg["user_dim"]), int(cfg["item_dim"])
    p = cfg["planted"]
    f_ix, f_v, u_ix, u_v, i_ix, i_v, item_of_row, labels = map(
        np.asarray, _game_rows(
            _key(cfg["shape_seed"], 0), _key(seed, 2),
            jnp.asarray(_units(du)), jnp.asarray(_units(di)),
            users=users, items=items, per_user=per_user, hashed=hashed,
            fk=int(cfg["fixed_nnz"]), du=du, ku=int(cfg["user_nnz"]),
            di=di, ki=int(cfg["item_nnz"]), density=float(p["density"]),
            fixed_std=float(p["fixed_margin_std"]),
            user_std=float(p["user_margin_std"]),
            item_std=float(p["item_margin_std"]),
        ))
    return GameData(
        fixed=SparseRows(f_ix, f_v, hashed + 1, hashed),
        sides={
            "user": SparseRows(u_ix, u_v, du, None),
            "item": SparseRows(i_ix, i_v, di, None),
        },
        entity_of_row={
            "user": (np.arange(n, dtype=np.int32) % users),
            "item": item_of_row.astype(np.int32),
        },
        num_entities={"user": users, "item": items},
        labels=labels,
    )


# Found by the name a configuration gives under ``generator``, beside
# ``data.py``'s own: importing this module is what adds it there.
GENERATORS = {"game_rows": game_rows}
bench_data.GENERATORS.update(GENERATORS)


def generate(cfg: Dict, seed: int) -> GameData:
    return GENERATORS[cfg["generator"]](cfg, seed)
