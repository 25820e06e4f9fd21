"""Seeded inputs for GLMix on the reference's default random-effect path:
a fixed effect over hashed features and a per-member model over the
ITEM-side features a row carries (the GLMix paper's ``s_j' alpha_m``),
which the program projects into each member's own index map (INDEX_MAP)
and caps at ``active_cap`` active rows a member.

``data.py``'s rules hold here too, and its helpers are used by import: the
ROWS (how many a member has, where they sit, feature ids, values, the
planted models, the labels) come from the configuration's ``shape_seed``,
and ``--seed`` only ORDERS a member's rows among that member's own
places, for a member that keeps all of its rows active. An over-cap
member's rows keep the order ``shape_seed`` gives them: the program draws
a capped member's active rows from its own fixed seed over the member's
rows in row order, so on every ``--seed`` the same rows are active, every
member's index map is the same and every seed fits the same model on the
same shapes. Nothing here imports the program.
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
class IndexMapData:
    fixed: SparseRows  # [n, k_fixed], intercept last
    # the item-side features a member's model reads, the member's
    # intercept (id ``dim - 1``) after them
    member: SparseRows  # [n, k_member]
    member_of_row: np.ndarray  # int32 [n]
    num_members: int
    labels: np.ndarray  # float32 [n]


def member_counts(cfg: Dict) -> np.ndarray:
    """Rows a member, int64 [members], from ``shape_seed``: draws of the
    truncated discrete power law ``P(c) ~ c ** -exponent`` on
    ``min .. max``, then one row added to (or taken from) as many members,
    drawn alike, as make the total exactly ``rows``, in as many rounds as
    that takes."""
    law = cfg["rows_law"]
    members, rows = int(cfg["members"]), int(cfg["rows"])
    c = np.arange(int(law["min"]), int(law["max"]) + 1, dtype=np.float64)
    p = c ** -float(law["exponent"])
    cdf = np.cumsum(p) / p.sum()
    k_draw, k_fix = jax.random.split(_key(cfg["shape_seed"], 3))
    u = np.asarray(jax.random.uniform(k_draw, (members,), jnp.float32))
    counts = c[np.minimum(np.searchsorted(cdf, u), len(c) - 1)].astype(np.int64)
    order = np.asarray(jax.random.permutation(k_fix, members))
    short = rows - int(counts.sum())
    while short:
        step = 1 if short > 0 else -1
        can = order[(counts[order] < int(law["max"])) if step > 0
                    else (counts[order] > int(law["min"]))]
        if not len(can):
            raise ValueError(f"rows {rows} do not fit the row law")
        take = can[:abs(short)]
        counts[take] += step
        short -= step * len(take)
    return counts


def _zipf_ids(key, n: int, k: int, d: int):
    """[n, k] ids of ``d`` features, distinct and ascending within a row:
    ranks drawn Zipf with exponent 1 (``P(r) ~ 1 / (r + 1.5)``, the
    continuous law's inverse at a uniform draw) over ``d - k + 1`` ranks,
    sorted, and each one a repeat of the one before moved to the next
    free rank up, so that the head stays heavy and a row names a feature
    once."""
    u = jax.random.uniform(key, (n, k))
    top = d - k + 1
    ranks = jnp.floor(jnp.exp(u * jnp.log(top + 1.0))).astype(jnp.int32) - 1
    ranks = jnp.sort(jnp.clip(ranks, 0, top - 1), axis=1)
    j = jnp.arange(k, dtype=jnp.int32)[None, :]
    return jax.lax.cummax(ranks - j, axis=1) + j


def _entry_normal(key, entity, feature):
    """A standard normal and a uniform for each (entity, feature) pair,
    the same wherever the pair occurs: a planted model over a space too
    wide to hold."""
    def one(e, f):
        k = jax.random.fold_in(jax.random.fold_in(key, e), f)
        k_n, k_u = jax.random.split(k)
        return jax.random.normal(k_n, ()), jax.random.uniform(k_u, ())

    return jax.vmap(jax.vmap(one, (None, 0)))(entity, feature)


@partial(jax.jit, static_argnames=(
    "hashed", "fk", "dm", "mk", "density", "fixed_std", "member_std"))
def _indexmap_rows(key, order_key, member_of_row, keeps_all, *, hashed, fk,
                   dm, mk, density, fixed_std, member_std):
    n = member_of_row.shape[0]
    (k_fixed, k_ids, k_perm, k_fv, k_mv, k_wf, k_wm, k_lab) = jax.random.split(
        key, 8)
    f_ix = _with_intercept(
        jax.random.randint(k_fixed, (n, fk), 0, hashed, dtype=jnp.int32),
        hashed, _round_up(fk + 1, 8),
    )
    f_v = jnp.zeros(f_ix.shape, jnp.float32)
    f_v = f_v.at[:, :fk].set(_values(k_fv, (n, fk))).at[:, fk].set(1.0)
    # ranks -> feature ids by a fixed relabelling: a hashed feature's id
    # says nothing of how often it occurs
    label = jax.random.permutation(k_perm, dm).astype(jnp.int32)
    ids = label[_zipf_ids(k_ids, n, mk, dm)]
    m_ix = _with_intercept(ids, dm, _round_up(mk + 1, 8))
    m_v = jnp.zeros(m_ix.shape, jnp.float32)
    m_v = m_v.at[:, :mk].set(_values(k_mv, (n, mk))).at[:, mk].set(1.0)
    w_fixed = _planted(k_wf, (hashed + 1,), fk, density, fixed_std)
    normal, uniform = _entry_normal(k_wm, member_of_row, m_ix[:, : mk + 1])
    scale = member_std / np.sqrt((mk + 1) * density)
    w_member = jnp.where(uniform < density, normal * scale, 0.0)
    margins = jnp.sum(w_fixed[f_ix] * f_v, axis=1) + jnp.sum(
        w_member * m_v[:, : mk + 1], axis=1
    )
    labels = _labels(k_lab, margins)
    # each member's rows change places among the member's own, where the
    # member keeps every row; an over-cap member's stay where they are
    at = jnp.arange(n, dtype=jnp.int32)
    draw = jax.random.randint(order_key, (n,), 0, n, dtype=jnp.int32)
    tie = jnp.where(keeps_all[member_of_row], draw, at)
    src = jnp.lexsort((at, tie, member_of_row))  # rows, grouped by member
    dst = jnp.lexsort((at, member_of_row))  # the member's places, in order
    order = jnp.zeros(n, jnp.int32).at[dst].set(src.astype(jnp.int32))
    return tuple(a[order] for a in (f_ix, f_v, m_ix, m_v, labels))


def indexmap_rows(cfg: Dict, seed: int) -> IndexMapData:
    """Rows of a GLMix deployment on INDEX_MAP: ``rows`` rows over
    ``members`` members, each member's count from :func:`member_counts`,
    the rows of all members shuffled together (a table is not grouped by
    member); a hashed fixed-effect shard with an intercept; ``member_nnz``
    item-side features of ``member_dim`` a row, ids Zipf (exponent 1),
    plus the member's intercept; logistic labels from a planted fixed
    model plus a planted model a member over the item-side features."""
    members = int(cfg["members"])
    counts = member_counts(cfg)
    member_of_row = np.asarray(jax.random.permutation(
        _key(cfg["shape_seed"], 4),
        jnp.asarray(np.repeat(np.arange(members, dtype=np.int32), counts)),
    ))
    keeps_all = counts <= int(cfg["active_cap"])
    hashed, dm = int(cfg["fixed_hashed_dim"]), int(cfg["member_dim"])
    p = cfg["planted"]
    f_ix, f_v, m_ix, m_v, labels = map(np.asarray, _indexmap_rows(
        _key(cfg["shape_seed"], 1), _key(seed, 2),
        jnp.asarray(member_of_row), jnp.asarray(keeps_all),
        hashed=hashed, fk=int(cfg["fixed_nnz"]), dm=dm,
        mk=int(cfg["member_nnz"]), density=float(p["density"]),
        fixed_std=float(p["fixed_margin_std"]),
        member_std=float(p["member_margin_std"]),
    ))
    return IndexMapData(
        fixed=SparseRows(f_ix, f_v, hashed + 1, hashed),
        member=SparseRows(m_ix, m_v, dm + 1, dm),
        member_of_row=member_of_row.astype(np.int32),
        num_members=members,
        labels=labels,
    )


# Found by the name a configuration gives under ``generator``, beside
# ``data.py``'s own: importing this module is what adds it there.
GENERATORS = {"indexmap_rows": indexmap_rows}
bench_data.GENERATORS.update(GENERATORS)


def generate(cfg: Dict, seed: int) -> IndexMapData:
    return GENERATORS[cfg["generator"]](cfg, seed)
