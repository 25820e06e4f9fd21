"""Seeded inputs for a factored random-effect deployment: ``data.py``'s
GLMix rows (a hashed fixed-effect shard with an intercept and a per-user
shard of ``user_dim`` features, ``rows_per_user`` rows a user) whose
per-user model is of rank ``planted.latent_dim``: ``w_u = B* gamma_u``,
``B*`` a seeded ``[user_dim, latent_dim]`` matrix and ``gamma_u ~ N(0,
I)``, scaled so that a row's user margin has the standard deviation
``planted.user_margin_std``. The fixed model is ``glmix_rows``' own.

``data.py``'s rules hold here too, and its helpers are used by import:
the ROWS come from the configuration's ``shape_seed``, their ORDER from
``--seed`` (a user's rows among themselves). Nothing here imports the
program.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import data as bench_data
from benchmark.data import (
    GlmixData,
    SparseRows,
    _key,
    _labels,
    _planted,
    _round_up,
    _values,
    _with_intercept,
    glmix_pattern,
)


@partial(jax.jit, static_argnames=(
    "users", "hashed", "d", "latent", "density", "fixed_margin_std",
    "user_margin_std"))
def _factored_rows(fixed_ids, user_ids, user_of_row, key, order_key, *, users,
                   hashed, d, latent, density, fixed_margin_std,
                   user_margin_std):
    n, fk = fixed_ids.shape
    uk = user_ids.shape[1]
    f_ix = _with_intercept(fixed_ids, hashed, _round_up(fk + 1, 8))
    k_fv, k_uv, k_wf, k_b, k_g, k_lab = jax.random.split(key, 6)
    f_v = jnp.zeros(f_ix.shape, jnp.float32)
    f_v = f_v.at[:, :fk].set(_values(k_fv, (n, fk)))
    f_v = f_v.at[:, fk].set(1.0)
    u_v = _values(k_uv, (n, uk))
    w_fixed = _planted(k_wf, (hashed + 1,), fk, density, fixed_margin_std)
    # rank-``latent`` user models: a row's margin is a sum of uk * latent
    # unit-variance products, scaled to the stated standard deviation
    b_star = jax.random.normal(k_b, (d, latent), jnp.float32) * jnp.float32(
        user_margin_std / np.sqrt(uk * latent)
    )
    gamma = jax.random.normal(k_g, (users, latent), jnp.float32)
    # each user's model over the features, [users, d], as glmix_rows holds it
    w_user = jnp.dot(gamma, b_star.T, precision=jax.lax.Precision.HIGHEST)
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


def glmix_factored_rows(cfg: Dict, seed: int) -> GlmixData:
    """GLMix rows whose per-user models are of rank ``planted.latent_dim``."""
    fixed_ids, user_ids, user_of_row = glmix_pattern(cfg)
    hashed = int(cfg["fixed_hashed_dim"])
    users = int(cfg["users"])
    d = int(cfg["user_dim"])
    p = cfg["planted"]
    f_ix, f_v, user_ids, u_v, labels = map(np.asarray, _factored_rows(
        fixed_ids, user_ids, user_of_row, _key(cfg["shape_seed"], 1), _key(seed, 2),
        users=users, hashed=hashed, d=d, latent=int(p["latent_dim"]),
        density=float(p["density"]),
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


# Found by the name a configuration gives under ``generator``, beside
# ``data.py``'s own: importing this module is what adds it there.
GENERATORS = {"glmix_factored_rows": glmix_factored_rows}
bench_data.GENERATORS.update(GENERATORS)


def generate(cfg: Dict, seed: int) -> GlmixData:
    return GENERATORS[cfg["generator"]](cfg, seed)
