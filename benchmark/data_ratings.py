"""Seeded inputs for a ratings deployment: ONE table of ratings, each by
a user on a movie, at the published counts of a rating data set
(``benchmark/configs/movielens-20m-mf.json``: MovieLens-20M's), under a
fixed effect over the movie's genres, a bias per user and per movie and a
rank-K factor pair (Koren, Bell, Volinsky 2009, eqs. 4-5).

``data.py``'s rules hold here too: the ROWS (how many ratings each user
and each movie has, who rated what, the genres, the planted model, the
ratings) come from the configuration's ``shape_seed``, their ORDER from
``--seed``, and the order moves a user's ratings only among themselves,
so every seed fits the same rows on the same shapes. Nothing is
downloaded and nothing here imports the program.

How many: the quantiles of a shifted log-normal, fitted by bisection so
that the heaviest entity, the floor and the TOTAL are the configuration's
to the unit, separately for users and for movies. (A power law in the
rank through the same three numbers, Zipf-Mandelbrot, is NOT a pair of
margins any table has: by Gale-Ryser it is 2.17M ratings short at the
published counts; the two log-normals are.)
Who rated what: users in the order of their counts, heaviest first, a
few at a time (a user with thousands of ratings alone); a user draws its
movies without replacement with odds in proportion to what each movie
still lacks of its own count (a Gumbel top-k), so that a (user, movie)
pair appears at most once, every user has its count to the unit, and the
movies keep theirs but for the few draws a batch makes from one
snapshot. The rows come out grouped by user.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from functools import partial
from statistics import NormalDist
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import data as bench_data
from benchmark.data import SparseRows, _key

BATCH = 256  # most users that draw from one snapshot of what the movies lack
DRAWS = 4096  # most draws a snapshot serves: a user over half that draws alone
CHUNK = 1 << 18  # rows a step of the rating pass gathers factors for


@dataclass
class RatingData:
    fixed: SparseRows  # [n, genre slots + 1]: a movie's genres, intercept last
    entity_of_row: Dict[str, np.ndarray]  # "user", "item": int32 [n]
    num_entities: Dict[str, int]
    labels: np.ndarray  # float32 [n], the half-star grid
    # told, not used by the program: the laws as drawn
    counts: Dict[str, np.ndarray]


def lognormal_counts(entities: int, lightest: int, heaviest: int,
                     total: int) -> np.ndarray:
    """``entities`` whole counts, heaviest first: the quantiles of a
    shifted log-normal, ``c = lightest - 1 + exp(mu + sigma z)`` at the
    ``entities`` mid-quantiles ``z`` of a standard normal, with the
    heaviest ``heaviest``, none under ``lightest`` and ``sum c = total``
    exactly: ``sigma`` by bisection (at a fixed top the sum falls as it
    grows), ``mu`` from the top, the fractions given to the entities that
    lost most in the rounding down."""
    inv = NormalDist().inv_cdf
    z = np.array([inv((i + 0.5) / entities) for i in range(entities)])[::-1]
    shift = lightest - 1

    def law(sigma: float) -> np.ndarray:
        mu = np.log(heaviest - shift) - sigma * z[0]
        return shift + np.exp(mu + sigma * z)

    lo, hi = 1e-3, 8.0
    if not law(hi).sum() <= total <= law(lo).sum():
        raise ValueError(
            f"no log-normal law over {entities} entities from {heaviest} "
            f"down to {lightest} sums to {total}"
        )
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if law(mid).sum() > total else (lo, mid)
    c = np.maximum(law(lo), lightest)
    whole = np.floor(c).astype(np.int64)
    whole[0] = heaviest
    # the top stays as it is; the rest share what rounding left over
    owed = total - int(whole.sum())
    inner = np.argsort(-(c - whole)[1:], kind="stable") + 1
    step = 1 if owed >= 0 else -1
    for i in range(abs(owed)):
        whole[inner[i % len(inner)]] += step
    return np.clip(whole, lightest, heaviest)


@partial(jax.jit, static_argnames=("k", "batch"))
def _draw_class(key, lack, counts, *, k: int, batch: int):
    """The users of one capacity class draw their movies, ``batch`` of
    them from one snapshot of ``lack`` and the next batch from what they
    left (a scan): each user its ``counts`` movies without replacement,
    odds in proportion to ``lack`` (a movie that lacks nothing goes
    last): the ``k`` best of log-odds plus Gumbel noise, by a sort."""

    def draw(lack, args):
        key, counts = args
        noise = jax.random.gumbel(key, (batch, lack.shape[0]))
        odds = jnp.log(jnp.maximum(lack, 1e-6).astype(jnp.float32))
        picked = jnp.argsort(-(odds[None, :] + noise), axis=1)[:, :k]
        taken = jnp.arange(k)[None, :] < counts[:, None]
        drawn = jnp.zeros_like(lack).at[picked.reshape(-1)].add(
            taken.reshape(-1).astype(lack.dtype))
        return lack - drawn, picked.astype(jnp.int32)

    counts = counts.reshape(-1, batch)
    lack, picked = jax.lax.scan(
        draw, lack, (jax.random.split(key, counts.shape[0]), counts))
    return picked.reshape(-1, k), lack


def who_rates_what(key, user_counts: np.ndarray, item_counts: np.ndarray):
    """(user rank, movie rank) of every rating, grouped by user rank,
    ``user_counts`` (heaviest first) to the unit. One program a capacity
    class of users (a power of two of draws)."""
    lack = jnp.asarray(item_counts, jnp.int32)
    if int(user_counts[0]) > len(item_counts):
        raise ValueError("a user has more ratings than there are movies")
    caps = 1 << np.ceil(np.log2(np.maximum(user_counts, 1))).astype(np.int64)
    classes = []
    for cap in sorted(set(caps.tolist()), reverse=True):
        counts = user_counts[caps == cap]  # a run: the counts are sorted
        k = int(min(cap, len(item_counts)))
        batch = int(max(1, min(BATCH, DRAWS // k)))
        pad = -len(counts) % batch
        padded = np.concatenate([counts, np.zeros(pad, counts.dtype)])
        classes.append((cap, counts, jnp.asarray(padded, jnp.int32), k, batch))
    # the classes' programs compile side by side (a sort in a scan takes a
    # quarter of a minute to compile for the chip); they RUN one after
    # another, each on what the one before left
    with ThreadPoolExecutor(len(classes)) as pool:
        programs = list(pool.map(
            lambda c: _draw_class.lower(
                key, lack, c[2], k=c[3], batch=c[4]).compile(),
            classes,
        ))
    users, items = [], []
    start = 0
    for (cap, counts, padded, k, _), draw in zip(classes, programs):
        picked, lack = draw(jax.random.fold_in(key, cap), lack, padded)
        picked = np.asarray(picked)[:len(counts)]
        taken = np.arange(k)[None, :] < counts[:, None]
        items.append(picked[taken])
        users.append(np.repeat(
            np.arange(start, start + len(counts), dtype=np.int32), counts))
        start += len(counts)
    return np.concatenate(users), np.concatenate(items)


def _movie_genres(key, movies: int, genres: int, slots: int,
                  mean: float, odds: np.ndarray) -> np.ndarray:
    """[movies, slots] genre ids, -1 pad: 1 to ``slots`` genres a movie,
    ``mean`` on average, drawn without replacement by ``odds``."""
    k_n, k_g = jax.random.split(key)
    extra = jax.random.geometric(k_n, 1.0 / mean, (movies,)) - 1  # mean - 1
    n = jnp.clip(1 + extra, 1, slots)
    noise = jax.random.gumbel(k_g, (movies, genres))
    _, picked = jax.lax.top_k(jnp.log(jnp.asarray(odds))[None, :] + noise, slots)
    return np.asarray(
        jnp.where(jnp.arange(slots)[None, :] < n[:, None], picked, -1), np.int32)


@jax.jit
def _ratings(key, mu, b_user, b_item, p, q, genre_effect, noise_std,
             users, items, genre_rows):
    """The planted model's ratings on the half-star grid, a chunk of rows
    a scan step (the gathered factor rows of one chunk are the only
    [*, K] temporaries)."""

    def chunk(_, args):
        u, i, g, k = args
        z = (
            mu + b_user[u] + b_item[i] + jnp.sum(p[u] * q[i], axis=-1)
            + jnp.sum(jnp.where(g >= 0, genre_effect[jnp.maximum(g, 0)], 0.0),
                      axis=-1)
            + noise_std * jax.random.normal(k, u.shape)
        )
        return None, jnp.clip(jnp.round(2.0 * z) / 2.0, 0.5, 5.0)

    keys = jax.random.split(key, users.shape[0])
    _, r = jax.lax.scan(chunk, None, (users, items, genre_rows, keys))
    return r.reshape(-1)


def rating_rows(cfg: Dict, seed: int) -> RatingData:
    users, items = int(cfg["users"]), int(cfg["items"])
    rated, n = int(cfg["rated_items"]), int(cfg["ratings"])
    genres, slots = int(cfg["genres"]), int(cfg["genre_slots"])
    rank = int(cfg["rank"])
    p_cfg = cfg["planted"]
    user_counts = lognormal_counts(
        users, int(cfg["min_ratings_per_user"]), int(cfg["max_ratings_per_user"]), n)
    item_counts = lognormal_counts(
        rated, int(cfg["min_ratings_per_item"]), int(cfg["max_ratings_per_item"]), n)
    shape = int(cfg["shape_seed"])
    u_rank, i_rank = who_rates_what(_key(shape, 0), user_counts, item_counts)
    # ranks to ids: a relabelling from shape_seed (the movies never rated
    # are ids too); the rows stay grouped by user, now in the order of ids
    rng = np.random.default_rng(shape)
    user_id = rng.permutation(users).astype(np.int32)
    item_id = rng.permutation(items).astype(np.int32)[:rated]
    user_of_row, item_of_row = user_id[u_rank], item_id[i_rank]
    # a user's ratings change places among themselves by --seed: rows in
    # the order of (user id, a number drawn a row from --seed)
    order = np.asarray(jax.lax.sort(
        (jnp.asarray(user_of_row),
         jax.random.bits(_key(seed, 2), (n,), jnp.uint32),
         jnp.arange(n, dtype=jnp.int32)), num_keys=2)[2])
    # the planted ratings are the ROW's (its user's, its movie's and its
    # own noise, drawn in the first order), so they move with it
    movie_genres = _movie_genres(
        _key(shape, 1), items, genres, slots, float(cfg["mean_genres_per_item"]),
        np.asarray(cfg["genre_odds"], np.float32))
    k_bu, k_bi, k_p, k_q, k_g, k_noise = jax.random.split(_key(shape, 3), 6)
    pad = -n % min(CHUNK, n)

    def chunks(a, fill):
        a = np.concatenate([a, np.full((pad,) + a.shape[1:], fill, a.dtype)])
        return jnp.asarray(a.reshape((-1, min(CHUNK, n)) + a.shape[1:]))

    factor_std = float(np.sqrt(float(p_cfg["factor_score_std"]) / np.sqrt(rank)))
    labels = np.asarray(_ratings(
        k_noise, jnp.float32(p_cfg["mu"]),
        float(p_cfg["user_bias_std"]) * jax.random.normal(k_bu, (users,)),
        float(p_cfg["item_bias_std"]) * jax.random.normal(k_bi, (items,)),
        factor_std * jax.random.normal(k_p, (users, rank)),
        factor_std * jax.random.normal(k_q, (items, rank)),
        float(p_cfg["genre_effect_std"]) * jax.random.normal(k_g, (genres,)),
        jnp.float32(p_cfg["noise_std"]),
        chunks(user_of_row, 0), chunks(item_of_row, 0),
        chunks(movie_genres[item_of_row], -1),
    ))[:n]
    user_of_row, item_of_row, labels = (
        a[order] for a in (user_of_row, item_of_row, labels))
    # the fixed effect's rows: the movie's genres at 1.0, the intercept
    # last, empty slots at id 0 with value 0.0
    g = movie_genres[item_of_row]
    f_ix = np.concatenate(
        [np.maximum(g, 0), np.full((n, 1), genres, np.int32)], axis=1)
    f_v = np.concatenate(
        [(g >= 0).astype(np.float32), np.ones((n, 1), np.float32)], axis=1)
    return RatingData(
        fixed=SparseRows(f_ix.astype(np.int32), f_v, genres + 1, genres),
        entity_of_row={"user": user_of_row, "item": item_of_row},
        num_entities={"user": users, "item": items},
        labels=labels.astype(np.float32),
        counts={"user": user_counts, "item": item_counts},
    )


# Found by the name a configuration gives under ``generator``, beside
# ``data.py``'s own: importing this module is what adds it there.
GENERATORS = {"rating_rows": rating_rows}
bench_data.GENERATORS.update(GENERATORS)


def generate(cfg: Dict, seed: int) -> RatingData:
    return GENERATORS[cfg["generator"]](cfg, seed)
