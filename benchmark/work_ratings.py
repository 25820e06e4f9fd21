"""Needed operations and bytes of a ratings deployment's coordinates,
from shapes alone (``work.py``'s rule: what the ALGORITHM needs, never
what an implementation's blocks move; padding slots do not count).

An ALS half-step over ``n`` ratings, ``E`` solved entities and rank ``K``
is an exact ridge solve an entity: the Gram ``X' W X`` is a ``K x K``
outer product a rating (``2 K^2`` FLOPs; the symmetric half is not
taken off), the right-hand side ``2 K``, the factorization and the two
triangular solves ``K^3 / 3 + 2 K^2`` an entity. It reads, a rating, the
partner's factor row (``4 K`` bytes: the rows of a popular partner are
read once a rating, the gather the algorithm is), the partner's code,
the rating and the residual of the other coordinates (12 bytes), and
writes ``4 K`` bytes an entity.

A bias over an intercept-only shard is the same solve at ``K = 1`` with
no row to gather: 12 bytes and 4 FLOPs a rating. The factor score reads
two factor rows and two codes a rating and writes a score.
"""

from __future__ import annotations

from typing import Dict


def als_half_step(*, ratings: int, entities: int, rank: int) -> Dict[str, float]:
    return {
        "flops": ratings * (2.0 * rank * rank + 2.0 * rank)
        + entities * (rank ** 3 / 3.0 + 2.0 * rank * rank),
        "bytes": ratings * (4.0 * rank + 12.0) + entities * 4.0 * rank,
    }


def bias_update(*, ratings: int, entities: int) -> Dict[str, float]:
    return {"flops": 4.0 * ratings, "bytes": 12.0 * ratings + 4.0 * entities}


def bias_score(*, ratings: int, entities: int) -> Dict[str, float]:
    return {"flops": 0.0, "bytes": 8.0 * ratings + 4.0 * entities}


def factor_score(*, ratings: int, rank: int) -> Dict[str, float]:
    return {
        "flops": 2.0 * rank * ratings,
        "bytes": ratings * (8.0 * rank + 8.0 + 4.0),
    }
