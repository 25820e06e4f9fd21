"""Needed operations and bytes of a factored random effect's passes, from
shapes alone (``work.py``'s rule: what the ALGORITHM needs, never what an
implementation moves; the densified rows the program makes are its own
cost and do not count).

The term of row ``i`` is ``z_i' B gamma_u(i)`` over ``k`` entries of
``z_i``, ``B`` ``[d, L]``. A pass of the projection fit (the margins, or
the gradient ``sum_i c_i z_i gamma_u(i)'``) is ``L`` multiply-adds an
entry, ``2 n k L`` FLOPs, and reads the ``n k`` entries (4 B of feature
id and 4 B of value). A latent bank update makes each slot's ``L``
latent features from its ``k`` entries and an ``L x L`` outer product a
slot for the normal equations: ``E S (k L + L^2)`` multiply-adds, reading
the entries once.
"""

from __future__ import annotations

from typing import Dict


def projection_pass(*, rows: int, entries: int, latent: int) -> Dict[str, float]:
    return {
        "flops": 2.0 * rows * entries * latent,
        "bytes": 8.0 * rows * entries,
    }


def latent_update(
    *, users: int, rows_per_user: int, entries: int, latent: int
) -> Dict[str, float]:
    slots = users * rows_per_user
    return {
        "flops": 2.0 * slots * (entries * latent + latent * latent),
        "bytes": 8.0 * slots * entries,
    }
