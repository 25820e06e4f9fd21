"""Needed operations and bytes of a bank update on INDEX_MAP, from shapes
alone (``work.py``'s rule: what the ALGORITHM needs, never what an
implementation moves; the padding of a capacity class, in rows or in
dimensions, is the program's own cost and does not count).

Each member is solved by damped Newton in the dual, on its OWN active
rows ``S`` and its own map's ``D`` dimensions: the sample Gram ``X X'``
once (``S^2 D`` multiply-adds), and an iteration's two passes over ``X``
(``X c`` and ``X' r``, ``2 S D`` multiply-adds) and solve of the
``S x S`` system (``S^3 / 3``, a Cholesky). An iteration reads the
active rows' entries (4 B of feature id, 4 B of value), their labels,
offsets and weights, and reads and writes the member's coefficients.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def bank_update(
    *, active: np.ndarray, dims: np.ndarray, entries: int, iterations: float
) -> Dict[str, float]:
    """``active`` [E] rows and ``dims`` [E] map widths a member,
    ``entries`` the live entries of all active rows, ``iterations`` the
    Newton iterations a member."""
    s = active.astype(np.float64)
    d = dims.astype(np.float64)
    return {
        "flops": float(np.sum(2.0 * s * s * d))
        + iterations * float(np.sum(4.0 * s * d + s ** 3 / 3.0)),
        "bytes": iterations * (
            8.0 * entries + 12.0 * float(s.sum()) + 8.0 * float(d.sum())
        ),
    }
