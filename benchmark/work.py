"""Needed operations and bytes, from shapes alone.

The yardstick for ``*_roofline_pct`` and ``step_mfu_pct.*``: what the
ALGORITHM needs, never what an implementation's schedule moves, so the
measure does not shift when the implementation does.

One value+gradient of a sparse GLM objective over COO data reads every
entry twice (the margin pass and the gradient pass) at 4 B of feature id
and 4 B of value, reads the three row vectors (labels, offsets, weights),
reads the coefficients once and writes the gradient once; it does a
multiply and an add per entry in each pass: 4 FLOPs an entry.
"""

from __future__ import annotations

import json
import os
from typing import Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


def glm_value_and_gradient(*, entries: int, rows: int, dim: int) -> Dict[str, float]:
    return {
        "flops": 4.0 * entries,
        "bytes": 2.0 * entries * 8 + 3.0 * rows * 4 + 2.0 * dim * 4,
    }


def sparse_score(*, entries: int, rows: int, dim: int) -> Dict[str, float]:
    """One scoring pass: every entry once, the coefficients once, one
    score a row written."""
    return {
        "flops": 2.0 * entries,
        "bytes": entries * 8.0 + rows * 4.0 + dim * 4.0,
    }


def add(*parts: Dict[str, float]) -> Dict[str, float]:
    return {
        "flops": sum(p["flops"] for p in parts),
        "bytes": sum(p["bytes"] for p in parts),
    }


def scale(part: Dict[str, float], k: float) -> Dict[str, float]:
    return {"flops": part["flops"] * k, "bytes": part["bytes"] * k}


def peaks_for(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; an unknown device is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in benchmark/peaks.json"
        )
    return table[device_kind]


def least_seconds(part: Dict[str, float], peaks: Dict[str, float]) -> Dict[str, object]:
    """The least time one chip could take, and which bound binds. The
    arithmetic is float32 on the vector units' side of the algorithm, but
    the published matrix peak is the only FLOP/s figure there is, so it is
    what FLOPs are held against; at these widths the byte bound binds by
    orders of magnitude either way."""
    t_flops = part["flops"] / peaks["flops_per_s"]
    t_bytes = part["bytes"] / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(t_flops, t_bytes),
        "bound": "bytes" if t_bytes >= t_flops else "flops",
    }
