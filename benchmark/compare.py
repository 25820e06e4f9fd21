"""The two measures every comparison with the reference uses."""

from __future__ import annotations

import numpy as np


def max_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Widest elementwise gap as a share of the reference's largest entry."""
    scale = float(np.max(np.abs(want)))
    return float(np.max(np.abs(got - want))) / max(scale, 1e-30)


def rel_gap(got: float, want: float) -> float:
    return abs(float(got) - float(want)) / max(abs(float(want)), 1e-30)
