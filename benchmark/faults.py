"""The faults a cell's check has to catch.

Two are planted in what a step returns, and each entry module holds its
own under ``FAULTS`` (a state returned unchanged; an answer altered where
it is produced): a function from the outputs ``take_outputs`` gave to the
outputs a broken timed path would have left. The third, half of the batch
left out, is planted in the program's own input (the entries'
``_row_weights``) or in the reference put in the program's place
(``reference_outputs(weights=half_batch(n))``). ``benchmark/tests`` puts
each under a whole run and sees ``correct`` come out false;
``benchmark/proof.py`` reads them on the chip at the cell's own size.
"""

from __future__ import annotations

import numpy as np

ALTERED_BY = 0.1  # the largest coefficient, off by a tenth


def half_batch(n: int) -> np.ndarray:
    """Every other row left out, the rest counted double (the mean taken
    over the rest)."""
    return np.where(np.arange(n) % 2 == 0, 2.0, 0.0).astype(np.float32)
