"""Helpers of the plain references and comparisons that decide `correct`.

Each deployment's module (`deployments/<name>.py`) states its own
reference: `expected` is a straightforward NumPy statement of the served
path's semantics over exactly the records that were published (and,
after the drain, committed), `observe` reads the same quantities back
from the program after the window, and `compare` gives each number
compared with its limit; a run is correct when none exceeds its limit.
A reference imports nothing of the program.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np


def rows(ints: Sequence, floats: Sequence = ()) -> np.ndarray:
    """Events as one int64 row each, comparable as wholes: the integer
    columns `ints`, then the `floats`, each by its float32 bits."""
    cols = [np.asarray(c, np.int64) for c in ints]
    cols += [np.asarray(c, np.float32).view(np.int32).astype(np.int64)
             for c in floats]
    return np.stack(cols, 1)


def unmatched(got: np.ndarray, want: np.ndarray) -> int:
    """Rows of either side with no equal row on the other, each row
    counted as often as it occurs."""
    both = np.concatenate([got, want])
    if both.shape[0] == 0:
        return 0
    side = np.r_[np.ones(got.shape[0]), -np.ones(want.shape[0])]
    _, inverse = np.unique(both, axis=0, return_inverse=True)
    return int(np.abs(np.bincount(inverse.ravel(), weights=side)).sum())
