"""The bytes one sparse logistic-regression step requires, counted on the
host from the batch, whatever the implementation does.

Sparse LR does about five floating-point operations per nonzero slot, so
bytes bound it and the share of the chip's peak is a share of HBM
bandwidth. A step must at least
  - read each nonzero slot's id and value, read the parameter it gathers
    and write its gradient: 4 B each, 16 B per slot;
  - read one label per sample: 4 B;
  - read and write the parameter and the adagrad accumulator of each
    distinct id: 16 B per distinct id.
A step that touches the whole table moves far more; that excess is what
the share exposes.
"""
from __future__ import annotations

import numpy as np

BYTES_PER_SLOT = 16
BYTES_PER_LABEL = 4
BYTES_PER_DISTINCT_ID = 16


def step_bytes(ids: np.ndarray) -> int:
    """Required bytes of one step over the (B, K) ids (-1 = empty slot)."""
    ids = np.asarray(ids)
    live = ids[ids >= 0]
    return (BYTES_PER_SLOT * int(live.size)
            + BYTES_PER_LABEL * int(ids.shape[0])
            + BYTES_PER_DISTINCT_ID * int(np.unique(live).size))
