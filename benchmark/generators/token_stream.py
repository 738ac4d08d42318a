"""Training batches from the seed: packed windows, every row different.

Parameters (traffic file): ``batch``, ``seq_len``. Batch ``i`` of seed ``s``
is a pure function of (s, i), so the reference reads the very batches the
program trained on without keeping them.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np


def batch(traffic: Dict[str, Any], model: Dict[str, Any], seed: int,
          index: int) -> Tuple[np.ndarray, np.ndarray]:
    """(inputs, targets), each (batch, seq_len) int32: windows of
    seq_len + 1 tokens, the targets shifted by one."""
    rng = np.random.default_rng([seed, index])
    rows = rng.integers(0, model["vocab_size"],
                        (traffic["batch"], traffic["seq_len"] + 1),
                        dtype=np.int32)
    return rows[:, :-1].copy(), rows[:, 1:].copy()
