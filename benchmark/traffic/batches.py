"""Training batches: a pool of seeded batches, cycled.

Mix parameters: ``batch``, ``seq``, ``pool``. Token ids and labels are
uniform over the vocabulary from ``--seed``; every seed gives the same
shapes, so the step does the same work.
"""

from __future__ import annotations

import numpy as np


def pool(mix: dict, seed: int, vocab: int) -> list:
    """[(ids, labels), ...] of int32 arrays (batch, seq)."""
    rng = np.random.default_rng([int(seed), 5])
    shape = (int(mix["batch"]), int(mix["seq"]))
    return [(rng.integers(0, vocab, shape, dtype=np.int32),
             rng.integers(0, vocab, shape, dtype=np.int32))
            for _ in range(int(mix["pool"]))]
