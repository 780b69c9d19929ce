"""The paper's random partition (section 5.2.1): balanced, each node's
block drawn at random (a shuffled round-robin), whatever the edges."""
from __future__ import annotations

import numpy as np


def partition(edges: np.ndarray, n: int, P: int,
              rng: np.random.Generator) -> np.ndarray:
    assign = np.arange(n, dtype=np.int64) % P
    rng.shuffle(assign)
    return assign
