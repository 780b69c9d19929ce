"""A large social graph: a heavy-tailed degree law, one celebrity hub and
a planted dense core (the shape of SNAP's com-LiveJournal).

Node ``0`` is the hub, a friend of exactly ``max_degree`` others; nodes
``1..size`` are the dense core, each pair linked with probability ``p``
(``dense = [size, p]``), drawn from a stream of its own so that the
core, and the graph's degeneracy it sets, is the same at any ``n``.
The rest are joined by a random recursive tree (one component; each
core member hangs off one of them), and then by Chung-Lu edges: both
ends of each drawn pair in proportion to Pareto weights of exponent
``gamma`` (degree law P(d) ~ d^-gamma), each weight capped so that its
expected degree is at most ``cap``: how many pair ends each node gets is
one multinomial draw, and the ends are shuffled into pairs.  Loops and
repeats are dropped and the Chung-Lu edges trimmed at random to exactly
``edges`` in all.  The
hub and the core take no Chung-Lu edges, so the hub's degree is exactly
``max_degree`` and every core member's degree inside the rest is one.
"""
from __future__ import annotations

import numpy as np

from bench.graphs import canonical


def generate(n: int, edges: int, max_degree: int, gamma: float, cap: float,
             dense: list, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    size, p = int(dense[0]), float(dense[1])
    core = np.arange(1, 1 + size)
    rest = np.arange(1 + size, n)

    order = rng.permutation(rest)
    parent = order[(rng.random(len(order) - 1)
                    * np.arange(1, len(order))).astype(np.int64)]
    iu, ju = np.triu_indices(size, 1)
    hit = np.random.default_rng([seed, 1]).random(iu.size) < p
    fixed = canonical(np.concatenate([
        np.stack([order[1:], parent], 1),
        np.stack([core, rng.choice(rest, size)], 1),
        np.stack([core[iu[hit]], core[ju[hit]]], 1),
        np.stack([np.zeros(max_degree, np.int64),
                  rng.choice(rest, max_degree, replace=False)], 1)]))
    del order, parent, iu, ju, hit

    need = edges - len(fixed)
    if need <= 0:
        raise ValueError(f"powerlaw_core: {len(fixed)} fixed edges leave "
                         f"none of {edges} to draw")
    w = (1 - rng.random(len(rest))) ** (-1 / (gamma - 1))
    mean_deg = 2 * need / len(rest)
    for _ in range(4):  # expected degree w * mean_deg / mean(w), capped
        w = np.minimum(w, cap * w.mean() / mean_deg)
    draw = int(1.08 * need) + 1024
    ends = np.repeat(rest, rng.multinomial(2 * draw, w / w.sum()))
    rng.shuffle(ends)
    free = canonical(ends.reshape(-1, 2))
    del ends, w
    span = np.int64(n)
    free = free[~np.isin(free[:, 0] * span + free[:, 1],
                         fixed[:, 0] * span + fixed[:, 1])]
    if len(free) < need:
        raise ValueError(f"powerlaw_core drew {need - len(free)} edges "
                         "too few")
    free = free[np.sort(rng.permutation(len(free))[:need])]
    out = canonical(np.concatenate([fixed, free]))
    deg = np.bincount(out.ravel(), minlength=n)
    if deg[1:].max() >= max_degree:
        raise ValueError(f"powerlaw_core: a node of degree "
                         f"{int(deg[1:].max())} rivals the hub's "
                         f"{max_degree}; lower cap")
    return out
