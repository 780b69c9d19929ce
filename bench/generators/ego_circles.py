"""Ego networks glued into one social graph (the shape of SNAP's
ego-Facebook: a few egos, each a friend of a whole community).

Egos are nodes ``0..len(egos)-1``, chained to each other; ego ``i``
befriends every member of its community and, to reach its degree
``egos[i]``, members of other communities.  Communities split the other
nodes in proportion to the egos' degrees.  Inside community ``i``,
``intra[i]`` edges are drawn Chung-Lu style from Pareto weights of
exponent ``gamma``, and ``dense = [community, size, p]`` plants a dense
group (its first ``size`` members, each pair with probability ``p``):
the graph's innermost core.  ``bridges`` random member pairs join the
communities.  Edges that are neither ego nor chain edges are then
trimmed at random to exactly ``edges``.
"""
from __future__ import annotations

import numpy as np

from bench.graphs import canonical


def generate(n: int, edges: int, egos: list, intra: list, gamma: float,
             dense: list, bridges: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    E = len(egos)
    d = np.asarray(egos, np.int64)
    share = d * (n - E) / d.sum()
    s = np.floor(share).astype(np.int64)
    s[np.argsort(s - share)[:n - E - s.sum()]] += 1
    first = E + np.concatenate([[0], np.cumsum(s)[:-1]])
    comm = [np.arange(a, a + k) for a, k in zip(first, s)]

    fixed = [np.stack([np.arange(E - 1), np.arange(1, E)], 1)]
    for i in range(E):
        fixed.append(np.stack([np.full(s[i], i), comm[i]], 1))
        extra = d[i] - s[i] - (i > 0) - (i < E - 1)
        if extra > 0:
            others = np.setdiff1d(np.arange(E, n), comm[i])
            fixed.append(np.stack(
                [np.full(extra, i), rng.choice(others, extra, replace=False)],
                1))
    free = []
    for i in range(E):
        iu, ju = np.triu_indices(s[i], 1)
        m = intra[i]
        if i == dense[0]:
            inner = (iu < dense[1]) & (ju < dense[1])
            hit = inner & (rng.random(iu.size) < dense[2])
            free.append(np.stack([comm[i][iu[hit]], comm[i][ju[hit]]], 1))
            m = max(0, m - int(hit.sum()))
            iu, ju = iu[~inner], ju[~inner]
        w = (1 - rng.random(s[i])) ** (-1 / (gamma - 1))
        p = w[iu] * w[ju]
        for _ in range(20):  # rescale until the clipped sum is m
            p = np.minimum(p * m / p.sum(), 1)
        hit = rng.random(p.size) < p
        free.append(np.stack([comm[i][iu[hit]], comm[i][ju[hit]]], 1))
    free.append(rng.integers(E, n, (bridges, 2)))

    fixed = canonical(np.concatenate(fixed))
    free = canonical(np.concatenate(free))
    free = free[~np.isin(free[:, 0] * n + free[:, 1],
                         fixed[:, 0] * n + fixed[:, 1])]
    over = len(fixed) + len(free) - edges
    if over < 0:
        raise ValueError(f"ego_circles drew {-over} edges too few")
    free = free[np.sort(rng.permutation(len(free))[over:])]
    return canonical(np.concatenate([fixed, free]))
