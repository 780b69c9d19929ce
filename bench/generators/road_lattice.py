"""A road network's shape: a connected, thinned square lattice.

Nodes sit on a square lattice (row-major, the last row partly filled).
A random spanning tree of the lattice (the minimum spanning tree under
random weights) keeps every node reachable, and lattice edges drawn at
random are added until there are exactly ``edges``.  ``hubs`` nodes, far
from the lattice's border and from each other, are junctions of degree
``max_degree`` (at most 24): each is joined to the nearest lattice
points around it, nearest first.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import minimum_spanning_tree

from bench.graphs import canonical


def _stencil(k: int) -> np.ndarray:
    """The ``k`` nearest offsets on the lattice, ties broken by angle."""
    r = np.arange(-3, 4)
    dx, dy = (a.ravel() for a in np.meshgrid(r, r, indexing="ij"))
    keep = (dx != 0) | (dy != 0)
    dx, dy = dx[keep], dy[keep]
    order = np.lexsort((np.arctan2(dy, dx), dx * dx + dy * dy))
    return np.stack([dx[order], dy[order]], 1)[:k]


def generate(n: int, edges: int, max_degree: int, hubs: int,
             seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    side = int(np.ceil(np.sqrt(n)))
    idx = np.arange(n)
    x, y = idx // side, idx % side
    right = idx[(y + 1 < side) & (idx + 1 < n)]
    down = idx[idx + side < n]
    lat = np.concatenate([np.stack([right, right + 1], 1),
                          np.stack([down, down + side], 1)])

    # hubs: interior, and 7 apart, so no two stencils touch
    cand = idx[(x >= 3) & (x < n // side - 3) & (y >= 3) & (y < side - 3)]
    picked = []
    for h in rng.permutation(cand):
        if all(max(abs(h // side - q // side), abs(h % side - q % side)) > 7
               for q in picked):
            picked.append(h)
            if len(picked) == hubs:
                break
    off = _stencil(max_degree)
    hub = np.asarray(picked, np.int64)
    spokes = np.stack([np.repeat(hub, len(off)),
                       ((hub // side)[:, None] + off[:, 0]).ravel() * side
                       + ((hub % side)[:, None] + off[:, 1]).ravel()], 1)

    w = rng.random(len(lat)) + 1.0
    A = sp.coo_matrix((w, (lat[:, 0], lat[:, 1])), shape=(n, n)).tocsr()
    T = minimum_spanning_tree(A).tocoo()
    tree = canonical(np.stack([T.row, T.col], 1))
    base = canonical(np.concatenate([tree, spokes]))
    rest = lat[~np.isin(lat[:, 0] * n + lat[:, 1],
                        base[:, 0] * n + base[:, 1])]
    # a hub keeps exactly its spokes: no further lattice edge at a hub
    rest = rest[~np.isin(rest, hub).any(1)]
    need = edges - len(base)
    if not 0 <= need <= len(rest):
        raise ValueError(f"road_lattice cannot reach {edges} edges")
    extra = rest[np.sort(rng.permutation(len(rest))[:need])]
    return canonical(np.concatenate([base, extra]))
