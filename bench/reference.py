"""The plain host reference: the same analytics by textbook methods.

Independent of the program: it reads only the benchmark's own edge list
(padded ids, one row per undirected edge).  Coreness by peeling, CC
labels by scipy, PageRank as a float64 power iteration with the
program's stated semantics: uniform start over real nodes, teleport
``(1-alpha)/n``, a node of degree 0 passes nothing on.  The CC labels
follow the program's convention: the smallest padded id in the
component, -1 on padding rows.  Copied in substance from the checks of
``chip_smoke.py`` as they stood when the benchmark was defined.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import connected_components


def adjacency(edges: np.ndarray, N: int) -> sp.csr_matrix:
    """Symmetric 0/1 CSR adjacency of the padded-id edge list."""
    u = np.concatenate([edges[:, 0], edges[:, 1]])
    v = np.concatenate([edges[:, 1], edges[:, 0]])
    return sp.csr_matrix((np.ones(len(u), np.float64), (u, v)),
                         shape=(N, N))


def degrees(A: sp.csr_matrix) -> np.ndarray:
    return np.diff(A.indptr).astype(np.int64)


def labels(A: sp.csr_matrix, mask: np.ndarray) -> np.ndarray:
    """Smallest padded id of each node's component; -1 on padding."""
    _, lab = connected_components(A, directed=False)
    low = np.full(lab.max() + 1, len(mask))
    np.minimum.at(low, lab, np.arange(len(mask)))
    return np.where(mask, low[lab], -1)


def coreness(A: sp.csr_matrix, mask: np.ndarray) -> np.ndarray:
    """Core numbers by peeling: at each level k remove every node of
    remaining degree <= k, and repeat until none is left at that level."""
    deg = degrees(A)
    indptr, ind = A.indptr, A.indices
    alive = mask.copy()
    core = np.zeros(len(mask), np.int64)
    k = 0
    while alive.any():
        k = max(k, int(deg[alive].min()))
        front = np.flatnonzero(alive & (deg <= k))
        while front.size:
            core[front] = k
            alive[front] = False
            cnt = indptr[front + 1] - indptr[front]
            first = np.repeat(indptr[front] - np.cumsum(cnt) + cnt, cnt)
            nb = ind[first + np.arange(cnt.sum())]
            deg -= np.bincount(nb, minlength=len(deg))
            nb = np.unique(nb)
            front = nb[alive[nb] & (deg[nb] <= k)]
    return core


def pagerank(A: sp.csr_matrix, mask: np.ndarray, steps: int,
             alpha: float) -> np.ndarray:
    """``steps`` float64 power-iteration steps from the uniform start."""
    deg = degrees(A)
    n_real = max(1, int(mask.sum()))
    r = np.where(mask, 1.0 / n_real, 0.0)
    for _ in range(steps):
        contrib = np.where(deg > 0, r / np.maximum(deg, 1), 0.0)
        r = np.where(mask, (1 - alpha) / n_real + alpha * (A @ contrib), 0.0)
    return r


def edges_of_keys(keyset: np.ndarray, N: int) -> np.ndarray:
    return np.stack([keyset // N, keyset % N], 1)


class Epoch:
    """Every reference field of one graph state, built on first use."""

    def __init__(self, edges: np.ndarray, mask: np.ndarray, steps: int,
                 alpha: float):
        self.edges, self.mask = edges, mask
        self.steps, self.alpha = steps, alpha
        self.A = adjacency(edges, len(mask))
        self.deg = degrees(self.A)
        self._core = self._labels = self._rank = None

    @property
    def core(self):
        if self._core is None:
            self._core = coreness(self.A, self.mask)
        return self._core

    @property
    def labels(self):
        if self._labels is None:
            self._labels = labels(self.A, self.mask)
        return self._labels

    @property
    def rank(self):
        if self._rank is None:
            self._rank = pagerank(self.A, self.mask, self.steps, self.alpha)
        return self._rank

    def nbr_max_core(self, u: int) -> int:
        row = self.A.indices[self.A.indptr[u]:self.A.indptr[u + 1]]
        return int(self.core[row].max()) if row.size else -1
