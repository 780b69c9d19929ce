"""Incremental-change plumbing: update batches, scenario sampling, routing.

The paper's "incremental changes are continuously read from the data
sources"; here a deterministic sampler produces the two experimental
scenarios of §5.2.1:

  * inter-partition — endpoints in *different* blocks,
  * intra-partition — endpoints in *the same* block,

for both insertions (non-adjacent pairs) and deletions (existing edges).
`apply_updates_host` is the checked host boundary: capacity / duplicate /
existence validation happens here, never on the TPU path.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .graph import CapacityError, GraphBlocks, insert_edge, delete_edge, PAD

Update = Tuple[int, int, int]  # (u, v, op)  op=+1 insert, -1 delete


def classify(g: GraphBlocks, u: int, v: int) -> str:
    return "intra" if (u // g.Cn) == (v // g.Cn) else "inter"


def _real_nodes_by_block(g: GraphBlocks) -> List[np.ndarray]:
    mask = np.asarray(g.node_mask)
    ids = np.arange(g.N)
    return [ids[(ids // g.Cn == b) & mask] for b in range(g.P)]


def _adjacent(nbr_np: np.ndarray, u: int, v: int) -> bool:
    return bool((nbr_np[u] == v).any())


def sample_insertions(
    g: GraphBlocks, count: int, scenario: str, seed: int = 0
) -> List[Update]:
    """Sample `count` non-adjacent node pairs for insertion.

    scenario: 'intra' -> same block, 'inter' -> different blocks.
    """
    rng = np.random.default_rng(seed)
    nbr_np = np.asarray(g.nbr)
    by_block = _real_nodes_by_block(g)
    nonempty = [b for b in range(g.P) if len(by_block[b]) >= 1]
    out: List[Update] = []
    taken: set = set()
    guard = 0
    while len(out) < count:
        guard += 1
        if guard > count * 1000:
            raise RuntimeError(f"could not sample {count} {scenario} insertions")
        if scenario == "intra":
            b = int(rng.choice([b for b in nonempty if len(by_block[b]) >= 2]))
            u, v = rng.choice(by_block[b], size=2, replace=False)
        else:
            b1, b2 = rng.choice(nonempty, size=2, replace=False)
            u = int(rng.choice(by_block[b1]))
            v = int(rng.choice(by_block[b2]))
        u, v = int(u), int(v)
        key = (min(u, v), max(u, v))
        if u == v or key in taken or _adjacent(nbr_np, u, v):
            continue
        taken.add(key)
        out.append((u, v, +1))
    return out


def sample_deletions(
    g: GraphBlocks, count: int, scenario: str, seed: int = 0
) -> List[Update]:
    """Sample `count` existing edges to delete, by scenario."""
    rng = np.random.default_rng(seed)
    nbr_np = np.asarray(g.nbr)
    src, col = np.nonzero(nbr_np >= 0)  # row-major: valid slots only
    dst = nbr_np[src, col]
    ok = src < dst
    src, dst = src[ok], dst[ok]
    same = (src // g.Cn) == (dst // g.Cn)
    pick = same if scenario == "intra" else ~same
    src, dst = src[pick], dst[pick]
    if len(src) < count:
        raise RuntimeError(
            f"only {len(src)} {scenario} edges available, need {count}"
        )
    idx = rng.choice(len(src), size=count, replace=False)
    return [(int(src[i]), int(dst[i]), -1) for i in idx]


def _insert_sorted(nbr: np.ndarray, deg: np.ndarray, u: int, v: int) -> None:
    """Splice v into row u at its sorted position (sorted-ELL invariant)."""
    d = deg[u]
    pos = int(np.searchsorted(nbr[u, :d], v))
    nbr[u, pos + 1:d + 1] = nbr[u, pos:d]
    nbr[u, pos] = v
    deg[u] += 1


def _delete_sorted(nbr: np.ndarray, deg: np.ndarray, u: int, v: int) -> None:
    """Remove v from row u, shifting left over the hole (invariant kept)."""
    d = deg[u]
    pos = int(np.searchsorted(nbr[u, :d], v))
    nbr[u, pos:d - 1] = nbr[u, pos + 1:d]
    nbr[u, d - 1] = PAD
    deg[u] -= 1


def apply_updates_host(g: GraphBlocks, updates: List[Update]) -> GraphBlocks:
    """Apply updates with host-side validation (capacity, dup, existence).

    Produces the same sorted canonical rows as the jitted
    `insert_edge`/`delete_edge` path, so replaying a batch through either
    path yields bit-identical `nbr` arrays.
    """
    deg = np.asarray(g.deg).copy()
    nbr = np.asarray(g.nbr).copy()
    for u, v, op in updates:
        if not (0 <= u < g.N and 0 <= v < g.N):
            # negative ids would silently wrap under numpy/jax indexing
            raise ValueError(f"update ({u},{v}) out of range [0, {g.N})")
        if u == v:
            # the jitted insert_edge/delete_edge assume no self-loops (module
            # invariant of graph.py); reject here, at the host boundary
            raise ValueError(f"self-loop update ({u},{v}) rejected")
        if op > 0:
            if (nbr[u] == v).any():
                raise ValueError(f"edge ({u},{v}) already present")
            if deg[u] >= g.Cd or deg[v] >= g.Cd:
                raise CapacityError(
                    f"degree capacity Cd={g.Cd} exceeded at ({u},{v})")
            _insert_sorted(nbr, deg, u, v)
            _insert_sorted(nbr, deg, v, u)
        else:
            if not (nbr[u] == v).any():
                raise ValueError(f"edge ({u},{v}) not present")
            _delete_sorted(nbr, deg, u, v)
            _delete_sorted(nbr, deg, v, u)
    import dataclasses

    return dataclasses.replace(
        g, nbr=jnp.asarray(nbr), deg=jnp.asarray(deg.astype(np.int32))
    )
