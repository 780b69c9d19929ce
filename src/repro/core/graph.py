"""Block-partitioned graph representation for BLADYG-on-TPU.

The paper's *block* (a connected subgraph held by one Akka worker) becomes a
fixed-capacity, padded array shard:

- Nodes are **relabeled block-contiguously**: block ``b`` owns the global
  padded index range ``[b*Cn, (b+1)*Cn)``.  ``block_of(u) = u // Cn`` — no
  lookup tables on the hot path, and sharding the leading axis of every node
  array over the ``workers`` mesh axis gives exactly one block per device.
- Adjacency is **ELL-padded**: ``nbr[N_pad, Cd]`` holds global padded
  neighbor ids, ``-1`` for padding.  Undirected edges are stored twice (once
  per endpoint), matching the degree semantics of the paper.
- Rows obey the **sorted-ELL invariant**: the valid slots of every row are
  in strictly ascending id order and the ``-1`` pads sit on the right
  (``nbr[u, :deg[u]]`` ascending, ``nbr[u, deg[u]:] == PAD``).  Every
  construction and mutation path (`build_blocks`, `build_ell_random`,
  `insert_edge`, `delete_edge`, `apply_updates_host`, `migrate_vertices`)
  maintains it, so sorted rows are the *canonical* form: the host and jitted
  update paths produce bit-identical arrays, and kernels may binary-search
  or merge-intersect neighbor rows instead of scanning them linearly.
- All shapes are static (``jit``/``shard_map`` friendly).  Capacity overflow
  is checked at the host boundary (`build_blocks`, `apply_updates_host`) and
  raises — the TPU path never reallocates.

This is the TPU-native analogue of the paper's per-worker hash-map state: the
price is padding, the payoff is that every BLADYG superstep is a dense,
statically-shaped SPMD program.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PAD = -1  # padding sentinel for neighbor slots / node ids

#: sort key for PAD slots — larger than any node id, so an ascending sort
#: leaves valid ids first (in order) and pads on the right
_PAD_KEY = np.iinfo(np.int32).max


class CapacityError(ValueError):
    """An operation needs more node (Cn) or degree (Cd) capacity than the
    blocks hold.  Subclasses ValueError so existing overflow handling keeps
    working; the elastic stream path catches this specifically to grow."""


def sort_nbr_rows(nbr: np.ndarray) -> np.ndarray:
    """Canonicalize ELL rows to the sorted-ELL invariant (host-side).

    Maps pads to +inf (int32 max), sorts each row ascending, and maps the
    pads back — valid slots end up ascending with pads on the right.  A
    no-op on rows that already satisfy the invariant.
    """
    keyed = np.where(nbr >= 0, nbr, _PAD_KEY)
    keyed = np.sort(keyed, axis=-1)
    return np.where(keyed == _PAD_KEY, PAD, keyed).astype(nbr.dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GraphBlocks:
    """A block-partitioned undirected graph with static capacities.

    Attributes
    ----------
    nbr:       (P*Cn, Cd) int32 — padded neighbor lists (global padded ids).
    deg:       (P*Cn,)    int32 — true degree of each node (0 for padding).
    node_mask: (P*Cn,)    bool  — True for real nodes.
    orig_id:   (P*Cn,)    int32 — original node id (PAD for padding rows).
    P, Cn, Cd: static ints — #blocks, node capacity / block, degree capacity.
    """

    nbr: jax.Array
    deg: jax.Array
    node_mask: jax.Array
    orig_id: jax.Array
    P: int = dataclasses.field(metadata=dict(static=True))
    Cn: int = dataclasses.field(metadata=dict(static=True))
    Cd: int = dataclasses.field(metadata=dict(static=True))

    # ---- static helpers -------------------------------------------------
    @property
    def N(self) -> int:
        """Padded node count (P*Cn)."""
        return self.P * self.Cn

    def block_of(self, u):
        return u // self.Cn

    @property
    def n_real(self) -> int:
        return int(np.asarray(jnp.sum(self.node_mask)))

    @property
    def m_real(self) -> int:
        return int(np.asarray(jnp.sum(self.deg))) // 2

    def valid_nbr_mask(self) -> jax.Array:
        return self.nbr >= 0

    def is_boundary(self) -> jax.Array:
        """True for nodes with at least one neighbor in another block."""
        nb_block = jnp.where(self.nbr >= 0, self.nbr // self.Cn, PAD)
        own = (jnp.arange(self.N) // self.Cn)[:, None]
        return jnp.any((nb_block != own) & (self.nbr >= 0), axis=1)

    def edge_cut(self) -> jax.Array:
        """Number of undirected edges crossing blocks."""
        nb_block = self.nbr // self.Cn
        own = (jnp.arange(self.N) // self.Cn)[:, None]
        cross = (nb_block != own) & (self.nbr >= 0)
        return jnp.sum(cross) // 2

    def grow(self, Cn: Optional[int] = None, Cd: Optional[int] = None):
        """Capacity escalation — see `grow_blocks`.  Returns (g2, rekey)."""
        return grow_blocks(self, Cn, Cd)


def _relabel(
    n: int, assign: np.ndarray, P: int, Cn: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Map original ids -> block-contiguous padded ids.

    Block ``b``'s nodes take the rows ``b*Cn + r`` in increasing
    original id.  Returns (new_of_old (n,), old_of_new (P*Cn,)).
    """
    pop = np.bincount(assign, minlength=P)
    if pop.size and pop.max() > Cn:
        b = int(np.argmax(pop))
        raise ValueError(
            f"block {b} overflows node capacity Cn={Cn} "
            f"(needs at least {int(pop[b])})")
    order = np.argsort(assign, kind="stable")
    blocks = assign[order]
    starts = np.concatenate([[0], np.cumsum(pop)[:-1]])
    new_of_old = np.empty(n, dtype=np.int64)
    new_of_old[order] = blocks * Cn + (np.arange(n) - starts[blocks])
    old_of_new = np.full(P * Cn, PAD, dtype=np.int64)
    old_of_new[new_of_old] = np.arange(n)
    return new_of_old, old_of_new


def build_blocks(
    edges: np.ndarray,
    n: int,
    assign: np.ndarray,
    P: int,
    Cn: Optional[int] = None,
    Cd: Optional[int] = None,
    deg_slack: int = 8,
    node_slack: int = 0,
) -> GraphBlocks:
    """Construct GraphBlocks from an edge list and a node->block assignment.

    Parameters
    ----------
    edges: (m, 2) int array of original node ids (undirected, no dups/loops
           required; they are cleaned here).
    n:     number of original nodes.
    assign:(n,) block id per node in [0, P).
    Cn:    node capacity per block (default: max block population, padded to
           a multiple of 8).
    Cd:    degree capacity (default: max degree + deg_slack) — insertions
           beyond this raise at the host boundary.
    node_slack: extra padding rows reserved per block on top of the default
           Cn (ignored when Cn is given explicitly).  Padding rows are the
           raw material of both `migrate_vertices` destinations and
           `core.hub_split.split_hubs` mirror replicas — split-aware builds
           reserve room here so hub slices can land in their readers'
           blocks without growing Cn (which would re-key every row id).
    """
    edges = np.asarray(edges, dtype=np.int64)
    if edges.size:
        # canonicalize: drop self loops + duplicates
        u, v = edges[:, 0], edges[:, 1]
        keep = u != v
        lo = np.minimum(u[keep], v[keep])
        hi = np.maximum(u[keep], v[keep])
        edges = np.unique(np.stack([lo, hi], 1), axis=0)
    assign = np.asarray(assign, dtype=np.int64)
    assert assign.shape == (n,), (assign.shape, n)
    assert P >= 1 and (assign >= 0).all() and (assign < P).all()

    pop = np.bincount(assign, minlength=P)
    if Cn is None:
        Cn = int(-(-(max(1, pop.max()) + max(0, int(node_slack))) // 8) * 8)
    deg = np.zeros(n, dtype=np.int64)
    if edges.size:
        np.add.at(deg, edges[:, 0], 1)
        np.add.at(deg, edges[:, 1], 1)
    if Cd is None:
        Cd = int(max(1, deg.max()) + deg_slack)
    if deg.size and deg.max() > Cd:
        raise ValueError(f"max degree {deg.max()} exceeds Cd={Cd}")

    new_of_old, old_of_new = _relabel(n, assign, P, Cn)
    N = P * Cn
    nbr = np.full((N, Cd), PAD, dtype=np.int64)
    fill = np.zeros(N, dtype=np.int64)
    if edges.size:
        # both directions of every edge; each row takes its entries in
        # occurrence order, and the sort below fixes the slot order
        lo, hi = new_of_old[edges[:, 0]], new_of_old[edges[:, 1]]
        src, dst = np.concatenate([lo, hi]), np.concatenate([hi, lo])
        nbr[src, _occurrence_ranks(src)] = dst
        fill = np.bincount(src, minlength=N).astype(np.int64)
    nbr = sort_nbr_rows(nbr)  # establish the sorted-ELL invariant
    node_mask = old_of_new >= 0

    return GraphBlocks(
        nbr=jnp.asarray(nbr, jnp.int32),
        deg=jnp.asarray(fill, jnp.int32),
        node_mask=jnp.asarray(node_mask),
        orig_id=jnp.asarray(old_of_new, jnp.int32),
        P=P,
        Cn=Cn,
        Cd=Cd,
    )


def _occurrence_ranks(ends: np.ndarray) -> np.ndarray:
    """rank[i] = how many earlier entries of `ends` equal ends[i] (O(m log m))."""
    order = np.argsort(ends, kind="stable")
    s = ends[order]
    starts = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
    counts = np.diff(np.r_[starts, len(s)])
    grouprank = np.arange(len(s)) - np.repeat(starts, counts)
    rank = np.empty(len(s), np.int64)
    rank[order] = grouprank
    return rank


def build_ell_random(
    N: int, Cd: int = 8, seed: int = 0, m_factor: float = 2.2
) -> GraphBlocks:
    """ER-style random graph built straight into ELL form (single block).

    Skips the edge-list + relabel path of `build_blocks` (too slow beyond
    ~10^5 nodes) by sampling ~m_factor*N node pairs and filling neighbor
    rows with vectorized passes: canonicalize + `np.unique` kills
    self-loops and duplicates, then each pass ranks the surviving pairs
    per endpoint and accepts those whose rank still fits the remaining
    degree capacity; pairs rejected only because an *earlier* pair was
    itself rejected get another chance next pass (the loop ends when a
    pass accepts nothing).  O(m log m) per pass, 2-3 passes in practice —
    minutes-to-seconds at the benchmark N vs the old per-pair Python loop.

    Deterministic per (N, Cd, seed, m_factor).  Structure note: the old
    loop filled rows in raw sample order (first-come, capacity greedy);
    this one processes pairs in canonical sorted order, so the *specific*
    edges kept at capacity pressure differ from the pre-vectorization
    version — same distributional shape, different instance.  Used by
    the large-N benchmarks/tests where the dense (N, N) adjacency is
    infeasible; random structure also keeps the min-H iteration's
    superstep count low (near-ring graphs cascade instead).
    """
    rng = np.random.default_rng(seed)
    uv = rng.integers(0, N, (int(m_factor * N), 2))
    lo = np.minimum(uv[:, 0], uv[:, 1])
    hi = np.maximum(uv[:, 0], uv[:, 1])
    keep = lo != hi
    pending = np.unique(np.stack([lo[keep], hi[keep]], 1), axis=0)

    nbr = np.full((N, Cd), PAD, np.int32)
    deg = np.zeros(N, np.int64)
    while len(pending):
        u, v = pending[:, 0], pending[:, 1]
        ranks = _occurrence_ranks(np.concatenate([u, v]))
        ok = ((deg[u] + ranks[:len(u)] < Cd)
              & (deg[v] + ranks[len(u):] < Cd))
        if not ok.any():
            break
        acc = pending[ok]
        au, av = acc[:, 0], acc[:, 1]
        ranks = _occurrence_ranks(np.concatenate([au, av]))
        nbr[au, deg[au] + ranks[:len(au)]] = av
        nbr[av, deg[av] + ranks[len(au):]] = au
        np.add.at(deg, np.concatenate([au, av]), 1)
        pending = pending[~ok]
    nbr = sort_nbr_rows(nbr)  # establish the sorted-ELL invariant
    return GraphBlocks(
        nbr=jnp.asarray(nbr), deg=jnp.asarray(deg, jnp.int32),
        node_mask=jnp.ones(N, bool),
        orig_id=jnp.arange(N, dtype=jnp.int32), P=1, Cn=N, Cd=Cd,
    )


def halo_slot_counts(g: GraphBlocks) -> Tuple[int, int]:
    """(intra, inter) valid neighbor-slot counts — the W2W halo payload.

    A superstep that gathers one value per neighbor slot (e.g. the min-H
    estimate exchange) moves exactly `intra` values inside blocks and
    `inter` values across block boundaries.  Host-side ints, cheap enough
    to recompute per engine run.
    """
    nbr = np.asarray(g.nbr)
    valid = nbr >= 0
    own = (np.arange(g.N) // g.Cn)[:, None]
    inter = int(np.sum(valid & (nbr // g.Cn != own)))
    return int(np.sum(valid)) - inter, inter


def halo_pair_counts(g: GraphBlocks) -> np.ndarray:
    """(P, P) matrix: valid neighbor slots in block-row b reading block b'.

    Row b column b' counts the per-superstep W2W values block b pulls
    from block b' under a one-value-per-neighbor-slot exchange; the
    diagonal is the intra-block traffic.  `halo_slot_counts` is the
    (trace of this matrix, off-diagonal sum) pair; the runtime's
    `HaloPlan` serves exactly the off-diagonal entries (deduplicated per
    boundary vertex at device granularity).
    """
    nbr = np.asarray(g.nbr)
    valid = nbr >= 0
    own = np.repeat(np.arange(g.N) // g.Cn, g.Cd).reshape(g.N, g.Cd)
    pairs = np.zeros((g.P, g.P), np.int64)
    np.add.at(pairs, (own[valid], nbr[valid] // g.Cn), 1)
    return pairs


def migrate_vertices(g: GraphBlocks, moves, *arrays):
    """Live §4.2 rebalancing: move real nodes to other blocks in place.

    `moves` is a sequence of (u, dest_block) with `u` a global padded id
    of a real node.  Each move swaps the node's row with a *padding* row
    of the destination block, so the whole migration is a permutation of
    the node axis under fixed (P, Cn, Cd): shapes never change and
    compiled kernels never re-specialize.  Node ids DO change — the
    returned `perm` (old id -> new id) lets the caller remap anything it
    holds (pending stream updates, cached id sets); `orig_id` rides the
    permutation, so original-id semantics are preserved automatically.

    Any extra `arrays` (coreness, per-node estimates, ...) are permuted
    along and returned in order.  Host-side preprocessing, like the
    partitioners: raises under a trace, on moving padding/duplicate
    nodes, on no-op moves, and when a destination block has no free
    padding slots (slots vacated by this very migration do NOT count —
    capacity is checked against the pre-migration layout).

    Returns (g', perm, *arrays').  Coreness is invariant under the
    permutation: `core'[perm[u]] == core[u]` bit-exactly (min-H is a
    pointwise fixpoint, indifferent to node order).
    """
    if isinstance(g.nbr, jax.core.Tracer):
        raise TypeError(
            "migrate_vertices is host-side preprocessing; it cannot run "
            "under jit/vmap tracing."
        )
    nbr = np.asarray(g.nbr)
    mask = np.asarray(g.node_mask)
    N, Cn = g.N, g.Cn
    perm = np.arange(N, dtype=np.int64)
    free = {
        b: list(np.flatnonzero(~mask[b * Cn:(b + 1) * Cn]) + b * Cn)
        for b in range(g.P)
    }
    seen: set = set()
    for u, b2 in moves:
        u, b2 = int(u), int(b2)
        if not (0 <= u < N) or not mask[u]:
            raise ValueError(f"cannot migrate non-real node {u}")
        if not (0 <= b2 < g.P):
            raise ValueError(f"destination block {b2} outside [0, {g.P})")
        if b2 == u // Cn:
            raise ValueError(f"no-op move: node {u} already in block {b2}")
        if u in seen:
            raise ValueError(f"duplicate move for node {u}")
        if not free[b2]:
            raise CapacityError(
                f"block {b2} has no free node capacity (Cn={Cn})")
        seen.add(u)
        t = free[b2].pop(0)
        perm[u], perm[t] = t, u  # swap node row with the padding row

    inv = np.empty(N, dtype=np.int64)
    inv[perm] = np.arange(N)
    remap_vals = np.where(nbr >= 0, perm[np.maximum(nbr, 0)], PAD)
    # remapping ids scrambles in-row order; re-sort to keep the invariant
    g2 = dataclasses.replace(
        g,
        nbr=jnp.asarray(sort_nbr_rows(remap_vals[inv]), jnp.int32),
        deg=jnp.asarray(np.asarray(g.deg)[inv], jnp.int32),
        node_mask=jnp.asarray(mask[inv]),
        orig_id=jnp.asarray(np.asarray(g.orig_id)[inv], jnp.int32),
    )
    out = tuple(jnp.asarray(np.asarray(a)[inv]) for a in arrays)
    return (g2, perm) + out


def grow_blocks(g: GraphBlocks, Cn: Optional[int] = None,
                Cd: Optional[int] = None):
    """Capacity escalation: pure pad-and-rekey to new (Cn, Cd).

    Block ``b``'s rows move from ``[b*Cn, b*Cn+Cn)`` to ``[b*Cn2,
    b*Cn2+Cn2)`` keeping their in-block slot ``r``, so the id map is

        ``rekey[b*Cn + r] = b*Cn2 + r``

    which is *globally monotone* whenever ``Cn2 >= Cn`` — remapped
    neighbor rows therefore stay ascending and the sorted-ELL invariant
    survives the rekey without a re-sort.  ``orig_id`` rides the
    relocation, so original-id semantics are untouched.  Growing is
    always legal; *shrinking* is legal exactly when the contents fit
    (every real node sits at ``r < Cn2`` and every degree is ``<= Cd2``)
    — the inverse of a grow that saw no migrations qualifies, which is
    what makes grow-then-shrink an id-stable round trip.

    Host-side preprocessing (raises under a trace), like
    `migrate_vertices`.  Returns ``(g2, rekey)`` with ``rekey`` the
    (N_old,) old-id -> new-id map (-1 for rows dropped by a shrink —
    necessarily padding).  Relocate any per-node arrays you hold with
    `relocate_rows`; note CC labels also need their *values* rekeyed
    (they hold padded ids): relocation first, then ``rekey[label]``.
    Min-member label canonicality commutes with the monotone rekey, so
    relabeled labels stay canonical bit-for-bit.
    """
    if isinstance(g.nbr, jax.core.Tracer):
        raise TypeError(
            "grow_blocks is host-side preprocessing; it cannot run "
            "under jit/vmap tracing."
        )
    Cn2 = g.Cn if Cn is None else int(Cn)
    Cd2 = g.Cd if Cd is None else int(Cd)
    if Cn2 < 1 or Cd2 < 1:
        raise ValueError(f"capacities must be >= 1, got Cn={Cn2} Cd={Cd2}")
    mask = np.asarray(g.node_mask)
    deg = np.asarray(g.deg)
    if Cn2 < g.Cn:
        slots = np.flatnonzero(mask) % g.Cn
        if slots.size and slots.max() >= Cn2:
            raise CapacityError(
                f"cannot shrink Cn {g.Cn} -> {Cn2}: a real node occupies "
                f"slot {int(slots.max())}")
    if Cd2 < g.Cd and deg.size and deg.max() > Cd2:
        raise CapacityError(
            f"cannot shrink Cd {g.Cd} -> {Cd2}: max degree is "
            f"{int(deg.max())}")
    N2 = g.P * Cn2
    old_r = np.arange(g.N) % g.Cn
    rekey = np.where(old_r < Cn2,
                     (np.arange(g.N) // g.Cn) * Cn2 + old_r, -1)
    r2 = np.arange(N2) % Cn2
    src = np.where(r2 < g.Cn, (np.arange(N2) // Cn2) * g.Cn + r2, -1)
    have = src >= 0
    srcc = np.maximum(src, 0)
    Cmin = min(g.Cd, Cd2)
    nbr = np.asarray(g.nbr)
    vals = nbr[srcc, :Cmin]
    vals = np.where(vals >= 0, rekey[np.maximum(vals, 0)], PAD)
    nbr2 = np.full((N2, Cd2), PAD, nbr.dtype)
    nbr2[:, :Cmin] = np.where(have[:, None], vals, PAD)
    g2 = GraphBlocks(
        nbr=jnp.asarray(nbr2, jnp.int32),
        deg=jnp.asarray(np.where(have, deg[srcc], 0), jnp.int32),
        node_mask=jnp.asarray(np.where(have, mask[srcc], False)),
        orig_id=jnp.asarray(
            np.where(have, np.asarray(g.orig_id)[srcc], PAD), jnp.int32),
        P=g.P, Cn=Cn2, Cd=Cd2,
    )
    return g2, rekey


def relocate_rows(arr, rekey: np.ndarray, N2: int, fill=0) -> np.ndarray:
    """Scatter an (N_old, ...) per-node array onto the post-`grow_blocks`
    node axis: row ``u`` lands at ``rekey[u]``; unsourced rows get `fill`.
    Host-side (numpy in, numpy out)."""
    arr = np.asarray(arr)
    out = np.full((N2,) + arr.shape[1:], fill, arr.dtype)
    ok = rekey >= 0
    out[rekey[ok]] = arr[ok]
    return out


def add_vertices_host(g: GraphBlocks, block: int, count: int = 1,
                      orig_ids=None):
    """Vertex arrival: activate `count` padding rows of `block` as fresh
    real (degree-0) nodes.

    Rows are taken lowest-index-first (deterministic, so a replayed log
    reproduces the same ids).  New nodes get original ids `orig_ids`, or
    consecutive ids after the current max when omitted.  Raises
    `CapacityError` when the block lacks free rows — the caller's cue to
    `grow_blocks` and retry.  Returns ``(g2, new_ids)`` with `new_ids`
    the (count,) padded ids of the new vertices.  Host-side.
    """
    if isinstance(g.nbr, jax.core.Tracer):
        raise TypeError(
            "add_vertices_host is host-side preprocessing; it cannot "
            "run under jit/vmap tracing."
        )
    b, count = int(block), int(count)
    if not 0 <= b < g.P:
        raise ValueError(f"block {b} outside [0, {g.P})")
    if count < 1:
        raise ValueError(f"count must be >= 1, got {count}")
    mask = np.asarray(g.node_mask).copy()
    free = np.flatnonzero(~mask[b * g.Cn:(b + 1) * g.Cn]) + b * g.Cn
    if len(free) < count:
        raise CapacityError(
            f"block {b} has {len(free)} free node rows, needs {count} "
            f"(Cn={g.Cn})")
    rows = free[:count]
    orig = np.asarray(g.orig_id).copy()
    if orig_ids is None:
        base = int(orig.max(initial=-1)) + 1
        orig_ids = np.arange(base, base + count)
    orig_ids = np.asarray(orig_ids, np.int64)
    if orig_ids.shape != (count,):
        raise ValueError(f"need {count} orig_ids, got {orig_ids.shape}")
    mask[rows] = True
    orig[rows] = orig_ids
    g2 = dataclasses.replace(
        g, node_mask=jnp.asarray(mask), orig_id=jnp.asarray(orig, jnp.int32))
    return g2, rows


def to_networkx_edges(g: GraphBlocks) -> np.ndarray:
    """Extract the (m, 2) edge list in *original* ids (test oracle helper)."""
    nbr = np.asarray(g.nbr)
    orig = np.asarray(g.orig_id)
    src = np.repeat(np.arange(g.N), g.Cd)
    dst = nbr.reshape(-1)
    ok = dst >= 0
    src, dst = src[ok], dst[ok]
    e = np.stack([orig[src], orig[dst]], 1)
    e = e[e[:, 0] < e[:, 1]]
    return np.unique(e, axis=0)


# ---------------------------------------------------------------------------
# Single-edge jitted updates (the maintenance hot path: paper measures
# per-edge insertion/deletion latency).  Both preserve the sorted-ELL
# invariant: insertion shifts the row right at the sorted position,
# deletion shifts it left over the hole.  O(Cd) vectorized per row — the
# static row shape means the shift compiles to a single select, no
# data-dependent control flow.
# ---------------------------------------------------------------------------


def _sorted_insert_row(row: jax.Array, val: jax.Array) -> jax.Array:
    """Insert `val` into a sorted ELL row, keeping valid slots ascending."""
    key = jnp.where(row >= 0, row, _PAD_KEY)
    pos = jnp.sum(key < val)  # insertion point among the valid prefix
    idx = jnp.arange(row.shape[0])
    shifted = row[jnp.maximum(idx - 1, 0)]  # row shifted right by one
    return jnp.where(idx < pos, row, jnp.where(idx == pos, val, shifted))


def _sorted_delete_row(row: jax.Array, val: jax.Array, deg: jax.Array):
    """Remove `val` from a sorted ELL row, shifting left over the hole."""
    C = row.shape[0]
    pos = jnp.argmax(row == val)
    idx = jnp.arange(C)
    shifted = row[jnp.minimum(idx + 1, C - 1)]  # row shifted left by one
    out = jnp.where(idx >= pos, shifted, row)
    return out.at[deg - 1].set(PAD)  # deg is the pre-delete degree


@jax.jit
def insert_edge(g: GraphBlocks, u: jax.Array, v: jax.Array) -> GraphBlocks:
    """Insert undirected edge (u, v); ids are global padded ids.

    Assumes u != v, capacity available, and the edge absent — all validated
    at the host boundary (`updates.apply_updates_host`, which rejects
    self-loops per the module invariant; duplicates would corrupt degree
    counts).  The TPU path itself never branches on those conditions.
    """
    vd = v.astype(g.nbr.dtype)
    ud = u.astype(g.nbr.dtype)
    nbr = g.nbr.at[u].set(_sorted_insert_row(g.nbr[u], vd))
    nbr = nbr.at[v].set(_sorted_insert_row(nbr[v], ud))
    deg = g.deg.at[u].add(1).at[v].add(1)
    return dataclasses.replace(g, nbr=nbr, deg=deg)


@jax.jit
def delete_edge(g: GraphBlocks, u: jax.Array, v: jax.Array) -> GraphBlocks:
    """Delete undirected edge (u, v) — shift-left in both sorted rows."""
    nbr = g.nbr.at[u].set(_sorted_delete_row(g.nbr[u], v, g.deg[u]))
    nbr = nbr.at[v].set(_sorted_delete_row(nbr[v], u, g.deg[v]))
    deg = g.deg.at[u].add(-1).at[v].add(-1)
    return dataclasses.replace(g, nbr=nbr, deg=deg)


def has_edge(g: GraphBlocks, u, v) -> jax.Array:
    return jnp.any(g.nbr[u] == v)
