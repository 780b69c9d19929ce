"""Skew-aware hub mirroring: vertex-cut replicas inside the block runtime.

Power-law graphs break the ELL layout's economics: ONE celebrity vertex
sets ``Cd`` for every row of ``GraphBlocks.nbr``, inflating memory,
gather work, and W2W halo payload for the whole mesh.  This module adds
the vertex-cut answer (PowerGraph-style, per the distributed-graph
analysis in PAPERS.md) *without* changing the block-centric runtime:

  * `split_hubs(g, threshold)` rewrites the graph so every vertex with
    ``deg > threshold`` becomes a **primary** row (its original row id)
    plus **mirror replica** rows, each holding one slice of at most
    ``threshold`` neighbors — so the split graph's ``Cd`` is the
    threshold, not the max degree.  Replicas occupy *existing padding
    rows*, preferentially in the block of the slice's readers (that
    locality is the halo-payload win), so every real row keeps its
    original index: CC label space, `orig_id` semantics, and the
    `to_networkx_edges` oracle are untouched.
  * The split graph is a **plain valid GraphBlocks** — sorted-ELL rows,
    exact degrees, nothing above `GraphBlocks` needs to know.  All
    kernels, `HaloPlan` tables, and the SPMD executor run it unchanged.
  * The `MirrorPlan` carries the replica bookkeeping the runner needs:
    which rows form a group, each row's primary, and the *logical*
    degree.  `kernels.ops.run_block_program(..., mirror=plan)` inserts a
    **combine-then-broadcast merge** between the neighbor combine and
    `BlockProgram.update`: per-slice partial aggregates are merged per
    group (min/sum exactly associative; hindex by bisection on the
    threshold, whose counts add exactly across slices) and the merged value
    is written back to every group row.  Because program state is
    replicated onto mirror rows (`BlockProgram.mirror_state`), replicas
    advance in lockstep with their primary and every *reader* of a
    replica row sees the primary's value — results are exact vs the
    unsplit graph on all backends (bit-exact for the integer combines,
    float-reassociation-tolerant for "sum").
  * "count_common" (triangles) exchanges whole neighbor rows, which a
    slice cannot serve locally; `run_common_mirror` runs it exactly via
    a canonicalized-row kernel pass plus per-slice pairwise corrections
    (see the function docstring).
  * `apply_mirrored_edits` is the host mutation path: capacity-routed
    inserts, ON-LINE splits when an insert would push a vertex over the
    threshold (the new edge lands in the freshly-allocated replica, so
    no existing row is rewired), and mirrored deletes that locate and
    splice the one (row_u, row_v) pair holding the edge.

Host-boundary module: construction, mutation, and the triangle
corrections are numpy preprocessing, same contract as `build_blocks` /
`migrate_vertices`.  The merge stage itself is pure device code in
`kernels.ops._mirror_merge` / `runtime.spmd.SpmdBlockProgram`.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from .graph import (PAD, CapacityError, GraphBlocks, _occurrence_ranks,
                    _relabel, halo_slot_counts, relocate_rows,
                    sort_nbr_rows)

#: monotonic MirrorPlan identity counter: every plan with distinct array
#: *content* carries a distinct `uid`, which keys the per-executor mesh
#: layout of its tables (`runtime.spmd.SpmdExecutor.mirror_tables`).
_UID_COUNTER = [0]


def _next_uid() -> int:
    _UID_COUNTER[0] += 1
    return _UID_COUNTER[0]


def _pow2(x: int, floor: int = 8) -> int:
    """Smallest power of two >= x, floored (compile-cache-stable sizing)."""
    k = floor
    while k < x:
        k *= 2
    return k


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MirrorPlan:
    """Replica bookkeeping for a hub-split graph (see module docstring).

    Attributes
    ----------
    primary_row:  (N,) int32 — primary row of each row's logical vertex
                  (self for non-replica rows, including padding).
    ldeg:         (N,) int32 — *logical* degree of the row's vertex (the
                  unsplit degree; 0 on padding rows).  This is what
                  `BlockCtx.deg` must carry under a mirrored run.
    primary_mask: (N,) bool — True for real non-replica rows; one True
                  per logical vertex (the frame init/queries reason in).
    grp_rows:     (Rp,) int32 — rows belonging to split groups, padded
                  with 0 (pad entries carry gid == Gmax and are inert).
    grp_gid:      (Rp,) int32 — group id per entry; Gmax on padding.
    row_gid:      (N,) int32 — group id of each row; Gmax off-group.
    Gmax, Km:     static ints — pow2-bucketed group count / max logical
                  hub degree (the bound the hindex merge bisects below;
                  a merged h-index never exceeds the logical degree).
    threshold:    static int — the split threshold == per-slice capacity.
    n_logical:    static int — real *logical* vertex count (what
                  `BlockCtx.n_real` must carry under a mirrored run).
    uid:          static int — plan identity token (see `_UID_COUNTER`).
    """

    primary_row: jax.Array
    ldeg: jax.Array
    primary_mask: jax.Array
    grp_rows: jax.Array
    grp_gid: jax.Array
    row_gid: jax.Array
    Gmax: int = dataclasses.field(metadata=dict(static=True))
    Km: int = dataclasses.field(metadata=dict(static=True))
    threshold: int = dataclasses.field(metadata=dict(static=True))
    n_logical: int = dataclasses.field(metadata=dict(static=True))
    uid: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_groups(self) -> int:
        gid = np.asarray(self.grp_gid)
        return len(np.unique(gid[gid < self.Gmax]))


def groups_of(plan: MirrorPlan) -> Dict[int, List[int]]:
    """Host view of the split groups: {primary row: [rows, primary first]}."""
    rows = np.asarray(plan.grp_rows)
    gid = np.asarray(plan.grp_gid)
    prow = np.asarray(plan.primary_row)
    out: Dict[int, List[int]] = {}
    for r, gx in zip(rows, gid):
        if gx >= plan.Gmax:
            continue
        out.setdefault(int(prow[r]), []).append(int(r))
    # primary first, replicas in allocation order (ascending is canonical)
    return {h: sorted(rs, key=lambda r: (r != h, r)) for h, rs in out.items()}


def _free_rows(mask: np.ndarray, Cn: int, P: int) -> Dict[int, List[int]]:
    """Free (padding) rows per block, ascending — replica allocation pool."""
    return {
        b: list(np.flatnonzero(~mask[b * Cn:(b + 1) * Cn]) + b * Cn)
        for b in range(P)
    }


def _alloc_replica(free: Dict[int, List[int]], pref: int, own: int) -> int:
    """Pop a free row: reader's block first, then the hub's, then any."""
    for b in (pref, own):
        if free.get(b):
            return free[b].pop(0)
    for b in sorted(free):
        if free[b]:
            return free[b].pop(0)
    raise CapacityError(
        "no free padding rows left for hub mirror replicas; rebuild the "
        "graph with node capacity headroom (build_blocks(node_slack=...)) "
        "or grow Cn (graph.grow_blocks / MirrorStream auto_grow)")


# ---------------------------------------------------------------------------
# Sorted-slice splice helpers (host-side numpy): the slice analogues of
# graph._sorted_insert_row/_sorted_delete_row.  Registered with tracelint's
# sorted-ELL rule — every mirror-path nbr write routes through these or
# through sort_nbr_rows.
# ---------------------------------------------------------------------------


def _sorted_slice_insert(row: np.ndarray, fill: int, val: int) -> None:
    """Insert `val` into a sorted ELL row slice in place (fill = old count).

    Shifts the tail right by one; caller guarantees fill < len(row) and
    `val` absent.  Keeps valid slots ascending with pads on the right.
    """
    pos = int(np.searchsorted(row[:fill], val))
    row[pos + 1:fill + 1] = row[pos:fill]
    row[pos] = val


def _sorted_slice_delete(row: np.ndarray, fill: int, val: int) -> None:
    """Remove `val` from a sorted ELL row slice in place (fill = old count).

    Shifts the tail left over the hole and re-pads the vacated slot.
    """
    pos = int(np.searchsorted(row[:fill], val))
    row[pos:fill - 1] = row[pos + 1:fill]
    row[fill - 1] = PAD


# ---------------------------------------------------------------------------
# Construction
# ---------------------------------------------------------------------------


def split_hubs(g: GraphBlocks, threshold: int) -> Tuple[GraphBlocks,
                                                        MirrorPlan]:
    """Split every vertex with deg > threshold into primary + mirror rows.

    Returns ``(g2, plan)`` where ``g2`` is a plain valid GraphBlocks with
    ``Cd == threshold`` and the same (P, Cn): hubs keep their original
    row as the primary (holding the first slice) and each further slice
    of at most `threshold` neighbors lands in an existing padding row —
    preferentially in the block its slice members live in, so slice
    reads stay block-local.  Non-hub rows are byte-identical up to the
    column truncation.  Raises when the padding rows run out (build with
    `build_blocks(node_slack=...)` headroom, or load the split graph
    straight from its edges with `build_split_blocks`, which sizes the
    padding itself).  The split rule is `_split_rule`'s, shared with
    `build_split_blocks`.

    Both endpoint sides of an edge re-point at the serving row of the
    other side, so ``g2`` is a consistent undirected ELL graph and every
    row obeys the sorted-ELL invariant.  Host-side preprocessing; raises
    under a trace.
    """
    if isinstance(g.nbr, jax.core.Tracer):
        raise TypeError("split_hubs is host-side preprocessing; it cannot "
                        "run under jit/vmap tracing.")
    t = _check_threshold(threshold)
    nbr = np.asarray(g.nbr, np.int64)
    deg = np.asarray(g.deg, np.int64)
    mask = np.asarray(g.node_mask)
    rows, cols = np.nonzero(nbr >= 0)
    vals = nbr[rows, cols]
    up = rows < vals  # each undirected edge once, as (lo, hi)
    free = np.flatnonzero(~mask)
    split = _split_rule(rows[up], vals[up], np.where(mask, deg, 0), t, g.Cn,
                        g.P, free)
    return _assemble(split, mask, np.asarray(g.orig_id, np.int64), deg,
                     g.P, g.Cn, t, n_logical=int(mask.sum()))


def _check_threshold(threshold: int) -> int:
    t = int(threshold)
    if t < 1:
        raise ValueError(f"threshold must be >= 1, got {t}")
    return t


class _Split(NamedTuple):
    """What `_split_rule` decides, in padded row ids."""

    slots: np.ndarray     # (2m,) serving row * N + partner's serving row
    replicas: np.ndarray  # (R,) replica rows, in request order
    req_hub: np.ndarray   # (R,) the hub each replica serves
    req_chunk: np.ndarray  # (R,) its slice number (1, 2, ...)
    hubs: np.ndarray      # (H,) hub rows, ascending
    N: int


def _hub_chunks(lo, hi, deg, t: int, Cn: int):
    """Each hub's slots in slice order, cut into slices of ``t``.

    A hub's neighbors are ordered own-block first, then by reader block,
    then by id, and consecutive runs of ``t`` form its slices.  Returns
    (directed slot index of each hub slot in that order, its slice
    number, and per replica request — slice 1, 2, ... of each hub in
    ascending hub order — its hub and its preferred block, the block of
    the slice's first member)."""
    src = np.concatenate([lo, hi])
    hs = np.flatnonzero(deg[src] > t)
    s, d = src[hs], np.concatenate([hi, lo])[hs]
    blk = d // Cn
    near = np.where(blk == s // Cn, 0, blk + 1)
    span = np.int64(max(int(d.max()) + 1, 1) if d.size else 1)
    order = np.argsort((s * (blk.max(initial=0) + 2) + near) * span + d)
    hs, s, d = hs[order], s[order], d[order]
    del order, blk, near
    chunk = _group_ranks(s) // t
    head = np.flatnonzero(np.r_[False, chunk[1:] != chunk[:-1]]
                          & (chunk > 0))
    return hs, chunk, s[head], chunk[head], d[head] // Cn


def _group_ranks(keys: np.ndarray) -> np.ndarray:
    """Position of each entry within its run of equal sorted ``keys``."""
    if not keys.size:
        return np.zeros(0, np.int64)
    first = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    return np.arange(len(keys)) - np.repeat(
        first, np.diff(np.r_[first, len(keys)]))


def _allocate(req_hub, req_pref, Cn: int, P: int,
              free: np.ndarray) -> np.ndarray:
    """A padding row for each replica request, in request order: the
    lowest free row of the preferred block, else of the hub's own block,
    else of the lowest block with one (`_alloc_replica`).  When every
    block can serve its preferred requests, that sequential rule is a
    rank within the block, so only a block that runs dry makes it walk
    the requests one by one."""
    free = np.asarray(free, np.int64)
    start = np.searchsorted(free, np.arange(P + 1, dtype=np.int64) * Cn)
    want = np.bincount(req_pref, minlength=P)
    if (want <= np.diff(start)).all():
        return free[start[req_pref] + _occurrence_ranks(req_pref)]
    pools = {b: list(free[start[b]:start[b + 1]]) for b in range(P)}
    return np.array([_alloc_replica(pools, int(p), int(h) // Cn)
                     for h, p in zip(req_hub, req_pref)], np.int64)


def _split_rule(lo, hi, deg, t: int, Cn: int, P: int, free,
                sized=None) -> _Split:
    """The one hub-split rule, on an edge list in padded row ids.

    ``lo``/``hi`` hold each undirected edge once; ``deg`` is each row's
    degree, 0 on padding; ``free`` the ascending padding rows replicas
    may take.  ``sized(req_pref)``, when given, is called with each
    request's preferred block before any row is allocated and returns
    ``(Cn, remap, free)`` for a node axis laid out anew (the loader
    sizes its padding from the requests); ``remap`` carries row ids
    into it and must keep their order.
    """
    hs, chunk, req_hub, req_chunk, req_pref = _hub_chunks(lo, hi, deg, t, Cn)
    hubs = np.flatnonzero(deg > t)
    N = P * Cn
    if sized is not None:
        Cn, remap, free = sized(req_pref)
        lo, hi, hubs, req_hub = (remap(x) for x in (lo, hi, hubs, req_hub))
        N = P * Cn
    replicas = _allocate(req_hub, req_pref, Cn, P, free)
    del req_pref
    m = len(lo)
    serve = np.concatenate([lo, hi])
    hub_of = serve[hs]
    more = chunk > 0
    first_req = np.searchsorted(req_hub, hub_of[more])
    hub_of[more] = replicas[first_req + chunk[more] - 1]
    serve[hs] = hub_of
    del hub_of, hs, chunk, first_req, more
    # the slot (u -> v) sits in u's serving row and points at v's serving
    # row for this edge, which is the serving row of the slot (v -> u)
    slots = serve * np.int64(N) + np.concatenate([serve[m:], serve[:m]])
    del serve
    slots.sort()
    return _Split(slots, replicas, req_hub, req_chunk, hubs, N)


def _assemble(split: _Split, mask, orig, deg_logical, P: int, Cn: int,
              t: int, n_logical: int) -> Tuple[GraphBlocks, MirrorPlan]:
    """The split graph and its plan from `_split_rule`'s decisions.

    ``mask``, ``orig`` and ``deg_logical`` are per row of the node axis
    the split was allocated in, before the replicas (the unsplit degree
    of each row's vertex)."""
    N = split.N
    src, dst = np.divmod(split.slots, np.int64(N))
    rank = _group_ranks(src)
    if rank.size and rank.max() >= t:
        raise AssertionError("slice overflow — hub split chunking bug")
    nbr2 = np.full((N, t), PAD, np.int32)
    nbr2[src, rank] = dst  # slots arrive sorted: rows ascend, pads right
    deg2 = np.bincount(src, minlength=N)
    del src, dst, rank
    mask = np.asarray(mask).copy()
    orig = np.asarray(orig, np.int64).copy()
    mask[split.replicas] = True
    orig[split.replicas] = orig[split.req_hub]
    g2 = GraphBlocks(
        nbr=jnp.asarray(nbr2), deg=jnp.asarray(deg2, jnp.int32),
        node_mask=jnp.asarray(mask), orig_id=jnp.asarray(orig, jnp.int32),
        P=P, Cn=Cn, Cd=t)
    # groups: each hub's primary row first, then its replicas by slice
    prim = np.concatenate([split.hubs, split.req_hub])
    rows = np.concatenate([split.hubs, split.replicas])
    order = np.lexsort((np.concatenate([np.zeros(len(split.hubs), np.int64),
                                        split.req_chunk]), prim))
    plan = _plan_arrays(N, deg_logical, mask, prim[order], rows[order],
                        threshold=t, n_logical=n_logical)
    return g2, plan


def build_split_blocks(edges: np.ndarray, n: int, assign: np.ndarray,
                       P: int, threshold: int
                       ) -> Tuple[GraphBlocks, MirrorPlan]:
    """Load a hub-split graph straight from its edge list.

    The same graph and plan as ``split_hubs(build_blocks(edges, n,
    assign, P, Cn=Cn), threshold)``, by the same split rule, without the
    unsplit graph's (N, max degree) array: host memory stays O(|E|) and
    no step loops over hubs in Python.  ``Cn`` is the least multiple of
    8 that holds every block's nodes and the replicas that prefer it.
    Nodes take block ``b``'s rows ``b*Cn + r`` in increasing original
    id, as `build_blocks` lays them out; each block's replicas take the
    rows after its nodes.  Host-side preprocessing.
    """
    with tracing.span("graph.split_load"):
        t = _check_threshold(threshold)
        assign = np.asarray(assign, np.int64)
        assert assign.shape == (n,), (assign.shape, n)
        e = np.asarray(edges, np.int64).reshape(-1, 2)
        lo, hi = np.minimum(e[:, 0], e[:, 1]), np.maximum(e[:, 0], e[:, 1])
        keep = lo != hi
        key = np.unique(lo[keep] * np.int64(max(n, 1)) + hi[keep])
        del e, lo, hi, keep
        lo0, hi0 = np.divmod(key, np.int64(max(n, 1)))
        del key
        deg0 = np.bincount(lo0, minlength=n) + np.bincount(hi0, minlength=n)
        pop = np.bincount(assign, minlength=P)
        C0 = max(1, int(pop.max()))
        new0, _ = _relabel(n, assign, P, C0)
        deg = np.zeros(P * C0, np.int64)
        deg[new0] = deg0

        def sized(req_pref):
            need = pop + np.bincount(req_pref, minlength=P)
            C = int(-(-max(1, int(need.max())) // 8) * 8)
            free = (np.arange(P)[:, None] * C
                    + np.arange(C)[None, :])[np.arange(C)[None, :]
                                             >= pop[:, None]]
            return C, lambda x: (x // C0) * C + x % C0, free

        split = _split_rule(new0[lo0], new0[hi0], deg, t, C0, P,
                            free=None, sized=sized)
        del lo0, hi0
        C = split.N // P
        new, old_of_new = _relabel(n, assign, P, C)
        deg_rows = np.zeros(split.N, np.int64)
        deg_rows[new] = deg0
        return _assemble(split, old_of_new >= 0, old_of_new, deg_rows, P, C,
                         t, n_logical=n)


def _plan_from_groups(N: int, deg_logical_of_row: np.ndarray,
                      mask: np.ndarray, groups: Dict[int, List[int]],
                      threshold: int, n_logical: int) -> MirrorPlan:
    """Assemble a MirrorPlan from {primary: [rows]} (host bookkeeping)."""
    items = sorted(groups.items())
    prim = np.array([h for h, rs in items for _ in rs], np.int64)
    rows = np.array([r for _, rs in items for r in rs], np.int64)
    return _plan_arrays(N, deg_logical_of_row, mask, prim, rows,
                        threshold, n_logical)


def _plan_arrays(N: int, deg_logical_of_row: np.ndarray, mask: np.ndarray,
                 prim: np.ndarray, rows: np.ndarray, threshold: int,
                 n_logical: int) -> MirrorPlan:
    """Assemble a MirrorPlan from the group rows ``rows`` and the primary
    ``prim`` of each, grouped by ascending primary, each group's rows in
    their given order (primary first)."""
    prow = np.arange(N, dtype=np.int64)
    prow[rows] = prim
    ldeg = np.where(mask, np.asarray(deg_logical_of_row)[prow], 0)
    primary_mask = mask & (prow == np.arange(N))
    new_group = np.r_[True, prim[1:] != prim[:-1]] if prim.size else \
        np.zeros(0, bool)
    gid = np.cumsum(new_group) - 1
    Gmax = _pow2(max(1, int(new_group.sum())))
    Rp = _pow2(max(1, len(rows)))
    grp_rows = np.zeros(Rp, np.int64)
    grp_gid = np.full(Rp, Gmax, np.int64)
    row_gid = np.full(N, Gmax, np.int64)
    grp_rows[:len(rows)] = rows
    grp_gid[:len(rows)] = gid
    row_gid[rows] = gid
    Km = _pow2(int(ldeg[prim].max()) if prim.size else 1)
    return MirrorPlan(
        primary_row=jnp.asarray(prow, jnp.int32),
        ldeg=jnp.asarray(ldeg, jnp.int32),
        primary_mask=jnp.asarray(primary_mask),
        grp_rows=jnp.asarray(grp_rows, jnp.int32),
        grp_gid=jnp.asarray(grp_gid, jnp.int32),
        row_gid=jnp.asarray(row_gid, jnp.int32),
        Gmax=Gmax, Km=Km, threshold=int(threshold),
        n_logical=int(n_logical), uid=_next_uid(),
    )


def grow_plan(plan: MirrorPlan, rekey: np.ndarray, g2: GraphBlocks
              ) -> MirrorPlan:
    """Relocate a MirrorPlan onto the post-`graph.grow_blocks` node axis.

    `rekey` is the (N_old,) old-id -> new-id map grow_blocks returned and
    `g2` the grown graph.  The rekey is monotone, so group ordering and
    the canonical within-group row order survive; the rebuilt plan is the
    relocated original with a fresh `uid`.  Host-side.
    """
    groups = {int(rekey[h]): [int(rekey[r]) for r in rs]
              for h, rs in groups_of(plan).items()}
    ldeg = relocate_rows(np.asarray(plan.ldeg), rekey, g2.N, 0)
    return _plan_from_groups(
        N=g2.N, deg_logical_of_row=ldeg, mask=np.asarray(g2.node_mask),
        groups=groups, threshold=plan.threshold,
        n_logical=plan.n_logical)


# ---------------------------------------------------------------------------
# On-line mutation: capacity-routed inserts, threshold-triggered splits,
# mirrored deletes.
# ---------------------------------------------------------------------------


def apply_mirrored_edits(
    g2: GraphBlocks, plan: MirrorPlan,
    edits: Iterable[Tuple[int, int, int]],
) -> Tuple[GraphBlocks, MirrorPlan]:
    """Apply (u, v, op) edits to a split graph; ids are PRIMARY row ids.

    op = +1 insert / -1 delete, sequential in order, exact:

      * an insert routes each endpoint to its first row with slice
        capacity left; a vertex whose every row is full gets a fresh
        replica (an **on-line split** when it was single-row: crossing
        the threshold is what filled it) — the new edge lands in the new
        replica, so no existing row is rewired;
      * a delete locates the ONE (row_u, row_v) pair holding the edge
        (slices partition the neighborhood) and splices both sides.

    Returns ``(g2', plan')``; the plan always carries a fresh `uid`
    (array content changed); a mirrored SPMD run recompiles only when
    the plan's pow2 sizes (Gmax, Km) or the halo capacities move.  Empty
    replicas left behind by deletes are retained: they are inert under
    every merge.  Host-side preprocessing; raises under a trace.
    """
    if isinstance(g2.nbr, jax.core.Tracer):
        raise TypeError("apply_mirrored_edits is host-side preprocessing; "
                        "it cannot run under jit/vmap tracing.")
    nbr = np.asarray(g2.nbr, np.int64).copy()
    deg = np.asarray(g2.deg, np.int64).copy()
    mask = np.asarray(g2.node_mask).copy()
    orig = np.asarray(g2.orig_id, np.int64).copy()
    prow = np.asarray(plan.primary_row, np.int64).copy()
    ldeg = np.asarray(plan.ldeg, np.int64).copy()
    N, Cn, Cd2 = g2.N, g2.Cn, g2.Cd
    t = plan.threshold
    groups = groups_of(plan)
    free = _free_rows(mask, Cn, g2.P)

    def rows_of(u: int) -> List[int]:
        return groups.get(u, [u])

    def edge_pair(u: int, v: int) -> Optional[Tuple[int, int]]:
        """The (row_u, row_v) holding edge (u, v), or None if absent."""
        rv_set = set(rows_of(v))
        for ru in rows_of(u):
            for x in nbr[ru, :deg[ru]]:
                if int(x) in rv_set:
                    return ru, int(x)
        return None

    def route(u: int, pref_block: int) -> int:
        """Row of u taking one more neighbor; allocates a replica if full."""
        for r in rows_of(u):
            if deg[r] < Cd2:
                return r
        r = _alloc_replica(free, pref_block, u // Cn)
        mask[r] = True
        orig[r] = orig[u]
        prow[r] = u
        groups[u] = rows_of(u) + [r]
        return r

    for u, v, op in edits:
        u, v, op = int(u), int(v), int(op)
        for x in (u, v):
            if not (0 <= x < N) or not mask[x] or prow[x] != x:
                raise ValueError(f"{x} is not a primary row of a real node")
        if u == v:
            raise ValueError(f"self-loop on {u}")
        pair = edge_pair(u, v)
        if op > 0:
            if pair is not None:
                raise ValueError(f"edge ({u}, {v}) already present")
            ru = route(u, v // Cn)
            rv = route(v, ru // Cn)
            _sorted_slice_insert(nbr[ru], int(deg[ru]), rv)
            _sorted_slice_insert(nbr[rv], int(deg[rv]), ru)
            deg[ru] += 1
            deg[rv] += 1
            ldeg[rows_of(u)] += 1
            ldeg[rows_of(v)] += 1
        elif op < 0:
            if pair is None:
                raise ValueError(f"edge ({u}, {v}) not present")
            ru, rv = pair
            _sorted_slice_delete(nbr[ru], int(deg[ru]), rv)
            _sorted_slice_delete(nbr[rv], int(deg[rv]), ru)
            deg[ru] -= 1
            deg[rv] -= 1
            ldeg[rows_of(u)] -= 1
            ldeg[rows_of(v)] -= 1
        else:
            raise ValueError(f"op must be +1/-1, got {op}")

    g3 = dataclasses.replace(
        g2,
        nbr=jnp.asarray(nbr, jnp.int32),
        deg=jnp.asarray(deg, jnp.int32),
        node_mask=jnp.asarray(mask),
        orig_id=jnp.asarray(orig, jnp.int32),
    )
    plan2 = _plan_from_groups(
        N=N, deg_logical_of_row=ldeg, mask=mask,
        groups=groups, threshold=t, n_logical=plan.n_logical)
    return g3, plan2


# ---------------------------------------------------------------------------
# Exact triangle counting on a split graph ("count_common" route).
# ---------------------------------------------------------------------------


class _RawCommonProgram:
    """Internal one-superstep program: raw count_common reduction.

    Mirrors TriangleCountProgram's shape but stores the raw reduction so
    `run_common_mirror` can correct + merge before the real program's
    single `update`.  Duck-types the BlockProgram contract (hashable
    static; `kernels.ops.run_block_program` is the runner).
    """

    combine = "count_common"
    halo_fill = -1
    max_steps = 1
    name = "triangles"

    def __hash__(self):
        return hash(type(self))

    def __eq__(self, other):
        return type(other) is type(self)

    def init(self, g):
        return (jnp.zeros(g.N, jnp.int32), jnp.asarray(g.nbr, jnp.int32))

    def halo_field(self, state):
        return state[1]

    def update(self, ctx, state, red):
        return (red.astype(jnp.int32), state[1])

    def changed(self, old, new):
        return jnp.bool_(True)


def _slice_sets(nbr: np.ndarray, deg: np.ndarray, rows: List[int]):
    """Canonical (primary-id, sorted, unique) member sets of given rows."""
    return [nbr[r, :deg[r]] for r in rows]


def run_common_mirror(g2: GraphBlocks, plan: MirrorPlan, program,
                      backend: str = "jnp",
                      interpret: Optional[bool] = None,
                      with_steps: bool = False,
                      state0=None):
    """Exact "count_common" (triangles) on a split graph, any backend.

    The slice rows make the naive kernel wrong twice over: row contents
    are *serving-row* ids (a hub appears under several ids), and a slot
    (u → v) only intersects u's own slice with ONE slice of v.  The
    exact route:

      1. **canonicalize** — map every stored id to its primary
         (`primary_row[nbr]`) and re-sort; the kernel then counts, per
         directed slot held by row a pointing at logical B,
         ``|C(a) ∩ C(primary_B)|`` where C(x) is row x's canonical
         member set (slices partition neighborhoods, so member sets are
         duplicate-free and the sorted-merge kernels stay exact);
      2. **correct** (host numpy) — each such slot needs the full grid
         ``Σ_{a'∈rows(A), b'∈rows(B)} |C(a') ∩ C(b')|``; the per-slot
         shortfall is credited to the row holding the slot.  Only slots
         with a hub endpoint need corrections, so the work is
         O(Σ_hub deg · slices);
      3. **merge + update** — group-sum the corrected reduction (every
         logical count lands on all of its rows) and run the real
         program's single `update` with the logical ctx.

    Returns like `run_block_program` (state, plus a superstep count of 1
    when `with_steps=True`).  `state0` is accepted for signature parity
    with the runner; count_common programs are single-step, so it only
    seeds non-counter state fields.
    """
    from ..kernels.ops import BlockCtx, run_block_program  # loaded by now

    nbr = np.asarray(g2.nbr, np.int64)
    deg = np.asarray(g2.deg, np.int64)
    prow_np = np.asarray(plan.primary_row, np.int64)
    canon = np.where(nbr >= 0, prow_np[np.maximum(nbr, 0)], PAD)
    canon = sort_nbr_rows(canon)
    gc = dataclasses.replace(g2, nbr=jnp.asarray(canon, jnp.int32))

    # 1. kernel pass on the canonical rows (fresh executor on the spmd
    #    backend: the halo plan must derive from gc's adjacency)
    raw_state = run_block_program(gc, _RawCommonProgram(), backend=backend,
                                  interpret=interpret)
    red = np.asarray(raw_state[0], np.int64)

    # 2. per-slot corrections for hub-incident edges
    groups = groups_of(plan)
    corr = np.zeros(g2.N, np.int64)
    for h, rows_h in groups.items():
        sets_h = _slice_sets(canon, deg, rows_h)
        union_pos = {r: i for i, r in enumerate(rows_h)}
        for r in rows_h:
            for xrow in nbr[r, :deg[r]]:
                xrow = int(xrow)
                W = int(prow_np[xrow])
                cx = canon[xrow, :deg[xrow]]
                inter = [len(np.intersect1d(cx, s, assume_unique=True))
                         for s in sets_h]
                if W in groups:
                    # hub–hub edge: handle only the (xrow -> h) direction
                    # here; the reverse appears when W's group is walked.
                    grid = sum(
                        len(np.intersect1d(
                            canon[y, :deg[y]], s, assume_unique=True))
                        for y in groups[W] for s in sets_h)
                    corr[xrow] += grid - inter[0]
                else:
                    # hub–nonhub edge: both directed slots settled here.
                    corr[xrow] += sum(inter) - inter[0]
                    corr[r] += sum(inter) - inter[union_pos[r]]
    red = red + corr

    # 3. group-sum merge: every row of a group carries the logical count
    for h, rows_h in groups.items():
        red[rows_h] = red[rows_h].sum()

    ctx = BlockCtx(deg=jnp.asarray(plan.ldeg, jnp.int32),
                   node_mask=g2.node_mask, n_real=plan.n_logical)
    if state0 is None:
        state0 = program.init(gc)
    state = program.update(ctx, state0, jnp.asarray(red, jnp.int32))
    return (state, jnp.int32(1)) if with_steps else state


# ---------------------------------------------------------------------------
# Accounting: the allocation + halo-payload story the benchmarks assert.
# ---------------------------------------------------------------------------


def mirror_report(g: GraphBlocks, g2: GraphBlocks,
                  plan: MirrorPlan) -> Dict[str, float]:
    """Allocation + per-superstep W2W payload, unsplit vs split.

    `slots_*` are the N·Cd ELL allocations (the memory the gather kernels
    sweep); `inter_*` the cross-block valid neighbor slots (the W2W halo
    payload of a one-value-per-slot superstep, `halo_slot_counts`);
    `merge_payload` the extra per-superstep elements the mirror merge
    moves (see `runtime.halo.mirror_merge_payload`).
    """
    from ..runtime.halo import mirror_merge_payload  # lazy: no cycle
    intra_u, inter_u = halo_slot_counts(g)
    intra_s, inter_s = halo_slot_counts(g2)
    return dict(
        slots_unsplit=g.N * g.Cd,
        slots_split=g2.N * g2.Cd,
        alloc_ratio=(g.N * g.Cd) / max(1, g2.N * g2.Cd),
        inter_unsplit=inter_u,
        inter_split=inter_s,
        intra_unsplit=intra_u,
        intra_split=intra_s,
        merge_payload=mirror_merge_payload(plan),
        n_groups=len(groups_of(plan)),
    )
