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
    group (min/sum exactly associative; hindex via count-histogram
    partials, the threshold-count formulation) and the merged value
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
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import (PAD, CapacityError, GraphBlocks, _occurrence_ranks,
                    halo_slot_counts, relocate_rows, sort_nbr_rows)

#: monotonic MirrorPlan identity counter — the SPMD fused loop closes over
#: the plan arrays (they are compile-time constants of the shard_map'd
#: step), so every plan with distinct array *content* must carry a distinct
#: `uid` for the compiled-step caches to key on (see CACHE_SCHEMAS).
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
                  hub degree (the hindex histogram width; exact because
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
    column truncation.  Raises when a block runs out of padding rows
    (build with `build_blocks(node_slack=...)` headroom).

    Both endpoint sides of an edge re-point at the serving row of the
    other side, so ``g2`` is a consistent undirected ELL graph and every
    row obeys the sorted-ELL invariant (established by `sort_nbr_rows`).
    Host-side preprocessing; raises under a trace.
    """
    if isinstance(g.nbr, jax.core.Tracer):
        raise TypeError("split_hubs is host-side preprocessing; it cannot "
                        "run under jit/vmap tracing.")
    t = int(threshold)
    if t < 1:
        raise ValueError(f"threshold must be >= 1, got {t}")
    nbr = np.asarray(g.nbr, np.int64)
    deg = np.asarray(g.deg, np.int64)
    mask = np.asarray(g.node_mask).copy()
    orig = np.asarray(g.orig_id, np.int64).copy()
    N, Cn, Cd = g.N, g.Cn, g.Cd

    hubs = np.flatnonzero(mask & (deg > t))
    free = _free_rows(mask, Cn, g.P)

    # serving-row maps, per directed slot of the ORIGINAL graph:
    #   rew[u, j]  — the row that holds u's slot j after the split
    #   rew2[u, j] — the row the slot's content re-points to (the partner
    #                endpoint's serving row for this edge)
    rew = np.repeat(np.arange(N, dtype=np.int64), Cd).reshape(N, Cd)
    rew2 = nbr.copy()
    groups: List[Tuple[int, List[int]]] = []
    for h in hubs:
        d = int(deg[h])
        nb = nbr[h, :d]  # sorted (ELL invariant)
        own = h // Cn
        blk = nb // Cn
        # own-block members first, then grouped by reader block: consecutive
        # chunks of <= t then cut along block boundaries where possible
        order = np.lexsort((nb, np.where(blk == own, -1, blk)))
        nb_o = nb[order]
        n_chunks = -(-d // t)
        rows_h = [int(h)]
        for ci in range(1, n_chunks):
            chunk = nb_o[ci * t:(ci + 1) * t]
            r = _alloc_replica(free, int(chunk[0] // Cn), int(own))
            rows_h.append(r)
            mask[r] = True
            orig[r] = orig[h]
        groups.append((int(h), rows_h))
        for ci, r in enumerate(rows_h):
            chunk = nb_o[ci * t:(ci + 1) * t]
            # u-side: these slots are served by row r
            rew[h, np.searchsorted(nb, chunk)] = r
            # partner side: w's slot pointing at h re-points to r
            for w in chunk:
                pos = np.searchsorted(nbr[w, :deg[w]], h)
                rew2[w, pos] = r

    valid = nbr >= 0
    src = rew[valid]
    dst = rew2[valid]
    nbr2 = np.full((N, t), PAD, np.int64)
    ranks = _occurrence_ranks(src)
    if ranks.size and ranks.max() >= t:
        raise AssertionError("slice overflow — split_hubs chunking bug")
    nbr2[src, ranks] = dst
    deg2 = np.bincount(src, minlength=N)
    nbr2 = sort_nbr_rows(nbr2)  # establish the sorted-ELL invariant

    g2 = GraphBlocks(
        nbr=jnp.asarray(nbr2, jnp.int32),
        deg=jnp.asarray(deg2, jnp.int32),
        node_mask=jnp.asarray(mask),
        orig_id=jnp.asarray(orig, jnp.int32),
        P=g.P, Cn=Cn, Cd=t,
    )
    plan = _plan_from_groups(
        N=N, deg_logical_of_row=deg, mask=mask,
        groups={h: rs for h, rs in groups}, threshold=t,
        n_logical=int(np.asarray(g.node_mask).sum()))
    return g2, plan


def _plan_from_groups(N: int, deg_logical_of_row: np.ndarray,
                      mask: np.ndarray, groups: Dict[int, List[int]],
                      threshold: int, n_logical: int) -> MirrorPlan:
    """Assemble a MirrorPlan from {primary: [rows]} (host bookkeeping)."""
    prow = np.arange(N, dtype=np.int64)
    for h, rows_h in groups.items():
        prow[rows_h] = h
    ldeg = np.where(mask, deg_logical_of_row[prow], 0)
    primary_mask = mask & (prow == np.arange(N))

    n_rows = sum(len(rs) for rs in groups.values())
    Gmax = _pow2(max(1, len(groups)))
    Rp = _pow2(max(1, n_rows))
    grp_rows = np.zeros(Rp, np.int64)
    grp_gid = np.full(Rp, Gmax, np.int64)
    row_gid = np.full(N, Gmax, np.int64)
    i = 0
    for gx, (h, rows_h) in enumerate(sorted(groups.items())):
        for r in rows_h:
            grp_rows[i] = r
            grp_gid[i] = gx
            row_gid[r] = gx
            i += 1
    Km = _pow2(int(ldeg[list(groups)].max()) if groups else 1)
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
    relocated original with a fresh `uid` (the mirrored compiled step
    re-keys exactly once per grow).  Host-side.
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
    (array content changed), so mirrored SPMD runs recompile per edit
    batch — batch edits per window, like the stream does.  Empty
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
