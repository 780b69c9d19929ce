"""Streaming update ingestion: route batches to owner blocks, escalate
cross-block work to the coordinator.

BLADYG's dynamic side is a *stream* of edge updates arriving at the
coordinator.  This module is that ingestion path over the block runtime:

  1. a window of up to R updates is taken off the stream and validated
     at the host boundary (against the *current* graph — streams may be
     generators, so there is no up-front whole-stream pass);
  2. one batched Theorem-1 candidate search (on the frontier kernels' R
     axis, or on the worker mesh under `backend="ell_spmd"`) determines
     each update's candidate set;
  3. updates that are **block-local** — both endpoints in one block and
     the candidate set confined to it — and independent of everything
     earlier in the window are applied together, with ONE joint clamped
     recompute (each update's recompute only moves nodes of its own
     block: the paper's workerCompute-only fast path);
  4. everything else escalates to the coordinator path (exact sequential
     maintenance, original stream order): cross-block endpoints,
     candidate sets that spill over the block boundary, and conflicts
     with earlier in-window updates.

Escalation order is what keeps this exact: an update is only hoisted
into the block-local batch if its candidate set is disjoint from every
*earlier* window column — the same commutation argument as
`core.kcore_dynamic.maintain_batch` — so the final coreness is
bit-identical to processing the stream one update at a time.

The routing verdict itself is computed ON DEVICE (`_route_window`, one
jitted function per window): the candidate-overlap matrix, the spill
test, and the accept/escalate scan all run where the candidate matrix
already lives, and only compact (R,)-masks plus per-block counts cross
to the host — queue management (window slicing, escalation dispatch,
migration bookkeeping) is all that remains host-side.

Two runtime-maintenance loops close over the stream:

  * **Executor reuse** — under `backend="ell_spmd"` ONE `SpmdExecutor`
    threads through the whole stream; every applied edit maintains its
    halo plan incrementally (`SpmdExecutor.apply_updates`, dirty workers
    only).  `StreamStats.plan_updates`/`plan_rebuilds` count the two
    paths: a steady-state stream performs ZERO full plan rebuilds.
  * **Live rebalancing** (`rebalance_threshold`) — after each window the
    §4.2 threshold protocol runs: per-block load summaries
    (workerCompute, `partition_dynamic.block_loads`) and the W2W pair
    matrix (`graph.halo_pair_counts`) reach the coordinator, which —
    when max/mean load exceeds the threshold — picks boundary-vertex
    moves (`partition_dynamic.choose_node_moves`) and executes them with
    `graph.migrate_vertices`: a pure node-axis permutation under fixed
    (P, Cn, Cd), so nothing recompiles and coreness is bit-preserved.
    Later stream updates still name nodes by their *pre-stream* padded
    ids; the router composes the migration permutations and remaps each
    window on ingest.

The loop body lives in `StreamSession` — a resumable stepper (open ->
`apply_window` -> `result`) so other device work can interleave between
windows; `run_stream` wraps it and returns a `StreamResult`, the uniform
(g, core, stats, labels) record (legacy tuple unpacking is shimmed with
a DeprecationWarning).  The query-serving layer (`repro.service`) is the
primary session consumer: it alternates update windows with query
batches on the one long-lived executor.
"""
from __future__ import annotations

import warnings
from functools import partial
from itertools import islice
from typing import (Any, Dict, Iterable, Iterator, List, NamedTuple,
                    Optional, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..core import kcore_dynamic as kd
from ..core import partition_dynamic as pd
from ..core.algorithms import connected_components, merge_labels
from ..core.graph import (CapacityError, add_vertices_host, grow_blocks,
                          halo_pair_counts, migrate_vertices, relocate_rows)
from ..core.kcore_dynamic import SPMD_BACKEND
from .halo import _pow2_ceil


class StreamStats(NamedTuple):
    """Routing + superstep accounting for one `run_stream` pass."""

    updates: int                 # total updates ingested
    batches: int                 # windows taken off the stream
    block_local: int             # applied on the block-local batched path
    escalated_cross_block: int   # endpoints in two blocks -> coordinator
    escalated_spill: int         # candidates left the owner block
    escalated_conflict: int      # overlapped an earlier window column
    bfs_steps: int               # frontier supersteps (all paths)
    recompute_steps: int         # clamped min-H supersteps (all paths)
    per_block: Tuple[int, ...]   # block-local updates applied per block
    plan_updates: int = 0        # incremental halo-plan maintenances (spmd)
    plan_rebuilds: int = 0       # full plan rebuilds (spmd; 0 in steady state)
    migrations: int = 0          # §4.2 rebalance rounds executed
    migrated_vertices: int = 0   # vertices moved across blocks in total
    cc_merges: int = 0           # CC labels maintained by O(1) label merges
    cc_recomputes: int = 0       # CC label recomputations (delete/migration)
    grows: int = 0               # capacity escalations (Cn/Cd pad-and-rekey)
    candidates: int = 0          # nodes the k-reachable searches reached,
                                 # summed over updates (all paths)

    @property
    def escalated(self) -> int:
        return (self.escalated_cross_block + self.escalated_spill
                + self.escalated_conflict)


class StreamResult(NamedTuple):
    """Uniform `run_stream` / `StreamSession.result` return value.

    `labels` is None unless CC maintenance was armed (`cc_labels=`).
    Tuple-unpacking a StreamResult still works — `__iter__` yields the
    legacy arity (3 fields, or 4 when labels were maintained) with a
    DeprecationWarning — but new code should read the named fields;
    indexing and `len()` see all 4 fields, NamedTuple-style.
    """

    g: Any                       # post-stream GraphBlocks
    core: jax.Array              # (N,) int32 maintained coreness
    stats: StreamStats
    labels: Optional[jax.Array] = None   # (N,) int32 CC labels or None

    def __iter__(self):
        warnings.warn(
            "tuple-unpacking run_stream's result is deprecated; read "
            ".g/.core/.stats/.labels on the returned StreamResult",
            DeprecationWarning, stacklevel=2)
        legacy = (self.g, self.core, self.stats)
        if self.labels is not None:
            legacy += (self.labels,)
        return iter(legacy)


def _owner_blocks(g, ids) -> np.ndarray:
    """Owning block of global padded node ids — THE routing rule (block-
    contiguous relabeling makes it pure arithmetic); every ownership
    decision in this module goes through here."""
    return np.asarray(ids) // g.Cn


def owner_block(g, u: int) -> int:
    """Owning block of a global padded node id (host-side routing key)."""
    return int(_owner_blocks(g, u))


def route_updates(
    g, updates: Iterable[Tuple[int, int, int]]
) -> Tuple[Dict[int, List[Tuple[int, int, int]]], List[Tuple[int, int, int]]]:
    """Host-side router: split a batch into per-owner-block queues plus the
    cross-block remainder the coordinator must handle itself.

    An update is routed to block b iff both endpoints live in b (the M2W
    directive then targets a single worker); otherwise it stays with the
    coordinator.  Returns ({block: [updates]}, cross_block_updates).
    """
    per_block: Dict[int, List[Tuple[int, int, int]]] = {}
    cross: List[Tuple[int, int, int]] = []
    for u, v, op in updates:
        bu, bv = owner_block(g, u), owner_block(g, v)
        if bu == bv:
            per_block.setdefault(bu, []).append((u, v, op))
        else:
            cross.append((u, v, op))
    return per_block, cross


class RouteMasks(NamedTuple):
    """Compact device-side routing verdict for one update window.

    accept/cross/spill/conflict partition the valid columns: each column
    lands in exactly ONE mask, by escalation precedence (cross-block wins
    over spill wins over conflict) — `spill`/`conflict` are escalation
    *reasons*, not the raw conditions (a cross-block column whose
    candidates also spill appears only in `cross`).
    """

    accept: jax.Array        # (R,) bool — block-local, no spill, no conflict
    cross: jax.Array         # (R,) bool — endpoints in two blocks
    spill: jax.Array         # (R,) bool — intra-block, candidates left the
                             #             owner block
    conflict: jax.Array      # (R,) bool — intra-block, no spill, overlapped
                             #             an earlier window column
    cand_ins: jax.Array      # (N,) bool — union candidates of accepted inserts
    cand_del: jax.Array      # (N,) bool — union candidates of accepted deletes
    per_block: jax.Array     # (P,) int32 — accepted updates per owner block
    candidates: jax.Array    # () int32 — candidates of the accepted columns


@partial(jax.jit, static_argnames=("Cn",))
@jax.named_scope("route")
def _route_window(cand, us, vs, ops_, valid, Cn: int) -> RouteMasks:
    """Device-side window routing: ONE fused kernel instead of the old host
    numpy pass (the O(N*R^2) `cand.T @ cand` overlap matmul, the spill
    matrix, and the accept/escalate scan).

    Escalation reasons replicate the host rule exactly: cross-block wins
    over spill wins over conflict, and a column conflicts iff its candidate
    set overlaps ANY earlier valid column (accepted or escalated) — the
    same commutation argument as `kcore_dynamic._independent_prefix`.
    Only the (R,)/(P,) compact outputs ever reach the host; the (N, R)
    candidate matrix stays on device.
    """
    N, R = cand.shape
    owner = us // Cn                                   # (R,) owning blocks
    intra = owner == (vs // Cn)
    block_of = jnp.arange(N, dtype=us.dtype) // Cn
    candv = cand & valid[None, :]
    spill = jnp.any(candv & (block_of[:, None] != owner[None, :]), axis=0)
    overlap = jnp.matmul(candv.T.astype(jnp.int32), candv.astype(jnp.int32))
    earlier = jnp.tril(jnp.ones((R, R), bool), k=-1)   # strictly lower
    conflict = jnp.any((overlap > 0) & earlier, axis=1)
    accept = valid & intra & ~spill & ~conflict
    cross = valid & ~intra
    esc_spill = valid & intra & spill
    esc_conflict = valid & intra & ~spill & conflict
    cand_ins = jnp.any(candv & (accept & (ops_ > 0))[None, :], axis=1)
    cand_del = jnp.any(candv & (accept & (ops_ < 0))[None, :], axis=1)
    per_block = jnp.zeros(N // Cn, jnp.int32).at[owner].add(
        accept.astype(jnp.int32))
    candidates = jnp.sum(candv & accept[None, :], dtype=jnp.int32)
    return RouteMasks(accept, cross, esc_spill, esc_conflict,
                      cand_ins, cand_del, per_block, candidates)


def _iter_windows(updates, R: int) -> Iterator[list]:
    it = iter(updates)
    while True:
        window = list(islice(it, R))
        if not window:
            return
        yield window


class StreamSession:
    """Resumable stream stepper: open -> `apply_window` -> `result`.

    Holds everything `run_stream` used to keep in loop locals — the
    current graph, maintained coreness (and optionally CC labels), the
    long-lived executor, the migration remap, and the routing/superstep
    counters — so a caller can interleave OTHER device work between
    windows: the query-serving loop (`repro.service`) applies one window,
    refreshes its analytics snapshot, answers a few query batches, and
    comes back, all on the ONE executor with zero steady-state
    recompiles.  `run_stream` is now a thin wrapper that opens a session
    and drains the whole iterable through it.

    Window contract: `apply_window` takes a list of at most `R` updates
    `(u, v, op)` with ids global padded *as of session open* (later
    migrations are remapped internally, exactly as `run_stream` always
    did); windows narrower than R are padded to the fixed width, so the
    compiled window kernels keep hitting.  Exactness guarantees are
    unchanged — the session IS `run_stream`'s loop body, extracted.

    NOTE: consumes the graph passed at open via jit buffer donation on
    the apply path (like `maintain_batch`); read `.g` back, and never
    hold references to a previous window's graph arrays.
    """

    def __init__(
        self,
        g,
        core,
        R: int = 8,
        backend: str = "jnp",
        W=None,
        executor=None,
        rebalance_threshold: Optional[float] = None,
        rebalance_max_moves: int = 8,
        cc_labels: Optional[jax.Array] = None,
        auto_grow: bool = False,
    ):
        if R < 1:
            raise ValueError(f"R must be >= 1, got {R}")
        spmd = backend == SPMD_BACKEND
        if executor is not None and not spmd:
            raise ValueError(
                f"executor= requires backend={SPMD_BACKEND!r} (got "
                f"{backend!r}); a non-mesh stream would leave the "
                "executor's halo plan stale."
            )
        self.R = int(R)
        self.backend = backend
        self._spmd = spmd
        self._W = W
        self.executor = None
        if spmd:
            self.executor = (executor if executor is not None
                             else kd._spmd_executor(g, W))
        self._ex_updates0 = self.executor.plan_updates if spmd else 0
        self._ex_rebuilds0 = self.executor.full_rebuilds if spmd else 0
        self.g = self.executor.place(g) if spmd else g
        self.core = jnp.asarray(core)
        self._track_labels = cc_labels is not None
        self.labels = (jnp.asarray(cc_labels) if self._track_labels
                       else None)
        self._rebalance_threshold = rebalance_threshold
        self._rebalance_max_moves = int(rebalance_max_moves)
        self._tot = dict(bfs=0, rec=0, cand=0, batched=0, seq=0, batches=0)
        # recompute supersteps of the block-local accepted path accumulate
        # ON DEVICE — apply_window never blocks on them; stats() pulls the
        # scalar once when asked
        self._rec_dev = jnp.int32(0)
        self._n_updates = 0
        self._n_local = 0
        self._esc_cross = self._esc_spill = self._esc_conflict = 0
        self._per_block = np.zeros(g.P, np.int64)
        self._migrations = self._migrated = 0
        self._remap: Optional[np.ndarray] = None  # open-time -> current ids
        self._cc_merges = self._cc_recomputes = 0
        #: capacity escalation — `apply_window`/`add_vertices` grow the
        #: blocks (pad-and-rekey) instead of raising CapacityError
        self._auto_grow = bool(auto_grow)
        self._grows = 0
        #: id space size at open: window ids below this are open-time
        #: padded ids; ids at/above are `add_vertices` handles resolved
        #: through `_virtual` (their CURRENT padded ids, kept composed
        #: across migrations and grows just like `_remap`)
        self._n_open = g.N
        self._virtual: List[int] = []
        # hub-split plan slot: always None on the plain session; the
        # serving layer reads getattr(session, "mirror") uniformly across
        # StreamSession and MirrorStream
        self.mirror = None

    @property
    def windows_applied(self) -> int:
        """Windows ingested so far (the serving layer's staleness clock)."""
        return self._tot["batches"]

    @tracing.span("stream.window")
    def apply_window(self, window: List[Tuple[int, int, int]]) -> None:
        """Ingest ONE window of at most R updates (see class docstring)."""
        if len(window) > self.R:
            raise ValueError(
                f"window of {len(window)} updates exceeds R={self.R}")
        if not window:
            return
        backend, W, tot = self.backend, self._W, self._tot
        window = [(self._cur(u), self._cur(v), op) for u, v, op in window]
        with tracing.span("stream.validate"):
            while True:
                try:
                    kd._validate_updates_host(self.g, window)
                    break
                except CapacityError:
                    if not self._auto_grow:
                        raise
                    # a row in this window is out of degree capacity:
                    # escalate Cd to the next pow2 and re-key the window
                    # ids (the grow relocates every row), then re-validate
                    # — one doubling almost always suffices (a window adds
                    # at most R edges).
                    rekey = self.grow(Cd=_pow2_ceil(self.g.Cd + 1))
                    window = [(int(rekey[u]), int(rekey[v]), op)
                              for u, v, op in window]
        g, core, ex, spmd = self.g, self.core, self.executor, self._spmd
        tot["batches"] += 1
        R = self.R
        n = len(window)
        self._n_updates += n
        us = np.zeros(R, np.int32)
        vs = np.zeros(R, np.int32)
        ops_ = np.zeros(R, np.int32)
        us[:n] = [u for u, _, _ in window]
        vs[:n] = [v for _, v, _ in window]
        ops_[:n] = [op for _, _, op in window]
        valid = np.zeros(R, bool)
        valid[:n] = True

        with tracing.span("stream.candidates"):
            if spmd:
                cand, steps = kd._batch_candidates_spmd(
                    ex, g, core, us, vs, valid)
            else:
                cand, steps = kd._batch_candidates(
                    g, core, jnp.asarray(us), jnp.asarray(vs),
                    jnp.asarray(valid), backend=backend)

        # routing on device: the (N, R) candidate matrix never reaches the
        # host — ONE transfer per window pulls the compact (R,)/(P,)
        # verdict (bundled with the superstep and candidate counters).
        with tracing.span("stream.route"):
            route = _route_window(
                jnp.asarray(cand), jnp.asarray(us), jnp.asarray(vs),
                jnp.asarray(ops_), jnp.asarray(valid), Cn=g.Cn)
            steps_h, accept, cross, spl, conf, nblk, ncand = jax.device_get(
                (steps, route.accept, route.cross, route.spill,
                 route.conflict, route.per_block, route.candidates))
        tot["bfs"] += int(steps_h)
        tot["cand"] += int(ncand)
        self._esc_cross += int(cross.sum())
        self._esc_spill += int(spl.sum())
        self._esc_conflict += int(conf.sum())

        if accept.any():
            # accepted updates stay at their window position; op=0 turns the
            # non-accepted columns into no-ops for the fixed-width apply
            us_a = np.where(accept, us, 0).astype(np.int32)
            vs_a = np.where(accept, vs, 0).astype(np.int32)
            ops_a = np.where(accept, ops_, 0).astype(np.int32)
            with tracing.span("stream.apply"):
                if spmd:
                    g, core, rec = kd._apply_and_recompute_spmd(
                        g, core, us_a, vs_a, ops_a, route.cand_ins,
                        route.cand_del, W=W, ex=ex)
                else:
                    g, core, rec = kd._apply_and_recompute(
                        g, core, jnp.asarray(us_a), jnp.asarray(vs_a),
                        jnp.asarray(ops_a), route.cand_ins, route.cand_del,
                        backend=backend)
            self._rec_dev = self._rec_dev + rec  # async; no host sync here
            self._n_local += int(accept.sum())
            self._per_block += nblk.astype(np.int64)

        # coordinator path, original stream order within the window
        for r in np.flatnonzero(cross | spl | conf):
            g, core = kd._maintain_one(g, core, window[r], tot, backend,
                                       W=W, ex=ex)

        # §4.2 repartition-threshold protocol, live: workerCompute load
        # summaries (W2M) -> masterCompute threshold + move selection ->
        # an executed node migration (a permutation, nothing recompiles)
        migrated_now = False
        if self._rebalance_threshold is not None:
            with tracing.span("stream.rebalance"):
                if pd.block_balance(g) > self._rebalance_threshold:
                    moves = pd.choose_node_moves(
                        g, max_moves=self._rebalance_max_moves,
                        pair_counts=halo_pair_counts(g))
                    if moves:
                        g, perm, core = migrate_vertices(g, moves, core)
                        self._compose_perm(perm)
                        self._migrations += 1
                        self._migrated += len(moves)
                        migrated_now = True
                        if spmd:
                            ex.rebuild(g)

        # CC label maintenance: inserts only ever JOIN components, so an
        # insert-only window is an O(1)-superstep on-device label merge;
        # deletions (possible splits) and migrations (canonical ids are
        # padded ids, which a migration permutes) re-propagate once on
        # the post-window graph.
        if self._track_labels:
            ins_mask = valid & (ops_ > 0)
            with tracing.span("stream.labels"):
                if (valid & (ops_ < 0)).any() or migrated_now:
                    self.labels = connected_components(g, backend=backend,
                                                       executor=ex)
                    self._cc_recomputes += 1
                elif ins_mask.any():
                    self.labels = merge_labels(
                        self.labels, jnp.asarray(us), jnp.asarray(vs),
                        jnp.asarray(ins_mask))
                    self._cc_merges += int(ins_mask.sum())
        self.g, self.core = g, core

    # ---- elastic growth / recovery surface ------------------------------

    def _cur(self, u) -> int:
        """Resolve an open-time id (or `add_vertices` handle) to the
        CURRENT padded id, through the composed migration/grow remap."""
        u = int(u)
        if u >= self._n_open:
            i = u - self._n_open
            if i >= len(self._virtual):
                raise ValueError(
                    f"unknown vertex handle {u} (have "
                    f"{len(self._virtual)} post-open vertices)")
            return self._virtual[i]
        if self._remap is None:
            return u
        cur = int(self._remap[u])
        if cur < 0:
            raise ValueError(f"open-time id {u} no longer exists")
        return cur

    def _compose_perm(self, perm: np.ndarray) -> None:
        """Fold a node-axis permutation/rekey into the open-time id maps."""
        if self._remap is None:
            self._remap = np.asarray(perm, np.int64).copy()
        else:
            self._remap = np.where(
                self._remap >= 0, perm[np.maximum(self._remap, 0)], -1)
        self._virtual = [int(perm[x]) for x in self._virtual]

    def grow(self, Cn: Optional[int] = None,
             Cd: Optional[int] = None) -> np.ndarray:
        """Capacity escalation on the LIVE session: pad-and-rekey the
        blocks to (Cn, Cd) — see `core.graph.grow_blocks` — relocating
        the maintained coreness and CC labels along (label *values* are
        padded ids, so they ride the same monotone rekey and stay
        canonical), folding the rekey into the open-time id remap, and
        re-keying the executor's mesh/plan (`SpmdExecutor.grow`).  The
        compiled caches re-specialize exactly once per grow; steady
        state stays at zero recompiles.  Returns the rekey map.
        """
        g2, rekey = grow_blocks(self.g, Cn, Cd)
        core = relocate_rows(jax.device_get(self.core), rekey, g2.N, 0)
        self.core = jnp.asarray(core)
        if self.labels is not None:
            lab = relocate_rows(jax.device_get(self.labels), rekey, g2.N, -1)
            lab = np.where(lab >= 0, rekey[np.maximum(lab, 0)], -1)
            self.labels = jnp.asarray(lab.astype(np.int32))
        self._compose_perm(rekey)
        self.g = g2
        if self._spmd:
            self.executor.grow(g2)
        self._grows += 1
        return rekey

    def add_vertices(self, block: int, count: int = 1) -> List[int]:
        """Vertex arrival: activate `count` fresh degree-0 nodes in
        `block` (`core.graph.add_vertices_host`), growing Cn first when
        the block is full and auto-grow is armed.  Returns stable
        HANDLES — ids in the session's open-time id space, usable in
        later windows like any open-time id (they survive migrations and
        grows; allocation is deterministic, so a replayed log hands back
        the same handles)."""
        while True:
            try:
                g2, rows = add_vertices_host(self.g, block, count)
                break
            except CapacityError:
                if not self._auto_grow:
                    raise
                self.grow(Cn=_pow2_ceil(self.g.Cn + 1))
        self.g = g2
        if self._spmd:
            self.executor.refresh_fields(g2)
        if self._track_labels:
            # a fresh isolated vertex is its own component (canonical
            # label == own padded id); coreness 0 already holds
            r = jnp.asarray(rows)
            self.labels = self.labels.at[r].set(
                r.astype(self.labels.dtype))
        base = self._n_open + len(self._virtual)
        self._virtual.extend(int(x) for x in rows)
        return list(range(base, base + len(rows)))

    def migrate(self, moves) -> np.ndarray:
        """Execute an explicit vertex migration (caller-chosen moves —
        the worker-loss recovery path evacuates a dead worker's blocks
        through this).  Same machinery as the §4.2 rebalance: a pure
        node-axis permutation composed into the id remap, an executor
        plan rebuild, and one CC re-propagation when labels are tracked
        (canonical ids are padded ids, which the permutation renames).
        Returns the permutation."""
        g, perm, core = migrate_vertices(self.g, moves, self.core)
        self.g, self.core = g, core
        self._compose_perm(perm)
        self._migrations += 1
        self._migrated += len(moves)
        if self._spmd:
            self.executor.rebuild(g)
        if self._track_labels:
            self.labels = connected_components(
                g, backend=self.backend, executor=self.executor)
            self._cc_recomputes += 1
        return perm

    def state_dict(self):
        """Everything needed to resume this stream elsewhere: a flat
        dict of arrays (a pytree `checkpoint.CheckpointManager` can
        save) plus a JSON-able meta dict of statics and counters.  The
        snapshot is topology-independent — `from_state` may rebuild on a
        different worker mesh (see `checkpoint.elastic`).  Arrays are
        COPIES: the apply path donates the live graph buffers, so shared
        references would die with the next window."""
        g = self.g
        arrays = {
            "core": jnp.copy(self.core),
            "g.deg": jnp.copy(g.deg),
            "g.nbr": jnp.copy(g.nbr),
            "g.node_mask": jnp.copy(g.node_mask),
            "g.orig_id": jnp.copy(g.orig_id),
            "rec_dev": jnp.copy(self._rec_dev),
        }
        if self.labels is not None:
            arrays["labels"] = jnp.copy(self.labels)
        if self._remap is not None:
            arrays["remap"] = jnp.asarray(self._remap)
        spmd, ex = self._spmd, self.executor
        meta = {
            "kind": "stream_session",
            "P": g.P, "Cn": g.Cn, "Cd": g.Cd,
            "R": self.R, "backend": self.backend,
            "auto_grow": self._auto_grow,
            "track_labels": self._track_labels,
            "has_remap": self._remap is not None,
            "n_open": self._n_open,
            "virtual": [int(x) for x in self._virtual],
            "rebalance_threshold": self._rebalance_threshold,
            "rebalance_max_moves": self._rebalance_max_moves,
            "tot": {k: int(v) for k, v in self._tot.items()},
            "counters": {
                "n_updates": self._n_updates,
                "n_local": self._n_local,
                "esc_cross": self._esc_cross,
                "esc_spill": self._esc_spill,
                "esc_conflict": self._esc_conflict,
                "migrations": self._migrations,
                "migrated": self._migrated,
                "cc_merges": self._cc_merges,
                "cc_recomputes": self._cc_recomputes,
                "grows": self._grows,
                "plan_updates":
                    (ex.plan_updates - self._ex_updates0) if spmd else 0,
                "plan_rebuilds":
                    (ex.full_rebuilds - self._ex_rebuilds0) if spmd else 0,
                "per_block": [int(x) for x in self._per_block],
            },
        }
        return arrays, meta

    @classmethod
    def from_state(cls, arrays, meta, W=None, backend: Optional[str] = None,
                   executor=None) -> "StreamSession":
        """Rebuild a session from `state_dict` output.  `W`/`backend`/
        `executor` override the snapshot's mesh shape — the elastic
        remesh path: the arrays are full logical (N,)/(N, Cd) values, so
        any worker count with W | P can adopt them."""
        from ..core.graph import GraphBlocks
        g = GraphBlocks(
            nbr=jnp.asarray(arrays["g.nbr"], jnp.int32),
            deg=jnp.asarray(arrays["g.deg"], jnp.int32),
            node_mask=jnp.asarray(arrays["g.node_mask"]),
            orig_id=jnp.asarray(arrays["g.orig_id"], jnp.int32),
            P=int(meta["P"]), Cn=int(meta["Cn"]), Cd=int(meta["Cd"]))
        sess = cls(
            g, arrays["core"], R=int(meta["R"]),
            backend=meta["backend"] if backend is None else backend,
            W=W, executor=executor,
            rebalance_threshold=meta["rebalance_threshold"],
            rebalance_max_moves=int(meta["rebalance_max_moves"]),
            cc_labels=arrays.get("labels") if meta["track_labels"] else None,
            auto_grow=bool(meta["auto_grow"]))
        sess._rec_dev = jnp.asarray(arrays["rec_dev"], jnp.int32)
        sess._remap = (np.asarray(jax.device_get(arrays["remap"]), np.int64)
                       if meta["has_remap"] else None)
        sess._n_open = int(meta["n_open"])
        sess._virtual = [int(x) for x in meta["virtual"]]
        sess._tot = {k: int(v) for k, v in meta["tot"].items()}
        c = meta["counters"]
        sess._n_updates = int(c["n_updates"])
        sess._n_local = int(c["n_local"])
        sess._esc_cross = int(c["esc_cross"])
        sess._esc_spill = int(c["esc_spill"])
        sess._esc_conflict = int(c["esc_conflict"])
        sess._migrations = int(c["migrations"])
        sess._migrated = int(c["migrated"])
        sess._cc_merges = int(c["cc_merges"])
        sess._cc_recomputes = int(c["cc_recomputes"])
        sess._grows = int(c["grows"])
        sess._per_block = np.asarray(c["per_block"], np.int64)
        if sess._spmd:
            # re-base the executor counter offsets so stats() keeps
            # counting from the snapshot's accumulated totals
            sess._ex_updates0 = (sess.executor.plan_updates
                                 - int(c["plan_updates"]))
            sess._ex_rebuilds0 = (sess.executor.full_rebuilds
                                  - int(c["plan_rebuilds"]))
        return sess

    def stats(self) -> StreamStats:
        """Routing/superstep accounting over every window applied so far."""
        spmd, ex = self._spmd, self.executor
        return StreamStats(
            updates=self._n_updates,
            batches=self._tot["batches"],
            block_local=self._n_local,
            escalated_cross_block=self._esc_cross,
            escalated_spill=self._esc_spill,
            escalated_conflict=self._esc_conflict,
            bfs_steps=self._tot["bfs"],
            recompute_steps=(self._tot["rec"]
                             + int(jax.device_get(self._rec_dev))),
            per_block=tuple(int(x) for x in self._per_block),
            plan_updates=(ex.plan_updates - self._ex_updates0) if spmd else 0,
            plan_rebuilds=(ex.full_rebuilds - self._ex_rebuilds0)
            if spmd else 0,
            migrations=self._migrations,
            migrated_vertices=self._migrated,
            cc_merges=self._cc_merges,
            cc_recomputes=self._cc_recomputes,
            grows=self._grows,
            candidates=self._tot["cand"],
        )

    def result(self) -> StreamResult:
        """Close out: the session's state as a `StreamResult` snapshot.

        The session stays usable afterwards (`result` is cheap and
        side-effect free); `close` is the self-documenting alias for the
        final call.
        """
        return StreamResult(g=self.g, core=self.core, stats=self.stats(),
                            labels=self.labels)

    close = result


def run_stream(
    g,
    core,
    updates: Iterable[Tuple[int, int, int]],
    R: int = 8,
    backend: str = "jnp",
    W=None,
    executor=None,
    rebalance_threshold: Optional[float] = None,
    rebalance_max_moves: int = 8,
    cc_labels: Optional[jax.Array] = None,
    auto_grow: bool = False,
) -> StreamResult:
    """Ingest an update stream; returns a `StreamResult` (g, core, stats,
    labels).

    Thin wrapper: opens a `StreamSession` and drains `updates` through it
    window by window — use the session directly to interleave other work
    (e.g. query serving) between windows.

    g: GraphBlocks (P blocks of Cn rows, nbr (N, Cd)); core: (N,) int32
    coreness of `g` (as `core.kcore.coreness` returns it).  `updates`
    may be any iterable (including a generator) of (u, v, op) with
    op = +1 insert / -1 delete, ids global padded *as of the call*
    (migrations remap later windows internally).  R is the window width
    (the stacked-frontier axis of the batched candidate search).
    Exactness: the final coreness equals sequential per-update
    maintenance — under live rebalancing up to the node-axis
    permutation, i.e. bit-identical when read through `orig_id`.  With
    `backend="ell_spmd"` every superstep runs on the worker mesh through
    ONE long-lived executor (pass `executor` to thread an existing
    `SpmdExecutor` across calls) whose halo plan is maintained
    incrementally per window.

    `rebalance_threshold` (e.g. 1.2) arms the §4.2 repartition-threshold
    protocol after every window: blocks report load summaries, the
    coordinator migrates boundary vertices when max/mean load exceeds
    the threshold.  `None` disables it.

    `cc_labels` (optional) arms connected-component maintenance: pass the
    canonical labels of the PRE-stream graph (as
    `core.algorithms.connected_components` returns them: (N,) int32, min
    member padded id per component, -1 on padding rows) and the stream
    keeps them exact window by window in `result.labels`.  Insert-only
    windows are maintained with O(1)-superstep label merges on device
    (inserts can only *join* components — `algorithms.merge_labels`); a
    window containing a deletion or followed by a §4.2 migration
    triggers one fresh propagation on the post-window graph (splits
    cannot be merged; node permutations relabel the canonical ids).
    `StreamStats.cc_merges` / `cc_recomputes` count the two paths, and
    the final labels are bit-identical to `connected_components(g')`.

    Returns `StreamResult(g, core, stats, labels)`; `labels` is None
    when `cc_labels` was not passed.  Legacy tuple unpacking (3 fields,
    or 4 with `cc_labels`) still works behind a DeprecationWarning.

    NOTE: consumes `g` via jit buffer donation on the escalation path
    (like `maintain_batch`) — use the returned graph.
    """
    session = StreamSession(
        g, core, R=R, backend=backend, W=W, executor=executor,
        rebalance_threshold=rebalance_threshold,
        rebalance_max_moves=rebalance_max_moves, cc_labels=cc_labels,
        auto_grow=auto_grow)
    for window in _iter_windows(updates, R):
        session.apply_window(window)
    return session.result()


class MirrorStream:
    """Stream ingestion over a hub-split graph (vertex-cut maintenance).

    `StreamSession`'s sibling for graphs that went through
    `core.hub_split.split_hubs`: holds the split `GraphBlocks` plus its
    `MirrorPlan` and ingests `(u, v, op)` edit windows where ids are
    PRIMARY row ids of the split graph.  Each window goes through
    `hub_split.apply_mirrored_edits` at the host boundary:

      * inserts land in the endpoint's first serving row with spare
        slice capacity — and when a vertex crosses the split threshold,
        a fresh replica row is allocated from the block's padding pool
        and the edge lands there (the ON-LINE split: no whole-graph
        re-split, no rewiring of existing slots);
      * deletes locate the unique serving-row pair that carries the
        edge (MIRRORED delete) and splice it out of both sorted slices.

    After each window the maintained analytics refresh with
    mirror-aware runs — `kcore.coreness(..., mirror=plan)` and
    optionally `connected_components(..., mirror=plan)` — which is
    exact by the split==unsplit parity guarantee.  (The Theorem-1
    clamped-recompute machinery reasons in the unsplit id space; a
    candidate-bounded mirrored maintenance pass is future work, so this
    session recomputes, and on `ell_spmd` each recompute builds a fresh
    executor and halo plan — stick to single-device backends for
    fine-grained mirrored streams.)

    Duck-types the slice of `StreamSession` the serving layer consumes:
    `.g`, `.core`, `.labels`, `.backend`, `.executor` (always None —
    plan maintenance under `SpmdExecutor` is future work),
    `.windows_applied`, `.mirror`, and `result()`.
    """

    def __init__(self, g, plan, backend: str = "jnp",
                 cc_labels: bool = False, auto_grow: bool = False):
        from ..core.hub_split import apply_mirrored_edits  # noqa: F401
        from ..core.kcore import coreness

        self.g = g
        self.mirror = plan
        self.backend = backend
        self.executor = None
        self._windows = 0
        self._n_updates = 0
        self.core = coreness(g, backend=backend, mirror=plan)
        self._track_labels = bool(cc_labels)
        self.labels = (connected_components(g, backend=backend, mirror=plan)
                       if self._track_labels else None)
        #: grow Cn (pad-and-rekey, plan relocated) when the replica pool
        #: runs dry mid-window, instead of raising CapacityError
        self._auto_grow = bool(auto_grow)
        self._grows = 0
        #: open-time row ids -> current (grows rekey every row); window
        #: ids stay open-time primary-row ids, like StreamSession's
        self._remap: Optional[np.ndarray] = None

    @property
    def windows_applied(self) -> int:
        return self._windows

    def grow(self, Cn: Optional[int] = None,
             Cd: Optional[int] = None) -> np.ndarray:
        """Capacity escalation under the vertex cut: pad-and-rekey the
        split graph (`core.graph.grow_blocks`) and relocate the
        `MirrorPlan` along (`core.hub_split.grow_plan`, a fresh uid).
        Analytics recompute
        mirror-aware, which is exact by split==unsplit parity.  Returns
        the rekey map."""
        from ..core.hub_split import grow_plan
        from ..core.kcore import coreness

        g2, rekey = grow_blocks(self.g, Cn, Cd)
        self.mirror = grow_plan(self.mirror, rekey, g2)
        self.g = g2
        self._remap = (np.asarray(rekey, np.int64).copy()
                       if self._remap is None
                       else np.where(self._remap >= 0,
                                     rekey[np.maximum(self._remap, 0)], -1))
        self._grows += 1
        self.core = coreness(g2, backend=self.backend, mirror=self.mirror)
        if self._track_labels:
            self.labels = connected_components(
                g2, backend=self.backend, mirror=self.mirror)
        return rekey

    def apply_window(self, window: List[Tuple[int, int, int]]) -> None:
        """Apply one edit window (open-time primary-row ids) and refresh
        analytics.  With auto-grow armed, a window that exhausts the
        replica pool grows Cn IN FLIGHT: `apply_mirrored_edits` mutates
        copies, so the failed attempt leaves no partial state — the
        whole window re-applies on the grown graph."""
        from ..core.hub_split import apply_mirrored_edits
        from ..core.kcore import coreness

        if not window:
            return
        if self._remap is not None:
            window = [(int(self._remap[u]), int(self._remap[v]), op)
                      for u, v, op in window]
        while True:
            try:
                g2, plan2 = apply_mirrored_edits(self.g, self.mirror, window)
                break
            except CapacityError:
                if not self._auto_grow:
                    raise
                rekey = self.grow(Cn=_pow2_ceil(self.g.Cn + 1))
                window = [(int(rekey[u]), int(rekey[v]), op)
                          for u, v, op in window]
        self.g, self.mirror = g2, plan2
        self._windows += 1
        self._n_updates += len(window)
        self.core = coreness(self.g, backend=self.backend,
                             mirror=self.mirror)
        if self._track_labels:
            self.labels = connected_components(
                self.g, backend=self.backend, mirror=self.mirror)

    def state_dict(self):
        """Snapshot arrays + meta, `StreamSession.state_dict`-shaped
        (graph and plan leaves in the flat dict, statics in meta)."""
        g, p = self.g, self.mirror
        arrays = {
            "core": jnp.copy(self.core),
            "g.deg": jnp.copy(g.deg),
            "g.nbr": jnp.copy(g.nbr),
            "g.node_mask": jnp.copy(g.node_mask),
            "g.orig_id": jnp.copy(g.orig_id),
            "plan.grp_gid": jnp.copy(p.grp_gid),
            "plan.grp_rows": jnp.copy(p.grp_rows),
            "plan.ldeg": jnp.copy(p.ldeg),
            "plan.primary_mask": jnp.copy(p.primary_mask),
            "plan.primary_row": jnp.copy(p.primary_row),
            "plan.row_gid": jnp.copy(p.row_gid),
        }
        if self.labels is not None:
            arrays["labels"] = jnp.copy(self.labels)
        if self._remap is not None:
            arrays["remap"] = jnp.asarray(self._remap)
        meta = {
            "kind": "mirror_stream",
            "P": g.P, "Cn": g.Cn, "Cd": g.Cd,
            "backend": self.backend,
            "auto_grow": self._auto_grow,
            "track_labels": self._track_labels,
            "has_remap": self._remap is not None,
            "Gmax": p.Gmax, "Km": p.Km, "threshold": p.threshold,
            "n_logical": p.n_logical,
            "windows": self._windows,
            "n_updates": self._n_updates,
            "grows": self._grows,
        }
        return arrays, meta

    @classmethod
    def from_state(cls, arrays, meta,
                   backend: Optional[str] = None) -> "MirrorStream":
        """Rebuild a mirrored session from `state_dict` output.  The
        restored plan carries a fresh uid (plan identity is per-process),
        so the first mirrored step after restore compiles once."""
        from ..core.graph import GraphBlocks
        from ..core.hub_split import MirrorPlan, _next_uid
        g = GraphBlocks(
            nbr=jnp.asarray(arrays["g.nbr"], jnp.int32),
            deg=jnp.asarray(arrays["g.deg"], jnp.int32),
            node_mask=jnp.asarray(arrays["g.node_mask"]),
            orig_id=jnp.asarray(arrays["g.orig_id"], jnp.int32),
            P=int(meta["P"]), Cn=int(meta["Cn"]), Cd=int(meta["Cd"]))
        plan = MirrorPlan(
            primary_row=jnp.asarray(arrays["plan.primary_row"], jnp.int32),
            ldeg=jnp.asarray(arrays["plan.ldeg"], jnp.int32),
            primary_mask=jnp.asarray(arrays["plan.primary_mask"]),
            grp_rows=jnp.asarray(arrays["plan.grp_rows"], jnp.int32),
            grp_gid=jnp.asarray(arrays["plan.grp_gid"], jnp.int32),
            row_gid=jnp.asarray(arrays["plan.row_gid"], jnp.int32),
            Gmax=int(meta["Gmax"]), Km=int(meta["Km"]),
            threshold=int(meta["threshold"]),
            n_logical=int(meta["n_logical"]), uid=_next_uid())
        sess = cls(g, plan,
                   backend=meta["backend"] if backend is None else backend,
                   cc_labels=bool(meta["track_labels"]),
                   auto_grow=bool(meta["auto_grow"]))
        # restore the maintained analytics verbatim (the ctor recomputed
        # them — bit-identical by the parity contract, but the snapshot
        # is the source of truth)
        sess.core = jnp.asarray(arrays["core"], jnp.int32)
        if meta["track_labels"]:
            sess.labels = jnp.asarray(arrays["labels"], jnp.int32)
        sess._remap = (np.asarray(jax.device_get(arrays["remap"]), np.int64)
                       if meta["has_remap"] else None)
        sess._windows = int(meta["windows"])
        sess._n_updates = int(meta["n_updates"])
        sess._grows = int(meta["grows"])
        return sess

    def result(self) -> StreamResult:
        """Current state as a `StreamResult` (routing/superstep stats are
        not metered on the mirrored path; those counters report zeros)."""
        zeros = StreamStats(
            updates=self._n_updates, batches=self._windows, block_local=0,
            escalated_cross_block=0, escalated_spill=0,
            escalated_conflict=0, bfs_steps=0, recompute_steps=0,
            per_block=tuple(0 for _ in range(self.g.P)),
            grows=self._grows)
        return StreamResult(g=self.g, core=self.core, stats=zeros,
                            labels=self.labels)

    close = result
