"""shard_map superstep execution: BLADYG modes as real collectives.

`SpmdExecutor` compiles the graph primitives over the worker mesh with the
halo plan baked in:

  W2W   — `_halo_exchange`: gather the send buffers, `lax.all_to_all`
          across the `workers` axis, scatter into the halo buffer; the
          neighbor read is then a purely local gather through the
          plan's local-frame adjacency.  By default the read is
          **split-phase** (`_overlap_select`): local slots gather from
          the field shard without waiting on the collective, only halo
          slots consume the all_to_all — bit-identical values, zero
          serialized collective phases per superstep
          (`SpmdExecutor(overlap=False)` restores strict ordering).
  W2M   — per-block summaries leave the shard through the sharded
          output (an all-gather) or a `lax.psum` for reduced flags.
  M2W   — the master's directive enters the next superstep replicated.
  Local — everything else: h-index / frontier math on the shard.

`SpmdEngine.run_spmd` is the program-level executor (the distributed
counterpart of `core.engine.BladygEngine.run`): it drives an
`SpmdProgram`'s worker/master ops and records per-superstep
`SuperstepTrace`s whose W2W numbers come from the **executed** halo plan
(`HaloPlan.slot_counts`), not from shape reconstruction.

Compiled step functions are cached per (mesh, halo capacity H): the plan
tables are *arguments*, not closure constants, so maintenance loops that
thread one executor through a stream (updating its plan in place via
`SpmdExecutor.apply_updates` — the halo changes with the adjacency)
reuse the compiled executables as long as the halo capacity holds, and
the capacity doubling policy makes sure it almost always does — jit's
shape cache handles the rest.

Bit-exactness: all math is int32/bool and identical to the single-device
reference (`kernels.ref`), so `coreness_spmd` equals
`ops.coreness_blocks(backend="jnp")` exactly for any worker count,
including the blocks-per-device fold and W = 1.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

import numpy as np

from .. import tracing
from ..kernels.ops import BlockCtx, merged_hindex
from ..kernels.ref import combine_rows, hindex_rows
from .halo import HaloPlan, build_halo_plan
from .mesh import AXIS, WorkerMesh, make_worker_mesh

P_ = PartitionSpec


def _halo_exchange(x_local, send_idx, recv_pos, H: int, fill):
    """One W2W round for a per-node field shard.

    x_local: (S, ...) — this worker's values.
    send_idx: (W, K)  — local rows to serve each receiver.
    recv_pos: (W, K)  — halo positions for each sender's values (pad
                        entries target the dump slot H).
    Returns the (H+2, ...) halo buffer: [0, H) real entries, H the dump
    slot, H+1 the PAD sentinel pinned at `fill`.
    """
    tail = x_local.shape[1:]
    sendbuf = x_local[send_idx]                              # (W, K, ...)
    recvbuf = jax.lax.all_to_all(
        sendbuf, AXIS, split_axis=0, concat_axis=0, tiled=True
    )
    buf = jnp.full((H + 2,) + tail, fill, x_local.dtype)
    return buf.at[recv_pos.reshape(-1)].set(
        recvbuf.reshape((-1,) + tail)
    ).at[H + 1].set(fill)


def _neighbor_vals(x_local, halo_buf, nbr_local):
    """Local gather through the plan's local-frame adjacency: (S, Cd, ...).

    The strict-ordered form: concatenating the halo buffer ahead of the
    gather makes EVERY neighbor read data-depend on the all_to_all, so
    the compute phase serializes behind the collective.
    """
    vals = jnp.concatenate([x_local, halo_buf], axis=0)
    return vals[nbr_local]


def _overlap_select(x_local, halo_buf, nbr_local):
    """Split-phase neighbor read: local slots bypass the halo buffer.

    Local-frame ids < S index this worker's own rows — their values are a
    pure local gather of `x_local` with NO data dependence on the
    all_to_all, so the scheduler is free to run that gather while the
    collective is still in flight; only the halo slots (ids >= S) wait.
    The select picks, slot for slot, exactly the values the strict
    concat-gather reads, so both orderings are bit-identical (the
    poisoned-halo test in tests/test_overlap.py pins the independence).
    """
    S = x_local.shape[0]
    is_local = nbr_local < S
    local_vals = jnp.take(x_local, jnp.clip(nbr_local, 0, S - 1), axis=0)
    halo_vals = jnp.take(
        halo_buf, jnp.clip(nbr_local - S, 0, halo_buf.shape[0] - 1), axis=0)
    mask = is_local.reshape(
        is_local.shape + (1,) * (local_vals.ndim - is_local.ndim))
    return jnp.where(mask, local_vals, halo_vals)


def _any_global(x) -> jax.Array:
    """Replicated 'any' across all shards (the W2M reduced flag)."""
    return jax.lax.psum(jnp.any(x).astype(jnp.int32), AXIS) > 0


def _exchange_gather(field, nbrl, send, recv, H, fill, overlap: bool = False):
    """W2W exchange + local gather: field (S, ...) -> (S, Cd, ...).

    send/recv arrive with their sharded leading worker axis of size 1.
    `overlap=True` uses the split-phase read (`_overlap_select`): the
    all_to_all is issued first and only halo slots consume it, local
    slots gather straight from `field` — same values, one fewer
    serialized collective phase per superstep.
    """
    with jax.named_scope("halo"):
        halo = _halo_exchange(field, send[0], recv[0], H, fill)
    with jax.named_scope("gather"):
        if overlap:
            return _overlap_select(field, halo, nbrl)
        return _neighbor_vals(field, halo, nbrl)


def _gather_field(field, nbrl, send, recv, H, fill, overlap: bool, names):
    """`_exchange_gather` over a declared halo field, tuple-aware.

    MultiPrograms declare tuple fields/fills (one per fused sub-program);
    each leaf exchanges with its own fill and dtype, under the named
    scope of its sub-program (`names`).
    """
    if isinstance(field, tuple):
        out = []
        for f, fl, name in zip(field, fill, names):
            with jax.named_scope(name):
                out.append(_exchange_gather(
                    f, nbrl, send, recv, H, jnp.asarray(fl, f.dtype),
                    overlap))
        return tuple(out)
    return _exchange_gather(field, nbrl, send, recv, H,
                            jnp.asarray(fill, field.dtype), overlap)


# ---------------------------------------------------------------------------
# Compiled step functions, cached per (mesh, H).  Plan tables and state are
# arguments, so executors rebuilt after graph updates hit this cache.
# ---------------------------------------------------------------------------

#: how many mesh step functions have been BUILT (jit-wrapped on a compiled-
#: cache miss): every `_smap` call bumps it, so a steady-state serving loop
#: — session windows + snapshot refreshes + query batches on one executor —
#: holds it constant after warmup.  Python-side and monotonic, the mesh
#: analogue of `kernels.ops.gather_trace_count`; tests snapshot it around
#: the post-warmup phase to assert ZERO recompiles.
_STEP_BUILDS = 0


def step_build_count() -> int:
    """Mesh step functions built so far (see `_STEP_BUILDS`)."""
    return _STEP_BUILDS


def _smap(fn, mesh, n_lead: int, n_rep: int, out_specs, name: str):
    """shard_map + jit: `n_lead` node-sharded args, `n_rep` replicated args,
    then the three plan tables (nbr_local / send / recv, worker-sharded).
    `name` names the compiled program (``jit_<name>`` in a profile's
    ``XLA Modules`` line), so each mesh step is told apart there."""
    global _STEP_BUILDS
    _STEP_BUILDS += 1
    fn.__name__ = fn.__qualname__ = name
    specs = [P_(AXIS)] * n_lead + [P_()] * n_rep + [P_(AXIS)] * 3
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(specs), out_specs=out_specs,
        check_vma=False,
    ))


@functools.lru_cache(maxsize=128)
def _compiled_hindex(mesh, H: int, overlap: bool):
    def local(est, nbrl, send, recv):
        vals = _exchange_gather(est, nbrl, send, recv, H, jnp.int32(-1),
                                overlap)
        return hindex_rows(vals)

    return _smap(local, mesh, 1, 0, P_(AXIS), "spmd_hindex")


@functools.lru_cache(maxsize=128)
def _compiled_frontier(mesh, H: int, overlap: bool):
    def local(f, elig, vis, nbrl, send, recv):
        vals = _exchange_gather(
            f.astype(jnp.int8), nbrl, send, recv, H, jnp.int8(0), overlap)
        return jnp.any(vals > 0, axis=1) & elig & ~vis

    return _smap(local, mesh, 3, 0, P_(AXIS), "spmd_frontier")


@functools.lru_cache(maxsize=128)
def _compiled_coreness(mesh, H: int, overlap: bool):
    @jax.named_scope("coreness")
    def local(est, mask, max_steps, nbrl, send, recv):
        def cond(c):
            _, changed, it = c
            return changed & (it < max_steps)

        def body(c):
            est, _, it = c
            vals = _exchange_gather(est, nbrl, send, recv, H, jnp.int32(-1),
                                    overlap)
            new = jnp.where(mask, jnp.minimum(est, hindex_rows(vals)), est)
            return new, _any_global(new != est), it + 1

        est, _, steps = jax.lax.while_loop(
            cond, body, (est, jnp.bool_(True), jnp.int32(0)))
        return est, steps

    return _smap(local, mesh, 2, 1, (P_(AXIS), P_()), "spmd_coreness")


@functools.lru_cache(maxsize=128)
def _compiled_reach(mesh, H: int, overlap: bool):
    @jax.named_scope("reach")
    def local(core, mask, roots, ks, max_steps, nbrl, send, recv):
        elig = (core[:, None] == ks[None, :]) & mask[:, None]
        visited0 = roots & elig

        def cond(c):
            _, _, cont, it = c
            return cont & (it < max_steps)

        def body(c):
            visited, frontier, _, it = c
            vals = _exchange_gather(
                frontier.astype(jnp.int8), nbrl, send, recv, H, jnp.int8(0),
                overlap)
            nxt = jnp.any(vals > 0, axis=1) & elig & ~visited
            return visited | nxt, nxt, _any_global(nxt), it + 1

        visited, _, _, steps = jax.lax.while_loop(
            cond, body,
            (visited0, visited0, _any_global(visited0), jnp.int32(0)))
        return visited, steps

    return _smap(local, mesh, 3, 2, (P_(AXIS), P_()), "spmd_reach")


@functools.lru_cache(maxsize=128)
def _compiled_recompute(mesh, H: int, overlap: bool):
    @jax.named_scope("recompute")
    def local(est, cand, mask, max_steps, nbrl, send, recv):
        move = cand & mask

        def cond(c):
            _, changed, it = c
            return changed & (it < max_steps)

        def body(c):
            est, _, it = c
            vals = _exchange_gather(est, nbrl, send, recv, H, jnp.int32(-1),
                                    overlap)
            new = jnp.where(move, jnp.minimum(est, hindex_rows(vals)), est)
            return new, _any_global(new != est), it + 1

        est, _, steps = jax.lax.while_loop(
            cond, body, (est, jnp.bool_(True), jnp.int32(0)))
        return est, steps

    return _smap(local, mesh, 3, 1, (P_(AXIS), P_()), "spmd_recompute")


class LocalCtx(NamedTuple):
    """Per-shard context handed to `SpmdProgram.worker_local`."""

    deg: jax.Array        # (S,) int32
    node_mask: jax.Array  # (S,) bool
    B: int                # blocks on this worker (fold)
    Cn: int               # nodes per block
    Cd: int
    aux: Any = ()         # this worker's shard of `SpmdProgram.operands`


class MeshLayout(NamedTuple):
    """What one superstep of the last mesh program run works over.

    S rows a worker gathering ``cols`` columns each (``slots`` = S·cols
    per field per superstep, the same on every worker), a halo of H
    entries, K entries a worker pair in the all_to_all; for a hub-split
    graph its replica ``groups``, the plan's group rows ``Rp``, the group
    rows each worker holds at most (``Rw``, padded), the h-index bound
    ``Km`` and the most ``rounds`` the h-index merge may take (0
    without a split)."""

    S: int
    cols: int
    H: int
    K: int
    groups: int
    Rp: int
    Rw: int
    Km: int
    rounds: int
    slots: int


_LAST_LAYOUT: Optional[MeshLayout] = None


def last_mesh_layout() -> Optional[MeshLayout]:
    """The `MeshLayout` of the last `SpmdEngine.run_spmd` (None before)."""
    return _LAST_LAYOUT


class SpmdExecutor:
    """Compiled halo-exchange primitives for one (graph, mesh) pair.

    Holds the worker mesh, the halo plan, and the per-(mesh, H) compiled
    step functions.  The plan is a function of `nbr` *contents*: after
    structural updates keep ONE executor alive and call `apply_updates`
    (dirty-worker incremental plan maintenance — the streaming hot path)
    or, after wholesale changes such as a vertex migration, `rebuild`.
    Both preserve the capacity floors, so the per-(mesh, H) compiled
    executables keep hitting; `full_rebuilds`/`plan_updates` count which
    path ran (a steady-state stream performs zero full rebuilds).

    `overlap` (default True) selects the split-phase neighbor read
    (`_overlap_select`): local slots gather without waiting on the
    all_to_all, so per superstep the compute serializes behind ZERO
    collective phases instead of one.  `overlap=False` is the
    strict-ordering fallback (the concat-gather of PR 3/4); both produce
    bit-identical values, and the executed count lands in each
    `SuperstepTrace.serialized_collectives`.
    """

    def __init__(self, g, W: Optional[int] = None,
                 wm: Optional[WorkerMesh] = None,
                 plan: Optional[HaloPlan] = None,
                 overlap: bool = True):
        self.wm = wm if wm is not None else make_worker_mesh(g, W=W)
        self.plan = plan if plan is not None else build_halo_plan(g, self.wm)
        #: split-phase halo read (False = strict-ordering fallback)
        self.overlap = bool(overlap)
        #: full from-scratch plan rebuilds after construction (`rebuild`)
        self.full_rebuilds = 0
        #: incremental plan maintenance calls (`apply_updates`)
        self.plan_updates = 0
        #: capacity escalations followed (`grow`) — each re-keys the
        #: compiled caches exactly once
        self.grows = 0
        self._refresh(g)

    def _refresh(self, g) -> None:
        """Re-stage the plan tables and per-node fields on device, each
        split over the worker mesh along its leading axis (every device
        holds only its own shard)."""
        sh = self.wm.node_sharding()
        self.node_mask = jax.device_put(jnp.asarray(g.node_mask), sh)
        self.deg = jax.device_put(jnp.asarray(g.deg, jnp.int32), sh)
        #: adjacency columns every superstep gathers (`_cols`)
        self.cols = self._cols()
        self._nbrl = jax.device_put(self.plan.nbr_local[:, :self.cols], sh)
        self._send = jax.device_put(self.plan.send_idx, sh)
        self._recv = jax.device_put(self.plan.recv_pos, sh)

    def place(self, g):
        """`g` with its per-node arrays split over the worker mesh like the
        plan tables: each device holds its own blocks' rows of the graph
        the stream's apply path edits, not a whole copy on the first."""
        return jax.device_put(g, self.wm.node_sharding())

    def _cols(self) -> int:
        """Adjacency columns the supersteps gather: the power of two
        (>= 8) above the widest row, at most Cd — a road network's rows
        hold ~4 of Cd = 70 slots.  Rows are left-filled (the sorted-ELL
        invariant), so column j holds a neighbor somewhere iff some row
        is wider than j: one column read per bucket finds the width on
        the host, with no device transfer."""
        nl = self.plan.nbr_local
        pad = nl.shape[0] // self.wm.W + self.plan.H + 1  # S + H + 1
        cols = 8
        while cols < nl.shape[1] and (nl[:, cols] != pad).any():
            cols *= 2
        return min(cols, nl.shape[1])

    @tracing.span("halo.update")
    def apply_updates(self, g, edits) -> None:
        """Incrementally maintain the halo plan after edge `edits`.

        `g` is the POST-update graph; `edits` are (u, v, op) triples
        (op = +1 insert / -1 delete / 0 padding no-op).  Only the workers
        owning an endpoint of a cross-worker edit are re-derived; the
        capacity doubling policy keeps the compiled caches warm.
        """
        self.plan = self.plan.apply_updates(g, edits)
        self._refresh(g)
        self.plan_updates += 1

    @tracing.span("halo.rebuild")
    def rebuild(self, g) -> None:
        """Full from-scratch plan rebuild (e.g. after `migrate_vertices`
        permuted the blocks).  Keeps the H/K capacity floors so compiled
        step functions survive the rebuild."""
        self.plan = build_halo_plan(
            g, self.wm, H_min=self.plan.H, K_min=self.plan.K)
        self._refresh(g)
        self.full_rebuilds += 1

    @tracing.span("halo.rebuild")
    def grow(self, g) -> None:
        """Follow a capacity escalation (`core.graph.grow_blocks`): refit
        the worker mesh to the new Cn — same W, same devices, only the
        block-fold geometry changes — and build a fresh halo plan at the
        new capacities (the old H/K floors describe the old id space, so
        they do not carry over).  Downstream, the per-(mesh, H) compiled
        steps re-specialize on the new shard shapes exactly once per
        grow and then keep hitting — the same pow2-bucket policy that
        keeps the steady-state stream at zero recompiles.
        """
        self.wm = make_worker_mesh(
            g, W=self.wm.W, devices=list(self.wm.mesh.devices.flat))
        self.plan = build_halo_plan(g, self.wm)
        self._refresh(g)
        self.grows += 1

    def mirror_tables(self, plan):
        """A hub-split plan's tables as mesh operands, each split over the
        workers like the plan tables: the logical degree of every row,
        and each worker's resident replica-group rows (local row, group
        id), padded to a power of two with (S, Gmax).  Built once per
        plan and mesh."""
        key = (plan.uid, self.wm)
        if getattr(self, "_mirror", (None,))[0] != key:
            S, W, G = self.wm.S, self.wm.W, plan.Gmax
            gid = np.asarray(plan.grp_gid, np.int64)
            rows = np.asarray(plan.grp_rows, np.int64)[gid < G]
            gid = gid[gid < G]
            w = rows // S
            Rw = 8
            while Rw < np.bincount(w, minlength=W).max(initial=0):
                Rw *= 2
            lrow = np.full((W, Rw), S, np.int32)
            lgid = np.full((W, Rw), G, np.int32)
            order = np.argsort(w, kind="stable")
            w, rows, gid = w[order], rows[order], gid[order]
            pos = np.arange(len(w)) - np.searchsorted(w, w)
            lrow[w, pos] = rows - w * S
            lgid[w, pos] = gid
            sh = self.wm.node_sharding()
            self._mirror = (key, (
                jax.device_put(jnp.asarray(plan.ldeg, jnp.int32), sh),
                jax.device_put(lrow.reshape(-1), sh),
                jax.device_put(lgid.reshape(-1), sh)))
            #: (replica groups, plan group rows Rp, rows a worker Rw)
            self.mirror_sizes = (int(gid.max(initial=-1)) + 1,
                                 len(np.asarray(plan.grp_rows)), Rw)
        return self._mirror[1]

    def refresh_fields(self, g) -> None:
        """Re-stage per-node fields (node_mask/deg) after a change that
        leaves the adjacency — and hence the halo plan — untouched
        (e.g. vertex arrival on padding rows)."""
        self._refresh(g)

    @property
    def _tables(self):
        return self._nbrl, self._send, self._recv

    def hindex(self, est: jax.Array) -> jax.Array:
        """h-index of neighbor estimates — one executed W2W superstep.

        est: (N,) int32 (N = P*Cn, sharded over workers as (S,) each);
        returns (N,) int32.
        """
        fn = _compiled_hindex(self.wm.mesh, self.plan.H, self.overlap)
        return fn(est.astype(jnp.int32), *self._tables)

    def frontier(self, f, eligible, visited) -> jax.Array:
        """One masked BFS hop for R stacked frontiers.

        f, eligible, visited: (N, R) bool; returns the next frontier as
        (N, R) bool (`f & eligible & ~visited` semantics of
        `ref.ell_frontier_hop_ref`).
        """
        fn = _compiled_frontier(self.wm.mesh, self.plan.H, self.overlap)
        return fn(f.astype(bool), eligible.astype(bool),
                  visited.astype(bool), *self._tables)

    def coreness(self, max_steps: int = 10_000) -> Tuple[jax.Array, jax.Array]:
        """Full min-H coreness on the mesh.

        Returns ((N,) int32 coreness, device int32 superstep count); the
        whole fixpoint is one on-mesh `lax.while_loop` (zero per-superstep
        host transfers).
        """
        fn = _compiled_coreness(self.wm.mesh, self.plan.H, self.overlap)
        est0 = jnp.where(self.node_mask, self.deg, 0).astype(jnp.int32)
        return fn(est0, self.node_mask, jnp.int32(max_steps), *self._tables)

    def k_reachable_batch(self, core, roots, ks, max_steps: int = 10_000):
        """R stacked k-reachability searches (semantics of
        `core.kcore_dynamic.k_reachable_batch`).

        core: (N,) int32; roots: (N, R) bool; ks: (R,) int32 per-search
        k levels.  Returns ((N, R) bool visited, device superstep count).
        """
        fn = _compiled_reach(self.wm.mesh, self.plan.H, self.overlap)
        return fn(jnp.asarray(core, jnp.int32), self.node_mask,
                  roots.astype(bool), jnp.asarray(ks, jnp.int32),
                  jnp.int32(max_steps), *self._tables)

    def restricted_recompute(self, est0, cand, max_steps: int = 10_000):
        """Clamped min-H iteration (only `cand` nodes move) on the mesh.

        est0: (N,) int32 upper bounds; cand: (N,) bool movable mask.
        Returns ((N,) int32 fixpoint, device superstep count).
        """
        fn = _compiled_recompute(self.wm.mesh, self.plan.H, self.overlap)
        return fn(jnp.asarray(est0, jnp.int32), cand.astype(bool),
                  self.node_mask, jnp.int32(max_steps), *self._tables)


# ---------------------------------------------------------------------------
# Program-level executor: the distributed BladygEngine.
# ---------------------------------------------------------------------------


class SpmdProgram:
    """A BLADYG program in per-shard form.

    `worker_local` sees only this worker's rows plus the halo-served
    neighbor values of the declared exchange field; `master_compute` runs
    replicated on the gathered per-block summaries, exactly the paper's
    masterCompute.
    """

    #: value PAD / dump slots read as (must match the field dtype)
    halo_fill = -1

    #: names the compiled steps (``spmd_fused_<name>``) and, for a tuple
    #: halo field, each leaf's named scope (`field_names`)
    name = "program"
    field_names = None

    #: True iff worker_local AND master_compute are jit-pure with
    #: structure-stable state (mstate/directive pytrees keep their shape
    #: across supersteps) — `SpmdEngine.run_spmd` then fuses the whole
    #: superstep loop into one on-device `lax.while_loop` (W2M as a real
    #: all-gather, the halt decision never leaving the mesh).  Programs
    #: with host-side master logic keep the default (one halt transfer per
    #: superstep).
    fusable = False

    def halo_field(self, wstate) -> jax.Array:
        """The (S, ...) per-node array whose values neighbors read (W2W)."""
        return wstate

    def worker_local(self, ctx: LocalCtx, wstate, nb_vals, directive):
        """(ctx, local state, (S, Cd, ...) neighbor values, directive)
        -> (local state', per-block summary with leading axis B)."""
        raise NotImplementedError

    def master_compute(self, mstate, summary):
        """(master state, gathered (P, ...) summaries)
        -> (master state', directive, halt)."""
        raise NotImplementedError


class SpmdCorenessProgram(SpmdProgram):
    """min-H coreness as an SPMD program (`core.kcore.CorenessProgram`
    routed through the mesh): the estimate vector is the exchanged field,
    the per-block changed flags are the W2M summary, the halt decision is
    the replicated M2W directive."""

    halo_fill = -1
    fusable = True  # pure worker/master ops: the loop runs on-device
    name = "coreness"

    # stateless: any two instances are interchangeable, so they share the
    # engine's compiled-step cache entry
    def __hash__(self):
        return hash(type(self))

    def __eq__(self, other):
        return type(other) is type(self)

    def worker_local(self, ctx, est, nb_vals, directive):
        new = jnp.where(
            ctx.node_mask, jnp.minimum(est, hindex_rows(nb_vals)), est)
        changed = jnp.any(
            (new != est).reshape(ctx.B, ctx.Cn), axis=1)  # per-block W2M
        return new, changed

    def master_compute(self, mstate, summary):
        return mstate, None, jnp.logical_not(jnp.any(summary))


def _mirror_merge_shard(red, field, nb_vals, tables, G: int, Km: int,
                        combine: str, S: int):
    """Cross-worker replica-group merge of per-slice partials (mesh form).

    The on-mesh twin of `kernels.ops._mirror_merge`: each worker folds
    only its own replica-group rows (``tables``: their local rows, S on
    pads, and group ids, G on pads; `SpmdExecutor.mirror_tables`) into a
    (G+1,) per-group table, the tables merge across workers with a
    pmin/psum per merged field, and every worker writes the merged
    aggregates back to its own group rows — the combine-then-broadcast
    step of the vertex-cut dataflow, riding the same mesh as the halo
    exchange.  hindex finds each group's h, capped at the group's
    estimate in ``field`` (the min-H update takes min(est, h)), off the
    already-halo-served `nb_vals` (`merged_hindex`: one psum of a (G+1,)
    table a round, so no second exchange, and one round once the
    estimate has converged); min/sum fold the per-slice reductions
    directly.  Scatter targets of pad entries are pushed out
    of bounds (dropped).
    """
    lrow, gid = tables
    mine = gid < G
    li = jnp.clip(lrow, 0, S - 1)
    if combine == "min":
        fill = jnp.iinfo(red.dtype).max
        vals = jnp.where(mine, red[li], fill)
        part = jnp.full((G + 1,), fill, red.dtype).at[gid].min(vals)
        out = jax.lax.pmin(part, AXIS)[gid]
    elif combine == "sum":
        vals = jnp.where(mine, red[li], jnp.zeros((), red.dtype))
        part = jnp.zeros((G + 1,), red.dtype).at[gid].add(vals)
        out = jax.lax.psum(part, AXIS)[gid]
    elif combine == "hindex":
        out = merged_hindex(nb_vals[li].astype(jnp.int32), mine, gid,
                            field[li], G, Km,
                            total=lambda x: jax.lax.psum(x, AXIS))
        out = out.astype(red.dtype)
    else:
        raise ValueError(
            f"combine {combine!r} has no mirror merge; count_common routes "
            "through core.hub_split.run_common_mirror")
    tgt = jnp.where(mine, li, S)  # OOB scatter drops pad writes
    return red.at[tgt].set(jnp.where(mine, out, jnp.zeros((), red.dtype)))


class SpmdBlockProgram(SpmdProgram):
    """Adapter: any `core.engine.BlockProgram` as an SPMD program.

    This is the ell_spmd execution of the structured superstep contract:
    the program's declared halo field is the exchanged W2W payload, its
    named combine runs as the post-halo local reduce
    (`kernels.ref.combine_rows` on the halo-served (S, Cd, ...) values),
    its update is per-shard workerCompute, and its local changed verdict
    is the W2M summary the replicated master folds into the halt
    decision.  `fusable=True`: the whole loop runs on-mesh through
    `SpmdEngine.run_spmd` with zero per-superstep host transfers.

    `mirror` (a `core.hub_split.MirrorPlan`) arms the vertex-cut
    dataflow: the update ctx carries the worker's slice of the LOGICAL
    degrees, and `_mirror_merge_shard` folds per-slice partials per
    replica group between combine and update.  The plan's tables enter
    the compiled step as sharded operands (`operands`,
    `SpmdExecutor.mirror_tables`), not as constants: only the plan's
    static sizes (Gmax, Km) are part of program identity, so a new plan
    of the same sizes reuses the compiled step.

    Hash/eq delegate to the wrapped program (plus the static real-node
    count and the plan's static sizes), so reusing a program object
    reuses the per-(mesh, H) compiled superstep.
    """

    fusable = True

    def __init__(self, prog, n_real: int, mirror=None):
        self.prog = prog
        self.n_real = int(n_real)
        self.halo_fill = prog.halo_fill
        self.name = prog.name
        if prog.combine == "multi":
            self.field_names = tuple(p.name for p in prog.programs)
        self.mirror = mirror
        self.mirror_key = (None if mirror is None
                           else (mirror.Gmax, mirror.Km))

    def __hash__(self):
        return hash((type(self), self.prog, self.n_real, self.mirror_key))

    def __eq__(self, other):
        return (type(other) is type(self) and other.prog == self.prog
                and other.n_real == self.n_real
                and other.mirror_key == self.mirror_key)

    def operands(self, ex):
        """The plan's tables on the executor's mesh (none unsplit)."""
        return () if self.mirror is None else ex.mirror_tables(self.mirror)

    def summary_shape(self):
        """Static W2M summary shape (the per-worker changed flag).

        `SpmdEngine._summary_shape` uses this instead of abstract-eval:
        the mirrored `worker_local` merges with collectives, which only
        exist inside shard_map — eval_shape outside the mesh would
        fail, and the summary shape is a structural constant anyway.
        """
        return jax.ShapeDtypeStruct((1,), jnp.bool_)

    def halo_field(self, wstate):
        return self.prog.halo_field(wstate)

    def worker_local(self, ctx: LocalCtx, state, nb_vals, directive):
        deg = ctx.deg
        S = deg.shape[0]
        if self.mirror is not None:
            deg, lrow, lgid = ctx.aux
        bctx = BlockCtx(deg=deg, node_mask=ctx.node_mask,
                        n_real=self.n_real)
        field = self.prog.halo_field(state)
        if self.prog.combine == "multi":
            # fused lockstep supersteps: one exchange per sub-field, one
            # shared halt reduction — per-field reduces are the standalone
            # formulations, so results match sub-programs run alone.
            red = []
            for c, f, nb, name in zip(self.prog.combines, field, nb_vals,
                                      self.field_names):
                with jax.named_scope(name):
                    red.append(combine_rows(c, f, nb))
            red = tuple(red)
        else:
            red = combine_rows(self.prog.combine, field, nb_vals)
        if self.mirror is not None:
            G, Km = self.mirror_key
            with jax.named_scope("mirror_merge"):
                if self.prog.combine == "multi":
                    red = tuple(
                        _mirror_merge_shard(r, f, nb, (lrow, lgid), G, Km,
                                            c, S)
                        for r, f, nb, c in zip(red, field, nb_vals,
                                               self.prog.combines))
                else:
                    red = _mirror_merge_shard(
                        red, field, nb_vals, (lrow, lgid), G, Km,
                        self.prog.combine, S)
        new = self.prog.update(bctx, state, red)
        changed = self.prog.changed(state, new)
        return new, changed.reshape(1)  # per-worker W2M flag

    def master_compute(self, mstate, summary):
        return mstate, None, jnp.logical_not(jnp.any(summary))


def fused_step(mesh, H: int, B: int, Cn: int, Cd: int, overlap: bool,
               program: SpmdProgram):
    """A program's whole superstep loop as ONE shard_map'd
    `lax.while_loop` over the worker mesh.

    The W2M summary becomes a real all-gather, masterCompute runs
    replicated on every worker, and the halt flag never reaches the
    host — the superstep count comes back as a device scalar.
    `max_supersteps` is an operand (like `_compiled_coreness`), so
    varying the cap never recompiles.  Arguments: the node-sharded
    state, degree, mask and `program.operands`; the replicated master
    state, directive and cap; the three plan tables.
    """
    def local(wstate, deg, mask, aux, mstate, directive, max_supersteps,
              nbrl, send, recv):
        ctx = LocalCtx(deg=deg, node_mask=mask, B=B, Cn=Cn, Cd=Cd, aux=aux)

        def cond(c):
            _, _, _, halt, it = c
            return (~halt) & (it < max_supersteps)

        def body(c):
            wstate, mstate, d, _, it = c
            field = program.halo_field(wstate)
            nb_vals = _gather_field(
                field, nbrl, send, recv, H, program.halo_fill, overlap,
                program.field_names)
            wstate2, summary = program.worker_local(ctx, wstate, nb_vals, d)
            full = jax.lax.all_gather(summary, AXIS, axis=0, tiled=True)
            mstate2, d2, halt = program.master_compute(mstate, full)
            if d2 is None:  # trace-time: keep carrying the placeholder
                d2 = d
            return wstate2, mstate2, d2, halt, it + 1

        wstate, mstate, _, _, n = jax.lax.while_loop(
            cond, body,
            (wstate, mstate, directive, jnp.bool_(False), jnp.int32(0)))
        return wstate, mstate, n

    return _smap(local, mesh, 4, 3, (P_(AXIS), P_(), P_()),
                 f"spmd_fused_{program.name}")


def _record_layout(ex: "SpmdExecutor", mirror) -> None:
    """Set `last_mesh_layout` for a run on ``ex`` (``mirror``: its plan)."""
    global _LAST_LAYOUT
    S, cols = ex.wm.S, ex.cols
    groups = Rp = Rw = Km = rounds = 0
    if mirror is not None:
        ex.mirror_tables(mirror)
        groups, Rp, Rw = ex.mirror_sizes
        Km, rounds = int(mirror.Km), int(mirror.Km).bit_length()
    _LAST_LAYOUT = MeshLayout(S=S, cols=cols, H=int(ex.plan.H),
                              K=int(ex.plan.K), groups=groups, Rp=Rp, Rw=Rw,
                              Km=Km, rounds=rounds, slots=S * cols)


class SpmdEngine:
    """Superstep scheduler over the worker mesh (cf. `BladygEngine`).

    Differences from the single-device engine: workerCompute executes
    under `shard_map` with a real halo exchange, and the recorded
    per-superstep W2W counts come from the executed `HaloPlan`
    (`plan.slot_counts()`), not from declared shapes.
    """

    #: compiled program steps, keyed by (mesh, H, B, Cn, Cd, program
    #: instance) — the program is part of the key because the closure
    #: captures it, so reusing one program object across runs (as
    #: `coreness_via_spmd` does) reuses the compiled superstep.
    _step_cache: dict = {}

    def __init__(self, g, W: Optional[int] = None,
                 executor: Optional[SpmdExecutor] = None):
        self.g = g
        self.ex = executor if executor is not None else SpmdExecutor(g, W=W)
        self.traces = []

    def _step_fn(self, program: SpmdProgram):
        ex = self.ex
        H = ex.plan.H
        B, Cn = ex.wm.B, ex.wm.Cn
        Cd = ex._nbrl.shape[1]
        overlap = ex.overlap
        mirror = getattr(program, "mirror_key", None)
        key = (ex.wm.mesh, H, B, Cn, Cd, overlap, program, mirror)
        cached = self._step_cache.get(key)
        if cached is not None:
            return cached

        def local(wstate, deg, mask, aux, directive, nbrl, send, recv):
            field = program.halo_field(wstate)
            nb_vals = _gather_field(
                field, nbrl, send, recv, H, program.halo_fill, overlap,
                program.field_names)
            ctx = LocalCtx(deg=deg, node_mask=mask, B=B, Cn=Cn, Cd=Cd,
                           aux=aux)
            return program.worker_local(ctx, wstate, nb_vals, directive)

        fn = _smap(local, ex.wm.mesh, 4, 1, (P_(AXIS), P_(AXIS)),
                   f"spmd_step_{program.name}")
        self._step_cache[key] = fn
        return fn

    def _fused_fn(self, program: SpmdProgram):
        """Whole superstep loop as ONE shard_map'd `lax.while_loop`
        (`fused_step`), cached per mesh, capacities and program."""
        ex = self.ex
        H = ex.plan.H
        B, Cn = ex.wm.B, ex.wm.Cn
        Cd = ex._nbrl.shape[1]
        overlap = ex.overlap
        mirror = getattr(program, "mirror_key", None)
        key = ("fused", ex.wm.mesh, H, B, Cn, Cd, overlap, program, mirror)
        cached = self._step_cache.get(key)
        if cached is None:
            cached = self._step_cache[key] = fused_step(
                ex.wm.mesh, H, B, Cn, Cd, overlap, program)
        return cached

    def _summary_shape(self, program: SpmdProgram, wstate, directive):
        """Abstract-eval the gathered W2M summary (coordinator granularity:
        leading axis P) for post-loop trace reconstruction.

        Programs may declare the shape statically via `summary_shape()`
        (mirrored `SpmdBlockProgram`s must: their worker_local calls
        `lax.axis_index`, which has no meaning outside shard_map)."""
        hint = getattr(program, "summary_shape", None)
        if hint is not None:
            return hint()
        Cd = self.ex._nbrl.shape[1]
        field_s = jax.eval_shape(program.halo_field, wstate)
        nb_s = jax.tree_util.tree_map(
            lambda fs: jax.ShapeDtypeStruct(
                (self.g.N, Cd) + tuple(fs.shape[1:]), fs.dtype),
            field_s)  # tuple fields (MultiProgram) map leaf-wise
        # ctx rides in by closure: its B/Cn/Cd ints must stay concrete
        # (eval_shape would abstract NamedTuple leaves into tracers)
        ctx = LocalCtx(deg=self.ex.deg, node_mask=self.ex.node_mask,
                       B=self.g.P, Cn=self.ex.wm.Cn, Cd=Cd)
        _, summary_s = jax.eval_shape(
            lambda w, nb, d: program.worker_local(ctx, w, nb, d),
            wstate, nb_s, directive)
        return summary_s

    def run_spmd(
        self,
        program: SpmdProgram,
        wstate: Any,
        mstate: Any,
        directive: Any = None,
        max_supersteps: int = 10_000,
        fuse: Optional[bool] = None,
    ) -> Tuple[Any, Any]:
        """Execute the program; worker steps run sharded on the mesh.

        `fuse=None` follows `program.fusable`: fusable programs run the
        whole loop device-resident (zero per-superstep host transfers —
        the halt flag is a mesh-side psum/all-gather decision and the
        superstep count comes back once, with the final state); other
        programs fall back to the host-driven loop below.  Either way the
        trace's W2W numbers are the executed halo plan's slot counts
        (block granularity — identical accounting to the paper's one
        worker per block, independent of the device fold).
        """
        from ..core.engine import BladygEngine, Mode, SuperstepTrace

        w2w = self.ex.plan.slot_counts()
        modes = getattr(program, "modes",
                        Mode.LOCAL | Mode.M2W | Mode.W2M | Mode.W2W)
        # collective phases the compute waited on per superstep: the strict
        # concat-gather serializes behind the halo all_to_all (1); the
        # split-phase overlap read serializes behind none (0).
        ser = 0 if self.ex.overlap else 1
        if fuse is None:
            fuse = getattr(program, "fusable", False)
        aux = getattr(program, "operands", lambda ex: ())(self.ex)
        _record_layout(self.ex, getattr(program, "mirror", None))
        if fuse:
            d0 = directive if directive is not None else jnp.int32(0)
            fn = self._fused_fn(program)
            wstate, mstate, n = fn(
                wstate, self.ex.deg, self.ex.node_mask, aux, mstate, d0,
                jnp.int32(max_supersteps), *self.ex._tables)
            # per-superstep message sizes are static: reconstruct the trace
            # in one bulk extend, metering the *initial* directive (as
            # BladygEngine.run_jit does) and the abstract summary shape.
            stats = BladygEngine._meter(
                self._summary_shape(program, wstate, d0), directive, w2w)
            (n_steps,) = jax.device_get((n,))
            self.traces.extend(
                SuperstepTrace(s, modes, stats, serialized_collectives=ser)
                for s in range(int(n_steps)))
            return wstate, mstate

        step = self._step_fn(program)
        it = 0
        while it < max_supersteps:
            # None directives still need an array through shard_map; the
            # metering sees the real (None) directive.
            d = directive if directive is not None else jnp.int32(0)
            wstate, summary = step(
                wstate, self.ex.deg, self.ex.node_mask, aux, d,
                *self.ex._tables)
            mstate, directive, halt = program.master_compute(mstate, summary)
            self.traces.append(SuperstepTrace(
                it, modes, BladygEngine._meter(summary, directive, w2w),
                serialized_collectives=ser))
            it += 1
            if bool(halt):
                break
        return wstate, mstate

    def message_totals(self):
        from ..core.engine import MessageStats

        tot = MessageStats()
        for t in self.traces:
            tot = tot + t.stats
        return tot


# ---------------------------------------------------------------------------
# Functional entry points (what `kernels.ops` dispatches to).
# ---------------------------------------------------------------------------


def coreness_spmd(g, W: Optional[int] = None, max_steps: int = 10_000,
                  executor: Optional[SpmdExecutor] = None) -> jax.Array:
    """Full coreness on the worker mesh — bit-identical to the jnp path."""
    ex = executor if executor is not None else SpmdExecutor(g, W=W)
    est, _ = ex.coreness(max_steps=max_steps)
    return est


def hindex_spmd(g, est, W: Optional[int] = None,
                executor: Optional[SpmdExecutor] = None) -> jax.Array:
    """One h-index superstep on the mesh.  Builds an executor per call —
    loops should construct `SpmdExecutor` once and call `.hindex`."""
    ex = executor if executor is not None else SpmdExecutor(g, W=W)
    return ex.hindex(est)


def frontier_spmd(g, f, eligible, visited, W: Optional[int] = None,
                  executor: Optional[SpmdExecutor] = None) -> jax.Array:
    """One masked BFS hop on the mesh (eligible may be (N,) or (N, R))."""
    ex = executor if executor is not None else SpmdExecutor(g, W=W)
    if eligible.ndim == 1:
        eligible = jnp.broadcast_to(eligible[:, None], f.shape)
    return ex.frontier(f, eligible, visited)
