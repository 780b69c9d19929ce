"""Incremental k-core maintenance — the heart of BLADYG (paper §4.1).

On an edge update the coordinator does NOT recompute coreness from scratch.
Per Theorem 1 [Li, Yu, Mao, TKDE'14] only nodes *k-reachable* from the
lower-coreness endpoint can change, where k = min(core(u), core(v)):
a node w is k-reachable from r if there is a path r ~> w whose nodes all
have coreness exactly k.

BLADYG execution plan (paper fig. 5 generalized):
  1. M2W: master ships the update (u, v) to the blocks owning u and v.
  2. workerCompute: frontier search for the candidate set, propagating
     W2W whenever the frontier crosses a block boundary.
  3. W2M: candidate summary back to the master.
  4. masterCompute: restricted recomputation on the candidate set only
     (clamped min-H supersteps; see kcore.py for the exactness argument),
     candidates' new coreness is written back.

Bounds used (both from Li-Yu-Mao): insertion can only *raise* a candidate's
coreness, by at most 1; deletion can only *lower* it, by at most 1.  So the
restricted iteration starts from `core + 1` (insert) / `core` (delete) on
candidates — a valid pointwise upper bound — and clamps everyone else.

We take the union of the k-reachable sets from both endpoints (a superset of
the theorem's candidate set in the unequal-coreness cases; supersets only
cost work, never correctness).  The search runs in the *pre-update* graph
for insertions (the theorem's "original graph G") and in the pre-update
graph for deletions as well, then the edge is applied and the restricted
iteration runs on the post-update graph.

Both primitives (frontier hop, clamped h-index) are obtained only through
the kernel backend registry (`repro.kernels.ops`) — the frontier kernels
carry an R axis, which `maintain_batch` uses to run up to R updates'
candidate searches in ONE sequence of supersteps:

Batched maintenance (`maintain_batch`): R updates whose candidate sets are
pairwise disjoint are *independent* — each update's search and restricted
recompute never reads state the others write (the BFS only expands through
its own k-level set, and the recompute clamps everything outside its
candidates).  So the searches stack on the frontier R axis (supersteps =
max instead of sum), the accepted edges apply together, and ONE joint
clamped recompute finishes the chunk.  Conflicting updates (overlapping
candidate sets, detected after the batched search) fall back to the exact
sequential path.  The result is bit-identical to sequential maintenance;
only the superstep count drops.  See EXPERIMENTS.md §Batched maintenance.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..kernels import ops
from .graph import GraphBlocks, insert_edge, delete_edge

#: backend name that routes maintenance supersteps through `repro.runtime`
#: (supported by `maintain_batch` / `runtime.run_stream`; the per-edge
#: jitted entry points reject it — the halo plan needs concrete arrays)
SPMD_BACKEND = "ell_spmd"


def _reject_spmd(backend: str, fn_name: str) -> None:
    if backend == SPMD_BACKEND:
        raise ValueError(
            f"{fn_name} does not support backend={SPMD_BACKEND!r}: it runs "
            "under jit, where the runtime's halo plan cannot be built from "
            "traced arrays. Use maintain_batch(..., backend='ell_spmd') or "
            "runtime.run_stream for mesh-executed maintenance."
        )


def _validate_updates_host(g: GraphBlocks, updates) -> None:
    """Host-boundary validation for a maintenance stream.

    Replays the whole stream through `updates.apply_updates_host` (which
    raises on self-loops, duplicate inserts, missing deletes, and degree-
    capacity overflow) and discards the result — the jitted maintenance
    path assumes validated input and would silently corrupt the ELL rows
    otherwise.
    """
    from .updates import apply_updates_host  # deferred: sibling module

    apply_updates_host(g, list(updates))


class MaintenanceStats(NamedTuple):
    candidates: jax.Array      # int32 — |candidate set|
    bfs_steps: jax.Array       # int32 — frontier supersteps (W2W rounds)
    recompute_steps: jax.Array # int32 — clamped min-H supersteps
    blocks_touched: jax.Array  # int32 — #blocks containing candidates
    inter_partition: jax.Array # bool  — update crossed a block boundary


class BatchMaintenanceStats(NamedTuple):
    """Aggregate accounting for one `maintain_batch` stream."""

    updates: int           # total updates processed
    batches: int           # number of batched chunks executed
    batched_updates: int   # updates that rode a batched chunk
    sequential_updates: int  # updates deferred to the sequential path
    bfs_steps: int         # total frontier supersteps (batched + sequential)
    recompute_steps: int   # total clamped min-H supersteps
    candidates: int        # total candidate-set size across updates


def k_reachable(
    g: GraphBlocks, core: jax.Array, roots: jax.Array, k: jax.Array,
    max_steps: int = 10_000, backend: str = "jnp",
) -> Tuple[jax.Array, jax.Array]:
    """Mask of nodes k-reachable from `roots` (incl. roots with core==k).

    Frontier expansion over the ELL adjacency, one hop per superstep, each
    hop dispatched through the kernel registry (`ops.frontier_blocks`).
    Returns (visited mask (N,), number of supersteps).
    """
    visited, steps = k_reachable_batch(
        g, core, roots[:, None], k[None], max_steps=max_steps, backend=backend
    )
    return visited[:, 0], steps


@jax.named_scope("reach")
def k_reachable_batch(
    g: GraphBlocks, core: jax.Array, roots: jax.Array, ks: jax.Array,
    max_steps: int = 10_000, backend: str = "jnp",
) -> Tuple[jax.Array, jax.Array]:
    """R stacked k-reachability searches sharing one superstep sequence.

    roots: (N, R) bool — per-search root sets; ks: (R,) int32 — per-search
    k level.  Column r expands only through nodes with core == ks[r].
    Returns (visited (N, R) bool, supersteps int32 = max over searches).
    """
    eligible = (core[:, None] == ks[None, :]) & g.node_mask[:, None]
    visited0 = roots & eligible
    adj = ops.dense_adj(g, backend)  # densify once, not per hop

    def cond(c):
        visited, frontier, it = c
        return jnp.any(frontier) & (it < max_steps)

    def body(c):
        visited, frontier, it = c
        nxt = ops.frontier_blocks(
            g, frontier, eligible, visited, backend=backend, adj=adj
        )
        return visited | nxt, nxt, it + 1

    visited, _, steps = jax.lax.while_loop(
        cond, body, (visited0, visited0, jnp.int32(0))
    )
    return visited, steps


@jax.named_scope("recompute")
def _restricted_recompute(
    g: GraphBlocks, est0: jax.Array, cand: jax.Array,
    max_steps: int = 10_000, backend: str = "jnp",
) -> Tuple[jax.Array, jax.Array]:
    """Clamped min-H iteration: only `cand` nodes move; returns (core', steps)."""
    adj = ops.dense_adj(g, backend)  # densify once, not per superstep

    def cond(c):
        est, changed, it = c
        return changed & (it < max_steps)

    def body(c):
        est, _, it = c
        h = ops.hindex_blocks(g, est, backend=backend, adj=adj)
        new = jnp.where(cand & g.node_mask, jnp.minimum(est, h), est)
        return new, jnp.any(new != est), it + 1

    est, _, steps = jax.lax.while_loop(cond, body, (est0, jnp.bool_(True), jnp.int32(0)))
    return est, steps


def _stats(g: GraphBlocks, cand, bfs_steps, rec_steps, u, v) -> MaintenanceStats:
    blocks = jnp.zeros(g.P, bool).at[jnp.arange(g.N) // g.Cn].max(cand)
    return MaintenanceStats(
        candidates=jnp.sum(cand).astype(jnp.int32),
        bfs_steps=bfs_steps.astype(jnp.int32),
        recompute_steps=rec_steps.astype(jnp.int32),
        blocks_touched=jnp.sum(blocks).astype(jnp.int32),
        inter_partition=(u // g.Cn) != (v // g.Cn),
    )


@partial(jax.jit, donate_argnums=(0,), static_argnames=("backend",))
def insert_edge_maintain(
    g: GraphBlocks, core: jax.Array, u: jax.Array, v: jax.Array,
    backend: str = "jnp",
) -> Tuple[GraphBlocks, jax.Array, MaintenanceStats]:
    """Insert (u, v) and maintain coreness.  u, v are global padded ids."""
    _reject_spmd(backend, "insert_edge_maintain")
    k = jnp.minimum(core[u], core[v])
    roots = jnp.zeros(g.N, bool).at[u].set(True).at[v].set(True)
    cand, bfs_steps = k_reachable(g, core, roots, k, backend=backend)
    # the endpoints themselves are always candidates (their degree changed)
    cand = cand | roots

    g2 = insert_edge(g, u, v)
    ub = jnp.where(cand, jnp.minimum(core + 1, g2.deg), core)
    new_core, rec_steps = _restricted_recompute(g2, ub, cand, backend=backend)
    return g2, new_core, _stats(g2, cand, bfs_steps, rec_steps, u, v)


@partial(jax.jit, donate_argnums=(0,), static_argnames=("backend",))
def delete_edge_maintain(
    g: GraphBlocks, core: jax.Array, u: jax.Array, v: jax.Array,
    backend: str = "jnp",
) -> Tuple[GraphBlocks, jax.Array, MaintenanceStats]:
    """Delete (u, v) and maintain coreness."""
    _reject_spmd(backend, "delete_edge_maintain")
    k = jnp.minimum(core[u], core[v])
    roots = jnp.zeros(g.N, bool).at[u].set(True).at[v].set(True)
    cand, bfs_steps = k_reachable(g, core, roots, k, backend=backend)
    cand = cand | roots

    g2 = delete_edge(g, u, v)
    # deletion can only lower candidates, by at most 1; old core is a UB,
    # but degree may now be below it.
    ub = jnp.where(cand, jnp.minimum(core, g2.deg), core)
    new_core, rec_steps = _restricted_recompute(g2, ub, cand, backend=backend)
    return g2, new_core, _stats(g2, cand, bfs_steps, rec_steps, u, v)


def maintain_batch_host(g, core, updates):
    """Host loop applying a sequence of (u, v, op) updates (op: +1 ins, -1 del).

    Returns (g, core, list_of_stats).  This mirrors the paper's experiment —
    per-edge maintenance latency, not batched amortization; `maintain_batch`
    is the amortized path.

    The stream is validated here (self-loops, duplicates, missing deletes,
    capacity) — this is a host boundary; the jitted maintain functions
    assume validated input and would corrupt the ELL rows otherwise.

    NOTE: consumes `g` via jit buffer donation (a no-op on CPU, enforced
    on TPU/GPU) — do not reuse the argument afterwards.
    """
    _validate_updates_host(g, updates)
    stats = []
    for u, v, op in updates:
        fn = insert_edge_maintain if op > 0 else delete_edge_maintain
        g, core, s = fn(g, jnp.asarray(core), jnp.int32(u), jnp.int32(v))
        stats.append(jax.device_get(s))
    return g, core, stats


# ---------------------------------------------------------------------------
# Batched maintenance: amortize supersteps over independent updates.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("backend",))
def _batch_candidates(
    g: GraphBlocks, core: jax.Array, us: jax.Array, vs: jax.Array,
    valid: jax.Array, backend: str = "jnp",
):
    """Candidate sets for up to R updates via one batched frontier search.

    us, vs: (R,) int32 endpoint ids (arbitrary on invalid columns);
    valid: (R,) bool.  The per-update k levels are derived on device
    (-1 on invalid columns keeps them empty).
    Returns (cand (N, R) bool, supersteps).
    """
    R = us.shape[0]
    cols = jnp.arange(R)
    ks = jnp.where(valid, jnp.minimum(core[us], core[vs]), -1)
    roots = (
        jnp.zeros((g.N, R), bool)
        .at[us, cols].max(valid)
        .at[vs, cols].max(valid)
    )
    visited, steps = k_reachable_batch(g, core, roots, ks, backend=backend)
    # endpoints are always candidates (their degree changes)
    return (visited | roots) & valid[None, :], steps


def _independent_prefix(cand: np.ndarray, valid: int) -> Tuple[List[int], List[int]]:
    """Greedily split update columns into (accepted, deferred).

    A column is accepted iff its candidate set is disjoint from every
    earlier column that was accepted — AND every earlier column that was
    deferred.  Disjointness covers shared endpoints too (endpoints are
    always in their own candidate set).

    The deferred check is what keeps the reordering sound: deferred
    updates are applied *after* the accepted batch, so accepting a column
    that conflicts with an earlier deferred one would swap the order of
    two dependent updates (e.g. an insert into a full row hoisted above
    the delete that frees the slot).  Conflict-free pairs commute — their
    candidate sets (which contain the endpoints) are disjoint, so they
    touch disjoint adjacency rows.
    """
    overlap = cand.T.astype(np.int64) @ cand.astype(np.int64)  # (R, R)
    accepted: List[int] = []
    deferred: List[int] = []
    for r in range(valid):
        # accepted + deferred == all earlier columns, so the rule reduces
        # to "disjoint from every earlier column"
        if not overlap[r, :r].any():
            accepted.append(r)
        else:
            deferred.append(r)
    return accepted, deferred


@jax.named_scope("apply_edges")
def _apply_edges(
    g: GraphBlocks, us: jax.Array, vs: jax.Array, ops_: jax.Array
) -> GraphBlocks:
    """Apply (R,) fixed-width updates: op = +1 insert / -1 delete / 0 no-op."""

    def apply_one(i, gg):
        u, v, op = us[i], vs[i], ops_[i]
        return jax.lax.switch(
            jnp.clip(op + 1, 0, 2),
            [
                lambda q: delete_edge(q, u, v),  # op == -1
                lambda q: q,                     # op ==  0 (padding)
                lambda q: insert_edge(q, u, v),  # op == +1
            ],
            gg,
        )

    return jax.lax.fori_loop(0, us.shape[0], apply_one, g)


@partial(jax.jit, donate_argnums=(0,), static_argnames=("backend",))
def _apply_and_recompute(
    g: GraphBlocks, core: jax.Array, us: jax.Array, vs: jax.Array,
    ops_: jax.Array, cand_ins: jax.Array, cand_del: jax.Array,
    backend: str = "jnp",
):
    """Apply accepted edges and run ONE joint clamped recompute.

    us, vs, ops_: (R,) fixed-width accepted updates, op = +1 insert /
    -1 delete / 0 padding no-op — fixed R keeps the jit cache to one entry
    regardless of how many updates each chunk accepts.
    cand_ins / cand_del: (N,) union masks of the accepted insert / delete
    candidate sets (disjoint by construction).
    """
    g2 = _apply_edges(g, us, vs, ops_)
    # per-update upper bounds (valid because the candidate sets are disjoint:
    # no node gets both an insert and a delete bound)
    ub = jnp.where(cand_ins, jnp.minimum(core + 1, g2.deg), core)
    ub = jnp.where(cand_del, jnp.minimum(core, g2.deg), ub)
    union = cand_ins | cand_del
    new_core, rec_steps = _restricted_recompute(g2, ub, union, backend=backend)
    return g2, new_core, rec_steps


# ---------------------------------------------------------------------------
# ell_spmd routing: the identical maintenance protocol with every superstep
# (k-reachability hops, clamped min-H recompute) executed on the worker mesh
# through the runtime subsystem's halo exchange.
# ---------------------------------------------------------------------------


def _spmd_executor(g: GraphBlocks, W=None, ex=None):
    """Host-boundary construction of the mesh executor (deferred import —
    `runtime` lazily dispatches back into `kernels.ops`).  When a live
    executor `ex` is threaded through, it is returned as-is: the caller
    owns keeping its plan in sync via `ex.apply_updates`."""
    if ex is not None:
        return ex
    from ..runtime.spmd import SpmdExecutor

    return SpmdExecutor(g, W=W)


def _batch_candidates_spmd(ex, g: GraphBlocks, core, us, vs, valid):
    """`_batch_candidates` with the frontier supersteps run on the mesh."""
    R = len(us)
    cols = jnp.arange(R)
    usj, vsj = jnp.asarray(us), jnp.asarray(vs)
    validj = jnp.asarray(valid)
    ks = jnp.where(validj, jnp.minimum(core[usj], core[vsj]), -1)
    roots = (
        jnp.zeros((g.N, R), bool)
        .at[usj, cols].max(validj)
        .at[vsj, cols].max(validj)
    )
    visited, steps = ex.k_reachable_batch(core, roots, ks)
    return (visited | roots) & validj[None, :], steps


def _apply_and_recompute_spmd(
    g: GraphBlocks, core, us, vs, ops_, cand_ins, cand_del, W=None, ex=None
):
    """`_apply_and_recompute` with the joint clamped recompute on the mesh.

    The halo plan depends on the adjacency: with a threaded executor `ex`
    the plan is maintained *incrementally* on the post-update graph
    (`ex.apply_updates` — dirty workers only, zero full rebuilds);
    without one, a fresh executor is built per call (the legacy path).
    Either way the compiled mesh steps are reused from the per-(mesh, H)
    cache whenever the halo capacity holds.
    """
    g2 = _apply_edges(g, jnp.asarray(us), jnp.asarray(vs), jnp.asarray(ops_))
    ub = jnp.where(cand_ins, jnp.minimum(core + 1, g2.deg), core)
    ub = jnp.where(cand_del, jnp.minimum(core, g2.deg), ub)
    union = cand_ins | cand_del
    if ex is None:
        ex = _spmd_executor(g2, W)
    else:
        ex.apply_updates(g2, list(zip(us, vs, ops_)))
    new_core, rec_steps = ex.restricted_recompute(ub, union)
    return g2, new_core, rec_steps


def _maintain_one_spmd(g: GraphBlocks, core, update, tot, W=None, ex=None):
    """Sequential (coordinator-path) maintenance of one update on the mesh.

    With a threaded executor `ex` the halo plan rides along incrementally
    (the edit touches at most two blocks); without one, executors are
    built per call as before.
    """
    u, v, op = update
    uj, vj = jnp.int32(u), jnp.int32(v)
    shared = ex is not None
    ex = _spmd_executor(g, W, ex)
    k = jnp.minimum(core[uj], core[vj])
    roots = jnp.zeros(g.N, bool).at[uj].set(True).at[vj].set(True)
    cand, bfs_steps = ex.k_reachable_batch(core, roots[:, None], k[None])
    cand = cand[:, 0] | roots

    g2 = insert_edge(g, uj, vj) if op > 0 else delete_edge(g, uj, vj)
    bump = core + 1 if op > 0 else core
    ub = jnp.where(cand, jnp.minimum(bump, g2.deg), core)
    if shared:
        ex.apply_updates(g2, [update])
        ex2 = ex
    else:
        ex2 = _spmd_executor(g2, W)
    new_core, rec_steps = ex2.restricted_recompute(ub, cand)
    # ONE bundled transfer for the three counters (three bare int() casts
    # would block the dispatch queue once each)
    bfs_h, rec_h, cand_h = jax.device_get(
        (bfs_steps, rec_steps, jnp.sum(cand)))
    tot["bfs"] += int(bfs_h)
    tot["rec"] += int(rec_h)
    tot["cand"] += int(cand_h)
    tot["seq"] += 1
    return g2, new_core


def maintain_batch(
    g: GraphBlocks,
    core: jax.Array,
    updates: Sequence[Tuple[int, int, int]],
    R: int = 8,
    backend: str = "jnp",
    W=None,
) -> Tuple[GraphBlocks, jax.Array, BatchMaintenanceStats]:
    """Maintain coreness over a stream of updates, R at a time.

    g: GraphBlocks (nbr (N, Cd), N = P*Cn); core: (N,) int32 coreness of
    `g`; updates: sequence of (u, v, op) with op = +1 insert / -1 delete
    and u, v global padded ids.  Returns (g', (N,) int32 core',
    BatchMaintenanceStats).

    Chunks of up to R (u, v, op) updates share one batched k-reachability
    search on the frontier kernels' R axis.  Updates whose candidate sets
    are pairwise disjoint are applied together with a single joint clamped
    recompute; the rest fall back to exact sequential maintenance within
    the chunk.  Final coreness is identical to sequential processing; the
    frontier superstep count is the batch maximum instead of the sum.

    The stream is validated here (self-loops, duplicates, missing deletes,
    capacity) — this is a host boundary (the jitted update path never
    re-validates).

    With `backend="ell_spmd"` every superstep (the batched k-reachability
    search and the joint clamped recompute) executes on the worker mesh
    via the runtime subsystem's halo exchange; `W` forces the worker
    count (default: as many devices as divide P).  ONE executor threads
    through the whole stream, its halo plan maintained incrementally
    after every applied edit (zero full plan rebuilds).  Results are
    identical to every other backend.

    NOTE: like the single-edge maintain functions, this CONSUMES `g` via
    jit buffer donation (a no-op on CPU, enforced on TPU/GPU) — do not
    reuse the argument afterwards; use the returned graph.
    """
    if R < 1:
        raise ValueError(f"R must be >= 1, got {R}")
    _validate_updates_host(g, updates)
    spmd = backend == SPMD_BACKEND
    # ONE executor threads through the whole stream on the mesh path; its
    # halo plan is maintained incrementally after every applied edit
    ex = _spmd_executor(g, W) if spmd else None

    core = jnp.asarray(core)
    tot = dict(bfs=0, rec=0, cand=0, batched=0, seq=0, batches=0)
    # batched-path recompute supersteps accumulate on device; pulled once
    # when the final stats are assembled
    rec_dev = jnp.int32(0)
    for start in range(0, len(updates), R):
        chunk = list(updates[start:start + R])
        if len(chunk) == 1:
            g, core = _maintain_one(g, core, chunk[0], tot, backend, W=W,
                                    ex=ex)
            continue
        n = len(chunk)
        us = np.zeros(R, np.int32)
        vs = np.zeros(R, np.int32)
        ops_ = np.zeros(R, np.int32)
        us[:n] = [u for u, _, _ in chunk]
        vs[:n] = [v for _, v, _ in chunk]
        ops_[:n] = [op for _, _, op in chunk]
        valid = np.zeros(R, bool)
        valid[:n] = True

        if spmd:
            cand, steps = _batch_candidates_spmd(ex, g, core, us, vs, valid)
        else:
            cand, steps = _batch_candidates(
                g, core, jnp.asarray(us), jnp.asarray(vs),
                jnp.asarray(valid), backend=backend,
            )
        # ONE bundled transfer pulls the candidate matrix together with
        # the superstep counter (int(steps) alone would sync separately)
        steps_h, cand_np = jax.device_get((steps, cand))
        tot["bfs"] += int(steps_h)
        tot["batches"] += 1
        cand_np = np.asarray(cand_np)
        accepted, deferred = _independent_prefix(cand_np, n)

        if accepted:
            acc = np.asarray(accepted)
            ins_cols = acc[ops_[acc] > 0]
            del_cols = acc[ops_[acc] < 0]
            cand_ins = jnp.asarray(cand_np[:, ins_cols].any(axis=1))
            cand_del = jnp.asarray(cand_np[:, del_cols].any(axis=1))
            # pad accepted updates to fixed width R (op=0 no-ops) so
            # _apply_and_recompute compiles once per R, not per |accepted|
            us_a = np.zeros(R, np.int32)
            vs_a = np.zeros(R, np.int32)
            ops_a = np.zeros(R, np.int32)
            us_a[:len(acc)] = us[acc]
            vs_a[:len(acc)] = vs[acc]
            ops_a[:len(acc)] = ops_[acc]
            if spmd:
                g, core, rec_steps = _apply_and_recompute_spmd(
                    g, core, us_a, vs_a, ops_a, cand_ins, cand_del, W=W,
                    ex=ex)
            else:
                g, core, rec_steps = _apply_and_recompute(
                    g, core,
                    jnp.asarray(us_a), jnp.asarray(vs_a), jnp.asarray(ops_a),
                    cand_ins, cand_del, backend=backend,
                )
            rec_dev = rec_dev + rec_steps  # async accumulate, no host sync
            tot["cand"] += int(cand_np[:, acc].sum())
            tot["batched"] += len(accepted)

        for r in deferred:
            g, core = _maintain_one(g, core, chunk[r], tot, backend, W=W,
                                    ex=ex)

    stats = BatchMaintenanceStats(
        updates=len(updates),
        batches=tot["batches"],
        batched_updates=tot["batched"],
        sequential_updates=tot["seq"],
        bfs_steps=tot["bfs"],
        recompute_steps=tot["rec"] + int(jax.device_get(rec_dev)),
        candidates=tot["cand"],
    )
    return g, core, stats


@tracing.span("stream.coordinator")
def _maintain_one(g, core, update, tot, backend, W=None, ex=None):
    """Sequential fallback for one update; accumulates into `tot`."""
    if backend == SPMD_BACKEND:
        return _maintain_one_spmd(g, core, update, tot, W=W, ex=ex)
    u, v, op = update
    fn = insert_edge_maintain if op > 0 else delete_edge_maintain
    g, core, s = fn(g, core, jnp.int32(u), jnp.int32(v), backend=backend)
    s = jax.device_get(s)  # ONE bundled pull of the whole stats tuple
    tot["bfs"] += int(s.bfs_steps)
    tot["rec"] += int(s.recompute_steps)
    tot["cand"] += int(s.candidates)
    tot["seq"] += 1
    return g, core
