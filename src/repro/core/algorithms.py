"""Workload library on the `BlockProgram` contract (BLADYG as a framework).

BLADYG's central claim is that the block-centric abstraction — partition,
block-local compute, W2W/W2M exchange, coordinator convergence — is
workload-generic, not a k-core implementation detail.  This module is the
proof: each workload below is a ~20-line `BlockProgram` (state + halo
field + named neighbor combine + update + halt), and the SAME program
object runs unchanged on every backend of the kernel registry through
`kernels.ops.run_block_program` — pure-jnp, dense-tile, ELL Pallas, or
sharded over the worker mesh with a real halo exchange.

Shipped workloads (the canonical kernel set of the "Thinking Like a
Vertex" survey):

  `ConnectedComponentsProgram` — min-label propagation: every node starts
      labeled with its own padded id and repeatedly keeps the minimum
      label among itself and its neighbors, so each component converges
      to the minimum padded id of its members (the *canonical* labeling;
      supersteps ~ component diameter).  Edge insertions merge two
      components and preserve canonicality in O(1) supersteps
      (`merge_labels`) — the natural dynamic workload of the stream loop.
  `PageRankProgram` — push-style PageRank on the undirected graph: the
      exchanged field is each node's outgoing contribution rank/deg, the
      combine is "sum", and the update applies teleport + damping.
      `tol=None` gives the fixed-iteration variant (`max_steps`
      supersteps exactly); a float tol halts when no node moved more
      than tol.  Mass at dangling (degree-0) real nodes is NOT
      redistributed — it decays into the teleport term; the test oracle
      implements the same convention.
  `TriangleCountProgram` — one "count_common" superstep over halo'd
      neighbor rows: red[u] counts ordered common-neighbor pairs, i.e.
      2 × triangles through u.  Per-node counts; sum/3 is the global
      total.
  `CorenessBlockProgram` — the §4.1 min-H iteration re-expressed on the
      contract (combine "hindex"): the program whose implicit structure
      this abstraction was extracted from.  The dedicated
      `ops.coreness_blocks` fixpoints remain the tuned production path
      (degree-bounded K, pad-once); this program is the parity witness
      that the contract subsumes them.

Doctest (the quickstart in 5 lines — swap the program, keep the runner):

    >>> import numpy as np
    >>> from repro.core import build_blocks
    >>> from repro.core.algorithms import (
    ...     connected_components, triangle_counts)
    >>> edges = np.array([[0, 1], [1, 2], [0, 2], [3, 4]])
    >>> g = build_blocks(edges, 5, np.array([0, 0, 0, 1, 1]), P=2)
    >>> mask = np.asarray(g.node_mask)
    >>> np.asarray(connected_components(g))[mask]        # min-id labels
    array([0, 0, 0, 8, 8], dtype=int32)
    >>> np.asarray(triangle_counts(g))[mask]             # one triangle
    array([1, 1, 1, 0, 0], dtype=int32)
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..kernels import ops
from .engine import BlockCtx, BlockProgram, MultiProgram
from .graph import GraphBlocks

#: the CC label of padding rows and the min-combine's absorbing fill
INT32_MAX = int(np.iinfo(np.int32).max)


class ConnectedComponentsProgram(BlockProgram):
    """Min-label propagation; converges to each component's min padded id."""

    combine = "min"
    halo_fill = INT32_MAX
    max_steps = 10_000
    name = "cc"

    def init(self, g: GraphBlocks) -> jax.Array:
        return jnp.where(g.node_mask, jnp.arange(g.N, dtype=jnp.int32),
                         INT32_MAX)

    def halo_field(self, state: jax.Array) -> jax.Array:
        return state

    def update(self, ctx: BlockCtx, state: jax.Array,
               red: jax.Array) -> jax.Array:
        return jnp.where(ctx.node_mask, jnp.minimum(state, red), state)


class PageRankProgram(BlockProgram):
    """Push-style PageRank; state = (rank, contribution), field = contrib.

    rank'[u] = (1 - alpha)/n_real + alpha * sum_{v ~ u} rank[v]/deg[v]
    on real nodes (0 on padding).  `tol` is the per-node halt tolerance
    on |rank' - rank| (None = fixed-iteration: exactly `max_steps`
    supersteps); ranks are float32 throughout, so cross-backend parity is
    allclose, not bit equality.
    """

    combine = "sum"
    halo_fill = 0.0
    name = "pagerank"

    def __init__(self, alpha: float = 0.85, tol: Optional[float] = 1e-6,
                 max_steps: int = 100):
        self.alpha = float(alpha)
        self.tol = None if tol is None else float(tol)
        self.max_steps = int(max_steps)

    def _key(self):
        return (self.alpha, self.tol, self.max_steps)

    def _contrib(self, deg: jax.Array, rank: jax.Array) -> jax.Array:
        return jnp.where(deg > 0, rank / jnp.maximum(deg, 1),
                         0.0).astype(jnp.float32)

    def init(self, g: GraphBlocks) -> Tuple[jax.Array, jax.Array]:
        n = jnp.maximum(jnp.sum(g.node_mask.astype(jnp.float32)), 1.0)
        rank = jnp.where(g.node_mask, 1.0 / n, 0.0).astype(jnp.float32)
        return rank, self._contrib(g.deg, rank)

    def halo_field(self, state) -> jax.Array:
        return state[1]

    def update(self, ctx: BlockCtx, state, red: jax.Array):
        base = (1.0 - self.alpha) / ctx.n_real
        rank = jnp.where(ctx.node_mask, base + self.alpha * red,
                         0.0).astype(jnp.float32)
        return rank, self._contrib(ctx.deg, rank)

    def changed(self, old, new) -> jax.Array:
        if self.tol is None:
            return jnp.bool_(True)  # fixed-iteration: max_steps bounds it
        return jnp.any(jnp.abs(new[0] - old[0]) > self.tol)


class TriangleCountProgram(BlockProgram):
    """One "count_common" superstep; state = (per-node counts, nbr rows)."""

    combine = "count_common"
    halo_fill = -1
    max_steps = 1  # a single exchange computes every count
    name = "triangles"

    def init(self, g: GraphBlocks):
        return jnp.zeros(g.N, jnp.int32), jnp.asarray(g.nbr, jnp.int32)

    def halo_field(self, state) -> jax.Array:
        return state[1]

    def update(self, ctx: BlockCtx, state, red: jax.Array):
        # red[u] = ordered common-neighbor pairs = 2 * triangles at u
        return red // 2, state[1]

    def mirror_state(self, state, primary_row: jax.Array):
        # counts are per-vertex (replicate); neighbor rows are per-ROW
        # slices — gathering them through primaries would duplicate the
        # primary's slice onto every mirror.
        return state[0][primary_row], state[1]


class CorenessBlockProgram(BlockProgram):
    """§4.1 min-H coreness on the generic contract (parity witness)."""

    combine = "hindex"
    halo_fill = -1
    max_steps = 10_000
    name = "coreness"

    def init(self, g: GraphBlocks) -> jax.Array:
        return jnp.where(g.node_mask, g.deg, 0).astype(jnp.int32)

    def halo_field(self, state: jax.Array) -> jax.Array:
        return state

    def update(self, ctx: BlockCtx, state: jax.Array,
               red: jax.Array) -> jax.Array:
        return jnp.where(ctx.node_mask, jnp.minimum(state, red), state)


# ---------------------------------------------------------------------------
# Friendly entry points (thin wrappers over `ops.run_block_program`).
# ---------------------------------------------------------------------------


def connected_components(
    g: GraphBlocks,
    backend: str = "auto",
    executor=None,
    max_steps: Optional[int] = None,
    with_steps: bool = False,
    mirror=None,
) -> Union[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Canonical component labels: label[u] = min padded id of u's component.

    Returns (N,) int32 with -1 on padding rows (plus the superstep count
    as a device scalar when `with_steps=True`).  Identical integers on
    every backend; supersteps scale with the largest component diameter.

    `mirror` (a `core.hub_split.MirrorPlan`) runs the hub-split dataflow;
    mirror rows only ever carry their primary's id, so labels stay in the
    unsplit id space and primaries match the unsplit run bit-exactly.
    """
    out = ops.run_block_program(
        g, ConnectedComponentsProgram(), backend=backend, executor=executor,
        max_steps=max_steps, with_steps=with_steps, mirror=mirror)
    state, steps = out if with_steps else (out, None)
    labels = jnp.where(g.node_mask, state, -1)
    return (labels, steps) if with_steps else labels


def pagerank(
    g: GraphBlocks,
    alpha: float = 0.85,
    tol: Optional[float] = 1e-6,
    max_steps: int = 100,
    backend: str = "auto",
    executor=None,
    with_steps: bool = False,
    mirror=None,
) -> Union[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Push-style PageRank over the undirected graph; (N,) float32 ranks.

    `tol=None` runs exactly `max_steps` supersteps (the fixed-iteration
    variant); otherwise the fused loop halts once no node moves more than
    `tol`.  Padding rows hold 0.0.  Under `mirror` (hub split) the slice
    partials re-associate the float sums — allclose to the unsplit run,
    not bit-equal (the integer workloads ARE bit-equal).
    """
    prog = PageRankProgram(alpha=alpha, tol=tol, max_steps=max_steps)
    out = ops.run_block_program(
        g, prog, backend=backend, executor=executor, with_steps=with_steps,
        mirror=mirror)
    if with_steps:
        (rank, _), steps = out
        return rank, steps
    return out[0]


def triangle_counts(
    g: GraphBlocks,
    backend: str = "auto",
    executor=None,
    with_steps: bool = False,
    mirror=None,
) -> Union[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Per-node triangle counts ((N,) int32, 0 on padding rows).

    tri[u] = number of triangles containing u; the global total is
    `triangle_total(counts)` = sum / 3 (each triangle has 3 corners).
    One superstep on every backend.  Under `mirror` the runner routes
    through the exact `hub_split.run_common_mirror` pass (canonicalized
    rows + per-slice corrections) — counts at primaries are bit-exact.
    """
    out = ops.run_block_program(
        g, TriangleCountProgram(), backend=backend, executor=executor,
        with_steps=with_steps, mirror=mirror)
    if with_steps:
        (counts, _), steps = out
        return counts, steps
    return out[0]


@tracing.span("analytics.fused")
def fused_analytics(
    g: GraphBlocks,
    alpha: float = 0.85,
    steps: int = 30,
    backend: str = "auto",
    executor=None,
    with_steps: bool = False,
    init: Optional[Tuple[jax.Array, jax.Array]] = None,
    mirror=None,
) -> Union[Tuple[jax.Array, jax.Array, jax.Array],
           Tuple[Tuple[jax.Array, jax.Array, jax.Array], jax.Array]]:
    """Coreness + CC labels + PageRank from ONE fused superstep loop.

    Builds a `MultiProgram` over `CorenessBlockProgram`,
    `ConnectedComponentsProgram`, and fixed-iteration
    `PageRankProgram(alpha, tol=None)` and runs exactly `steps` fused
    supersteps: each superstep reads the neighbor slots once and serves
    all three reduces off the shared gather.  Returns
    ``(coreness, labels, rank)`` — coreness (N,) int32 (0 on padding),
    labels (N,) int32 (-1 on padding), rank (N,) float32 (0.0 on
    padding) — each bit-identical to its standalone program run for the
    same superstep count, provided `steps` covers the min/hindex
    programs' convergence (their updates idle at the fixpoint).

    `init=(core, labels)` warm-starts the two monotone sub-programs from
    maintained values (labels in the `connected_components` convention:
    -1 on padding, unmasked here to the internal `INT32_MAX`).  Both are
    fixpoints of their own updates — min-H of true coreness returns the
    coreness, min-label of canonical labels returns the labels — so when
    the inputs are exact (as the stream loop keeps them) they ride
    through the fused loop bit-unchanged while PageRank, always reset to
    its uniform init here, still runs its `steps` fixed iterations.
    This is the serving path's snapshot refresh: one fused loop, three
    fields, no standalone convergence budget for coreness/CC needed.

    `mirror` (a `core.hub_split.MirrorPlan`) runs the whole fused loop
    under the vertex-cut dataflow: one merge stage per field per
    superstep, coreness/CC bit-exact vs the unsplit run, PageRank
    allclose (float slice sums re-associate).
    """
    pr = PageRankProgram(alpha=alpha, tol=None, max_steps=steps)
    prog = MultiProgram(
        (CorenessBlockProgram(), ConnectedComponentsProgram(), pr),
        max_steps=steps)
    state0 = None
    if init is not None:
        core0, labels0 = init
        gi = g if mirror is None else ops._mirror_init_view(g, mirror)
        state0 = (
            jnp.asarray(core0, jnp.int32),
            jnp.where(g.node_mask, jnp.asarray(labels0, jnp.int32),
                      INT32_MAX),
            pr.init(gi),
        )
    out = ops.run_block_program(
        g, prog, backend=backend, executor=executor, with_steps=with_steps,
        state0=state0, mirror=mirror)
    state, n = out if with_steps else (out, None)
    core, lab, (rank, _) = state
    results = (core, jnp.where(g.node_mask, lab, -1), rank)
    return (results, n) if with_steps else results


def triangle_total(counts: jax.Array) -> jax.Array:
    """Global triangle count from per-node counts (device int scalar)."""
    return jnp.sum(counts) // 3


@jax.jit
@jax.named_scope("labels")
def merge_labels(labels: jax.Array, us: jax.Array, vs: jax.Array,
                 valid: jax.Array) -> jax.Array:
    """Exact CC maintenance for a fixed-width batch of edge INSERTIONS.

    labels: (N,) canonical component labels (min member padded id, as
    `connected_components` returns on real rows); us, vs: (R,) int32
    endpoint ids; valid: (R,) bool (False columns are no-ops).  Each
    insertion replaces the larger of the two endpoint labels with the
    smaller everywhere — the merged component keeps its minimum member
    id, so canonicality is preserved and the result is bit-identical to
    recomputation from scratch.  Deletions cannot be maintained this way
    (a split needs a fresh propagation); the stream loop recomputes on
    delete windows.
    """

    def body(i, lab):
        la, lb = lab[us[i]], lab[vs[i]]
        lo, hi = jnp.minimum(la, lb), jnp.maximum(la, lb)
        return jnp.where(valid[i] & (lab == hi), lo, lab)

    return jax.lax.fori_loop(0, us.shape[0], body, labels)
