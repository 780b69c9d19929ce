"""Kernel backend registry: one dispatch layer for every BLADYG hot loop.

Three interchangeable executions of the two core graph primitives (h-index
of neighbor estimates; masked frontier hop), all exact and bit-identical:

  "jnp"    pure-jnp ELL gather/scatter (`ref.py`) — XLA everywhere, the
           oracle, and the fastest CPU path.
  "dense"  dense-tile Pallas kernels (`kcore_hindex.py`, `frontier.py`) —
           materializes an O(N^2) bf16 adjacency; MXU matmuls; only viable
           for small blocks.
  "ell"    ELL block-sparse Pallas kernels (`ell_hindex.py`,
           `ell_frontier.py`) — consumes `GraphBlocks.nbr` tiles directly,
           O(N*Cd) memory; the scaling path.

A fourth, explicit-only backend executes over the device mesh:

  "ell_spmd"  shard_map over the `workers` mesh axis (`repro.runtime`):
              each device owns a fold of blocks, the neighbor gather is a
              real halo exchange (all-to-all per the precomputed
              `HaloPlan`).  Never chosen by "auto"; host-boundary only —
              the halo plan derives from concrete adjacency, so calls
              under an outer jit trace raise.  Loops should build ONE
              `SpmdExecutor` and thread it through the `executor=`
              parameter of the dispatch entry points; without it each call
              rebuilds the halo plan from scratch.

`backend="auto"` resolves per call: jnp off-TPU (Pallas would run in the
interpreter), dense for blocks small enough to densify profitably
(N <= DENSE_AUTO_MAX), ell beyond.  `core.kcore`, `core.kcore_dynamic`, and
the benchmarks call the primitives *only* through this layer — adding a
backend (the shard_map multi-device path arrived exactly this way) is a
registry entry, not a core-algorithm change.

Fixpoints are device-resident: `coreness_blocks` fuses the whole min-H
iteration into one jitted `lax.while_loop` on every backend (Pallas calls
inside the loop body on dense/ell), so a fixpoint costs ZERO per-superstep
host transfers and returns its superstep count as a device scalar
(`with_steps=True`).  The only host sync is the once-per-fixpoint
`degree_bound` read that buckets the kernels' threshold/sort bound K to a
power of two — the bucketing keeps the per-(shape, K) compiled caches
hitting while the bound tracks the graph instead of the padded Cd.  The
program runner's `ell` fixpoint reads `hybrid_split` there instead: a
pow2 head width every row gathers, plus a pow2 bucket of the few rows
wider than it, gathered again whole.

Beyond the two k-core primitives, the registry carries the named
*neighbor combines* of the `BlockProgram` contract ("min" | "sum" |
"hindex" | "count_common", see `COMBINES`), each with a per-backend
execution — `neighbor_combine_blocks` for one superstep,
`run_block_program` for a whole program fixpoint (CC, PageRank,
triangle counting, coreness: `core.algorithms`).  The program runner is
the generalization of the coreness fixpoint below: one fused
`lax.while_loop` on jnp/dense/ell, the on-mesh `SpmdEngine` fused loop
on ell_spmd, zero per-superstep host transfers either way.

The GraphBlocks-level entry points (`hindex_blocks`, `frontier_blocks`,
`coreness_blocks`, `neighbor_combine_blocks`, `run_block_program`)
duck-type on `.nbr`/`.deg`/`.node_mask`/`.N`/`.Cd` (plus `.n_real` for
the program runner) so this module never imports `repro.core` (no
import cycle; `core.engine` imports the `BlockCtx` contract type from
here).

The raw dense wrappers (`hindex`, `frontier_step`, `coreness_dense`) keep
their historical adjacency-matrix signatures for the kernel sweep tests.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .kcore_hindex import hindex_counts as _hindex_pallas
from .frontier import frontier_step as _frontier_pallas
from .ell_hindex import hindex_ell as _hindex_ell_pallas
from .ell_frontier import frontier_step_ell as _frontier_ell_pallas
from .ell_cc import MIN_FILL, neighbor_min_ell as _min_ell_pallas
from .ell_pagerank import neighbor_sum_ell as _sum_ell_pallas
from .ell_triangles import (
    VARIANTS as TRIANGLE_VARIANTS,
    neighbor_common_ell as _common_ell_pallas,
)
from .ell_multi import neighbor_multi_ell as _multi_ell_pallas

BACKENDS = ("jnp", "dense", "ell", "ell_spmd")

#: neighbor combines of the BlockProgram contract, each with a per-backend
#: execution (pure-jnp gather, dense-adjacency form, ELL Pallas kernel, or
#: post-halo `ref.combine_rows` on the mesh)
COMBINES = ("min", "sum", "hindex", "count_common")

#: combines a fused MultiProgram superstep may bundle (ell_multi.py); the
#: meta-combine name "multi" dispatches to the fused shared-gather paths
MULTI_COMBINES = ("min", "sum", "hindex")

#: auto picks the dense MXU path up to this many (padded) nodes; beyond it
#: the O(N^2) adjacency dominates memory and ELL wins (see EXPERIMENTS.md).
DENSE_AUTO_MAX = 4096

#: crossover table for "auto" on the TPU: jnp for tiny graphs (kernel
#: launch + pad overhead dominates tiles this small), dense while the
#: O(N^2) adjacency is affordable, ell beyond.  The bounds have not been
#: measured on a chip yet (ROADMAP S2).  Entries are (inclusive N upper
#: bound, backend); None = no bound.
AUTO_CROSSOVER = ((512, "jnp"), (DENSE_AUTO_MAX, "dense"), (None, "ell"))
JNP_AUTO_MAX = AUTO_CROSSOVER[0][0]


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _interpret(interpret: Optional[bool]) -> bool:
    """Pallas mode for a kernel call: the caller's choice, else the
    interpreter on the CPU only — every other platform compiles (and a
    kernel that cannot lower there fails loudly, never falls back)."""
    if interpret is not None:
        return interpret
    return jax.devices()[0].platform == "cpu"


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _pow2_bucket(x: int, floor: int = 128) -> int:
    """Smallest power of two >= x, floored at `floor` (a lane multiple)."""
    k = floor
    while k < x:
        k *= 2
    return k


def _tile_dims(N: int, T: int) -> tuple:
    """(Tp, Np): clamp the tile to the 128-lane-padded N, pad N to tiles.

    Single source of truth for the node-axis padding of every kernel
    wrapper — `dense_bytes` relies on it, so the >4 GiB infeasibility
    estimate always matches what the dense wrapper would allocate.
    """
    Tp = min(T, max(128, _pad_to(N, 128)))
    return Tp, _pad_to(N, Tp)


def resolve_backend(backend: Optional[str], N: int) -> str:
    """Resolve "auto" (or None) to a concrete backend name for a graph size.

    Off-TPU, always jnp (Pallas would run interpreted).  On TPU the
    `AUTO_CROSSOVER` table applies: jnp up to JNP_AUTO_MAX padded nodes
    (small tiles lose more to kernel launch + padding than they gain),
    dense while the O(N^2) adjacency stays affordable, ell beyond.
    """
    if backend in (None, "auto"):
        if not _on_tpu():
            return "jnp"  # Pallas would run interpreted — jnp is the fast path
        for bound, b in AUTO_CROSSOVER:
            if bound is None or N <= bound:
                return b
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS + ('auto',)}")
    return backend


def dense_bytes(N: int, T: int = 256) -> int:
    """HBM the dense backend would need for its padded bf16 adjacency."""
    _, Np = _tile_dims(N, T)
    return Np * Np * 2


#: narrowest ELL column bucket: a road network (max degree ~6) gathers
#: 8 slots per row, not a 128-lane row of pads
ELL_MIN_COLS = 8


def _ell_cols(K: int) -> int:
    """Kernel column width for a column bound K: the power of two >= K
    below 128 lanes (down to `ELL_MIN_COLS`), else K padded to 128."""
    if K <= 64:
        return _pow2_bucket(max(1, K), floor=ELL_MIN_COLS)
    return max(128, _pad_to(K, 128))


def degree_bound(g) -> int:
    """pow2-bucketed max-degree threshold bound for the h-index kernels.

    ONE host sync per call (read at the top of a fixpoint, never inside) —
    h(u) <= deg(u), so any bound >= max degree is exact, and the power-of-
    two bucketing means maintenance streams that nudge the max degree keep
    hitting the same compiled kernels.  Under a jit trace (where the
    degrees are abstract) this falls back to the static padded-Cd bound,
    which is always safe and costs no transfer.
    """
    Cdp = max(128, _pad_to(g.Cd, 128))
    if isinstance(g.deg, jax.core.Tracer) or g.N == 0:
        return Cdp
    d = int(jax.device_get(jnp.max(g.deg)))
    return min(Cdp, _pow2_bucket(max(1, d), floor=ELL_MIN_COLS))


#: narrowest head of a hybrid ELL adjacency (`hybrid_split`); the kernels
#: take any power of two below 128 lanes (`ell_hindex.check_cols`)
HYBRID_MIN_COLS = 4


class HybridSplit(NamedTuple):
    """How a fixpoint's `ell` adjacency splits (see `hybrid_split`)."""

    cols: int         # C: the full row width, `_ell_cols` of the widest row
    head_cols: int    # W: the columns every row gathers
    tail_rows: int    # rows wider than W, gathered again at width C
    tail_padded: int  # their pow2 row bucket (floor one tile), 0 = no tail
    slots: int        # slots gathered per field per superstep


class HybridEll(NamedTuple):
    """A fixpoint's `ell` adjacency operand (built by `_hybrid_ell`): the
    first W columns of every padded row, plus the whole C-column rows of
    the few rows wider than W and their row ids (N at pad entries).
    Without a tail, `head` is the whole (Np, C) adjacency."""

    head: jax.Array
    tail: Optional[jax.Array] = None
    rows: Optional[jax.Array] = None


@jax.jit
def _fill_widths(nbr: jax.Array):
    """(rows wider than 2^k for each k below the padded Cd's bit length,
    the widest row): the row fills of a left-filled adjacency, read as a
    histogram by pow2 class, counted from the slots themselves."""
    fill = jnp.sum(nbr >= 0, axis=1, dtype=jnp.int32)
    Cdp = max(128, _pad_to(nbr.shape[1], 128))
    widths = jnp.asarray([1 << k for k in range(Cdp.bit_length())],
                         jnp.int32)
    return jnp.sum(fill[:, None] > widths, axis=0), jnp.max(fill)


def hybrid_split(nbr: jax.Array, T: int = 256) -> HybridSplit:
    """Split the fused `ell` gather's adjacency into a head and a tail.

    ONE host sync per call (read at the top of a fixpoint, never inside;
    it stands in for `degree_bound` there).  The full width C is the
    kernels' column bucket of the widest row, as `degree_bound` gives it
    for left-filled rows; the head width W is the power of two in
    [HYBRID_MIN_COLS, C] that gathers the fewest slots,
    ``Np * W + tail_padded * C``, where the tail is every row wider than
    W, bucketed to a power of two of at least one tile.  A head narrower
    than C only comes with a tail, and ties keep W = C: a graph whose
    rows share one width keeps the single (Np, C) adjacency.  Both W and
    the tail bucket are powers of two, so maintenance streams keep
    hitting the same compiled programs.  Under a jit trace this falls
    back to the padded Cd and no tail, like `degree_bound`.
    """
    N, Cd = nbr.shape
    Cdp = max(128, _pad_to(Cd, 128))
    _, Np = _tile_dims(N, T)
    if isinstance(nbr, jax.core.Tracer) or N == 0:
        return HybridSplit(Cdp, Cdp, 0, 0, Np * Cdp)
    wider, widest = jax.device_get(_fill_widths(nbr))
    C = min(Cdp, _ell_cols(max(1, int(widest))))
    best = HybridSplit(C, C, 0, 0, Np * C)
    W = HYBRID_MIN_COLS
    while W < C:
        tail = int(wider[W.bit_length() - 1])
        if tail:
            tail_p = _pow2_bucket(tail, floor=T)
            slots = Np * W + tail_p * C
            if slots < best.slots:
                best = HybridSplit(C, W, tail, tail_p, slots)
        W *= 2
    return best


# ---------------------------------------------------------------------------
# Dense-path wrappers (historical adjacency-matrix API, kept for the sweeps).
# ---------------------------------------------------------------------------


def _pad_dense_adj(adj: jax.Array, N: int, Np: int) -> jax.Array:
    """Pad a dense adjacency to the tile-aligned bf16 form the kernels eat."""
    return jnp.zeros((Np, Np), jnp.bfloat16).at[:N, :N].set(
        adj.astype(jnp.bfloat16))


def hindex(
    adj: jax.Array,
    est: jax.Array,
    K: Optional[int] = None,
    T: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """h-index per node via the dense-tile kernel (pads N, K as needed).

    K=None uses the static node-count bound (h <= deg < N) — jit-safe and
    free of host syncs; hot loops should pass the graph's degree bound
    (`degree_bound`) for a tighter count matrix.
    """
    N = adj.shape[0]
    if K is None:
        K = max(1, N)  # h <= deg <= N-1: static, no hidden device_get
    Kp = max(128, _pad_to(K, 128))
    Tp, Np = _tile_dims(N, T)
    interpret = _interpret(interpret)
    adj_p = _pad_dense_adj(adj, N, Np)
    est_p = jnp.full((Np,), -1, jnp.int32).at[:N].set(est.astype(jnp.int32))
    h = _hindex_pallas(adj_p, est_p, K=Kp, T=Tp, interpret=interpret)
    return h[:N]


def frontier_step(
    adj: jax.Array,
    f: jax.Array,
    eligible: jax.Array,
    visited: jax.Array,
    T: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Masked BFS hop; pads N to tile and R to 128 lanes."""
    N, R = f.shape
    Rp = max(128, _pad_to(R, 128))
    Tp, Np = _tile_dims(N, T)
    interpret = _interpret(interpret)
    adj_p = _pad_dense_adj(adj, N, Np)
    f_p = jnp.zeros((Np, Rp), jnp.bfloat16).at[:N, :R].set(f.astype(jnp.bfloat16))
    e_p = jnp.zeros((Np,), jnp.int32).at[:N].set(eligible.astype(jnp.int32))
    v_p = jnp.zeros((Np, Rp), jnp.int32).at[:N, :R].set(
        visited.astype(jnp.int32))
    nxt = _frontier_pallas(adj_p, f_p, e_p, v_p, T=Tp, interpret=interpret)
    return nxt[:N, :R]


@functools.partial(
    jax.jit,
    static_argnames=("kind", "K", "T", "interpret", "max_steps"))
def _coreness_fused(mat_p, est0_p, mask_p, kind, K, T, interpret, max_steps):
    """Fused min-H fixpoint: the backend kernel inside ONE while_loop.

    mat_p is the padded bf16 adjacency (kind="dense") or the padded ELL
    neighbor lists (kind="ell") — the only thing the two kernel paths
    disagree on; everything else (clamp, convergence, step counting) is
    shared here so the fixpoint semantics cannot diverge per backend.
    """

    def h_of(est):
        if kind == "dense":
            return _hindex_pallas(mat_p, est, K=K, T=T, interpret=interpret)
        return _hindex_ell_pallas(mat_p, est, K=K, T=T, interpret=interpret)

    def cond(c):
        _, changed, it = c
        return changed & (it < max_steps)

    def body(c):
        est, _, it = c
        new = jnp.where(mask_p, jnp.minimum(est, h_of(est)), est)
        return new, jnp.any(new != est), it + 1

    est, _, steps = jax.lax.while_loop(
        cond, body, (est0_p, jnp.bool_(True), jnp.int32(0)))
    return est, steps


def _run_fused_coreness(mat, est0, mask, N, kind, K, T, interpret, max_steps):
    """Pad once (host boundary), run the fused fixpoint: (est[:N], steps)."""
    Tp, Np = _tile_dims(N, T)
    est0_p = jnp.zeros((Np,), jnp.int32).at[:N].set(est0)
    mask_p = jnp.zeros((Np,), bool).at[:N].set(mask)
    if kind == "dense":
        mat_p, Kk = _pad_dense_adj(mat, N, Np), max(128, _pad_to(K, 128))
    else:
        mat_p, Kk, Tp, Np = _pad_ell(mat, K, T)
    est_p, steps = _coreness_fused(
        mat_p, est0_p, mask_p, kind=kind, K=Kk, T=Tp, interpret=interpret,
        max_steps=max_steps)
    return est_p[:N], steps


def coreness_dense(
    adj: jax.Array,
    T: int = 256,
    max_steps: int = 10_000,
    interpret: Optional[bool] = None,
    with_steps: bool = False,
) -> Union[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full coreness via the kernelized min-H iteration (dense path).

    Matches `ref.coreness_dense_ref` and `core.kcore.coreness` exactly.
    The whole fixpoint is ONE jitted `lax.while_loop` (zero per-superstep
    host transfers); the only sync is the once-per-call degree-bound read
    for the threshold count K (pow2-bucketed for compile-cache stability).
    `with_steps=True` additionally returns the superstep count as a device
    scalar.
    """
    N = adj.shape[0]
    deg = jnp.sum(adj > 0, axis=1).astype(jnp.int32)
    K = _pow2_bucket(int(jax.device_get(jnp.max(deg))) + 1 if N else 1)
    interpret = _interpret(interpret)
    est, steps = _run_fused_coreness(
        adj, deg, jnp.ones((N,), bool), N, "dense", K, T, interpret, max_steps)
    return (est, steps) if with_steps else est


# ---------------------------------------------------------------------------
# ELL-path wrappers (pad N to tile, Cd and R to 128 lanes).
# ---------------------------------------------------------------------------


def _pad_ell(nbr: jax.Array, K: Optional[int], T: int):
    """Pad an ELL adjacency for the kernels: (nbr_p, Ck, Tp, Np).

    K=None keeps the always-safe padded-Cd column bound; a max-degree K
    (left-filled rows, see `degree_bound`) shrinks the columns the kernels
    gather and reduce to `_ell_cols(K)` (at most the padded Cd) — the
    pow2 bucketing upstream keeps Ck stable across maintenance streams.
    The fused `ell` fixpoint pads through `_hybrid_ell`, which cuts the
    padded rows further into a narrow head and a tail of wide rows.
    """
    N, Cd = nbr.shape
    Cdp = max(128, _pad_to(Cd, 128))
    Ck = Cdp if K is None else min(Cdp, _ell_cols(K))
    Tp, Np = _tile_dims(N, T)
    Cc = min(Cd, Ck)  # source columns that can hold valid slots
    nbr_p = jnp.full((Np, Ck), -1, jnp.int32).at[:N, :Cc].set(
        nbr[:, :Cc].astype(jnp.int32))
    return nbr_p, Ck, Tp, Np


@functools.partial(jax.jit,
                   static_argnames=("cols", "head_cols", "tail_padded", "T"))
def _hybrid_ell(nbr: jax.Array, cols: int, head_cols: int, tail_padded: int,
                T: int = 256) -> HybridEll:
    """The `HybridEll` operand of a `hybrid_split` (its `cols`,
    `head_cols`, `tail_padded`): `nbr` padded to tiles and `cols`
    columns, its first `head_cols` columns as the head, and the rows
    whose fill is wider than that as the tail, in row order.  Tail pad
    entries hold no slots and the row id N, which names no output row."""
    N = nbr.shape[0]
    nbr_p = _pad_ell(nbr, cols, T)[0]
    if not tail_padded:
        return HybridEll(nbr_p)
    wide = jnp.sum(nbr_p[:N] >= 0, axis=1) > head_cols
    rows = jnp.nonzero(wide, size=tail_padded, fill_value=N)[0]
    rows = rows.astype(jnp.int32)
    tail = nbr_p.at[rows].get(mode="fill", fill_value=-1)
    return HybridEll(nbr_p[:, :head_cols], tail, rows)


def hindex_ell(
    nbr: jax.Array,
    est: jax.Array,
    T: int = 256,
    interpret: Optional[bool] = None,
    K: Optional[int] = None,
) -> jax.Array:
    """h-index per node via the ELL block-sparse kernel — O(N*Cd) memory.

    K (optional) is the max-degree column bound; exactness for K < Cd
    requires left-filled rows (`GraphBlocks`).
    """
    N, Cd = nbr.shape
    interpret = _interpret(interpret)
    nbr_p, Ck, Tp, Np = _pad_ell(nbr, K, T)
    est_p = jnp.full((Np,), -1, jnp.int32).at[:N].set(est.astype(jnp.int32))
    h = _hindex_ell_pallas(nbr_p, est_p, K=Ck, T=Tp, interpret=interpret)
    return h[:N]


def frontier_step_ell(
    nbr: jax.Array,
    f: jax.Array,
    eligible: jax.Array,
    visited: jax.Array,
    T: int = 256,
    interpret: Optional[bool] = None,
    K: Optional[int] = None,
) -> jax.Array:
    """Masked BFS hop over the ELL adjacency; eligible is (N, R) per-column.

    K (optional) bounds the neighbor columns swept, like `hindex_ell`,
    down to one 128-lane chunk (the kernel folds whole lane chunks).
    """
    N, Cd = nbr.shape
    R = f.shape[1]
    interpret = _interpret(interpret)
    nbr_p, Ck, Tp, Np = _pad_ell(nbr, None if K is None else max(K, 128), T)

    def pad(x):
        return jnp.zeros((Np, R), bool).at[:N].set(x.astype(bool))

    nxt = _frontier_ell_pallas(nbr_p, pad(f), pad(eligible), pad(visited),
                               K=Ck, T=Tp, interpret=interpret)
    return nxt[:N]


def neighbor_min_ell(
    nbr: jax.Array,
    field: jax.Array,
    T: int = 256,
    interpret: Optional[bool] = None,
    K: Optional[int] = None,
) -> jax.Array:
    """Row-wise min of neighbor field values via the ELL kernel.

    nbr: (N, Cd) int32 (-1 padded); field: (N,) int32.  Neighborless rows
    return int32 max (the min combine's absorbing fill).  K optionally
    bounds the swept columns (left-filled rows, see `degree_bound`).
    """
    N, _ = nbr.shape
    interpret = _interpret(interpret)
    nbr_p, Ck, Tp, Np = _pad_ell(nbr, K, T)
    field_p = jnp.full((Np,), MIN_FILL, jnp.int32).at[:N].set(
        field.astype(jnp.int32))
    red = _min_ell_pallas(nbr_p, field_p, K=Ck, T=Tp, interpret=interpret)
    return red[:N]


def neighbor_sum_ell(
    nbr: jax.Array,
    field: jax.Array,
    T: int = 256,
    interpret: Optional[bool] = None,
    K: Optional[int] = None,
) -> jax.Array:
    """Row-wise float32 sum of neighbor field values via the ELL kernel.

    nbr: (N, Cd) int32 (-1 padded); field: (N,) float32.  Neighborless
    rows return 0.0.  K optionally bounds the swept columns.
    """
    N, _ = nbr.shape
    interpret = _interpret(interpret)
    nbr_p, Ck, Tp, Np = _pad_ell(nbr, K, T)
    field_p = jnp.zeros((Np,), jnp.float32).at[:N].set(
        field.astype(jnp.float32))
    red = _sum_ell_pallas(nbr_p, field_p, K=Ck, T=Tp, interpret=interpret)
    return red[:N]


def neighbor_common_ell(
    nbr: jax.Array,
    rows: jax.Array,
    T: int = 256,
    interpret: Optional[bool] = None,
    K: Optional[int] = None,
    variant: str = "merge",
) -> jax.Array:
    """Directed common-neighbor counts via the ELL intersection kernel.

    nbr, rows: (N, Cd) int32 (-1 padded) — the adjacency swept and the
    per-node row field intersected (identical for whole-graph use).
    Returns (N,) int32: red[u] = sum_j |rows[u] ∩ rows[nbr[u, j]]|.
    K bounds BOTH column axes (left-filled rows required for K < Cd).

    variant="merge" (default) is the O(N*Cd^2*log Cd) sorted binary-probe
    intersection — the kernel canonicalizes the row field on the way in
    (a no-op under the sorted-ELL invariant), so it is exact for ANY slot
    order; "allpairs" keeps the legacy O(N*Cd^3) match for the benchmark
    sweep.  Both are bit-identical to `ref.ell_common_ref`.
    """
    N, _ = nbr.shape
    interpret = _interpret(interpret)
    K = None if K is None else max(K, 128)  # whole lane chunks
    nbr_p, Ck, Tp, Np = _pad_ell(nbr, K, T)
    rows_p, _, _, _ = _pad_ell(rows, K, T)
    red = _common_ell_pallas(nbr_p, rows_p, K=Ck, T=Tp, interpret=interpret,
                             variant=variant)
    return red[:N]


def neighbor_multi_ell(
    nbr: jax.Array,
    fields: Tuple[jax.Array, ...],
    combines: Tuple[str, ...],
    T: int = 256,
    interpret: Optional[bool] = None,
    K: Optional[int] = None,
) -> Tuple[jax.Array, ...]:
    """Fused multi-field neighbor reduce — ONE adjacency read for k fields.

    nbr: (N, Cd) int32 (-1 padded); fields: one (N,) vector per combine;
    combines: static tuple from `MULTI_COMBINES`.  Pads once and serves
    every field's gather + reduce off the shared neighbor-slot indices
    (`ell_multi.py`); each output is bit-identical to its standalone
    kernel.  K optionally bounds the swept columns (left-filled rows).
    """
    nbr_p = _pad_ell(nbr, K, T)[0]
    return _multi_ell_padded(nbr_p, nbr.shape[0], tuple(fields),
                             tuple(combines), _interpret(interpret), T)


def _multi_ell_padded(nbr_p, N: int, fields, combines, interpret: bool,
                      T: int = 256) -> Tuple[jax.Array, ...]:
    """`neighbor_multi_ell` on an adjacency `_pad_ell` already padded (a
    fixpoint pads once, outside its loop): pads the (N,) fields to the
    padded rows, reduces every field, slices back to N."""
    Np, Ck = nbr_p.shape
    Tp, _ = _tile_dims(N, T)
    fills = {"min": MIN_FILL, "sum": 0.0, "hindex": -1}
    dtypes = {"min": jnp.int32, "sum": jnp.float32, "hindex": jnp.int32}
    fields_p = tuple(
        jnp.full((Np,), fills[c], dtypes[c]).at[:N].set(f.astype(dtypes[c]))
        for c, f in zip(combines, fields))
    reds = _multi_ell_pallas(
        nbr_p, fields_p, combines, K=Ck, T=Tp, interpret=interpret)
    return tuple(r[:N] for r in reds)


def _multi_ell_hybrid(adj: HybridEll, N: int, fields, combines,
                      interpret: bool, T: int = 256) -> Tuple[jax.Array, ...]:
    """`_multi_ell_padded` over a `HybridEll`: every row reduces its head
    columns, then each tail row's output is overwritten by the reduction
    of its whole row, so min, sum and hindex stay exact (tail pad entries
    name row N and are dropped)."""
    reds = _multi_ell_padded(adj.head, N, fields, combines, interpret, T)
    if adj.tail is None:
        return reds
    tails = _multi_ell_pallas(adj.tail, tuple(fields), combines,
                              K=adj.tail.shape[1], T=T, interpret=interpret)
    return tuple(r.at[adj.rows].set(t, mode="drop")
                 for r, t in zip(reds, tails))


# ---------------------------------------------------------------------------
# GraphBlocks-level dispatch — the only entry points core code may use.
# ---------------------------------------------------------------------------


def hindex_blocks(
    g,  # GraphBlocks (duck-typed: .nbr, .N, .Cd)
    est: jax.Array,
    backend: str = "auto",
    interpret: Optional[bool] = None,
    adj: Optional[jax.Array] = None,
    executor=None,
    K: Optional[int] = None,
) -> jax.Array:
    """h-index of neighbor estimates for every node, via the chosen backend.

    g: a GraphBlocks (N = P*Cn padded rows, nbr (N, Cd) int32 with -1
    PAD); est: (N,) int32 current estimates.  Returns (N,) int32 —
    h[u] = h-index of {est[v] : v ~ u}, 0 for neighborless rows.

    All backends are exact and identical (h <= deg <= Cd, so the static
    threshold bound K = Cd keeps the kernel paths jit-safe; fixpoints pass
    the tighter `degree_bound` via K).  Loops that call the dense backend
    repeatedly should densify once and pass `adj` (see `dense_adj`); loops
    on the mesh backend should build one `SpmdExecutor` and pass it via
    `executor=` instead of paying a halo-plan rebuild per call.
    """
    b = resolve_backend(backend, g.N)
    if b == "jnp":
        return ref.ell_hindex_ref(g.nbr, est).astype(jnp.int32)
    if b == "ell":
        return hindex_ell(g.nbr, est, interpret=interpret, K=K)
    if b == "ell_spmd":
        from ..runtime.spmd import hindex_spmd  # lazy: no import cycle

        return hindex_spmd(g, est, executor=executor)
    if adj is None:
        adj = ref.ell_to_dense(g.nbr, g.N)
    return hindex(adj, est, K=g.Cd + 1 if K is None else K,
                  interpret=interpret)


def _eligible_cols(eligible: jax.Array, R: int) -> jax.Array:
    """Broadcast a shared (N,) eligibility mask to the (N, R) column form."""
    if eligible.ndim == 1:
        return jnp.broadcast_to(eligible[:, None], (eligible.shape[0], R))
    return eligible


def dense_adj(g, backend: str) -> Optional[jax.Array]:
    """Densify once for a loop over dense-backend calls; None otherwise."""
    if resolve_backend(backend, g.N) == "dense":
        return ref.ell_to_dense(g.nbr, g.N)
    return None


def frontier_blocks(
    g,  # GraphBlocks (duck-typed)
    f: jax.Array,
    eligible: jax.Array,
    visited: jax.Array,
    backend: str = "auto",
    interpret: Optional[bool] = None,
    adj: Optional[jax.Array] = None,
    executor=None,
    K: Optional[int] = None,
) -> jax.Array:
    """One masked BFS hop for R stacked frontiers, via the chosen backend.

    f, visited: (N, R) bool; eligible: (N,) shared or (N, R) per-column.
    Returns the next frontier as (N, R) bool.  As with `hindex_blocks`,
    pass a precomputed `adj` when looping over dense-backend hops and a
    long-lived `executor` when looping on the mesh backend.
    """
    R = f.shape[1]
    elig = _eligible_cols(eligible, R)
    b = resolve_backend(backend, g.N)
    if b == "jnp":
        return ref.ell_frontier_hop_ref(g.nbr, f, elig, visited)
    if b == "ell":
        return frontier_step_ell(
            g.nbr, f, elig, visited, interpret=interpret, K=K) > 0
    if b == "ell_spmd":
        from ..runtime.spmd import frontier_spmd  # lazy: no import cycle

        return frontier_spmd(g, f, elig, visited, executor=executor)
    # dense kernel takes a shared (N,) eligibility; fold the per-column mask
    # into `visited` (a node ineligible for column r can never enter it).
    if adj is None:
        adj = ref.ell_to_dense(g.nbr, g.N)
    vis_aug = visited.astype(bool) | ~elig.astype(bool)
    ones = jnp.ones((g.N,), bool)
    return frontier_step(adj, f, ones, vis_aug, interpret=interpret) > 0


@functools.partial(jax.jit, static_argnames=("max_steps",))
def _coreness_blocks_jnp(g, max_steps: int = 10_000):
    est0 = jnp.where(g.node_mask, g.deg, 0).astype(jnp.int32)

    def cond(c):
        est, changed, it = c
        return changed & (it < max_steps)

    def body(c):
        est, _, it = c
        h = ref.ell_hindex_ref(g.nbr, est)
        new = jnp.where(g.node_mask, jnp.minimum(est, h), est)
        return new, jnp.any(new != est), it + 1

    est, _, steps = jax.lax.while_loop(
        cond, body, (est0, jnp.bool_(True), jnp.int32(0)))
    return est, steps


def coreness_blocks(
    g,  # GraphBlocks (duck-typed)
    backend: str = "auto",
    max_steps: int = 10_000,
    interpret: Optional[bool] = None,
    executor=None,
    with_steps: bool = False,
) -> Union[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full min-H coreness of every node (0 on padding rows), any backend.

    g: GraphBlocks; returns (N,) int32 coreness (N = P*Cn padded rows),
    plus the superstep count as a device int32 scalar when
    `with_steps=True`.

    Every backend runs the whole fixpoint device-resident — one jitted
    `lax.while_loop` (with the Pallas kernel in the body on dense/ell, or
    the shard_map'd halo-exchange loop on ell_spmd) — so there are ZERO
    per-superstep host transfers; the only sync is the once-per-call
    `degree_bound` read on the kernel paths.  `with_steps=True` returns
    (coreness, supersteps) with the count as a device scalar.
    """
    b = resolve_backend(backend, g.N)
    if b == "jnp":
        est, steps = _coreness_blocks_jnp(g, max_steps)
        return (est, steps) if with_steps else est
    if b == "ell_spmd":
        from ..runtime.spmd import SpmdExecutor  # lazy: no import cycle

        ex = executor if executor is not None else SpmdExecutor(g)
        est, steps = ex.coreness(max_steps=max_steps)
        return (est, steps) if with_steps else est
    interpret = _interpret(interpret)
    K = degree_bound(g)  # the single host sync of the whole fixpoint
    est0 = jnp.where(g.node_mask, g.deg, 0).astype(jnp.int32)
    mat = ref.ell_to_dense(g.nbr, g.N) if b == "dense" else g.nbr
    est, steps = _run_fused_coreness(
        mat, est0, g.node_mask, g.N, b, K, 256, interpret, max_steps)
    return (est, steps) if with_steps else est


# ---------------------------------------------------------------------------
# BlockProgram execution: the generic fused superstep runner.  The program
# contract itself lives in `core.engine.BlockProgram` (which imports the
# context type from here — kernels never import core); the workloads live
# in `core.algorithms`.
# ---------------------------------------------------------------------------


class BlockCtx(NamedTuple):
    """Per-node context handed to `BlockProgram.update`.

    The same program code runs over the whole graph (jnp/dense/ell
    backends: ``n = N = P*Cn`` rows) or over one worker's shard of it
    (ell_spmd: ``n = S = N/W`` rows) — update math must therefore be
    elementwise/broadcast over the leading node axis and may reduce only
    through the values it is handed.

    Attributes
    ----------
    deg:       (n,) int32 — true degree per node (0 on padding rows).
    node_mask: (n,) bool  — True for real nodes.
    n_real:    int        — GLOBAL real-node count (static host int; e.g.
                            the PageRank teleport denominator).
    """

    deg: jax.Array
    node_mask: jax.Array
    n_real: int


def _combine_jnp(nbr: jax.Array, field: jax.Array, combine: str) -> jax.Array:
    """Whole-graph gather + reduce, pure jnp (the oracle execution)."""
    if combine == "min":
        return ref.ell_min_ref(nbr, field)
    if combine == "sum":
        return ref.ell_sum_ref(nbr, field)
    if combine == "hindex":
        return ref.ell_hindex_ref(nbr, field).astype(jnp.int32)
    if combine == "count_common":
        return ref.ell_common_ref(nbr, field)
    raise ValueError(f"unknown combine {combine!r}; expected one of {COMBINES}")


def _combine_ell(nbr: jax.Array, field: jax.Array, combine: str,
                 interpret: Optional[bool], K: Optional[int]) -> jax.Array:
    """Whole-graph gather + reduce via the ELL Pallas kernels."""
    if combine == "min":
        return neighbor_min_ell(nbr, field, interpret=interpret, K=K)
    if combine == "sum":
        return neighbor_sum_ell(nbr, field, interpret=interpret, K=K)
    if combine == "hindex":
        return hindex_ell(nbr, field, interpret=interpret, K=K)
    if combine == "count_common":
        return neighbor_common_ell(nbr, field, interpret=interpret, K=K)
    raise ValueError(f"unknown combine {combine!r}; expected one of {COMBINES}")


def _combine_dense(adj: jax.Array, field: jax.Array, combine: str,
                   Cd: int) -> jax.Array:
    """Dense-adjacency formulations of the combines (adj: (N, N) 0/1).

    min  — masked elementwise min over the adjacency row.
    sum  — the classic SpMV as an MXU matmul: adj @ field.
    hindex — threshold-count matmul (`ref.hindex_counts_ref`, K = Cd + 1:
             exact because h <= deg <= Cd).
    count_common — diag(A^3) as sum(A ∘ A², axis=1): red[u] counts every
             ordered common-neighbor pair at u, identical to the ELL
             intersection.
    """
    if combine == "min":
        fill = jnp.iinfo(jnp.int32).max
        vals = jnp.where(adj > 0, field[None, :].astype(jnp.int32), fill)
        return jnp.min(vals, axis=1)
    if combine == "sum":
        return adj.astype(jnp.float32) @ field.astype(jnp.float32)
    if combine == "hindex":
        return ref.hindex_counts_ref(adj, field, K=Cd + 1)
    if combine == "count_common":
        a = (adj > 0).astype(jnp.float32)
        return jnp.sum(a * (a @ a), axis=1).astype(jnp.int32)
    raise ValueError(f"unknown combine {combine!r}; expected one of {COMBINES}")


# ---------------------------------------------------------------------------
# Fused multi-combine executions (MultiProgram: one adjacency read serves
# every sub-program's gather) + the trace-time gather accounting that
# proves it.
# ---------------------------------------------------------------------------

#: how many adjacency-gather dispatches the program runner has TRACED (not
#: executed): `_block_program_fused` bumps it once per `red_of` trace, so
#: lowering a fused MultiProgram superstep counts 1 where lowering its k
#: sub-programs separately counts k.  Python-side and monotonic; tests
#: snapshot around an explicit `.lower()` (jit cache hits do not retrace,
#: hence do not count).
_GATHER_TRACES = 0


def _count_gather() -> None:
    global _GATHER_TRACES
    _GATHER_TRACES += 1


def gather_trace_count() -> int:
    """Adjacency-gather dispatches traced so far (see `_GATHER_TRACES`)."""
    return _GATHER_TRACES


#: the `HybridSplit` of the last fused `ell` adjacency `run_block_program`
#: built (None before the first): how far the head/tail split engages
_LAST_SPLIT: Optional[HybridSplit] = None


def last_hybrid_split() -> Optional[HybridSplit]:
    """Head width, real and padded tail rows, and slots gathered per field
    per superstep of the last fused `ell` adjacency (`_LAST_SPLIT`)."""
    return _LAST_SPLIT


def _combine_multi_jnp(nbr: jax.Array, fields, combines) -> Tuple:
    """Shared-gather multi reduce, pure jnp: one clip/validity, k takes."""
    valid = nbr >= 0
    idx = jnp.clip(nbr, 0)
    outs = []
    for c, f in zip(combines, fields):
        if c == "min":
            vals = jnp.where(valid, f.astype(jnp.int32)[idx], MIN_FILL)
            outs.append(jnp.min(vals, axis=1))
        elif c == "sum":
            vals = jnp.where(valid, f.astype(jnp.float32)[idx], 0.0)
            outs.append(jnp.sum(vals, axis=1))
        elif c == "hindex":
            vals = jnp.where(valid, f.astype(jnp.int32)[idx], -1)
            outs.append(ref.hindex_rows(vals).astype(jnp.int32))
        else:
            raise ValueError(
                f"combine {c!r} not fusable; expected one of {MULTI_COMBINES}")
    return tuple(outs)


def _combine_multi_dense(adj: jax.Array, fields, combines, Cd: int) -> Tuple:
    """Dense multi reduce: per-combine dense forms over one resident adj.

    The dense adjacency is already materialized once for the whole
    fixpoint, so "one adjacency read" is the resident (N, N) operand —
    each combine is a separate reduction over it.
    """
    return tuple(
        _combine_dense(adj, f, c, Cd) for c, f in zip(combines, fields))


def neighbor_combine_blocks(
    g,  # GraphBlocks (duck-typed: .nbr, .N, .Cd)
    field: jax.Array,
    combine: str,
    backend: str = "auto",
    interpret: Optional[bool] = None,
    adj: Optional[jax.Array] = None,
    K: Optional[int] = None,
) -> jax.Array:
    """One gather + reduce superstep of a named combine, via any backend.

    field: (N,) values for "min"/"sum"/"hindex", (N, Cd) neighbor rows for
    "count_common".  Loops over the dense backend should densify once and
    pass `adj` (see `dense_adj`).  The ell_spmd backend has no standalone
    combine entry — its reductions only exist downstream of a halo
    exchange; use `run_block_program(backend="ell_spmd")`.
    """
    b = resolve_backend(backend, g.N)
    if b == "jnp":
        return _combine_jnp(g.nbr, field, combine)
    if b == "ell":
        return _combine_ell(g.nbr, field, combine, interpret, K)
    if b == "ell_spmd":
        raise ValueError(
            "neighbor_combine_blocks has no ell_spmd path: mesh combines "
            "only exist inside a halo-exchange superstep — run the whole "
            "program via run_block_program(backend='ell_spmd')."
        )
    if adj is None:
        adj = ref.ell_to_dense(g.nbr, g.N)
    return _combine_dense(adj, field, combine, g.Cd)


def _mirror_merge(red, field, nbr, mirror, combine: str) -> jax.Array:
    """Merge per-slice partial aggregates across each hub replica group.

    The combine-then-broadcast stage of the vertex-cut dataflow
    (`core.hub_split`): entries of `red` at group rows are replaced by
    the LOGICAL aggregate of the full sliced neighborhood; all other
    rows pass through untouched.  Per combine:

      min    — segmented min over the group's partials (exactly
               associative: the slices partition the neighborhood, so
               the merged min sees the identical value multiset).
      sum    — segmented add (bit-exact for ints; float PageRank sums
               re-associate across slices — allclose, not bit-equal).
      hindex — partials do NOT compose through h values; the merge
               counts the slices' values at or above a threshold, which
               ADD exactly across slices, and finds each group's h,
               capped at the group's estimate (the min-H update takes
               min(est, h) anyway), by searching the threshold
               (`merged_hindex`).

    Pure device code; the scatter targets of pad entries are pushed out
    of bounds (dropped) so a pad row id can never collide with a real
    group row's write.
    """
    rows, gid, G = mirror.grp_rows, mirror.grp_gid, mirror.Gmax
    live = gid < G
    if combine == "min":
        fill = jnp.iinfo(red.dtype).max
        vals = jnp.where(live, red[rows], fill)
        part = jnp.full((G + 1,), fill, red.dtype).at[gid].min(vals)
        out = part[gid]
    elif combine == "sum":
        vals = jnp.where(live, red[rows], jnp.zeros((), red.dtype))
        part = jnp.zeros((G + 1,), red.dtype).at[gid].add(vals)
        out = part[gid]
    elif combine == "hindex":
        rn = nbr[rows]
        f = field.astype(jnp.int32)
        ve = jnp.where(rn >= 0, f[jnp.clip(rn, 0)], -1)
        out = merged_hindex(ve, live, gid, f[rows], G,
                            mirror.Km).astype(red.dtype)
    else:
        raise ValueError(
            f"combine {combine!r} has no mirror merge; count_common routes "
            "through core.hub_split.run_common_mirror")
    tgt = jnp.where(live, rows, red.shape[0])  # OOB scatter drops pad writes
    return red.at[tgt].set(jnp.where(live, out, jnp.zeros((), red.dtype)))


def merged_hindex(vals, live, gid, cap, G: int, Km: int, total=None):
    """h-index of each replica group's whole neighbourhood, exactly.

    vals: (R, C) int32 — the neighbour values of each group entry's
    slice (-1 on pads); live: (R,) bool — real entries; gid: (R,) int32
    — the entry's group in [0, G) (G on pads); cap: (R,) int32 — a
    bound on the answer, equal on all of a group's entries; Km: a bound
    on every group's h (its logical degree's pow2 bucket).
    h = max{t : #{values >= t} >= t} over the union of the group's
    slices; the count at one threshold is a sum over slices, so each
    group searches its own threshold, each round counting every slice's
    values at or above the group's candidate and summing them per group
    in a (G + 1,) table.
    ``total`` merges that table across workers (a psum on the mesh;
    nothing on one device).  No array is Km wide.

    The result is min(h, cap), which is all the min-H update
    (``min(est, h)``) needs when ``cap`` is the estimate itself (a cap
    of Km gives h).  The first round tests the upper end of each group's
    range, min(cap, Km), then the range is bisected; the rounds stop
    once every group's range has closed (a scalar merged by ``total``),
    at most ``Km.bit_length()`` of them.  A converged estimate, whose
    neighbourhood's h is at least itself, costs one round.  Returns the
    (R,) result of each entry's group.
    """
    merge = (lambda x: x) if total is None else total
    hi = jnp.clip(cap.astype(jnp.int32), 0, Km)

    def round_(c):
        i, lo, hi, _ = c
        mid = jnp.where(i == 0, hi, (lo + hi + 1) // 2)
        cnt = jnp.sum(vals >= mid[:, None], axis=1, dtype=jnp.int32)
        tab = jnp.zeros((G + 1,), jnp.int32).at[gid].add(
            jnp.where(live, cnt, 0))
        ok = merge(tab)[gid] >= mid
        lo, hi = jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)
        open_ = merge(jnp.sum(live & (lo < hi), dtype=jnp.int32))
        return i + 1, lo, hi, open_ > 0

    _, lo, _, _ = jax.lax.while_loop(
        lambda c: c[3] & (c[0] < int(Km).bit_length()), round_,
        (jnp.int32(0), jnp.zeros(gid.shape, jnp.int32), hi, jnp.bool_(True)))
    return lo


def _mirror_merged(red, field, nbr, mirror, program):
    """Apply `_mirror_merge` per field of a (possibly multi-) program,
    under the named scope ``mirror_merge``."""
    with jax.named_scope("mirror_merge"):
        if program.combine == "multi":
            return tuple(
                _mirror_merge(r, f, nbr, mirror, c)
                for r, f, c in zip(red, field, program.combines))
        return _mirror_merge(red, field, nbr, mirror, program.combine)


@functools.partial(
    jax.jit, static_argnames=("program", "b", "interpret", "max_steps",
                              "n_real"))
def _block_program_fused(g, state0, adj, mirror, program, b: str,
                         interpret: bool, max_steps: int, n_real: int):
    """The generic fused fixpoint: program supersteps in ONE while_loop.

    The loop body is (halo field -> backend combine -> block-local update
    -> local halt verdict); nothing inside touches the host, so a run
    costs ZERO per-superstep transfers on every backend and the superstep
    count comes back as a device scalar, exactly like the dedicated
    coreness fixpoints of PR 4.

    `mirror` (a `core.hub_split.MirrorPlan` or None) arms the vertex-cut
    dataflow: the update ctx carries the LOGICAL degrees and real-node
    count, and a `_mirror_merge` stage between combine and update folds
    per-slice partials into per-vertex aggregates.  The plan rides as a
    jit OPERAND (its statics are treedef metadata), so single-device
    mirrored streams never recompile on plan content changes.
    """
    deg = g.deg if mirror is None else mirror.ldeg
    ctx = BlockCtx(deg=jnp.asarray(deg, jnp.int32), node_mask=g.node_mask,
                   n_real=n_real)

    def red_of(field):
        _count_gather()  # trace-time accounting: 1 per fused dispatch
        if program.combine == "multi":
            if b == "jnp":
                return _combine_multi_jnp(g.nbr, field, program.combines)
            if b == "ell":
                return _multi_ell_hybrid(adj, g.N, field, program.combines,
                                         interpret)
            return _combine_multi_dense(adj, field, program.combines, g.Cd)
        if b == "jnp":
            return _combine_jnp(g.nbr, field, program.combine)
        if b == "ell" and program.combine in MULTI_COMBINES:
            return _multi_ell_hybrid(adj, g.N, (field,), (program.combine,),
                                     interpret)[0]
        if b == "ell":
            return _combine_ell(g.nbr, field, program.combine, interpret,
                                None)
        return _combine_dense(adj, field, program.combine, g.Cd)

    def cond(c):
        _, changed, it = c
        return changed & (it < max_steps)

    def body(c):
        state, _, it = c
        field = program.halo_field(state)
        red = red_of(field)
        if mirror is not None:
            red = _mirror_merged(red, field, g.nbr, mirror, program)
        new = program.update(ctx, state, red)
        return new, program.changed(state, new), it + 1

    state, _, steps = jax.lax.while_loop(
        cond, body, (state0, jnp.bool_(True), jnp.int32(0)))
    return state, steps


def _mirror_init_view(g, mirror):
    """Logical facade for `program.init` under a mirrored run.

    init formulas read degrees and the real-node mask (e.g. PageRank's
    1/deg contributions and teleport mass); on a split graph the LOGICAL
    quantities live in the plan, so init sees them through a replaced
    view — then `mirror_state` replicates the per-primary values onto
    mirror rows so replicas start (and stay) in lockstep.
    """
    import dataclasses as _dc
    return _dc.replace(g, deg=mirror.ldeg, node_mask=mirror.primary_mask)


def _mirror_state0(program, state0, mirror):
    """Replicate a whole-graph state onto mirror rows (idempotent)."""
    rep = getattr(program, "mirror_state", None)
    if rep is not None:
        return rep(state0, mirror.primary_row)
    return jax.tree_util.tree_map(lambda a: a[mirror.primary_row], state0)


def run_block_program(
    g,  # GraphBlocks (duck-typed)
    program,  # core.engine.BlockProgram (hashable static)
    backend: str = "auto",
    max_steps: Optional[int] = None,
    interpret: Optional[bool] = None,
    executor=None,
    with_steps: bool = False,
    state0: Optional[Any] = None,
    mirror=None,  # core.hub_split.MirrorPlan for a hub-split graph
) -> Union[Any, Tuple[Any, jax.Array]]:
    """Run a `BlockProgram` to its halt fixpoint, via the chosen backend.

    The structured contract (init → halo field → named combine → update →
    halt reduction) is what makes ONE runner serve every backend: on
    jnp/dense/ell the whole superstep loop fuses into a single jitted
    `lax.while_loop` (`_block_program_fused`); on ell_spmd the identical
    program runs over the worker mesh through `SpmdEngine.run_spmd`'s
    fused loop, with the halo field served by a real W2W all-to-all and
    the halt decision psum'd on-mesh.  Either way: ZERO per-superstep
    host transfers, superstep counts as device scalars.

    Host-boundary entry (like the ell_spmd dispatch paths): `program.init`
    and the real-node count read need concrete arrays — do not call under
    an outer jit trace.  Mesh loops should pass a long-lived
    `SpmdExecutor` via `executor=`; `max_steps=None` takes the program's
    own bound.  Returns the final program state, plus the executed
    superstep count when `with_steps=True`.

    On `ell` the loop's adjacency is a `HybridEll`, built once outside
    the loop after the one `hybrid_split` read: each superstep gathers
    and reduces the head's W columns of every row, then the tail's whole
    C-column rows, whose outputs replace those rows' head outputs.  The
    split is read from the rows' fills, not from `g.deg`, so it holds for
    a mirrored graph's slices too; `last_hybrid_split` reports it.

    `state0` (optional) warm-starts the fixpoint from a caller-supplied
    state instead of `program.init(g)` — the serving path's snapshot
    refresh uses this to resume monotone programs (min-label CC, min-H
    coreness) AT their fixpoint, where one pass through `update` is the
    identity, so maintained fields ride through bit-unchanged while
    fixed-iteration sub-programs (PageRank) still execute.  The caller
    owns the contract that the state matches `program.init`'s structure
    (same pytree, shapes, dtypes).

    `mirror` (optional) declares `g` a hub-split graph and arms the
    vertex-cut dataflow (`core.hub_split`): init runs against the
    logical degree/mask view, the state replicates onto mirror rows
    (`program.mirror_state`), the per-superstep ctx carries logical
    degrees and real-node count, and a merge stage folds per-slice
    partials per replica group between combine and update —
    "count_common" programs route through the exact
    `hub_split.run_common_mirror` pass instead.  Results match the
    unsplit graph exactly (bit-exact for integer combines).
    """
    b = resolve_backend(backend, g.N)
    if program.combine != "multi" and program.combine not in COMBINES:
        raise ValueError(
            f"unknown combine {program.combine!r}; expected one of "
            f"{COMBINES + ('multi',)}")
    if mirror is not None and program.combine == "count_common":
        from ..core.hub_split import run_common_mirror  # lazy: no cycle

        return run_common_mirror(
            g, mirror, program, backend=b, interpret=interpret,
            with_steps=with_steps, state0=state0)
    ms = int(program.max_steps if max_steps is None else max_steps)
    # GraphBlocks property read (duck-typed, host sync) — under a mirror
    # the ctx must carry the LOGICAL vertex count, not the row count.
    n_real = int(g.n_real) if mirror is None else int(mirror.n_logical)
    if state0 is None:
        state0 = program.init(g if mirror is None
                              else _mirror_init_view(g, mirror))
    if mirror is not None:
        state0 = _mirror_state0(program, state0, mirror)
    if b == "ell_spmd":
        from ..runtime.spmd import (  # lazy: no import cycle
            SpmdBlockProgram, SpmdEngine, SpmdExecutor)

        ex = executor if executor is not None else SpmdExecutor(g)
        eng = SpmdEngine(g, executor=ex)
        state, _ = eng.run_spmd(
            SpmdBlockProgram(program, n_real, mirror=mirror), state0, None,
            max_supersteps=ms)
        steps = jnp.int32(len(eng.traces))
        return (state, steps) if with_steps else state
    interpret = _interpret(interpret)
    # the loop's adjacency operand, built once outside it: the dense
    # matrix, or the ELL lists padded to tiles and split into a narrow
    # head and a tail of the few wide rows
    adj = None
    if b == "dense":
        adj = ref.ell_to_dense(g.nbr, g.N)
    elif b == "ell" and program.combine != "count_common":
        global _LAST_SPLIT
        _LAST_SPLIT = split = hybrid_split(g.nbr)
        adj = _hybrid_ell(g.nbr, split.cols, split.head_cols,
                          split.tail_padded)
    state, steps = _block_program_fused(
        g, state0, adj, mirror, program=program, b=b, interpret=interpret,
        max_steps=ms, n_real=n_real)
    return (state, steps) if with_steps else state
