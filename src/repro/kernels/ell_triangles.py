"""Pallas TPU kernel: ELL block-sparse neighbor-row intersection (triangles).

Triangle counting is the "count_common" neighbor combine of the
`BlockProgram` contract: the exchanged field is each node's *neighbor row
itself* ((N, Cd), global ids), and the per-node reduction is

    red[u] = sum_j |N(u) ∩ N(nbr[u, j])|

— the number of ordered (v, w) pairs closing a triangle at u, i.e. twice
the per-node triangle count.  Ids are compared for equality only, so the
global padded ids work unchanged whether the neighbor rows arrive from
the local matrix (this kernel) or from a halo exchange (the ell_spmd
path, where `ref.common_rows` reduces the halo-served (S, Cd, Cd) rows).

Two variants (`VARIANTS`):

``merge`` (default) — exploits the **sorted-ELL invariant** (`core.graph`):
  every row's valid slots ascend with pads on the right, so after keying
  pads to int32-max each row is monotone and membership is a binary
  search.  Per swept slot j the kernel gathers the neighbor's keyed row
  and locates every element of the tile's own rows with ceil(log2 C)
  vectorized lo/hi probe rounds (`take_along_axis` over the (T, C) mid
  matrix) — O(N * Cd^2 * log Cd) work instead of the all-pairs cube, and
  the probes are full-tile vector ops, not scalar loops.  The slot sweep
  early-exits at the highest occupied column of the tile (pad-right rows
  make column occupancy monotone).  The ops.py wrapper re-keys + sorts
  the row field on the way in, which is a no-op permutation under the
  invariant but makes the kernel correct for arbitrary slot orders too.

``allpairs`` — the legacy O(N * Cd^3) formulation: per swept slot a
  (T, C, C) all-pairs id match against the tile's own rows, PAD masked on
  both sides.  Kept as the measuring stick for the merge speedup and as
  the fallback that assumes nothing about slot order.

Both variants: O(N * Cd) memory (never densifies), a max-degree column
bound K < Cd (left-filled rows, `ops.degree_bound`) restricts both the
swept slots and the compared columns.  Validated in interpret mode
against `ref.ell_common_ref`.

No compiled TPU lowering: each swept slot gathers whole neighbor rows
from the (N, C) row matrix inside the kernel, which Mosaic cannot lower
(only 2-D gathers, no value `dynamic_slice`), and moving that gather out
of the kernel would materialize N * C^2 ids.  With `interpret=False` the
entry point raises `NotImplementedError` instead of compiling; the jnp
and ell_spmd backends serve "count_common" on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: intersection variants: sorted binary-probe merge vs legacy all-pairs
VARIANTS = ("merge", "allpairs")

#: key pads sort/compare above every real id (ids are < N <= int32 max)
_PAD_KEY = jnp.iinfo(jnp.int32).max


def _occupied_cols(nbr, C):
    """Highest occupied column + 1 of a (T, C) tile (0 if all-pad)."""
    cols_any = jnp.any(nbr >= 0, axis=0)
    return jnp.max(jnp.where(cols_any, jnp.arange(C, dtype=jnp.int32) + 1, 0))


def _ell_merge_kernel(nbr_ref, own_ref, rows_ref, out_ref, *, C: int, T: int):
    nbr = nbr_ref[...]    # (T, C) int32 neighbor ids, -1 padded
    own = own_ref[...]    # (T, C) int32 keyed sorted rows (pads = _PAD_KEY)
    rows = rows_ref[...]  # (N, C) int32 keyed sorted row matrix
    own_ok = own != _PAD_KEY
    # lower-bound bisect needs the [lo, hi) interval to close to length 0,
    # i.e. ceil(log2 C) + 1 = C.bit_length() rounds for C a power of two
    n_bits = max(1, C.bit_length())

    def body(j, acc):
        col = jax.lax.dynamic_slice(nbr, (0, j), (T, 1))[:, 0]      # (T,)
        v_row = jnp.take(rows, jnp.clip(col, 0), axis=0)             # (T, C)

        # vectorized lower/upper-bound bisect of every own[t, i] in
        # v_row[t, :]; ub - lb = occurrence count, so duplicate ids (legal
        # in raw ELL fields, not in validated graphs) score like all-pairs
        def probe(_, st):
            lb_lo, lb_hi, ub_lo, ub_hi = st
            mid_l = (lb_lo + lb_hi) >> 1
            mv_l = jnp.take_along_axis(v_row, jnp.clip(mid_l, 0, C - 1), axis=1)
            right_l = mv_l < own
            mid_u = (ub_lo + ub_hi) >> 1
            mv_u = jnp.take_along_axis(v_row, jnp.clip(mid_u, 0, C - 1), axis=1)
            right_u = mv_u <= own
            return (
                jnp.where(right_l, mid_l + 1, lb_lo),
                jnp.where(right_l, lb_hi, mid_l),
                jnp.where(right_u, mid_u + 1, ub_lo),
                jnp.where(right_u, ub_hi, mid_u),
            )

        zeros = jnp.zeros((T, C), jnp.int32)
        full = jnp.full((T, C), C, jnp.int32)
        lb, _, ub, _ = jax.lax.fori_loop(
            0, n_bits, probe, (zeros, full, zeros, full))
        occ = jnp.where(own_ok, ub - lb, 0)
        cnt = jnp.sum(occ, axis=1)                                   # (T,)
        return acc + jnp.where(col >= 0, cnt, 0)

    jmax = _occupied_cols(nbr, C)  # early exit: pad-right ⇒ slots ≥ jmax empty
    red = jax.lax.fori_loop(0, jmax, body, jnp.zeros((T,), jnp.int32))
    out_ref[...] = red[:, None]


def _ell_allpairs_kernel(nbr_ref, own_ref, rows_ref, out_ref, *, C: int, T: int):
    nbr = nbr_ref[...]    # (T, C) int32 neighbor ids, -1 padded
    own = own_ref[...]    # (T, C) int32 this tile's exchanged rows
    rows = rows_ref[...]  # (N, C) int32 full row matrix (the field)
    own_ok = own >= 0

    def body(j, acc):
        col = jax.lax.dynamic_slice(nbr, (0, j), (T, 1))[:, 0]      # (T,)
        v_rows = jnp.take(rows, jnp.clip(col, 0), axis=0)           # (T, C)
        match = (
            (own[:, :, None] == v_rows[:, None, :])
            & own_ok[:, :, None]
            & (v_rows >= 0)[:, None, :]
        )
        cnt = jnp.sum(match.astype(jnp.int32), axis=(1, 2))          # (T,)
        return acc + jnp.where(col >= 0, cnt, 0)

    red = jax.lax.fori_loop(0, C, body, jnp.zeros((T,), jnp.int32))
    out_ref[...] = red[:, None]


@functools.partial(
    jax.jit, static_argnames=("K", "T", "interpret", "variant"))
@jax.named_scope("ell_triangles")
def neighbor_common_ell(
    nbr: jax.Array,
    rows: jax.Array,
    K: int,
    T: int = 256,
    interpret: bool = False,
    variant: str = "merge",
) -> jax.Array:
    """Directed common-neighbor counts over the ELL adjacency.

    nbr: (N, Cd) int32 (-1 padded) — the adjacency swept; rows: (N, Cd)
    int32 — the exchanged per-node row field intersected (equal to `nbr`
    for whole-graph execution).  K bounds the columns of BOTH (exact for
    K >= Cd, or K < Cd on left-filled rows).  Returns (N,) int32:
    red[u] = sum_j |rows[u] ∩ rows[nbr[u, j]]| over valid slots j.
    N % T == 0 and Cd, K multiples of 128 (pad via the ops.py wrapper).

    variant="merge" canonicalizes the row field (key pads to int32-max,
    sort ascending — a no-op under the sorted-ELL invariant) and binary-
    probes memberships; "allpairs" is the legacy cubic match.  Counts are
    intersection sizes, so both variants are bit-identical.
    """
    N, Cd = nbr.shape
    assert rows.shape == (N, Cd), (rows.shape, nbr.shape)
    assert N % T == 0, (N, T)
    assert Cd % 128 == 0 and K % 128 == 0, (Cd, K)
    assert variant in VARIANTS, variant
    if not interpret:
        raise NotImplementedError(
            "ell_triangles has no compiled TPU lowering (its per-slot "
            "neighbor-row gather needs the whole (N, Cd) row matrix inside "
            "the kernel); run count_common on backend='jnp' or 'ell_spmd'")
    C = min(Cd, K)
    ni = N // T

    if variant == "merge":
        kernel = functools.partial(_ell_merge_kernel, C=C, T=T)
        field = jnp.sort(
            jnp.where(rows[:, :C] >= 0, rows[:, :C], _PAD_KEY), axis=1)
    else:
        kernel = functools.partial(_ell_allpairs_kernel, C=C, T=T)
        field = rows[:, :C]

    out = pl.pallas_call(
        kernel,
        grid=(ni,),
        in_specs=[
            pl.BlockSpec((T, C), lambda i: (i, 0)),  # neighbor-id row tile
            pl.BlockSpec((T, C), lambda i: (i, 0)),  # own exchanged rows
            pl.BlockSpec((N, C), lambda i: (0, 0)),   # full row matrix
        ],
        out_specs=pl.BlockSpec((T, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(nbr[:, :C], field, field)
    return out[:, 0]
