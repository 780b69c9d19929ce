"""Pallas TPU kernel: ELL block-sparse h-index sweep (k-core hot loop).

The dense-tile kernel in `kcore_hindex.py` materializes an O(N^2) adjacency —
fine for small blocks, fatal at BLADYG scale (the paper's blocks exist
precisely because no worker can hold a dense view).  This kernel consumes the
`GraphBlocks` ELL representation directly:

    nbr[N, Cd] int32   padded neighbor ids (-1 = empty slot)
    est[N]     int32   current coreness estimates

The neighbor gather `vals[u, j] = est[nbr[u, j]]` (PAD slots -> -1) runs in
XLA ahead of the kernel (`ell_row_call`, in node chunks of `ROW_CHUNK`
rows), so the kernel streams (T, C) value tiles and never holds the (N,)
estimate vector in fast memory — at roadNet-CA scale that vector alone
would overflow the TPU's scoped VMEM.  Per row tile of T nodes (grid axis
i), the row h-index

    h[t] = max{k : #{j : vals[t, j] >= k} >= k}

is a vectorized per-row binary search on k, O(C log C): the predicate
cnt(k) >= k is monotone (cnt is non-increasing in k, k increasing), so
ceil(log2(C + 1)) rounds of one (T, C) compare + row count each pin down
the largest k that holds.

Threshold/column bound K: because h(u) <= deg(u) <= Cd, any K >= max degree
is exact *when the rows are left-filled* (valid slots before PAD slots).
The `GraphBlocks` **sorted-ELL invariant** implies left-filling: every
construction/mutation path (`build_blocks`, `insert_edge`'s sorted-position
shift-right, `delete_edge`'s shift-left, `migrate_vertices`' re-sort)
keeps valid slots ascending with pads on the right.  Callers that can
bound the max degree (see `ops.degree_bound`) pass K < Cd and only the
first K neighbor columns are gathered; K = Cd is always safe and assumes
nothing about slot order.

Memory: O(N*K) for the gathered values + O(N) for estimates, vs O(N^2)
for the dense path.  Bit-identical to `ref.ell_hindex_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def check_cols(*cols: int) -> None:
    """Column widths the ELL kernels take: a multiple of 128 lanes, or a
    power of two below 128 (a narrow (T, C) block spans its whole row, so
    a low-degree graph gathers only the slots it can fill)."""
    for c in cols:
        assert c % 128 == 0 or (0 < c < 128 and c & (c - 1) == 0), cols


#: rows gathered per kernel call: the pre-kernel gather materializes one
#: (rows, C) value matrix per field, so chunking the node axis bounds that
#: scratch (256 MiB per int32 field at C = 256) instead of letting it grow
#: with N — at roadNet-CA scale an unchunked fused superstep needs ~9 GB
ROW_CHUNK = 1 << 18


def ell_row_call(kernel, nbr, fields, fills, out_dtypes, T: int,
                 interpret: bool, row_args=(), *, name: str):
    """The ELL family's launch: gather every field through `nbr` in XLA
    (`vals[u, j] = field[nbr[u, j]]`, PAD slots -> that field's fill),
    then run `kernel` over (T, C) row tiles of the gathered values (plus
    (T, k) tiles of each (N, k) `row_args` entry), one (N,) output per
    entry of `out_dtypes`.  The gather keeps the (N,) fields out of the
    kernel's VMEM blocks.  In a profile the gathers sit under the named
    scope ``gather`` and the kernel under `name`, its module's name.

    The gather costs per slot, pads included, so `nbr` should be only as
    wide as its rows need: the fused `ell` fixpoint calls this twice per
    superstep, on a narrow head of every row and on a tail of the few
    wide rows (`ops.hybrid_split`).  The fields are the gather's 1-D
    tables and need not have `nbr`'s row count.

    Nodes are processed in chunks of `ROW_CHUNK` rows inside one
    `fori_loop`; the last chunk's start is clamped to N - rows, so it
    overlaps its predecessor and rewrites identical values.
    """
    N, C = nbr.shape
    rows = min(N, max(T, ROW_CHUNK // T * T))

    def call(nb, *extra):
        ok, idx = nb >= 0, jnp.clip(nb, 0)
        vals = []
        with jax.named_scope("gather"):
            for f, fl in zip(fields, fills):
                # one gather at a time: side by side, XLA stages only one
                # of the (N,) fields in VMEM and the others gather from
                # HBM; serialized, each field gets VMEM for its own gather
                f, idx = jax.lax.optimization_barrier((f, idx))
                vals.append(jnp.where(ok, f[idx], jnp.asarray(fl, f.dtype)))
                idx, vals[-1] = jax.lax.optimization_barrier((idx, vals[-1]))
        with jax.named_scope(name):
            outs = pl.pallas_call(
                kernel,
                grid=(rows // T,),
                in_specs=[pl.BlockSpec((T, C), lambda i: (i, 0))
                          for _ in vals]
                + [pl.BlockSpec((T, a.shape[1]), lambda i: (i, 0))
                   for a in extra],
                out_specs=[pl.BlockSpec((T, 1), lambda i: (i, 0))
                           for _ in out_dtypes],
                out_shape=[jax.ShapeDtypeStruct((rows, 1), d)
                           for d in out_dtypes],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel",)),
                interpret=interpret,
            )(*vals, *extra)
        return tuple(o[:, 0] for o in outs)

    sliced = (nbr,) + tuple(row_args)
    if rows == N:
        return call(*sliced)

    def body(i, outs):
        start = jnp.minimum(i * rows, N - rows)
        got = call(*(jax.lax.dynamic_slice_in_dim(a, start, rows)
                     for a in sliced))
        return tuple(jax.lax.dynamic_update_slice_in_dim(o, g, start, 0)
                     for o, g in zip(outs, got))

    return jax.lax.fori_loop(
        0, -(-N // rows), body,
        tuple(jnp.zeros((N,), d) for d in out_dtypes))


def hindex_bisect(vals: jax.Array) -> jax.Array:
    """Row h-index of a (T, C) value tile by per-row binary search: (T, 1).

    Invariant: cnt(lo) >= lo holds (lo = 0 trivially) and every k > hi
    fails; each round tests mid = ceil((lo + hi) / 2).
    """
    T, C = vals.shape
    lo = jnp.zeros((T, 1), jnp.int32)
    hi = jnp.full((T, 1), C, jnp.int32)
    for _ in range(max(1, C.bit_length())):  # ceil(log2(C + 1)) rounds
        mid = (lo + hi + 1) >> 1
        cnt = jnp.sum((vals >= mid).astype(jnp.int32), axis=1, keepdims=True)
        ok = cnt >= mid
        lo = jnp.where(ok, mid, lo)
        hi = jnp.where(ok, hi, mid - 1)
    return lo


def _ell_hindex_bisect_kernel(vals_ref, out_ref):
    out_ref[...] = hindex_bisect(vals_ref[...])


@functools.partial(jax.jit, static_argnames=("K", "T", "interpret"))
def hindex_ell(
    nbr: jax.Array,
    est: jax.Array,
    K: int,
    T: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """h-index of every node from the ELL adjacency.

    nbr: (N, Cd) int32 (-1 padded), est: (N,) int32, K: threshold/column
    bound — exact iff every row's valid slots lie in the first K columns
    and h <= K (always true for K >= Cd; for max-degree-bounded K < Cd the
    rows must be left-filled, the `GraphBlocks` invariant).  When K < Cd
    only the first K neighbor columns are read.  N must be a multiple of T;
    Cd and K pass `check_cols` (pad via the ops.py wrapper).
    """
    N, Cd = nbr.shape
    assert est.shape == (N,), (est.shape, N)
    assert N % T == 0, (N, T)
    check_cols(Cd, K)
    C = min(Cd, K)  # columns actually read
    (h,) = ell_row_call(_ell_hindex_bisect_kernel, nbr[:, :C],
                        (est.astype(jnp.int32),), (-1,), (jnp.int32,), T,
                        interpret, name="ell_hindex")
    return h
