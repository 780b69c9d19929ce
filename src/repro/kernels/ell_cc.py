"""Pallas TPU kernel: ELL block-sparse neighbor-min sweep (label propagation).

The connected-components hot loop is the "min" neighbor combine of the
`BlockProgram` contract: each superstep every node pulls its neighbors'
current component labels and keeps the minimum.  Structurally this is the
h-index kernel (`ell_hindex.py`) with the row reduction swapped — gather
through the ELL neighbor lists, reduce each row — so it shares the same
tiling and the same pre-kernel XLA gather (`ell_hindex.ell_row_call`):

    nbr[N, Cd]   int32   padded neighbor ids (-1 = empty slot)
    field[N]     int32   current labels (component = min member id)

Per row tile of T nodes (grid axis i) the kernel reads the (T, C) tile of
gathered labels (PAD slots -> int32 max, the min-combine's absorbing fill)
and writes out[t] = min over the row.  Rows with no valid slots reduce to
int32 max — `BlockProgram.update` takes `min(own, red)`, so the fill is
harmless by construction.  A max-degree column bound K < Cd (left-filled
rows, `ops.degree_bound`) restricts the gather like the sibling kernels.
Bit-identical to `ref.ell_min_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .ell_hindex import check_cols, ell_row_call

#: absorbing fill for the min combine (what PAD slots read as)
MIN_FILL = jnp.iinfo(jnp.int32).max


def _ell_min_kernel(vals_ref, out_ref):
    out_ref[...] = jnp.min(vals_ref[...], axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("K", "T", "interpret"))
def neighbor_min_ell(
    nbr: jax.Array,
    field: jax.Array,
    K: int,
    T: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Row-wise min of neighbor field values over the ELL adjacency.

    nbr: (N, Cd) int32 (-1 padded); field: (N,) int32; K: column bound —
    exact iff every row's valid slots lie in the first K columns (always
    true for K >= Cd; K < Cd needs left-filled rows, the `GraphBlocks`
    invariant).  Returns (N,) int32 with int32-max on neighborless rows.
    N % T == 0; Cd and K pass `check_cols` (pad via the ops.py wrapper).
    """
    N, Cd = nbr.shape
    assert field.shape == (N,), (field.shape, N)
    assert N % T == 0, (N, T)
    check_cols(Cd, K)
    C = min(Cd, K)
    (red,) = ell_row_call(_ell_min_kernel, nbr[:, :C],
                          (field.astype(jnp.int32),), (MIN_FILL,),
                          (jnp.int32,), T, interpret, name="ell_cc")
    return red
