"""Pallas TPU kernel: ELL block-sparse neighbor-sum sweep (PageRank push).

The push-style PageRank superstep is the "sum" neighbor combine of the
`BlockProgram` contract: every node's exchanged field is its outgoing
contribution rank/deg, and each superstep sums the contributions of its
neighbors.  Same ELL tiling and pre-kernel XLA gather as the h-index/min
kernels (`ell_hindex.ell_row_call`), float32 payload:

    nbr[N, Cd]   int32    padded neighbor ids (-1 = empty slot)
    field[N]     float32  per-node contribution (rank[u] / deg[u])

Per row tile of T nodes (grid axis i) the kernel reads the (T, C) tile of
gathered contributions (PAD slots -> 0.0, the sum-combine's absorbing
fill) and writes out[t] = sum over the row.  The accumulation order within
a row is the same axis-1 reduction the jnp oracle performs, so
cross-backend drift stays at normal float32 reassociation noise (the
parity tests use allclose, not bit equality).  A max-degree column bound
K < Cd (left-filled rows) is honored like the sibling kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .ell_hindex import check_cols, ell_row_call


def _ell_sum_kernel(vals_ref, out_ref):
    out_ref[...] = jnp.sum(vals_ref[...], axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("K", "T", "interpret"))
def neighbor_sum_ell(
    nbr: jax.Array,
    field: jax.Array,
    K: int,
    T: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Row-wise sum of neighbor field values over the ELL adjacency.

    nbr: (N, Cd) int32 (-1 padded); field: (N,) float32; K: column bound
    (exact for K >= Cd, or K < Cd on left-filled rows).  Returns (N,)
    float32 with 0.0 on neighborless rows.  N % T == 0; Cd and K pass
    `check_cols` (pad via the ops.py wrapper).
    """
    N, Cd = nbr.shape
    assert field.shape == (N,), (field.shape, N)
    assert N % T == 0, (N, T)
    check_cols(Cd, K)
    C = min(Cd, K)
    (red,) = ell_row_call(_ell_sum_kernel, nbr[:, :C],
                          (field.astype(jnp.float32),), (0.0,),
                          (jnp.float32,), T, interpret, name="ell_pagerank")
    return red
