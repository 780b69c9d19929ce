"""Pallas TPU kernel: fused multi-field ELL superstep (one launch, k fields).

A `MultiProgram` (`core.engine`) advances several `BlockProgram`s in
lockstep — e.g. coreness + CC labels + PageRank.  Run separately, every
sub-program's superstep is its own gather + kernel launch over the same
(N, Cd) ELL adjacency.  Fused, the slot validity mask and clipped gather
indices are computed once, every field's gather reads them (one XLA
fusion, `ell_hindex.ell_row_call`), and ONE pallas launch per
row tile reduces all k value tiles.

Supported per-field combines (`MULTI_COMBINES` in ops.py): "min" (CC
label propagation, int32), "sum" (PageRank push, float32), "hindex"
(min-H coreness, int32).  "count_common" is excluded — its field is the
(N, Cd) row matrix, which would defeat the shared-gather point.  Each
reduce is the standalone kernel's formulation (same fill, same reduction
axis/order; hindex by `ell_hindex.hindex_bisect`), so fused results are
bit-identical to the dedicated `ell_cc` / `ell_pagerank` / `ell_hindex`
launches.

Tiling is the family standard: row tiles of T nodes on grid axis i, a
max-degree column bound K < Cd honored on left-filled rows.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from .ell_cc import MIN_FILL
from .ell_hindex import check_cols, ell_row_call, hindex_bisect

#: combines the fused kernel can serve, with their (dtype, pad fill)
_FIELD_SPEC = {
    "min": (jnp.int32, MIN_FILL),
    "sum": (jnp.float32, 0.0),
    "hindex": (jnp.int32, -1),
}


def _reduce_one(combine: str, vals: jax.Array) -> jax.Array:
    """The standalone kernels' row reductions: (T, C) -> (T, 1)."""
    if combine == "min":
        return jnp.min(vals, axis=1, keepdims=True)
    if combine == "sum":
        return jnp.sum(vals, axis=1, keepdims=True)
    return hindex_bisect(vals)


def _ell_multi_kernel(*refs, combines: Tuple[str, ...]):
    n = len(combines)
    for combine, v_ref, o_ref in zip(combines, refs[:n], refs[n:]):
        o_ref[...] = _reduce_one(combine, v_ref[...])


@functools.partial(
    jax.jit, static_argnames=("combines", "K", "T", "interpret"))
def neighbor_multi_ell(
    nbr: jax.Array,
    fields: Sequence[jax.Array],
    combines: Tuple[str, ...],
    K: int,
    T: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, ...]:
    """Fused multi-field neighbor reduce over one shared ELL gather.

    nbr: (N, Cd) int32 (-1 padded); fields: one 1-D table per combine
    (int32 for "min"/"hindex", float32 for "sum"), usually (N,), but a
    subset of rows (the tail of a hybrid ELL, `ops._multi_ell_hybrid`)
    gathers from the whole graph's tables; combines: static tuple of
    names from `_FIELD_SPEC`.  Returns one (N,) reduction per field,
    each bit-identical to its standalone kernel.  N % T == 0; Cd and K
    pass `check_cols` (pad via the ops.py wrapper).
    """
    N, Cd = nbr.shape
    assert len(fields) == len(combines) >= 1, (len(fields), combines)
    for c, f in zip(combines, fields):
        assert c in _FIELD_SPEC, c
        assert f.ndim == 1, (c, f.shape)
    assert N % T == 0, (N, T)
    check_cols(Cd, K)
    C = min(Cd, K)
    dtypes = tuple(_FIELD_SPEC[c][0] for c in combines)
    return ell_row_call(
        functools.partial(_ell_multi_kernel, combines=combines), nbr[:, :C],
        tuple(f.astype(d) for f, d in zip(fields, dtypes)),
        tuple(_FIELD_SPEC[c][1] for c in combines), dtypes, T, interpret,
        name="ell_multi")
