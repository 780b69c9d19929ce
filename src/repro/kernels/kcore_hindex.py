"""Pallas TPU kernel: dense-tile h-index sweep (the k-core hot loop).

GPU/actor formulation (paper): per-node gather of neighbor core estimates +
per-node histogram — irregular, pointer-chasing.

TPU reformulation (DESIGN §2): with thresholds k = 1..K,

    cnt = A @ B,   B[v, k-1] = (est[v] >= k)        -> (T×T)@(T×K) MXU matmuls
    h[u] = max{k : cnt[u, k-1] >= k}                -> VPU reduction

The grid is (node_tiles i, node_tiles j); j is a sequential reduction over
adjacency column tiles accumulating into a VMEM scratch of shape (T, K); the
h-index epilogue fires on the last j step.  A is consumed as 0/1 bf16 tiles
(products are exact; f32 accumulation is exact for counts < 2^24).

Alignment: T and K are multiples of 128 (MXU native), so every matmul is
(128m × 128m) @ (128m × 128k) — no padding waste inside the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _hindex_kernel(est_ref, adj_ref, out_ref, acc_ref, *, K: int, nj: int, T: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # B[v, k-1] = (est[v] >= k) for the j-th column tile of nodes
    est_j = est_ref[...]  # (T, 1) int32
    ks = jax.lax.broadcasted_iota(jnp.int32, (T, K), 1) + 1
    B = (est_j >= ks).astype(adj_ref.dtype)  # (T, K)
    acc_ref[...] += jnp.dot(
        adj_ref[...], B, preferred_element_type=jnp.float32
    )

    @pl.when(j == nj - 1)
    def _epilogue():
        cnt = acc_ref[...]  # (T, K) f32 exact counts
        ks1 = (jax.lax.broadcasted_iota(jnp.int32, (T, K), 1) + 1).astype(
            jnp.float32
        )
        # cnt[:, k] is non-increasing in k, so the indicator is
        # prefix-monotone and its sum equals the h-index.
        h = jnp.sum((cnt >= ks1).astype(jnp.int32), axis=1, keepdims=True)
        out_ref[...] = h


@functools.partial(
    jax.jit, static_argnames=("K", "T", "interpret")
)
def hindex_counts(
    adj: jax.Array,
    est: jax.Array,
    K: int,
    T: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """h-index of every node; dense adjacency path.

    adj: (N, N) 0/1 (bf16/f32), est: (N,) int32, K: threshold bound —
    exact iff K >= max(est) + 1 (callers use K = max degree + 1).
    N must be a multiple of T; K a multiple of 128 (pad via ops.py wrapper).
    """
    N = adj.shape[0]
    assert adj.shape == (N, N) and est.shape == (N,)
    assert N % T == 0, (N, T)
    assert K % 128 == 0, K
    ni = nj = N // T

    kernel = functools.partial(_hindex_kernel, K=K, nj=nj, T=T)
    out = pl.pallas_call(
        kernel,
        grid=(ni, nj),
        in_specs=[
            pl.BlockSpec((T, 1), lambda i, j: (j, 0)),  # est column tile
            pl.BlockSpec((T, T), lambda i, j: (i, j)),  # adjacency tile
        ],
        out_specs=pl.BlockSpec((T, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((T, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(est[:, None], adj)
    return out[:, 0]
