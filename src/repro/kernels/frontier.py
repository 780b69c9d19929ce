"""Pallas TPU kernel: batched frontier expansion (k-reachability BFS hop).

The Theorem-1 candidate search is a BFS restricted to nodes whose coreness
equals k.  One hop for R stacked frontiers (R concurrent updates — the
batched-maintenance optimization in EXPERIMENTS §Perf):

    next = (A @ F > 0) ∧ eligible ∧ ¬visited

A @ F is a (T×T)@(T×R) MXU matmul per adjacency tile — GraphBLAS-style
SpMV-as-matmul; the masking epilogue is VPU elementwise work fused into the
same kernel (no extra HBM round-trip for `hit`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _frontier_kernel(adj_ref, f_ref, elig_ref, vis_ref, out_ref, acc_ref, *, nj: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        adj_ref[...], f_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(j == nj - 1)
    def _epilogue():
        hit = acc_ref[...] > 0.0
        elig = elig_ref[...] > 0  # (T, 1) broadcasts over R
        vis = vis_ref[...] > 0
        out_ref[...] = (hit & elig & ~vis).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("T", "interpret"))
def frontier_step(
    adj: jax.Array,
    f: jax.Array,
    eligible: jax.Array,
    visited: jax.Array,
    T: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """One masked BFS hop for R stacked frontiers.

    adj: (N, N) 0/1 bf16/f32; f: (N, R) 0/1; eligible: (N,) 0/1;
    visited: (N, R) 0/1.  Returns next frontier (N, R) int32 0/1 (masks
    and output are int32: the TPU's vector compare has no int8 form).
    N % T == 0 and R % 128 == 0 (pad via ops.py wrapper).
    """
    N, R = f.shape
    assert adj.shape == (N, N) and eligible.shape == (N,)
    assert visited.shape == (N, R)
    assert N % T == 0 and R % 128 == 0, (N, T, R)
    ni = nj = N // T

    kernel = functools.partial(_frontier_kernel, nj=nj)
    out = pl.pallas_call(
        kernel,
        grid=(ni, nj),
        in_specs=[
            pl.BlockSpec((T, T), lambda i, j: (i, j)),  # adjacency tile
            pl.BlockSpec((T, R), lambda i, j: (j, 0)),  # frontier rows (j!)
            pl.BlockSpec((T, 1), lambda i, j: (i, 0)),  # eligible
            pl.BlockSpec((T, R), lambda i, j: (i, 0)),  # visited
        ],
        out_specs=pl.BlockSpec((T, R), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, R), jnp.int32),
        scratch_shapes=[pltpu.VMEM((T, R), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(adj, f.astype(adj.dtype), eligible[:, None].astype(jnp.int32),
      visited.astype(jnp.int32))
    return out
