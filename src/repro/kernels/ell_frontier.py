"""Pallas TPU kernel: ELL block-sparse frontier expansion (batched BFS hop).

Sibling of `frontier.py` (dense A @ F formulation) that consumes the
`GraphBlocks` ELL neighbor lists directly — O(N*Cd) memory, no densification.
One masked hop for R stacked frontiers (R concurrent updates, the batched
maintenance axis of `core.kcore_dynamic.maintain_batch`):

    next[u, r] = (exists j: f[nbr[u, j], r]) & eligible[u, r] & ~visited[u, r]

For undirected ELL storage (every edge stored in both endpoint rows) the
gather formulation above equals the scatter-or over outgoing slots.  Unlike
the dense kernel, `eligible` here carries a per-frontier column axis —
batched maintenance stacks updates with *different* k values, so each
column has its own k-level eligibility mask.

Bit-packed frontiers: the R columns pack into ceil(R / 32) int32 words per
node (bit r % 32 of word r // 32), so the pre-kernel XLA gather
(`ell_hindex.ell_row_call`, PAD slots -> 0) moves one (N, C) int32 matrix
per word — the size of the adjacency itself — instead of an (N, C, R)
frontier block.  Per row tile of T nodes (grid axis i) the kernel ORs the
(T, C) word tile across its row (128-lane chunks, then a log2(128)-step
lane rotation), and fuses the eligibility/visited epilogue as bitwise ops
on the packed words: every operation is int32, which every TPU generation
lowers.  A max-degree column bound K < Cd (left-filled rows, see
`ops.degree_bound`) restricts the gather to the first K slots.
Bit-identical to `ref.ell_frontier_hop_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ell_hindex import ell_row_call

#: frontier columns per packed int32 word
WORD = 32

_LANES = 128


def pack_words(x: jax.Array) -> jax.Array:
    """(N, R) 0/1 -> (N, ceil(R/32)) int32 bit words (bit r%32 of word r//32)."""
    N, R = x.shape
    nw = -(-R // WORD)
    bits = jnp.zeros((N, nw * WORD), jnp.int32).at[:, :R].set(
        x.astype(jnp.int32))
    bits = bits.reshape(N, nw, WORD) << jnp.arange(WORD, dtype=jnp.int32)
    return jax.lax.reduce(bits, jnp.int32(0), jax.lax.bitwise_or, (2,))


def unpack_words(w: jax.Array, R: int) -> jax.Array:
    """Inverse of `pack_words`: (N, nw) int32 -> (N, R) bool."""
    bits = (w[:, :, None] >> jnp.arange(WORD, dtype=jnp.int32)) & 1
    return bits.reshape(w.shape[0], -1)[:, :R] > 0


def _ell_frontier_kernel(vals_ref, elig_ref, vis_ref, out_ref, *, C: int):
    # OR across the row: fold the 128-lane chunks, then rotate-and-OR the
    # lanes (after log2(128) steps every lane holds the whole row's OR)
    def fold(c, acc):
        lanes = pl.ds(pl.multiple_of(c * _LANES, _LANES), _LANES)
        return acc | vals_ref[:, lanes]

    acc = jax.lax.fori_loop(1, C // _LANES, fold, vals_ref[:, 0:_LANES])
    for shift in (64, 32, 16, 8, 4, 2, 1):  # log2(_LANES) rotations
        acc = acc | pltpu.roll(acc, shift, 1)
    out_ref[...] = acc[:, 0:1] & elig_ref[...] & ~vis_ref[...]


@functools.partial(jax.jit, static_argnames=("K", "T", "interpret"))
def frontier_step_ell(
    nbr: jax.Array,
    f: jax.Array,
    eligible: jax.Array,
    visited: jax.Array,
    K: int,
    T: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """One masked BFS hop for R stacked frontiers over the ELL adjacency.

    nbr: (N, Cd) int32 (-1 padded); f, eligible, visited: (N, R) 0/1
    (per-column k-level eligibility masks).  K is the column bound: exact
    iff valid slots lie in the first K columns (K >= Cd always works;
    K < Cd needs left-filled rows — the `GraphBlocks` invariant).  Returns
    the next frontier (N, R) bool.  N % T == 0 and Cd, K multiples of 128
    (pad via the ops.py wrapper); R is free.
    """
    N, Cd = nbr.shape
    R = f.shape[1]
    assert f.shape == (N, R) and visited.shape == (N, R), (f.shape, visited.shape)
    assert eligible.shape == (N, R), eligible.shape
    assert N % T == 0 and Cd % 128 == 0 and K % 128 == 0, (N, T, Cd, K)
    C = min(Cd, K)
    fw, ew, vw = pack_words(f), pack_words(eligible), pack_words(visited)
    kernel = functools.partial(_ell_frontier_kernel, C=C)
    words = [
        ell_row_call(kernel, nbr[:, :C], (fw[:, w],), (0,), (jnp.int32,), T,
                     interpret, row_args=(ew[:, w:w + 1], vw[:, w:w + 1]),
                     name="ell_frontier")[0]
        for w in range(fw.shape[1])]
    return unpack_words(jnp.stack(words, axis=1), R)
