"""Kernel-variant and fixpoint-latency sweeps (the BENCH_kernels.json source).

Four measurement surfaces for the kernel pass:

  * `kernels/hindex/*` — the h-index kernel variants at a (N, Cd) grid:
    the O(Cd log Cd) per-row binary search vs the legacy O(Cd*K)
    threshold-count kernel (K = Cd), plus the single-superstep latency of each registry
    backend.  Off-TPU the Pallas rows run in interpret mode — relative
    variant cost, not hardware speed; parity vs `ref.ell_hindex_ref` is
    asserted on every row (this file is part of the --smoke gate).
  * `kernels/coreness/*` — the full min-H fixpoint as ONE fused
    `lax.while_loop` (`ops.coreness_blocks`) vs a host-driven replica of
    the pre-refactor loop (one `device_get` convergence check per
    superstep).  The derived field carries the superstep count so
    us/superstep is recoverable from the JSON trajectory.
  * `kernels/triangles/*` — the sorted-merge binary-probe intersection
    vs the legacy all-pairs cube on the same adjacency, bit-parity
    asserted against `ref.ell_common_ref` on both.
  * `kernels/multi/*` — the fused multi-field superstep
    (`ops.neighbor_multi_ell`: coreness + CC + PageRank reduces off ONE
    adjacency read) vs the three standalone kernel launches, per-field
    bit-parity asserted.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import build_blocks, build_ell_random
from repro.core.partition import node_random_partition
from repro.graphgen import barabasi_albert
from repro.kernels import ops, ref

from .common import row, timeit_us


def _timed(fn, reps: int) -> float:
    out = fn()            # warmup / compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / max(1, reps) * 1e6


def _hostloop_coreness(g, backend: str):
    """Pre-refactor fixpoint: one kernel launch + one host sync/superstep."""
    est = jnp.where(g.node_mask, g.deg, 0).astype(jnp.int32)
    adj = ops.dense_adj(g, backend)
    steps = 0
    while True:
        h = ops.hindex_blocks(g, est, backend=backend, adj=adj)
        new = jnp.where(g.node_mask, jnp.minimum(est, h), est)
        steps += 1
        if bool(jax.device_get(jnp.all(new == est))):
            break
        est = new
    return est, steps


def run(seed: int = 0, smoke: bool = False) -> List[Tuple[str, float, str]]:
    rows = []
    reps = 3 if smoke else 10

    # ---- h-index kernel: full columns vs the degree-bucketed bound ------
    shapes = [(512, 256)] if smoke else [(512, 256), (2048, 256), (2048, 512)]
    for N, Cd in shapes:
        g = build_ell_random(N, Cd=Cd, seed=seed, m_factor=Cd / 3)
        est = jnp.asarray(g.deg, jnp.int32)
        want = np.asarray(ref.ell_hindex_ref(g.nbr, est))
        K = ops.degree_bound(g)
        got = ops.hindex_ell(g.nbr, est)
        np.testing.assert_array_equal(np.asarray(got), want)
        rows.append(row(
            f"kernels/hindex/N{g.N}/Cd{Cd}/bisect",
            _timed(lambda: ops.hindex_ell(g.nbr, est), reps), f"K={K}"))
        # degree-bucketed K: same kernel, fewer columns swept
        got = ops.hindex_ell(g.nbr, est, K=K)
        np.testing.assert_array_equal(np.asarray(got), want)
        rows.append(row(
            f"kernels/hindex/N{g.N}/Cd{Cd}/bisect_degK",
            _timed(lambda: ops.hindex_ell(g.nbr, est, K=K), reps),
            f"K={K}"))

    # ---- single-superstep latency per backend -------------------------
    n = 240 if smoke else 1000
    edges = barabasi_albert(n, 4, seed=seed)
    nn = int(edges.max()) + 1
    g = build_blocks(edges, nn, node_random_partition(nn, 8, seed=seed),
                     P=8, deg_slack=24)
    est = jnp.where(g.node_mask, g.deg, 0).astype(jnp.int32)
    want = np.asarray(ref.ell_hindex_ref(g.nbr, est))
    for b in ("jnp", "dense", "ell"):
        got = ops.hindex_blocks(g, est, backend=b)
        np.testing.assert_array_equal(np.asarray(got).astype(want.dtype), want)
        us = _timed(lambda bb=b: ops.hindex_blocks(g, est, backend=bb), reps)
        rows.append(row(f"kernels/superstep/N{g.N}/{b}", us, "parity=ok"))

    # ---- triangles: sorted-merge vs all-pairs intersection ------------
    tri_shapes = [(320, 24)] if smoke else [(320, 24), (320, 128), (1024, 64)]
    for N, Cd in tri_shapes:
        gt = build_ell_random(N, Cd=Cd, seed=seed, m_factor=Cd / 3)
        want = np.asarray(ref.ell_common_ref(gt.nbr, gt.nbr))
        us_by = {}
        for variant in ("merge", "allpairs"):
            got = ops.neighbor_common_ell(gt.nbr, gt.nbr, variant=variant)
            np.testing.assert_array_equal(np.asarray(got), want)
            us_by[variant] = _timed(
                lambda v=variant: ops.neighbor_common_ell(
                    gt.nbr, gt.nbr, variant=v), reps)
        speedup = us_by["allpairs"] / max(us_by["merge"], 1e-9)
        for variant, us in us_by.items():
            rows.append(row(
                f"kernels/triangles/N{gt.N}/Cd{Cd}/{variant}", us,
                f"merge_speedup={speedup:.1f}x;parity=ok"))

    # ---- fused multi-field superstep vs three standalone launches -----
    for N, Cd in ([(512, 32)] if smoke else [(512, 32), (2048, 64)]):
        gm = build_ell_random(N, Cd=Cd, seed=seed, m_factor=Cd / 3)
        est = jnp.asarray(gm.deg, jnp.int32)
        lab = jnp.arange(gm.N, dtype=jnp.int32)
        contrib = jnp.where(gm.deg > 0, 1.0 / jnp.maximum(gm.deg, 1),
                            0.0).astype(jnp.float32)
        combines = ("hindex", "min", "sum")

        def fused():
            return ops.neighbor_multi_ell(
                gm.nbr, (est, lab, contrib), combines)

        def separate():
            return (ops.hindex_ell(gm.nbr, est),
                    ops.neighbor_min_ell(gm.nbr, lab),
                    ops.neighbor_sum_ell(gm.nbr, contrib))

        for f, s in zip(fused(), separate()):
            np.testing.assert_array_equal(np.asarray(f), np.asarray(s))
        us_f = _timed(fused, reps)
        us_s = _timed(separate, reps)
        ratio = us_s / max(us_f, 1e-9)
        rows.append(row(f"kernels/multi/N{gm.N}/Cd{Cd}/fused", us_f,
                        f"fields=3;separate_speedup={ratio:.1f}x;parity=ok"))
        rows.append(row(f"kernels/multi/N{gm.N}/Cd{Cd}/separate", us_s,
                        "fields=3"))

    # ---- fused vs host-synced fixpoint --------------------------------
    for b in ("jnp", "dense", "ell"):
        core_h, steps_h = _hostloop_coreness(g, b)
        t_host = timeit_us(lambda bb=b: jax.block_until_ready(
            _hostloop_coreness(g, bb)[0]), n=reps)
        def fused(bb=b):
            return ops.coreness_blocks(g, backend=bb, with_steps=True)

        core_f, steps_f = fused()
        np.testing.assert_array_equal(np.asarray(core_h), np.asarray(core_f))
        assert int(steps_f) == steps_h, (b, int(steps_f), steps_h)
        t_fused = _timed(lambda: fused()[0], reps)
        rows.append(row(
            f"kernels/coreness/N{g.N}/{b}/fused", t_fused,
            f"steps={int(steps_f)};"
            f"hostloop_speedup={t_host / max(t_fused, 1e-9):.1f}x"))
        rows.append(row(
            f"kernels/coreness/N{g.N}/{b}/hostloop", t_host,
            f"steps={steps_h}"))

    # ---- skew sweep: hub-mirrored vs unsplit fixpoint -----------------
    from . import bench_skew
    rows += bench_skew.kernel_rows(seed=seed, smoke=smoke,
                                   prefix="kernels/skew")
    return rows


if __name__ == "__main__":
    from .common import print_rows
    print_rows(run())
