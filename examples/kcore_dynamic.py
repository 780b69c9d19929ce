"""End-to-end driver: dynamic k-core maintenance on a DS1-shaped graph.

The full BLADYG pipeline of paper §4.1/§5.2.1:
  1. generate a Nearest-Neighbor synthetic graph (DS1 family),
  2. partition into 8 blocks (BFS edge-cut partitioner),
  3. static distributed coreness (min-H supersteps) through the kernel
     backend registry (`--backend jnp|dense|ell|ell_spmd|auto`),
  4. stream 200 mixed inter/intra insertions+deletions through the
     Theorem-1 maintenance path (per-update, or via the streaming router
     `repro.runtime.run_stream` with `--stream`),
  5. verify against recompute-from-scratch and report AIT/ADT + candidate
     statistics.

Run:  PYTHONPATH=src python examples/kcore_dynamic.py [--nodes 10000]
      [--backend ell_spmd --stream]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    build_blocks, coreness, insert_edge_maintain, delete_edge_maintain)
from repro.core.partition import node_bfs_partition
from repro.core.updates import sample_insertions, sample_deletions
from repro.graphgen import nearest_neighbor_graph
from repro.kernels import ops

ap = argparse.ArgumentParser()
ap.add_argument("--nodes", type=int, default=4000)
ap.add_argument("--updates", type=int, default=200)
ap.add_argument("--blocks", type=int, default=8)
ap.add_argument("--backend", default="auto",
                choices=list(ops.BACKENDS) + ["auto"],
                help="kernel registry backend; ell_spmd = worker mesh")
ap.add_argument("--stream", action="store_true",
                help="ingest updates through runtime.run_stream (block "
                     "routing + coordinator escalation) instead of the "
                     "per-update loop")
args = ap.parse_args()

print(f"== generating DS1-shaped graph ({args.nodes} nodes) ==")
edges = nearest_neighbor_graph(args.nodes, u=0.86, seed=7)
n = int(edges.max()) + 1
print(f"   n={n} m={len(edges)}")

print(f"== partitioning into {args.blocks} blocks (BFS edge-cut) ==")
assign = node_bfs_partition(edges, n, args.blocks, seed=1)
g = build_blocks(edges, n, assign, P=args.blocks, deg_slack=64)
print(f"   edge cut: {int(g.edge_cut())} / {g.m_real}")

print(f"== static distributed k-core decomposition "
      f"(backend={args.backend}) ==")
t0 = time.time()
core = coreness(g, backend=args.backend)
jax.block_until_ready(core)
print(f"   max coreness {int(jnp.max(core))} in {time.time() - t0:.2f}s "
      f"(resolved '{ops.resolve_backend(args.backend, g.N)}')")

print(f"== streaming {args.updates} updates through Theorem-1 maintenance ==")
q = args.updates // 4
ups = (sample_insertions(g, q, "inter", seed=2)
       + sample_insertions(g, q, "intra", seed=3)
       + sample_deletions(g, q, "inter", seed=4)
       + sample_deletions(g, q, "intra", seed=5))

if args.stream:
    from repro.runtime import run_stream

    t0 = time.time()
    res = run_stream(g, core, ups, R=8, backend=args.backend)
    g, core, st = res.g, res.core, res.stats
    jax.block_until_ready(core)
    dt = time.time() - t0
    print(f"   {st.updates} updates in {dt:.2f}s: "
          f"{st.block_local} block-local, {st.escalated} escalated "
          f"(cross={st.escalated_cross_block} spill={st.escalated_spill} "
          f"conflict={st.escalated_conflict}), "
          f"{st.bfs_steps} BFS + {st.recompute_steps} recompute supersteps")
else:
    # the per-update maintenance loop supports the single-device backends
    per_update_backend = ops.resolve_backend(
        args.backend if args.backend != "ell_spmd" else "jnp", g.N)
    lat, cands, blocks_touched = [], [], []
    for u, v, op in ups:
        fn = insert_edge_maintain if op > 0 else delete_edge_maintain
        t0 = time.time()
        g, core, st = fn(g, core, jnp.int32(u), jnp.int32(v),
                         backend=per_update_backend)
        jax.block_until_ready(core)
        lat.append(time.time() - t0)
        cands.append(int(st.candidates))
        blocks_touched.append(int(st.blocks_touched))

    print(f"   mean latency {np.mean(lat[2:]) * 1e3:.1f} ms  "
          f"mean candidates {np.mean(cands):.0f}/{n}  "
          f"mean blocks touched {np.mean(blocks_touched):.1f}/{args.blocks}")

print("== verifying against recompute-from-scratch ==")
ref = coreness(g, backend="jnp")
assert (np.asarray(ref) == np.asarray(core)).all()
print("   maintained coreness == recomputed coreness ✓")
