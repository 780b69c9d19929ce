#!/usr/bin/env python3
"""Chip smoke: the system's main path once on a TPU, checked on the host.

    python chip_smoke.py [--seed 0]              # one chip
    python chip_smoke.py --chips 4 [--seed 0]    # the 4-chip worker mesh only

Deployment: `GRAPH_TASKS["roadnet-ca"]` — the paper's Table 1 road network
stand-in (`graphgen.snap_like`), 8 random blocks, `deg_slack=64`.

One chip, in one process:

  1. static — the full-scale graph (scale 1.0: N = 1,965,206 nodes, 4.1M
              edges) through one converged `fused_analytics` pass
              (coreness + CC + PageRank) with the backend resolved by
              "auto" — on the TPU that is `ell`, the compiled Pallas
              kernels — checked against networkx core numbers, scipy
              components and a float64 power iteration.
  2. serve  — the served path `StreamSession(backend="ell_spmd")` ->
              `QueryServer` on the task at `SERVE_SCALE` of its size:
              `WINDOWS` windows of R=8 updates in the task's inter/intra
              insert/delete mix, a few query batches of every kind
              between windows, then the final coreness, CC labels,
              PageRank and query answers against the host reference.

`--chips 4` runs only the served path, on a W=4 worker mesh (P=8 blocks
-> 2 per device), and checks that each device holds a quarter of every
copy of the adjacency.

Every phase prints its wall seconds, compile seconds and the device's
`peak_bytes_in_use`.  The last line is one JSON object with the device as
JAX reports it; any failed phase exits non-zero, and without a TPU the
script exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ALPHA = 0.85
PR_STEPS = 30   # snapshot refresh PageRank iterations (ServiceConfig)
R = 8           # update window width
WINDOWS = 16    # update windows through the served path
#: the served phase's fraction of the full road network: every window's
#: candidate search walks the k-shell holding most nodes, one whole-graph
#: superstep per hop, so a window costs ~(diameter x N) gathers.
#: N stays above ops.DENSE_AUTO_MAX, so "auto" resolves to `ell`.
SERVE_SCALE = 0.004


def _parse():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    return ap.parse_args()


class Phase:
    """Times one phase: wall seconds, compile seconds, device peak bytes."""

    compile_s = 0.0

    def __init__(self, name, device):
        self.name, self.device = name, device

    @classmethod
    def listen(cls, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            cls.compile_s += duration

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), Phase.compile_s
        return self

    def __exit__(self, *exc):
        stats = self.device.memory_stats() or {}
        print(json.dumps({
            "phase": self.name, "ok": exc[0] is None,
            "seconds": time.perf_counter() - self.t0,
            "compile_seconds": Phase.compile_s - self.c0,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        }), flush=True)


# ---------------------------------------------------------------------------
# host reference: plain numpy / scipy / networkx on the padded-id graph
# ---------------------------------------------------------------------------


def _host_graph(nbr, mask):
    import scipy.sparse as sp

    us, js = np.nonzero(nbr >= 0)
    vs = nbr[us, js]
    A = sp.csr_matrix((np.ones(len(us)), (us, vs)), shape=(len(mask),) * 2)
    return A, us, vs


def _ref_labels(A, mask):
    """Canonical CC labels: min member padded id, -1 on padding rows."""
    from scipy.sparse.csgraph import connected_components

    _, lab = connected_components(A, directed=False)
    ids = np.arange(len(mask))
    low = np.full(lab.max() + 1, len(mask))
    np.minimum.at(low, lab, ids)
    return np.where(mask, low[lab], -1)


def _cc_supersteps(A, labels, mask):
    """Supersteps min-label propagation needs: the largest distance from a
    component's min-id node inside it (component size bounds the small
    ones), plus the superstep that finds nothing changed."""
    from scipy.sparse.csgraph import shortest_path

    roots, sizes = np.unique(labels[mask], return_counts=True)
    bound = 0
    for root, size in zip(roots, sizes):
        if size <= 1024:
            bound = max(bound, int(size))
            continue
        d = shortest_path(A, unweighted=True, indices=int(root),
                          directed=False)
        bound = max(bound, int(d[np.isfinite(d)].max()))
    return bound + 1


def _ref_core(us, vs, mask):
    import networkx as nx

    G = nx.Graph()
    G.add_nodes_from(np.flatnonzero(mask).tolist())
    G.add_edges_from(zip(us.tolist(), vs.tolist()))
    core = np.zeros(len(mask), np.int64)
    for u, c in nx.core_number(G).items():
        core[u] = c
    return core


def _ref_rank(A, deg, mask, steps, converge=False):
    """Push PageRank in float64 (dangling mass decays into teleport)."""
    n_real = max(1, int(mask.sum()))
    r = np.where(mask, 1.0 / n_real, 0.0)
    for _ in range(steps):
        contrib = np.where(deg > 0, r / np.maximum(deg, 1), 0.0)
        r2 = np.where(mask, (1 - ALPHA) / n_real + ALPHA * (A @ contrib), 0.0)
        if converge and np.abs(r2 - r).max() < 1e-15:
            return r2
        r = r2
    return r


def _check(name, ok, detail=""):
    print(json.dumps({"check": name, "ok": bool(ok), "detail": detail}),
          flush=True)
    if not ok:
        raise AssertionError(f"{name}: {detail}")


def _check_rank(name, got, want):
    err = np.abs(got - want)
    rel = float((err / np.maximum(want, 1e-30)).max())
    _check(name, np.allclose(got, want, rtol=1e-4, atol=0.0),
           f"max_abs={float(err.max())!r} max_rel={rel!r}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def _build(task, scale, seed):
    from repro.core import build_blocks
    from repro.core.partition import node_random_partition
    from repro.graphgen.snap_like import snap_like

    edges = snap_like(task.dataset, scale=scale, seed=seed)
    n = int(edges.max()) + 1
    g = build_blocks(edges, n, node_random_partition(n, task.blocks, seed=seed),
                     P=task.blocks, deg_slack=task.deg_slack)
    jax.block_until_ready(g.nbr)
    print(json.dumps({"graph": task.name, "scale": scale, "nodes": n,
                      "edges": int(len(edges)), "N": g.N, "P": g.P,
                      "Cn": g.Cn, "Cd": g.Cd}), flush=True)
    return g


def static_phase(task, seed, device):
    """The full-scale graph through one converged fused pass on "auto"."""
    from repro.core.algorithms import fused_analytics
    from repro.kernels import ops

    with Phase("static/build", device):
        g = _build(task, 1.0, seed)
    with Phase("static/host_reference", device):
        nbr, mask = np.asarray(g.nbr), np.asarray(g.node_mask)
        A, us, vs = _host_graph(nbr, mask)
        labels_ref = _ref_labels(A, mask)
        steps = _cc_supersteps(A, labels_ref, mask)
        core_ref = _ref_core(us, vs, mask)
        rank_ref = _ref_rank(A, np.asarray(g.deg), mask, steps,
                             converge=True)
    backend = ops.resolve_backend("auto", g.N)
    print(json.dumps({"static_backend": backend, "supersteps": steps}),
          flush=True)
    _check("static/auto_resolves_to_ell", backend == "ell", backend)
    with Phase("static/fused_analytics", device):
        (core, labels, rank), n = fused_analytics(
            g, alpha=ALPHA, steps=steps, backend="auto", with_steps=True)
        host = jax.device_get((core, labels, rank, n))
    _check("static/supersteps", int(host[3]) == steps, f"{host[3]} of {steps}")
    _check("static/coreness", np.array_equal(host[0], core_ref),
           f"{int((host[0] != core_ref).sum())} nodes differ")
    _check("static/cc_labels", np.array_equal(host[1], labels_ref),
           f"{int((host[1] != labels_ref).sum())} nodes differ")
    _check_rank("static/pagerank", host[2], rank_ref)


def _mixed_updates(g, windows, seed):
    from repro.core.updates import sample_deletions, sample_insertions

    per = windows * R // 4
    return (sample_insertions(g, per, "inter", seed=seed + 1)
            + sample_insertions(g, per, "intra", seed=seed + 2)
            + sample_deletions(g, per, "inter", seed=seed + 3)
            + sample_deletions(g, per, "intra", seed=seed + 4))


def serve_phase(task, W, seed, device):
    from repro.core.algorithms import connected_components
    from repro.kernels import ops
    from repro.runtime import StreamSession
    from repro.runtime.spmd import SpmdExecutor
    from repro.runtime.stream import _iter_windows
    from repro.service import (QueryServer, ServiceConfig, core_of,
                               degree_of, nbr_max_core_of, same_component,
                               topk_pagerank)

    rng = np.random.default_rng(seed)
    with Phase("serve/build", device):
        g = _build(task, SERVE_SCALE, seed)
        ups = _mixed_updates(g, WINDOWS, seed)
        real = np.flatnonzero(np.asarray(g.node_mask))
    with Phase("serve/open", device):
        ex = SpmdExecutor(g, W=W)
        core = ops.coreness_blocks(g, backend="ell_spmd", executor=ex)
        labels = connected_components(g, backend="ell_spmd", executor=ex)
        sess = StreamSession(g, core, R=R, backend="ell_spmd", W=W,
                             executor=ex, cc_labels=labels)
        srv = QueryServer(sess, config=ServiceConfig(
            refresh_every=1, pr_steps=PR_STEPS, alpha=ALPHA))

    def split(name, x):
        """Each of the W devices holds its 1/W share of `x`."""
        shards = x.addressable_shards
        got = sorted((str(sh.device), int(sh.data.nbytes)) for sh in shards)
        print(json.dumps({"bytes_per_device": name, "shards": got}),
              flush=True)
        _check(f"serve/{name}_split",
               len({d for d, _ in got}) == W
               and all(b * W == x.nbytes for _, b in got), str(got))

    print(json.dumps({"mesh_workers": ex.wm.W, "blocks_per_worker": ex.wm.B,
                      "gathered_columns": int(ex._nbrl.shape[1]),
                      "Cd": g.Cd}), flush=True)

    def feed():
        us, vs = rng.choice(real, 8), rng.choice(real, 8)
        return ([core_of(u) for u in us] + [degree_of(u) for u in us]
                + [nbr_max_core_of(u) for u in us]
                + [same_component(u, v) for u, v in zip(us, vs)]
                + [topk_pagerank(10)])

    submitted = []
    with Phase("serve/windows", device):
        for window in _iter_windows(ups, R):
            submitted += [srv.submit(q) for q in feed()]
            srv.step(window)  # apply, refresh the snapshot, answer batches
        final = [srv.submit(q) for q in feed()]
        srv.pump()
        snap = srv.state.snapshot
        jax.block_until_ready(snap.rank)
    # the executor's local-frame adjacency and the graph the apply path
    # edits (plus the snapshot's copy) are each split over the mesh
    split("executor_nbr", ex._nbrl)
    split("session_nbr", sess.g.nbr)
    split("snapshot_nbr", snap.nbr)
    st = sess.stats()
    print(json.dumps({"windows": st.batches, "updates": st.updates,
                      "bfs_supersteps": st.bfs_steps,
                      "recompute_supersteps": st.recompute_steps,
                      "cc_recomputes": st.cc_recomputes,
                      "epoch": snap.epoch,
                      "answered": sum(r.done for r in submitted + final)}),
          flush=True)
    _check("serve/windows", st.batches == WINDOWS, str(st.batches))
    _check("serve/all_answered", all(r.done for r in submitted + final),
           f"{len(submitted) + len(final)} requests")

    with Phase("serve/host_reference", device):
        nbr, mask = np.asarray(snap.nbr), np.asarray(snap.node_mask)
        deg = np.asarray(snap.deg)
        A, us, vs = _host_graph(nbr, mask)
        core_ref = _ref_core(us, vs, mask)
        labels_ref = _ref_labels(A, mask)
        rank_ref = _ref_rank(A, deg, mask, PR_STEPS)
    core, labels, rank = jax.device_get((snap.core, snap.labels, snap.rank))
    _check("serve/coreness", np.array_equal(core, core_ref),
           f"{int((core != core_ref).sum())} nodes differ")
    _check("serve/cc_labels", np.array_equal(labels, labels_ref),
           f"{int((labels != labels_ref).sum())} nodes differ")
    _check("serve/degree",
           np.array_equal(deg, np.bincount(us, minlength=len(mask))))
    _check_rank("serve/pagerank", rank, rank_ref)

    def expect(q):
        if q.kind == "core":
            return int(core_ref[q.u])
        if q.kind == "degree":
            return int(deg[q.u])
        if q.kind == "nbr_max_core":
            row = nbr[q.u][nbr[q.u] >= 0]
            return int(core_ref[row].max()) if row.size else -1
        if q.kind == "same_component":
            return bool(labels_ref[q.u] == labels_ref[q.v])
        return None

    wrong = 0
    for r in final:
        if r.query.kind == "topk_pagerank":
            ids, vals = r.answer
            kth = np.sort(rank_ref)[-r.query.k]
            wrong += int(not (np.all(rank_ref[ids] >= kth * (1 - 1e-4))
                              and np.allclose(vals, rank_ref[ids], rtol=1e-4)))
        else:
            wrong += int(r.answer != expect(r.query))
    _check("serve/query_answers", wrong == 0 and all(
        r.epoch == snap.epoch for r in final), f"{wrong} of {len(final)} wrong")


def main() -> int:
    args = _parse()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.configs.bladyg_graph import GRAPH_TASKS

    print(json.dumps({"compile_cache": enable_compile_cache(),
                      "device_kind": dev.device_kind,
                      "devices": len(devices)}), flush=True)
    jax.monitoring.register_event_duration_secs_listener(Phase.listen)
    task = GRAPH_TASKS["roadnet-ca"]
    if args.chips == 1:
        static_phase(task, args.seed, dev)
    serve_phase(task, args.chips, args.seed, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
