"""Back-to-back snapshot refreshes on a static graph.

The refresh is the serving layer's own call on the batch path:
``fused_analytics(g, steps, backend, init=(core, labels))``, warm-started
from exact coreness and CC labels (set-up computes them with the host
reference), so coreness and labels ride through at their fixpoint while
PageRank runs its fixed steps.  Each refresh ends in
``block_until_ready``; the window runs refreshes until the first one
that ends at or after its length.

Checked against the host reference after the window: coreness, labels
and PageRank of the last refresh and of a sample of earlier ones drawn
from the seed.  A refresh from the exact values only has to keep them,
so one more pass of the same compiled call, untimed, starts from a
warm start perturbed as the seed draws (coreness raised and labels set
to the node's own id on a few nodes, both still bounds the updates
converge from) and has to return the exact coreness and labels.
"""
from __future__ import annotations

import time

import numpy as np

from .. import reference, traffic
from ..harness import Run, Window, build_graph, peak_bytes

#: earlier refreshes kept for the check, besides the last
SAMPLED = 4
#: share of real nodes whose coreness, and whose label, the check pass
#: perturbs
PERTURBED = 0.01


def perturbed(core: np.ndarray, labels: np.ndarray, real: np.ndarray,
              rng: np.random.Generator):
    """A warm start the refresh has to repair: coreness raised by 1-3 on
    some real nodes (still an upper bound), and some labels replaced by
    the node's own id (still no smaller than the component's least)."""
    k = max(1, int(PERTURBED * len(real)))
    core, labels = core.copy(), labels.copy()
    up = rng.choice(real, k, replace=False)
    core[up] += rng.integers(1, 4, k)
    own = real[labels[real] != real]
    own = rng.choice(own, min(k, len(own)), replace=False)
    labels[own] = own
    return core, labels


def run(cell, r: Run) -> Run:
    import jax
    import jax.numpy as jnp
    from repro.core import build_blocks
    from repro.core.algorithms import fused_analytics

    cfg = cell.config
    steps, alpha = cfg["refresh"]["pr_steps"], cfg["refresh"]["alpha"]
    graph = build_graph(cfg, cell.seed, cell.root)
    g = build_blocks(graph.edges0, graph.n, graph.assign, P=graph.P,
                     deg_slack=cfg["deg_slack"])
    if g.Cn != graph.Cn or not np.array_equal(
            np.asarray(g.orig_id)[graph.new], np.arange(graph.n)):
        raise RuntimeError("the loader lays nodes out differently from "
                           "bench/graphs.layout")
    ref = reference.Epoch(graph.edges, graph.mask, steps, alpha)
    init = (jnp.asarray(ref.core, jnp.int32),
            jnp.asarray(ref.labels, jnp.int32))

    def refresh(start=init):
        out = fused_analytics(g, alpha=alpha, steps=steps,
                              backend=cfg["backend"], init=start)
        jax.block_until_ready(out)
        return out

    refresh()  # set-up: compile, or load from the cache
    rng = traffic.rng_for(cell.seed, 6)
    kept, done = [], 0
    with Window(r) as win:
        while True:
            if cell.trace:
                with r.span("refresh"):
                    out = refresh()
            else:
                out = refresh()
            done += 1
            # reservoir sample of earlier refreshes, drawn from the seed
            if len(kept) < SAMPLED:
                kept.append(out)
            else:
                j = int(rng.integers(0, done))
                if j < SAMPLED:
                    kept[j] = out
            if time.perf_counter() - win.t0 >= cell.seconds:
                break
        t1 = win.close()
    r.e2e["refresh_s"] = (t1 - win.t0) / done
    r.memory_peak_bytes = peak_bytes()
    r.counters.update(refreshes=done, supersteps=done * steps,
                      n_real=int(graph.mask.sum()),
                      n_edges=int(len(graph.edges)))
    r.attempted, r.failed = done, 0
    outs = [jax.device_get(o) for o in kept + [out]]
    del kept, out
    start = perturbed(ref.core, ref.labels, graph.real,
                      traffic.rng_for(cell.seed, 7))
    repaired = jax.device_get(refresh(tuple(
        jnp.asarray(x, jnp.int32) for x in start)))
    del init, g
    m = graph.mask
    core_wrong = max(int((o[0] != ref.core).sum()) for o in outs)
    labels_wrong = max(int((o[1] != ref.labels).sum()) for o in outs)
    rank_err = max(float((np.abs(o[2][m] - ref.rank[m]) / ref.rank[m]).max())
                   for o in outs)
    r.counters["refreshes_checked"] = len(outs)
    r.counters["perturbed_core"] = int((start[0] != ref.core).sum())
    r.counters["perturbed_labels"] = int((start[1] != ref.labels).sum())
    r.check("repair_core_wrong", int((repaired[0] != ref.core).sum()), 0)
    r.check("repair_labels_wrong",
            int((repaired[1] != ref.labels).sum()), 0)
    r.check("core_wrong", core_wrong, 0)
    r.check("labels_wrong", labels_wrong, 0)
    r.check("rank_rel_err", rank_err, cell.limits["rank_rel_err"])
    return r
