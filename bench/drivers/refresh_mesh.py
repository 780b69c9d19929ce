"""Back-to-back snapshot refreshes of a hub-split graph on the worker mesh.

The refresh is the serving layer's own call on the batch path, over a
graph whose hubs are split into slices of ``split_threshold`` neighbours
and whose blocks are spread over ``workers`` chips:
``fused_analytics(g, steps, backend, executor=SpmdExecutor(g, W,
**executor), init=(core, labels), mirror=plan)``, with the
configuration's ``executor`` options.  Every superstep gathers the three
fields through the halo exchange across chips and merges each hub's
slices across its replica group.  The split graph is loaded straight
from the edge list (`build_split_blocks`).  The host reference computes
exact coreness and CC labels for the warm start on the original ids, in
a second thread while the split graph loads and its halo plan is built,
and they are then laid on the program's rows.  The window runs
refreshes until the first one that ends at or after its length, each
ending in ``block_until_ready``.

Checked against the host reference on the unsplit graph, at each
logical vertex's primary row (replica rows carry no result of their
own): degree, coreness, labels and PageRank of the last refresh and of
a sample of earlier ones drawn from the seed, and the repair pass of
`drivers/refresh.py`, from a perturbed warm start drawn from the seed.
The repair pass runs in set-up, where it is also the refresh that
compiles the call: at this scale a refresh takes tens of seconds, in
which the host computes the reference PageRank.
"""
from __future__ import annotations

import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import collectives, graphs, reference, traffic
from ..harness import Run, Window, peak_bytes
from .refresh import SAMPLED, perturbed


def run(cell, r: Run) -> Run:
    import jax
    import jax.numpy as jnp
    from repro.core.algorithms import fused_analytics
    from repro.core.hub_split import build_split_blocks
    from repro.runtime.spmd import SpmdExecutor, last_mesh_layout

    cfg = cell.config
    steps, alpha = cfg["refresh"]["pr_steps"], cfg["refresh"]["alpha"]
    n, P = int(cfg["graph"]["n"]), int(cfg["blocks"])
    phases, last = {}, [time.perf_counter()]

    def lap(name):  # seconds of each phase: stderr, and the line's notes
        now = time.perf_counter()
        phases[name] = round(now - last[0], 3)
        last[0] = now
        print(f"phase {name} {phases[name]} s", file=sys.stderr, flush=True)

    edges0 = graphs.generate(cfg["graph"], cell.root)
    assign = graphs.partition(cfg["partitioner"], edges0, n, P,
                              traffic.rng_for(cell.seed, 0), cell.root)
    lap("graph")
    with ThreadPoolExecutor(1) as pool:
        # the host reference peels the graph, in its original ids, while
        # the split graph loads and its halo plan is built
        peeled = pool.submit(_peeled, edges0, n, steps, alpha)
        g, plan = build_split_blocks(edges0, n, assign, P,
                                     cfg["split_threshold"])
        lap("split_load")
        new = graphs.layout(assign, P, g.Cn)
        primary = np.asarray(plan.primary_mask)
        if not (np.array_equal(np.asarray(g.orig_id)[new], np.arange(n))
                and primary[new].all() and int(primary.sum()) == n):
            raise RuntimeError("the split loader lays primary rows out "
                               "differently from bench/graphs.layout")
        del edges0
        mask = np.zeros(g.N, bool)
        mask[new] = True
        ex = SpmdExecutor(g, W=int(cfg["workers"]), **cfg["executor"])
        lap("halo_plan")
        ref = peeled.result()
        want = _at_rows(ref, new, g.N)
        lap("reference")
        init = (jnp.asarray(want["core"], jnp.int32),
                jnp.asarray(want["labels"], jnp.int32))

        def refresh(start=init):
            out = fused_analytics(g, alpha=alpha, steps=steps,
                                  backend=cfg["backend"], executor=ex,
                                  init=start, mirror=plan)
            jax.block_until_ready(out)
            return out

        # set-up: the repair pass, which compiles (or loads from the cache);
        # the host reference's PageRank is computed while the chips work
        start = perturbed(want["core"], want["labels"], np.flatnonzero(mask),
                          traffic.rng_for(cell.seed, 7))
        ranked = pool.submit(lambda: ref.rank)
        repaired = jax.device_get(refresh(tuple(
            jnp.asarray(x, jnp.int32) for x in start)))
        want["rank"] = np.zeros(g.N)
        want["rank"][new] = ranked.result()
    lap("repair_and_compile")
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
    last[0] = t1
    r.e2e["refresh_s"] = (t1 - win.t0) / done
    if r.trace_dir is not None:
        secs = collectives.seconds(r.trace_dir)
        if secs is not None:
            r.counters["collective_s"] = secs
    r.memory_peak_bytes = peak_bytes()
    lay = last_mesh_layout()
    r.counters.update(
        refreshes=done, supersteps=done * steps, n_real=n,
        n_edges=int(len(ref.edges)), chips=ex.wm.W, rows=g.N, Cn=g.Cn,
        replicas=int(np.asarray(g.node_mask).sum()) - n,
        degeneracy=int(want["core"].max()), slots_per_superstep=lay.slots,
        **{f"mesh_{k}": v for k, v in lay._asdict().items() if k != "slots"})
    r.attempted, r.failed = done, 0
    outs = [jax.device_get(o) for o in kept + [out]]
    del kept, out
    ldeg = np.asarray(plan.ldeg)
    del init, g, plan, ex
    m = mask
    core_wrong = max(int((o[0][m] != want["core"][m]).sum()) for o in outs)
    labels_wrong = max(int((o[1][m] != want["labels"][m]).sum())
                       for o in outs)
    rank_err = max(float((np.abs(o[2][m] - want["rank"][m])
                          / want["rank"][m]).max()) for o in outs)
    r.counters["refreshes_checked"] = len(outs)
    r.counters["perturbed_core"] = int((start[0] != want["core"]).sum())
    r.counters["perturbed_labels"] = int(
        (start[1] != want["labels"]).sum())
    r.check("degree_wrong", int((ldeg[m] != want["deg"][m]).sum()), 0)
    r.check("repair_core_wrong",
            int((repaired[0][m] != want["core"][m]).sum()), 0)
    r.check("repair_labels_wrong",
            int((repaired[1][m] != want["labels"][m]).sum()), 0)
    r.check("core_wrong", core_wrong, 0)
    r.check("labels_wrong", labels_wrong, 0)
    r.check("rank_rel_err", rank_err, cell.limits["rank_rel_err"])
    lap("after_window")
    r.notes["phases_s"] = phases
    return r


def _peeled(edges0, n: int, steps: int, alpha: float) -> reference.Epoch:
    """The host reference on the original ids, its coreness and CC
    labels computed (PageRank on first use)."""
    ref = reference.Epoch(graphs.canonical(edges0), np.ones(n, bool),
                          steps, alpha)
    for field in ("core", "labels"):  # each computed on first use
        getattr(ref, field)
    return ref


def _at_rows(ref: reference.Epoch, new: np.ndarray, N: int) -> dict:
    """The reference's degree, coreness and labels at the program's rows
    (original id ``v`` at padded row ``new[v]``; 0, and -1 for labels, on
    every other row).  A label is the least padded row of the component,
    as the program reports it."""
    deg, core = np.zeros(N, np.int64), np.zeros(N, np.int64)
    deg[new], core[new] = ref.deg, ref.core
    least = np.full(len(new), N, np.int64)
    np.minimum.at(least, ref.labels, new)
    labels = np.full(N, -1, np.int64)
    labels[new] = least[ref.labels]
    return {"deg": deg, "core": core, "labels": labels}
