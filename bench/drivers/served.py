"""The served path: `StreamSession` -> `QueryServer`, as a service runs it.

Update windows go through ``QueryServer.step`` (apply, refresh the epoch
snapshot, answer the queued reads); reads through ``submit``, a fixed
number of each kind before every window.  Updates wait as a backlog that
never empties: the measured window's work is as many update windows as
fit.

Checked against the host reference after the window: the final graph,
degree, coreness, CC labels and PageRank of the last published snapshot,
and every read answered at a sample of epochs (the last among them),
each against the reference of the graph at its own epoch.
"""
from __future__ import annotations

import time

import numpy as np

from .. import graphs, reference, traffic
from ..harness import Run, Window, build_graph, peak_bytes, ready

#: epochs whose answers are checked, besides the last
SAMPLED_EPOCHS = 3


def _open(cfg: dict, graph):
    """The program as a deployment opens it, on the benchmark's edges."""
    from repro.core import build_blocks
    from repro.core.algorithms import connected_components
    from repro.kernels import ops
    from repro.runtime import StreamSession
    from repro.runtime.spmd import SpmdExecutor
    from repro.service import QueryServer, ServiceConfig

    g = build_blocks(graph.edges0, graph.n, graph.assign, P=graph.P,
                     deg_slack=cfg["deg_slack"])
    if g.Cn != graph.Cn or not np.array_equal(
            np.asarray(g.orig_id)[graph.new], np.arange(graph.n)):
        raise RuntimeError("the loader lays nodes out differently from "
                           "bench/graphs.layout")
    backend, W = cfg["backend"], cfg["workers"]
    ex = SpmdExecutor(g, W=W)
    core = ops.coreness_blocks(g, backend=backend, executor=ex)
    labels = connected_components(g, backend=backend, executor=ex)
    sess = StreamSession(g, core, R=cfg["window"], backend=backend, W=W,
                         executor=ex, cc_labels=labels)
    return QueryServer(sess, config=ServiceConfig(**cfg["service"]))


def _query(t):
    from repro.service import queries as q

    kind, u, v, k = t
    if kind == "same_component":
        return q.same_component(u, v)
    if kind == "topk_pagerank":
        return q.topk_pagerank(k)
    return {"core": q.core_of, "degree": q.degree_of,
            "nbr_max_core": q.nbr_max_core_of}[kind](u)


def _warm_queries(srv, plan, graph, spec: dict) -> None:
    """Compile every query shape this traffic can ask for, and no other:
    each batch bucket of each point kind, each top-k width bucket."""
    from repro.service import queries as q

    if not plan.reads:
        return
    rd = spec["reads"]
    kinds = sorted({t[0] for t in plan.reads})
    sizes = {kd: rd["per_window"][kd] for kd in kinds}
    u = int(graph.real[0])
    for kd in kinds:
        if kd == "topk_pagerank":
            lo, hi = rd.get("topk_k", [1, 1])
            for k in sorted({q.topk_bucket(k, graph.N)
                             for k in range(lo, hi + 1)}):
                srv.submit(_query((kd, u, u, k)))
                srv.pump()
            continue
        for b in sorted({q.batch_bucket(s) for s in range(1, sizes[kd] + 1)}):
            for _ in range(min(b, srv.config.max_batch)):
                srv.submit(_query((kd, u, u, 1)))
            srv.pump()


def _instrument(run: Run, srv) -> None:
    """Traced runs: host spans around apply and refresh, each to ready."""
    sess, state = srv.session, srv.state
    apply0, refresh0 = sess.apply_window, state.refresh

    def apply_window(window):
        with run.span("apply"):
            apply0(window)
            ready((sess.g.nbr, sess.g.deg, sess.core, sess.labels))

    def refresh():
        with run.span("refresh"):
            snap = refresh0()
            ready(snap)
        return snap

    sess.apply_window = apply_window
    state.refresh = refresh


def run(cell, r: Run) -> Run:
    cfg, spec = cell.config, cell.traffic
    R = int(cfg["window"])
    graph = build_graph(cfg, cell.seed, cell.root)
    plan = traffic.generate(spec, graph, cell.seed, R)
    srv = _open(cfg, graph)
    sess = srv.session
    windows = [plan.updates[i:i + R] for i in range(0, len(plan.updates), R)]
    records = []                # (plan index, request or None)
    epoch_windows = {0: 0}      # published epoch -> windows it covers
    log = []                    # the windows applied, in order
    stale = 0
    k = plan.reads_per_window

    def turn(updates) -> float:
        """One serving turn over ``updates``; returns its publish time."""
        nonlocal stale
        log.append(updates)
        srv.step(updates)
        snap = srv.state.snapshot
        ready(snap)
        epoch_windows[snap.epoch] = len(log)
        stale += int(snap.windows != len(log))
        return time.perf_counter()

    def window_turn(wi: int) -> float:
        for i in range(wi * k, (wi + 1) * k):
            records.append((i, srv.submit(_query(plan.reads[i]))))
        return turn(windows[wi])

    # set-up: the first windows compile the maintenance paths
    warm = plan.warm_windows
    for wi in range(warm):
        window_turn(wi)
    _warm_queries(srv, plan, graph, spec)
    if cell.trace:
        _instrument(r, srv)
    stats0 = sess.stats()
    applied = warm  # backlog windows taken
    with Window(r) as win:
        t0 = win.t0
        # whole windows until the first publish at or after the window's
        # length (or the backlog's end: then the traffic file's count is
        # too small for this program)
        while applied < len(windows):
            t = window_turn(applied)
            applied += 1
            if t - t0 >= cell.seconds:
                break
        r.counters["backlog_left"] = len(windows) - applied
        t1 = win.close()
        r.e2e["update_rate"] = R * (applied - warm) / (t1 - t0)
    r.memory_peak_bytes = peak_bytes()
    st = sess.stats()
    win_windows = st.batches - stats0.batches
    r.counters.update(
        windows=win_windows,
        supersteps=(st.bfs_steps - stats0.bfs_steps
                    + st.recompute_steps - stats0.recompute_steps),
        updates=st.updates - stats0.updates,
        reads=len(records), epochs=len(epoch_windows))
    in_window = [rec for rec in records if rec[0] >= warm * k]
    r.attempted = (st.updates - stats0.updates) + len(in_window)
    # a shed read failed; an admitted read never answered breaks the
    # service's guarantee
    unanswered = sum(req is not None and not req.done
                     for _, req in in_window)
    r.failed = unanswered + sum(req is None for _, req in in_window)
    r.counters["shed"] = r.failed - unanswered

    # --- after the window: the program's outputs, then the reference ---
    import jax
    snap = srv.state.snapshot
    got = jax.device_get((snap.nbr, snap.deg, snap.core, snap.labels,
                          snap.rank))
    final_epoch = snap.epoch
    answers = [(i, req.epoch, req.answer) for i, req in records
               if req is not None and req.done]
    del srv, sess, snap
    _compare(r, cell, graph, log, got, final_epoch, answers, epoch_windows,
             plan, stale)
    r.check("unanswered", unanswered, 0)
    return r


def _pairs(nbr: np.ndarray, N: int) -> np.ndarray:
    u, j = np.nonzero(nbr >= 0)
    return np.sort(u.astype(np.int64) * N + nbr[u, j])


def _compare(r: Run, cell, graph, windows, got, final_epoch, answers,
             epoch_windows, plan, stale) -> None:
    """The reference of an epoch is the graph after the windows the
    benchmark had driven when it saw that epoch published; the last
    published snapshot has to cover every window."""
    cfg, N = cell.config, graph.N
    steps, alpha = cfg["service"]["pr_steps"], cfg["service"]["alpha"]
    lim = cell.limits["rank_rel_err"]
    rng = traffic.rng_for(cell.seed, 5)
    read_epochs = sorted({e for _, e, _ in answers} - {final_epoch})
    pick = set(rng.permutation(read_epochs)[:SAMPLED_EPOCHS].tolist())
    pick.add(final_epoch)
    want = {}
    for e in pick:
        want.setdefault(epoch_windows[e], []).append(e)
    # replay the stream once, keeping the graph at each wanted epoch
    keys = set(graphs.keys(graph.edges, N).tolist())
    states, final = {}, None
    for w in range(len(windows) + 1):
        if w in want or w == len(windows):
            arr = np.sort(np.fromiter(keys, np.int64, len(keys)))
            ep = reference.Epoch(reference.edges_of_keys(arr, N), graph.mask,
                                 steps, alpha)
            states.update({e: ep for e in want.get(w, [])})
            final = ep
        if w == len(windows):
            break
        for u, v, op in windows[w]:
            key = min(u, v) * N + max(u, v)
            if op > 0:
                keys.add(key)
            else:
                keys.discard(key)
    ref = final
    nbr, deg, core, labels, rank = got
    e = ref.edges
    want_pairs = np.sort(np.concatenate([e[:, 0] * N + e[:, 1],
                                         e[:, 1] * N + e[:, 0]]))
    have = _pairs(nbr, N)
    r.check("graph_pairs_wrong",
            np.setxor1d(have, want_pairs).size + (have.size
                                                  - np.unique(have).size), 0)
    r.check("degree_wrong", int((deg != ref.deg).sum()), 0)
    r.check("core_wrong", int((core != ref.core).sum()), 0)
    r.check("labels_wrong", int((labels != ref.labels).sum()), 0)
    m = graph.mask
    r.check("rank_rel_err",
            float((np.abs(rank[m] - ref.rank[m]) / ref.rank[m]).max()), lim)
    wrong = checked = 0
    for i, epoch, ans in answers:
        if epoch not in states:
            continue
        checked += 1
        wrong += int(not _right(plan.reads[i], ans, states[epoch], lim))
    r.counters["answers_checked"] = checked
    r.check("answers_wrong", wrong, 0)
    r.check("stale_publishes", stale, 0)


def _right(query, ans, ref, lim: float) -> bool:
    kind, u, v, k = query
    if kind == "core":
        return ans == ref.core[u]
    if kind == "degree":
        return ans == ref.deg[u]
    if kind == "nbr_max_core":
        return ans == ref.nbr_max_core(u)
    if kind == "same_component":
        return ans == bool(ref.labels[u] == ref.labels[v])
    ids, vals = np.asarray(ans[0]), np.asarray(ans[1], np.float64)
    rank = ref.rank
    kth = np.sort(rank[ref.mask])[-k]
    return bool(len(ids) == k and len(set(ids.tolist())) == k
                and ref.mask[ids].all()
                and (rank[ids] >= kth * (1 - lim)).all()
                and (np.abs(vals - rank[ids]) <= lim * rank[ids]).all())
