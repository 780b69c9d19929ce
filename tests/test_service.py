"""Query service correctness: snapshot parity, batching, the serving loop.

Three contracts under test:

  1. **Snapshot parity** — every batched query answer over an epoch
     snapshot is bit-identical to a from-scratch recompute on that
     epoch's graph (`coreness` / `connected_components` /
     `pagerank(tol=None, max_steps=pr_steps)`), on the jnp and ell_spmd
     backends (CI runs this file at 1 AND 8 forced host devices).
  2. **Transfer discipline** — steady-state serving performs exactly ONE
     `jax.device_get` per answered batch, and zero recompiles after
     warmup (gather/query/mesh-step trace counters all hold still).
  3. **The serving loop** — admission control sheds at the bound,
     buckets batch by kind, and the end-to-end interleave (>= 100 mixed
     queries during a multi-window stream) answers everything exactly
     with zero executor rebuilds.
"""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp import given, settings, st

from repro.core import build_blocks, coreness
from repro.core.algorithms import connected_components, pagerank
from repro.core.partition import node_random_partition
from repro.core.updates import sample_deletions, sample_insertions
from repro.graphgen import barabasi_albert
from repro.kernels import ops
from repro.runtime import StreamSession
from repro.runtime import spmd as spmd_mod
from repro.runtime.stream import _iter_windows
from repro.service import (
    AnalyticsState,
    QueryServer,
    ServiceConfig,
    core_of,
    degree_of,
    nbr_max_core_of,
    query_trace_count,
    same_component,
    topk_pagerank,
)
from repro.service.queries import run_batch, topk_bucket

P = 4
PR_STEPS = 10
ALPHA = 0.85


@pytest.fixture
def count_device_get(monkeypatch):
    calls = {"n": 0}
    orig = jax.device_get

    def counting(x):
        calls["n"] += 1
        return orig(x)

    monkeypatch.setattr(jax, "device_get", counting)
    return calls


def _graph(n=140, seed=7):
    edges = barabasi_albert(n, 3, seed=seed)
    nn = int(edges.max()) + 1
    assign = node_random_partition(nn, P, seed=2)
    return build_blocks(edges, nn, assign, P=P, deg_slack=48)


def _mixed_updates(g, count=16, seed=11):
    per = max(1, count // 4)
    return (sample_insertions(g, per, "inter", seed=seed)
            + sample_insertions(g, per, "intra", seed=seed + 1)
            + sample_deletions(g, per, "inter", seed=seed + 2)
            + sample_deletions(g, per, "intra", seed=seed + 3))


def _open_session(g, backend, R=4):
    core = coreness(g, backend="jnp")
    labels = connected_components(g, backend="jnp")
    return StreamSession(g, core, R=R, backend=backend, cc_labels=labels)


def _epoch_graph(g0, snap):
    """The snapshot's topology as a GraphBlocks — the recompute target."""
    return dataclasses.replace(
        g0, nbr=snap.nbr, deg=snap.deg, node_mask=snap.node_mask,
        orig_id=snap.orig_id)


def _epoch_oracle(g0, snap, backend):
    """From-scratch recompute of every queryable field on snap's graph.

    Same backend as the serving session: int fields are cross-backend
    bit-identical anyway, float32 PageRank only within its own backend.
    """
    eg = _epoch_graph(g0, snap)
    return {
        "core": np.asarray(coreness(eg, backend=backend)),
        "labels": np.asarray(connected_components(eg, backend=backend)),
        "rank": np.asarray(pagerank(eg, alpha=ALPHA, tol=None,
                                    max_steps=PR_STEPS, backend=backend)),
        "deg": np.asarray(eg.deg),
        "nbr": np.asarray(eg.nbr),
        "N": eg.N,
    }


@functools.lru_cache(maxsize=None)
def _serving_trace(backend):
    """One serving run per backend: [(EpochSnapshot, oracle), ...].

    Epoch 0 is the pre-stream graph; every later epoch follows one more
    applied window (refresh_every=1), including delete windows (CC
    recompute path) and insert windows (merge path).
    """
    g = _graph()
    sess = _open_session(g, backend)
    state = AnalyticsState(sess, alpha=ALPHA, pr_steps=PR_STEPS)
    g0 = _graph()  # fresh arrays: sess donated g's buffers
    trace = [(state.snapshot, _epoch_oracle(g0, state.snapshot, backend))]
    for window in _iter_windows(_mixed_updates(g0), 4):
        sess.apply_window(window)
        snap = state.refresh()
        trace.append((snap, _epoch_oracle(g0, snap, backend)))
    return trace


@settings(max_examples=10, deadline=None)
@given(st.sampled_from(["jnp", "ell_spmd"]), st.integers(0, 10_000))
def test_batched_queries_bit_identical_to_epoch_recompute(backend, seed):
    """Hypothesis parity: random mixed batches against every epoch of a
    mixed-update serving run answer bit-identically to recomputation."""
    rng = np.random.default_rng(seed)
    for snap, ora in _serving_trace(backend):
        real = np.flatnonzero(np.asarray(snap.node_mask))
        n_q = int(rng.integers(1, 24))
        us = rng.choice(real, n_q)
        vs = rng.choice(real, n_q)

        got = run_batch(snap, "core", [core_of(u) for u in us])
        assert got == [int(x) for x in ora["core"][us]]
        got = run_batch(snap, "degree", [degree_of(u) for u in us])
        assert got == [int(x) for x in ora["deg"][us]]

        got = run_batch(snap, "nbr_max_core",
                        [nbr_max_core_of(u) for u in us])
        for u, ans in zip(us, got):
            row = ora["nbr"][u]
            nbrs = row[row >= 0]
            ref = int(ora["core"][nbrs].max()) if nbrs.size else -1
            assert ans == ref

        got = run_batch(snap, "same_component",
                        [same_component(u, v) for u, v in zip(us, vs)])
        assert got == [bool(ora["labels"][u] == ora["labels"][v])
                       for u, v in zip(us, vs)]

        k = int(rng.integers(1, 12))
        kk = topk_bucket(k, ora["N"])
        [(ids, ranks)] = run_batch(snap, "topk_pagerank",
                                   [topk_pagerank(k)], k=kk)
        ref_vals, ref_ids = jax.device_get(
            jax.lax.top_k(jnp.asarray(ora["rank"]), kk))
        assert ids == ref_ids[:k].tolist()
        assert ranks == ref_vals[:k].tolist()  # float bit-equality


def test_snapshot_survives_buffer_donation():
    """Applying more windows donates the live graph's buffers; an already
    published snapshot must stay readable (copies, not references)."""
    g = _graph()
    sess = _open_session(g, "jnp")
    state = AnalyticsState(sess, alpha=ALPHA, pr_steps=PR_STEPS)
    snap0 = state.snapshot
    for window in _iter_windows(_mixed_updates(_graph()), 4):
        sess.apply_window(window)
    # the epoch-0 snapshot still answers without touching donated buffers
    real = np.flatnonzero(np.asarray(snap0.node_mask))
    got = run_batch(snap0, "core", [core_of(int(real[0]))])
    assert got == [int(np.asarray(snap0.core)[real[0]])]


def test_one_device_get_per_answered_batch(count_device_get):
    """Steady-state serving: each answered batch costs exactly ONE
    transfer, regardless of how many queries it carries."""
    g = _graph()
    sess = _open_session(g, "jnp")
    srv = QueryServer(sess, config=ServiceConfig(
        refresh_every=1, pr_steps=PR_STEPS, alpha=ALPHA, max_batch=32))
    real = np.flatnonzero(np.asarray(srv.state.snapshot.node_mask))
    # warm the compiled caches with one batch per kind
    for u in real[:8]:
        srv.submit(core_of(u))
        srv.submit(degree_of(u))
        srv.submit(same_component(u, real[0]))
    srv.pump()

    for u in real[:16]:
        srv.submit(core_of(u))        # 16 queries -> 1 batch
        srv.submit(degree_of(u))      # 16 queries -> 1 batch
    for u in real[:4]:
        srv.submit(same_component(u, real[1]))  # 4 queries -> 1 batch
    count_device_get["n"] = 0
    answered = srv.pump()
    assert answered == 36
    assert count_device_get["n"] == 3, count_device_get["n"]


def test_admission_control_sheds_at_the_bound():
    g = _graph()
    sess = _open_session(g, "jnp")
    srv = QueryServer(sess, config=ServiceConfig(
        max_queue=8, pr_steps=PR_STEPS))
    real = np.flatnonzero(np.asarray(srv.state.snapshot.node_mask))
    results = [srv.submit(core_of(real[i % len(real)])) for i in range(12)]
    assert sum(r is not None for r in results) == 8
    assert srv.metrics.total_shed == 4
    assert srv.queued == 8
    assert srv.pump() == 8
    assert all(r.done for r in results if r is not None)
    s = srv.metrics.summary()
    assert s["answered"] == 8 and s["shed"] == 4
    assert np.isfinite(s["p50_ms"]) and np.isfinite(s["p99_ms"])


def test_submit_validates_ids_and_kinds():
    from repro.service import Query

    g = _graph()
    sess = _open_session(g, "jnp")
    srv = QueryServer(sess, config=ServiceConfig(pr_steps=PR_STEPS))
    with pytest.raises(ValueError, match="kind"):
        srv.submit(Query("bogus"))
    with pytest.raises(ValueError, match="outside"):
        srv.submit(core_of(g.N + 5))
    with pytest.raises(ValueError, match="outside"):
        srv.submit(topk_pagerank(g.N + 1))


def test_e2e_serving_mid_stream_exact_zero_rebuilds_zero_recompiles():
    """The acceptance run: a StreamSession and the query server
    interleaved on the worker mesh — >= 100 mixed queries answered
    during a multi-window update stream, every answer bit-identical to
    recompute on its epoch's graph, zero executor rebuilds, and zero
    recompiles after warmup (gather/query/mesh-step counters)."""
    g = _graph(n=160, seed=9)
    g0 = _graph(n=160, seed=9)
    sess = _open_session(g, "ell_spmd")
    srv = QueryServer(sess, config=ServiceConfig(
        refresh_every=1, pr_steps=PR_STEPS, alpha=ALPHA, max_batch=64))
    ups = _mixed_updates(g0, count=32, seed=21)
    # interleave inserts and deletes so EVERY window carries both ops:
    # warmup (the first half) then traces the merge path AND the
    # delete-triggered CC recompute path
    ins, dels = ups[:16], ups[16:]
    ups = [u for pair in zip(ins, dels) for u in pair]
    windows = list(_iter_windows(ups, 4))
    assert len(windows) == 8

    rng = np.random.default_rng(3)
    real = np.flatnonzero(np.asarray(srv.state.snapshot.node_mask))
    requests = []

    def feed():
        out = []
        for _ in range(4):
            u, v = (int(x) for x in rng.choice(real, 2))
            out += [core_of(u), degree_of(u), nbr_max_core_of(u),
                    same_component(u, v), topk_pagerank(5)]
        return out

    def play(ws):
        for w in ws:
            for query in feed():
                req = srv.submit(query)
                assert req is not None
                requests.append(req)
            srv.step(w)

    play(windows[:4])  # warmup: insert AND delete windows, all kinds
    # the refresh reads a hybrid head and tail (`SpmdExecutor.split`),
    # and the measured windows insert and delete edges at its wide rows:
    # the split's pow2 buckets hold, so nothing retraces per window
    split = sess.executor.split
    assert 0 < split.head_cols < split.cols and split.tail_padded
    lay = spmd_mod.last_mesh_layout()
    assert (lay.head_cols, lay.tail_padded) == (split.head_cols,
                                                split.tail_padded)
    deg = np.asarray(sess.g.deg)
    for op in (1, -1):
        assert any(o == op and max(deg[u], deg[v]) > split.head_cols
                   for w in windows[4:] for u, v, o in w), op
    traces0 = (ops.gather_trace_count(), query_trace_count(),
               spmd_mod.step_build_count())
    play(windows[4:])
    assert (ops.gather_trace_count(), query_trace_count(),
            spmd_mod.step_build_count()) == traces0  # ZERO recompiles
    assert sess.executor.full_rebuilds == 0          # ZERO rebuilds
    srv.pump()

    assert len(requests) >= 100 and all(r.done for r in requests)
    assert srv.metrics.total_answered == len(requests)
    assert srv.metrics.qps() > 0

    # every answer == recompute on its epoch's graph, bit-identical:
    # replay the same stream to rebuild each epoch's graph + oracle
    epochs = sorted({r.epoch for r in requests})
    assert epochs == list(range(min(epochs), max(epochs) + 1))
    sess2 = _open_session(_graph(n=160, seed=9), "ell_spmd")
    state2 = AnalyticsState(sess2, alpha=ALPHA, pr_steps=PR_STEPS)
    oracles = {0: _epoch_oracle(g0, state2.snapshot, "ell_spmd")}
    for w in windows:
        sess2.apply_window(w)
        snap = state2.refresh()
        oracles[snap.epoch] = _epoch_oracle(g0, snap, "ell_spmd")
    for r in requests:
        ora = oracles[r.epoch]
        q = r.query
        if q.kind == "core":
            assert r.answer == int(ora["core"][q.u])
        elif q.kind == "degree":
            assert r.answer == int(ora["deg"][q.u])
        elif q.kind == "nbr_max_core":
            row = ora["nbr"][q.u]
            nbrs = row[row >= 0]
            ref = int(ora["core"][nbrs].max()) if nbrs.size else -1
            assert r.answer == ref
        elif q.kind == "same_component":
            assert r.answer == bool(ora["labels"][q.u]
                                    == ora["labels"][q.v])
        else:
            kk = topk_bucket(q.k, ora["N"])
            vals, ids = jax.device_get(
                jax.lax.top_k(jnp.asarray(ora["rank"]), kk))
            assert r.answer == (ids[:q.k].tolist(), vals[:q.k].tolist())

    # the final session state is exact too
    res = sess.result()
    assert (np.asarray(res.core)
            == np.asarray(coreness(res.g, backend="jnp"))).all()
    assert (np.asarray(res.labels)
            == np.asarray(connected_components(res.g,
                                               backend="jnp"))).all()


def test_serve_drains_and_reports_staleness():
    """`serve` with a cadenced refresh: staleness stays <= refresh_every
    and the final drain leaves nothing queued."""
    g = _graph()
    sess = _open_session(g, "jnp")
    srv = QueryServer(sess, config=ServiceConfig(
        refresh_every=2, pr_steps=PR_STEPS))
    real = np.flatnonzero(np.asarray(srv.state.snapshot.node_mask))

    def feed(i):
        return [core_of(int(real[i % len(real)])), topk_pagerank(3)]

    res = srv.serve(list(_mixed_updates(_graph())), feed)
    assert srv.queued == 0
    assert srv.metrics.total_answered == 8
    assert srv.metrics.staleness_max() <= 2
    assert res.stats.batches == 4
