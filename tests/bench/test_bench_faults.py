"""The check that decides ``correct`` fails a broken program.

Each fault breaks the timed path underneath the harness, which then
drives the rest of a run on the CPU at a tiny size (the chip check
skipped) and has to report ``correct`` false, with the number that
caught it over its limit.  The control (the reference's PageRank in
bfloat16, put in the program's place) has to fail too.
"""
import numpy as np
import pytest

import bench_tiny

INGEST = "tiny-social.ingest_small"
REFRESH = "tiny-road.refresh"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("checkout"))


def _apply_unchanged(mp):
    from repro.runtime.stream import StreamSession
    mp.setattr(StreamSession, "apply_window", lambda self, window: None)


def _half_window(mp):
    from repro.runtime.stream import StreamSession
    apply = StreamSession.apply_window
    mp.setattr(StreamSession, "apply_window",
               lambda self, w: apply(self, w[:len(w) // 2]))


def _refresh_unchanged(mp):
    from repro.service.state import AnalyticsState
    refresh = AnalyticsState.refresh

    def stale(self):
        if self._front is None:
            return refresh(self)
        return self._front
    mp.setattr(AnalyticsState, "refresh", stale)


def _answer_altered(mp):
    from repro.service import queries
    run_batch = queries.run_batch

    def altered(snap, kind, qs, k=0):
        out = run_batch(snap, kind, qs, k=k)
        if kind in ("core", "degree", "nbr_max_core"):
            out[0] += 1
        return out
    mp.setattr(queries, "run_batch", altered)


def _control(mp):
    import repro.core.algorithms as alg
    import repro.service.state as state

    from bench import control
    mp.setattr(alg, "fused_analytics", alg.fused_analytics)
    mp.setattr(state, "fused_analytics", state.fused_analytics)
    control.install()


def _pass_unchanged(mp):
    """The refresh hands back its warm start and a PageRank that never
    left the uniform start."""
    import jax.numpy as jnp

    import repro.core.algorithms as alg

    def unchanged(g, alpha=0.85, steps=30, init=None, **kw):
        n = g.node_mask.sum()
        rank = jnp.where(g.node_mask, 1.0 / n, 0.0).astype(jnp.float32)
        return init[0], init[1], rank
    mp.setattr(alg, "fused_analytics", unchanged)


def _start_kept(mp):
    """Coreness and labels handed back as they came in (PageRank right):
    a refresh from the exact values cannot tell."""
    import repro.core.algorithms as alg
    fused = alg.fused_analytics

    def kept(*a, init=None, **kw):
        _, _, rank = fused(*a, init=init, **kw)
        return init[0], init[1], rank
    mp.setattr(alg, "fused_analytics", kept)


def _label_altered(mp):
    import repro.core.algorithms as alg
    fused = alg.fused_analytics

    def altered(*a, **kw):
        core, labels, rank = fused(*a, **kw)
        return core, labels.at[0].add(1), rank
    mp.setattr(alg, "fused_analytics", altered)


CASES = [
    (INGEST, _apply_unchanged, {"graph_pairs_wrong", "stale_publishes"}),
    (INGEST, _half_window, {"graph_pairs_wrong"}),
    (INGEST, _refresh_unchanged, {"stale_publishes"}),
    (INGEST, _answer_altered, {"answers_wrong"}),
    (INGEST, _control, {"rank_rel_err"}),
    (REFRESH, _pass_unchanged, {"rank_rel_err", "repair_core_wrong"}),
    (REFRESH, _start_kept, {"repair_core_wrong", "repair_labels_wrong"}),
    (REFRESH, _label_altered, {"labels_wrong"}),
    (REFRESH, _control, {"rank_rel_err"}),
]


@pytest.mark.parametrize("cell,fault,caught", CASES,
                         ids=[f"{c}-{f.__name__[1:]}" for c, f, _ in CASES])
def test_fault_makes_run_incorrect(root, monkeypatch, cell, fault, caught):
    fault(monkeypatch)
    out = bench_tiny.measure(root, cell)
    failed = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert out["correct"] is False
    assert caught <= failed, out["checks"]


def test_control_rank_is_a_power_iteration():
    """The control computes the reference's PageRank, only coarser."""
    import jax.numpy as jnp

    from bench import control, graphs, reference
    e = graphs.generate({"generator": "ego_circles", "n": 300,
                         "edges": 1200, "egos": [60, 40, 30],
                         "intra": [500, 400, 300], "gamma": 2.2,
                         "dense": [0, 12, 0.8], "bridges": 40, "seed": 1})
    N = 320
    mask = np.zeros(N, bool)
    mask[:300] = True
    A = reference.adjacency(e, N)
    nbr = np.full((N, int(reference.degrees(A).max())), -1, np.int32)
    for u in range(N):
        row = A.indices[A.indptr[u]:A.indptr[u + 1]]
        nbr[u, :row.size] = row
    got = np.asarray(control.rank_bf16(
        jnp.asarray(nbr), jnp.asarray(reference.degrees(A), jnp.int32),
        jnp.asarray(mask), steps=30, alpha=0.85))
    want = reference.pagerank(A, mask, 30, 0.85)
    rel = np.abs(got[mask] - want[mask]) / want[mask]
    assert 1e-3 < rel.max() < 0.2
