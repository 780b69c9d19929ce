"""Cross-backend parity of the BlockProgram workloads (tentpole acceptance).

Connected components, PageRank, and triangle counting must agree on the
dense, ell, and ell_spmd registry backends AND with a host reference
(networkx for CC/triangles, a straight numpy power iteration for
PageRank) — on hypothesis-random ragged graphs, with Cd not a multiple
of 128, and on single-block (P = 1) meshes.  The fused fixpoints must
also keep the PR-4 contract: zero per-superstep `jax.device_get`s.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import networkx as nx

from _hyp import given, settings, st

from repro.core import (
    build_blocks, connected_components, coreness, merge_labels, pagerank,
    triangle_counts, triangle_total,
)
from repro.core.algorithms import (
    ConnectedComponentsProgram, CorenessBlockProgram, PageRankProgram,
    TriangleCountProgram,
)
from repro.core.updates import sample_deletions, sample_insertions
from repro.graphgen import barabasi_albert
from repro.kernels import ops, ref
from repro.runtime import run_stream

from _road import road_graph

ALL_BACKENDS = ("jnp", "dense", "ell", "ell_spmd")


# ---------------------------------------------------------------------------
# construction + host oracles
# ---------------------------------------------------------------------------


def _rand_graph(n, m, P, seed):
    """Random graph with a random block assignment (ragged Cd, never a
    multiple of 128 at these sizes)."""
    rng = np.random.default_rng(seed)
    uv = rng.integers(0, n, (max(1, m), 2))
    uv = uv[uv[:, 0] != uv[:, 1]]
    if not len(uv):
        uv = np.array([[0, 1]]) if n >= 2 else np.zeros((0, 2), np.int64)
    assign = rng.integers(0, P, n)
    return build_blocks(uv, n, assign, P=P,
                        deg_slack=int(rng.integers(3, 11)))


def _nx_graph(g):
    """Rebuild the graph in padded-id space for the networkx oracles."""
    G = nx.Graph()
    G.add_nodes_from(np.flatnonzero(np.asarray(g.node_mask)).tolist())
    nbr = np.asarray(g.nbr)
    us, vs = np.nonzero(nbr >= 0)
    G.add_edges_from(zip(us.tolist(), nbr[us, vs].tolist()))
    return G


def _cc_ref(g):
    """Canonical labels (min member padded id), -1 on padding rows."""
    want = np.full(g.N, -1, np.int64)
    for comp in nx.connected_components(_nx_graph(g)):
        want[list(comp)] = min(comp)
    return want


def _tri_ref(g):
    want = np.zeros(g.N, np.int64)
    for u, t in nx.triangles(_nx_graph(g)).items():
        want[u] = t
    return want


def _pagerank_ref(g, alpha=0.85, tol=1e-8, max_steps=500):
    """The documented semantics in plain numpy: teleport over real nodes,
    push contributions rank/deg, dangling mass NOT redistributed."""
    mask = np.asarray(g.node_mask)
    deg = np.asarray(g.deg)
    nbr = np.asarray(g.nbr)
    n_real = max(1, int(mask.sum()))
    r = np.where(mask, 1.0 / n_real, 0.0).astype(np.float32)
    for _ in range(max_steps):
        contrib = np.where(deg > 0, r / np.maximum(deg, 1), 0).astype(
            np.float32)
        red = np.where(nbr >= 0, contrib[np.clip(nbr, 0, None)], 0).sum(1)
        r2 = np.where(mask, (1 - alpha) / n_real + alpha * red, 0).astype(
            np.float32)
        done = np.abs(r2 - r).max(initial=0) <= tol
        r = r2
        if done:
            break
    return r


# ---------------------------------------------------------------------------
# kernel-level parity of the new combines at ragged shapes
# ---------------------------------------------------------------------------


def _ragged_ell(n, cd, seed):
    rng = np.random.default_rng(seed)
    nbr = np.full((n, cd), -1, np.int32)
    degs = rng.integers(0, cd + 1, n)
    degs[rng.random(n) < 0.2] = 0  # force all-padding rows
    for i in range(n):
        nbr[i, : degs[i]] = rng.integers(0, n, degs[i])
    return jnp.asarray(nbr)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 150), st.integers(1, 170), st.integers(0, 10_000))
def test_min_sum_kernels_match_oracles_ragged(n, cd, seed):
    """Cd deliberately spans non-multiples of 128 (wrapper pads)."""
    nbr = _ragged_ell(n, cd, seed)
    rng = np.random.default_rng(seed + 1)
    fi = jnp.asarray(rng.integers(-5, n + 5, n).astype(np.int32))
    got = np.asarray(ops.neighbor_min_ell(nbr, fi, interpret=True))
    np.testing.assert_array_equal(got, np.asarray(ref.ell_min_ref(nbr, fi)))
    ff = jnp.asarray(rng.random(n).astype(np.float32))
    got = np.asarray(ops.neighbor_sum_ell(nbr, ff, interpret=True))
    np.testing.assert_allclose(
        got, np.asarray(ref.ell_sum_ref(nbr, ff)), rtol=1e-6)


@settings(max_examples=6, deadline=None)
@given(st.integers(2, 60), st.integers(1, 12), st.integers(0, 10_000))
def test_common_kernel_matches_oracle_ragged(n, cd, seed):
    nbr = _ragged_ell(n, cd, seed)
    got = np.asarray(ops.neighbor_common_ell(nbr, nbr, interpret=True))
    np.testing.assert_array_equal(
        got, np.asarray(ref.ell_common_ref(nbr, nbr)))


def test_combine_dispatch_rejects_unknowns():
    g = _rand_graph(10, 20, 2, 0)
    with pytest.raises(ValueError, match="combine"):
        ops.neighbor_combine_blocks(g, jnp.zeros(g.N, jnp.int32), "bogus",
                                    backend="jnp")
    with pytest.raises(ValueError, match="ell_spmd"):
        ops.neighbor_combine_blocks(g, jnp.zeros(g.N, jnp.int32), "min",
                                    backend="ell_spmd")


# ---------------------------------------------------------------------------
# workload parity: dense == ell == ell_spmd == host reference
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(st.integers(2, 90), st.integers(1, 200), st.sampled_from([1, 2, 4]),
       st.integers(0, 10_000))
def test_connected_components_parity_all_backends(n, m, P, seed):
    g = _rand_graph(n, m, P, seed)
    want = _cc_ref(g)
    for b in ALL_BACKENDS:
        got = np.asarray(connected_components(g, backend=b))
        np.testing.assert_array_equal(got, want, err_msg=f"backend={b}")


@settings(max_examples=6, deadline=None)
@given(st.integers(2, 70), st.integers(1, 160), st.sampled_from([1, 2, 4]),
       st.integers(0, 10_000))
def test_triangle_counts_parity_all_backends(n, m, P, seed):
    g = _rand_graph(n, m, P, seed)
    want = _tri_ref(g)
    total = int(want.sum()) // 3
    for b in ALL_BACKENDS:
        got = np.asarray(triangle_counts(g, backend=b))
        np.testing.assert_array_equal(got, want, err_msg=f"backend={b}")
        assert int(triangle_total(jnp.asarray(got))) == total


@settings(max_examples=6, deadline=None)
@given(st.integers(2, 90), st.integers(1, 200), st.sampled_from([1, 2, 4]),
       st.integers(0, 10_000))
def test_pagerank_parity_all_backends(n, m, P, seed):
    g = _rand_graph(n, m, P, seed)
    want = _pagerank_ref(g)
    for b in ALL_BACKENDS:
        got = np.asarray(pagerank(g, tol=1e-8, max_steps=500, backend=b))
        np.testing.assert_allclose(got, want, atol=2e-6,
                                   err_msg=f"backend={b}")


def test_pagerank_fixed_iteration_variant_runs_exactly_max_steps():
    g = _rand_graph(40, 90, 2, 3)
    for b in ALL_BACKENDS:
        r, steps = pagerank(g, tol=None, max_steps=7, backend=b,
                            with_steps=True)
        assert int(steps) == 7, (b, int(steps))
    # the tolerance-halt variant stops early on the same graph
    _, steps = pagerank(g, tol=1e-3, max_steps=500, backend="jnp",
                        with_steps=True)
    assert int(steps) < 500


def test_coreness_block_program_matches_dedicated_fixpoint():
    """The contract subsumes coreness: CorenessBlockProgram == kcore path."""
    g = _rand_graph(60, 150, 4, 7)
    want = np.asarray(coreness(g, backend="jnp"))
    for b in ALL_BACKENDS:
        est = ops.run_block_program(g, CorenessBlockProgram(), backend=b)
        np.testing.assert_array_equal(np.asarray(est), want,
                                      err_msg=f"backend={b}")


def test_cd_over_128_and_unaligned():
    """An explicit Cd = 130 (> lane width, % 128 != 0) graph."""
    edges = barabasi_albert(90, 5, seed=2)
    n = int(edges.max()) + 1
    g = build_blocks(edges, n, np.zeros(n, np.int64), P=1, Cd=130)
    np.testing.assert_array_equal(
        np.asarray(connected_components(g, backend="ell")), _cc_ref(g))
    np.testing.assert_array_equal(
        np.asarray(triangle_counts(g, backend="ell")), _tri_ref(g))
    np.testing.assert_allclose(
        np.asarray(pagerank(g, tol=1e-8, max_steps=500, backend="ell")),
        _pagerank_ref(g), atol=2e-6)


def test_single_block_mesh_spmd():
    """P = 1: the whole graph folds onto one worker; the mesh path must
    still serve every workload (halo plan with no cross-worker edges)."""
    edges = barabasi_albert(50, 3, seed=5)
    n = int(edges.max()) + 1
    g = build_blocks(edges, n, np.zeros(n, np.int64), P=1, deg_slack=9)
    np.testing.assert_array_equal(
        np.asarray(connected_components(g, backend="ell_spmd")), _cc_ref(g))
    np.testing.assert_array_equal(
        np.asarray(triangle_counts(g, backend="ell_spmd")), _tri_ref(g))
    np.testing.assert_allclose(
        np.asarray(pagerank(g, tol=1e-8, max_steps=500,
                            backend="ell_spmd")),
        _pagerank_ref(g), atol=2e-6)


def test_spmd_executor_threading_reuses_one_executor(monkeypatch):
    from repro.runtime import SpmdExecutor
    from repro.runtime import spmd as spmd_mod

    g = _rand_graph(60, 140, 4, 11)
    ex = SpmdExecutor(g)
    built = {"n": 0}
    orig_init = spmd_mod.SpmdExecutor.__init__

    def counting_init(self, *a, **kw):
        built["n"] += 1
        return orig_init(self, *a, **kw)

    monkeypatch.setattr(spmd_mod.SpmdExecutor, "__init__", counting_init)
    connected_components(g, backend="ell_spmd", executor=ex)
    pagerank(g, tol=1e-6, backend="ell_spmd", executor=ex)
    triangle_counts(g, backend="ell_spmd", executor=ex)
    assert built["n"] == 0, "run_block_program built a fresh SpmdExecutor"


# ---------------------------------------------------------------------------
# zero per-superstep host transfers (PR-4 contract, counter-asserted)
# ---------------------------------------------------------------------------


def _path_graph(n=96, P=1):
    """A path: min-label propagation walks it end to end, so the CC
    fixpoint takes O(n) supersteps."""
    edges = np.stack([np.arange(n - 1), np.arange(1, n)], 1)
    return build_blocks(edges, n, np.zeros(n, np.int64) if P == 1 else
                        (np.arange(n) * P) // n, P=P, deg_slack=6)


@pytest.fixture
def count_device_get(monkeypatch):
    calls = {"n": 0}
    orig = jax.device_get

    def counting(x):
        calls["n"] += 1
        return orig(x)

    monkeypatch.setattr(jax, "device_get", counting)
    return calls


def test_workload_fixpoints_transfer_count_is_o1(count_device_get):
    g = _path_graph()
    for b in ("jnp", "dense", "ell"):
        # the ELL fixpoint reads its degree bound once, before the loop
        # (like `coreness_blocks`); no backend transfers per superstep
        entry = 1 if b == "ell" else 0
        count_device_get["n"] = 0
        labels, steps = connected_components(g, backend=b, with_steps=True)
        assert count_device_get["n"] == entry, (b, count_device_get["n"])
        assert hasattr(steps, "dtype")  # device scalar, not a host int
        assert int(steps) > 20, (b, int(steps))
        count_device_get["n"] = 0
        pagerank(g, tol=1e-8, max_steps=300, backend=b)
        assert count_device_get["n"] == entry, (b, count_device_get["n"])
        count_device_get["n"] = 0
        triangle_counts(g, backend=b)
        assert count_device_get["n"] == 0, (b, count_device_get["n"])


def test_workload_fixpoints_transfer_count_is_o1_with_tail(
        count_device_get):
    """The hybrid split is read in the same one transfer per fixpoint."""
    g = road_graph(768, 4, 12, Cn=384)
    count_device_get["n"] = 0
    _, steps = connected_components(g, backend="ell", with_steps=True)
    assert count_device_get["n"] == 1, count_device_get["n"]
    assert ops.last_hybrid_split().tail_rows == 4
    assert int(steps) > 10  # many supersteps, still one transfer


def test_workload_fixpoint_spmd_one_transfer_per_run(count_device_get):
    g = _path_graph(64, P=2)
    count_device_get["n"] = 0
    _, steps = connected_components(g, backend="ell_spmd", with_steps=True)
    assert int(steps) > 20
    # ONE device_get per run (the fused loop's superstep count), never
    # one per superstep
    assert count_device_get["n"] <= 2, (count_device_get["n"], int(steps))


# ---------------------------------------------------------------------------
# the hybrid ELL (a narrow head for every row, a tail of the wide rows)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,hubs,Cn", [(700, 1, 350), (768, 4, 384)])
def test_hybrid_workloads_match_oracles(n, hubs, Cn):
    """Standalone CC, coreness and PageRank on a split `ell` adjacency."""
    g = road_graph(n, hubs, 12, Cn=Cn)
    labels = connected_components(g, backend="ell")
    split = ops.last_hybrid_split()
    assert (split.head_cols, split.tail_rows) == (4, hubs)
    np.testing.assert_array_equal(np.asarray(labels), _cc_ref(g))
    np.testing.assert_array_equal(
        np.asarray(ops.run_block_program(g, CorenessBlockProgram(),
                                         backend="ell")),
        np.asarray(coreness(g, backend="jnp")))
    np.testing.assert_allclose(
        np.asarray(pagerank(g, tol=1e-8, max_steps=500, backend="ell")),
        _pagerank_ref(g), atol=2e-6)


def test_hybrid_mirrored_hub_split():
    """A hub-split graph's slices pick their own split from the rows'
    fills; the mirrored `ell` run equals the mirrored jnp run."""
    from repro.core.algorithms import fused_analytics
    from repro.core.hub_split import split_hubs

    g2, plan = split_hubs(road_graph(700, 4, 12, node_slack=32),
                          threshold=8)
    got = fused_analytics(g2, steps=30, backend="ell", mirror=plan)
    split = ops.last_hybrid_split()
    assert split.cols == 8 and split.head_cols == 4 and split.tail_rows > 0
    want = fused_analytics(g2, steps=30, backend="jnp", mirror=plan)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=1e-6, atol=0)


# ---------------------------------------------------------------------------
# dynamic CC in the stream loop
# ---------------------------------------------------------------------------


def test_merge_labels_insert_only_is_exact():
    g = _rand_graph(70, 60, 2, 21)
    labels = connected_components(g, backend="jnp")
    ups = sample_insertions(g, 8, "inter", seed=22)
    us = jnp.asarray([u for u, _, _ in ups], jnp.int32)
    vs = jnp.asarray([v for _, v, _ in ups], jnp.int32)
    from repro.core.updates import apply_updates_host

    g2 = apply_updates_host(g, ups)
    merged = merge_labels(labels, us, vs, jnp.ones(len(ups), bool))
    np.testing.assert_array_equal(
        np.asarray(merged), np.asarray(connected_components(g2,
                                                            backend="jnp")))


@pytest.mark.parametrize("backend", ["jnp", "ell_spmd"])
def test_run_stream_maintains_cc_labels(backend):
    edges = barabasi_albert(120, 3, seed=31)
    n = int(edges.max()) + 1
    rng = np.random.default_rng(32)
    g = build_blocks(edges, n, rng.integers(0, 4, n), P=4, deg_slack=24)
    core = coreness(g, backend="jnp")
    labels0 = connected_components(g, backend="jnp")
    ups = (sample_insertions(g, 6, "inter", seed=33)
           + sample_deletions(g, 3, "intra", seed=34)
           + sample_insertions(g, 5, "intra", seed=35))
    res = run_stream(
        g, core, list(ups), R=4, backend=backend, cc_labels=labels0)
    g2, core2, stats, labels = res.g, res.core, res.stats, res.labels
    np.testing.assert_array_equal(
        np.asarray(labels),
        np.asarray(connected_components(g2, backend="jnp")))
    assert stats.cc_merges + stats.cc_recomputes > 0
    # exactness of the coreness path is untouched
    np.testing.assert_array_equal(
        np.asarray(coreness(g2, backend="jnp")), np.asarray(core2))


def test_run_stream_insert_only_cc_never_recomputes():
    edges = barabasi_albert(100, 3, seed=41)
    n = int(edges.max()) + 1
    rng = np.random.default_rng(42)
    g = build_blocks(edges, n, rng.integers(0, 4, n), P=4, deg_slack=24)
    core = coreness(g, backend="jnp")
    labels0 = connected_components(g, backend="jnp")
    ups = sample_insertions(g, 8, "inter", seed=43)
    res = run_stream(g, core, list(ups), R=4, cc_labels=labels0)
    g2, stats, labels = res.g, res.stats, res.labels
    assert stats.cc_recomputes == 0
    assert stats.cc_merges == len(ups)
    np.testing.assert_array_equal(
        np.asarray(labels),
        np.asarray(connected_components(g2, backend="jnp")))
