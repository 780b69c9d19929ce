"""Device-resident fixpoints: kernel-variant parity, fused-loop equivalence,
and the zero-per-superstep-host-transfer contract.

Three acceptance surfaces of the device-residency work:

  * hypothesis parity of the sort-based h-index kernel and the chunked
    frontier kernel vs the `ref.py` oracles at ragged N/Cd — including Cd
    not a multiple of 128, all-padding rows, and the max-degree column
    bound K < Cd (left-filled rows);
  * fused `lax.while_loop` fixpoints == the pre-refactor host-driven loop,
    bit-exact coreness AND identical superstep counts, on every backend;
  * `jax.device_get` call counting: a fixpoint performs O(1) host
    transfers regardless of its superstep count, and `run_stream`'s window
    routing performs one transfer per window, never per superstep.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp import given, settings, st

from repro.core import build_blocks, coreness
from repro.core.partition import node_random_partition
from repro.graphgen import barabasi_albert, erdos_renyi
from repro.kernels import ops, ref
from repro.runtime import run_stream

ALL_BACKENDS = ("jnp", "dense", "ell")


# ---------------------------------------------------------------------------
# ragged-shape construction helpers
# ---------------------------------------------------------------------------


def _ragged_ell(n, cd, seed):
    """Left-filled ELL rows with ragged degrees (some rows all padding)."""
    rng = np.random.default_rng(seed)
    nbr = np.full((n, cd), -1, np.int32)
    degs = rng.integers(0, cd + 1, n)
    degs[rng.random(n) < 0.2] = 0  # force all-padding rows
    for i in range(n):
        nbr[i, : degs[i]] = rng.integers(0, n, degs[i])
    est = rng.integers(0, n + 2, n).astype(np.int32)
    return jnp.asarray(nbr), jnp.asarray(est), int(degs.max(initial=0))


# ---------------------------------------------------------------------------
# kernel parity at ragged shapes (hypothesis)
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 150), st.integers(1, 170), st.integers(0, 10_000))
def test_hindex_ell_variants_match_oracle_ragged(n, cd, seed):
    """Cd deliberately spans non-multiples of 128 (wrapper pads)."""
    nbr, est, _ = _ragged_ell(n, cd, seed)
    got = np.asarray(ops.hindex_ell(nbr, est, interpret=True))
    want = np.asarray(ref.ell_hindex_ref(nbr, est))
    np.testing.assert_array_equal(got, want)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 120), st.integers(1, 300), st.integers(0, 10_000))
def test_hindex_ell_degree_bound_K_exact_on_left_filled(n, cd, seed):
    """K from the pow2-bucketed max degree (possibly < padded Cd) is exact
    because GraphBlocks rows are left-filled — the `degree_bound` policy."""
    nbr, est, max_deg = _ragged_ell(n, cd, seed)
    K = ops._pow2_bucket(max(1, max_deg))
    got = np.asarray(ops.hindex_ell(nbr, est, K=K, interpret=True))
    want = np.asarray(ref.ell_hindex_ref(nbr, est))
    np.testing.assert_array_equal(got, want)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 120), st.integers(1, 150), st.integers(1, 6),
       st.integers(0, 10_000))
def test_frontier_ell_chunked_matches_oracle_ragged(n, cd, R, seed):
    nbr, _, max_deg = _ragged_ell(n, cd, seed)
    rng = np.random.default_rng(seed + 1)
    f = jnp.asarray(rng.random((n, R)) < 0.15)
    elig = jnp.asarray(rng.random((n, R)) < 0.7)
    vis = jnp.asarray(rng.random((n, R)) < 0.1)
    want = np.asarray(ref.ell_frontier_hop_ref(nbr, f, elig, vis))
    got = np.asarray(
        ops.frontier_step_ell(nbr, f, elig, vis, interpret=True)) > 0
    np.testing.assert_array_equal(got, want)
    # degree-bounded column sweep (left-filled rows)
    K = ops._pow2_bucket(max(1, max_deg))
    got_k = np.asarray(
        ops.frontier_step_ell(nbr, f, elig, vis, interpret=True, K=K)) > 0
    np.testing.assert_array_equal(got_k, want)


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 150), st.integers(1, 40), st.integers(0, 10_000))
def test_ell_narrow_degree_columns_match_oracle(n, cd, seed):
    """A low max degree gathers a narrow power-of-two column bucket
    (8..64 slots, not a 128-lane row); every combine stays exact."""
    nbr, est, max_deg = _ragged_ell(n, cd, seed)
    K = ops._pow2_bucket(max(1, max_deg), floor=ops.ELL_MIN_COLS)
    assert ops._pad_ell(nbr, K, 256)[1] == ops._ell_cols(K) <= max(8, K)
    lab = jnp.arange(n, dtype=jnp.int32)[::-1]
    contrib = (est % 7).astype(jnp.float32) / 7
    got = ops.neighbor_multi_ell(nbr, (est, lab, contrib),
                                 ("hindex", "min", "sum"), K=K,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.asarray(ref.ell_hindex_ref(nbr, est)))
    np.testing.assert_array_equal(np.asarray(got[1]),
                                  np.asarray(ref.ell_min_ref(nbr, lab)))
    np.testing.assert_allclose(np.asarray(got[2]),
                               np.asarray(ref.ell_sum_ref(nbr, contrib)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# fused fixpoint == host-driven loop (coreness AND superstep counts)
# ---------------------------------------------------------------------------


def _hostloop_coreness(g, backend):
    """The pre-refactor fixpoint: one host round-trip per superstep."""
    est = jnp.where(g.node_mask, g.deg, 0).astype(jnp.int32)
    adj = ops.dense_adj(g, backend)
    steps = 0
    while True:
        h = ops.hindex_blocks(g, est, backend=backend, adj=adj,
                              interpret=True)
        new = jnp.where(g.node_mask, jnp.minimum(est, h), est)
        steps += 1
        if bool(jax.device_get(jnp.all(new == est))):
            break
        est = new
    return np.asarray(est), steps


def _graphs():
    ba = barabasi_albert(140, 4, seed=3)
    er = erdos_renyi(120, 380, seed=8)
    out = []
    for name, edges in (("ba", ba), ("er", er)):
        n = int(edges.max()) + 1
        out.append((name, build_blocks(
            edges, n, node_random_partition(n, 4, seed=1), P=4,
            deg_slack=24)))
    return out


def test_fused_fixpoint_matches_hostloop_all_backends():
    for name, g in _graphs():
        for b in ALL_BACKENDS:
            want, want_steps = _hostloop_coreness(g, b)
            est, steps = ops.coreness_blocks(
                g, backend=b, interpret=True, with_steps=True)
            # step counts come back as device scalars, not host ints
            assert hasattr(steps, "dtype"), type(steps)
            np.testing.assert_array_equal(np.asarray(est), want)
            assert int(steps) == want_steps, (name, b, int(steps), want_steps)


def test_fused_fixpoint_spmd_step_count_matches_jnp():
    from repro.runtime import SpmdExecutor

    _, g = _graphs()[0]
    _, steps_jnp = ops.coreness_blocks(g, backend="jnp", with_steps=True)
    est, steps_mesh = ops.coreness_blocks(
        g, backend="ell_spmd", with_steps=True)
    assert int(steps_mesh) == int(steps_jnp)
    np.testing.assert_array_equal(
        np.asarray(est), np.asarray(ops.coreness_blocks(g, backend="jnp")))
    # executor threading: no fresh executor per call
    ex = SpmdExecutor(g)
    est2 = ops.coreness_blocks(g, backend="ell_spmd", executor=ex)
    np.testing.assert_array_equal(np.asarray(est2), np.asarray(est))


def test_coreness_blocks_threads_executor_without_rebuilding(monkeypatch):
    from repro.runtime import SpmdExecutor
    from repro.runtime import spmd as spmd_mod

    _, g = _graphs()[1]
    ex = SpmdExecutor(g)
    built = {"n": 0}
    orig_init = spmd_mod.SpmdExecutor.__init__

    def counting_init(self, *a, **kw):
        built["n"] += 1
        return orig_init(self, *a, **kw)

    monkeypatch.setattr(spmd_mod.SpmdExecutor, "__init__", counting_init)
    core = ops.coreness_blocks(g, backend="ell_spmd", executor=ex)
    h = ops.hindex_blocks(g, jnp.asarray(core), backend="ell_spmd",
                          executor=ex)
    f = jnp.zeros((g.N, 1), bool).at[0, 0].set(True)
    ops.frontier_blocks(g, f, g.node_mask, jnp.zeros((g.N, 1), bool),
                        backend="ell_spmd", executor=ex)
    assert built["n"] == 0, "dispatch built a fresh SpmdExecutor per call"
    np.testing.assert_array_equal(
        np.asarray(h), np.asarray(ref.ell_hindex_ref(g.nbr, jnp.asarray(core))))


# ---------------------------------------------------------------------------
# zero per-superstep host transfers (device_get call counting)
# ---------------------------------------------------------------------------


def _slow_cascade_graph(n=96):
    """A chain of triangles: the min-H cascade walks the chain, so the
    fixpoint takes O(n) supersteps — enough to separate per-superstep from
    per-fixpoint transfer counts."""
    edges = []
    for i in range(n - 2):
        edges.append((i, i + 1))
    edges.append((n - 2, n - 1))
    edges.append((n - 3, n - 1))  # one triangle at the far end
    edges = np.asarray(edges)
    return build_blocks(edges, n, np.zeros(n, int), P=1, deg_slack=16)


@pytest.fixture
def count_device_get(monkeypatch):
    calls = {"n": 0}
    orig = jax.device_get

    def counting(x):
        calls["n"] += 1
        return orig(x)

    monkeypatch.setattr(jax, "device_get", counting)
    return calls


def test_coreness_fixpoint_transfer_count_is_o1(count_device_get):
    g = _slow_cascade_graph()
    for b in ALL_BACKENDS:
        count_device_get["n"] = 0
        est, steps = ops.coreness_blocks(
            g, backend=b, interpret=True, with_steps=True)
        n_gets = count_device_get["n"]
        assert int(steps) > 20, (b, int(steps))  # a genuinely long fixpoint
        # at most the one degree_bound read — NEVER one per superstep
        assert n_gets <= 1, (b, n_gets, int(steps))


def test_clamped_recompute_has_no_per_superstep_transfers(count_device_get):
    from repro.core import insert_edge_maintain

    g = _slow_cascade_graph()
    core = coreness(g, backend="jnp")
    count_device_get["n"] = 0
    g2, core2, st = insert_edge_maintain(
        g, jnp.asarray(core), jnp.int32(0), jnp.int32(4))
    assert count_device_get["n"] == 0  # fully jitted: nothing crosses
    assert int(st.recompute_steps) >= 1


def test_run_stream_routing_transfers_per_window_not_per_superstep(
        count_device_get):
    """One routed window = one device_get (the compact verdict bundle),
    independent of how many BFS/recompute supersteps the window costs."""
    g = _slow_cascade_graph(64)
    core = coreness(g, backend="jnp")
    ups = [(0, 8, +1), (20, 30, +1), (40, 50, +1), (2, 10, +1)]
    count_device_get["n"] = 0
    res = run_stream(
        jax.tree.map(lambda x: jnp.copy(x) if hasattr(x, "dtype") else x, g),
        core, list(ups), R=2)
    g2, core2, stats = res.g, res.core, res.stats
    n_gets = count_device_get["n"]
    assert stats.batches == 2
    assert stats.bfs_steps + stats.recompute_steps > stats.batches
    # window routing: ONE bundled transfer per window; escalated updates
    # (the sequential coordinator path) may add a bounded constant each;
    # +1: stats() pulls the device-resident recompute-superstep counter
    # once at close-out (apply_window itself never blocks on it)
    assert n_gets <= stats.batches + 1 + 2 * stats.escalated, (
        n_gets, stats.batches, stats.escalated)
    # exactness unchanged
    np.testing.assert_array_equal(
        np.asarray(coreness(g2, backend="jnp")), np.asarray(core2))


def test_run_stream_routing_bit_identical_to_host_reference():
    """The device-side `_route_window` verdict reproduces the host rule:
    cross-block > spill > conflict, conflicts vs ANY earlier column."""
    from repro.runtime.stream import _route_window

    rng = np.random.default_rng(0)
    N, R, Cn = 48, 6, 12
    for trial in range(25):
        cand = rng.random((N, R)) < 0.25
        us = rng.integers(0, N, R)
        vs = rng.integers(0, N, R)
        ops_ = rng.choice([-1, 1], R)
        n = int(rng.integers(1, R + 1))
        valid = np.arange(R) < n
        cand = cand & valid[None, :]
        for r in range(R):  # endpoints are always candidates
            if valid[r]:
                cand[us[r], r] = cand[vs[r], r] = True

        # host reference (the pre-refactor routing pass)
        block_of = np.arange(N) // Cn
        owner = us[:n] // Cn
        intra = owner == vs[:n] // Cn
        spill = (cand[:, :n] & (block_of[:, None] != owner[None, :])).any(0)
        overlap = cand.T.astype(np.int64) @ cand.astype(np.int64)
        acc_ref, cross_ref, spill_ref, conf_ref = [], [], [], []
        for r in range(n):
            conflicts = bool(overlap[r, :r].any())
            if intra[r] and not spill[r] and not conflicts:
                acc_ref.append(r)
            elif not intra[r]:
                cross_ref.append(r)
            elif spill[r]:
                spill_ref.append(r)
            else:
                conf_ref.append(r)

        route = _route_window(
            jnp.asarray(cand), jnp.asarray(us.astype(np.int32)),
            jnp.asarray(vs.astype(np.int32)),
            jnp.asarray(ops_.astype(np.int32)), jnp.asarray(valid), Cn=Cn)
        assert list(np.flatnonzero(np.asarray(route.accept))) == acc_ref
        assert list(np.flatnonzero(np.asarray(route.cross))) == cross_ref
        assert list(np.flatnonzero(np.asarray(route.spill))) == spill_ref
        assert list(np.flatnonzero(np.asarray(route.conflict))) == conf_ref
        acc = np.asarray(route.accept)
        ins = cand[:, np.flatnonzero(acc & (ops_ > 0))].any(1)
        dele = cand[:, np.flatnonzero(acc & (ops_ < 0))].any(1)
        np.testing.assert_array_equal(np.asarray(route.cand_ins), ins)
        np.testing.assert_array_equal(np.asarray(route.cand_del), dele)
        want_blocks = np.zeros(N // Cn, np.int32)
        np.add.at(want_blocks, us[np.flatnonzero(acc)] // Cn, 1)
        np.testing.assert_array_equal(np.asarray(route.per_block), want_blocks)


def test_run_spmd_fused_has_no_per_superstep_transfers(count_device_get):
    """The fused SPMD superstep loop pulls ONE scalar (the count) for the
    whole run; the halt decision stays on the mesh."""
    from repro.core import coreness_via_spmd

    g = _slow_cascade_graph()
    count_device_get["n"] = 0
    core, eng = coreness_via_spmd(g)
    supersteps = len(eng.traces)
    assert supersteps > 20
    assert count_device_get["n"] <= 2, (count_device_get["n"], supersteps)
    np.testing.assert_array_equal(
        np.asarray(core), np.asarray(coreness(g, backend="jnp")))
