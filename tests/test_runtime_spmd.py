"""Distributed block runtime: mesh fold, halo plan, shard_map execution.

Runs on whatever devices exist: with one CPU device every test still
exercises the full shard_map/all-to-all path at W = 1 (all blocks folded
onto one worker); the multi-device CI job re-runs this file under
`XLA_FLAGS=--xla_force_host_platform_device_count=8` so the halo exchange
carries real cross-device traffic and the blocks-per-device fold is
covered with W > 1 as well.

The two headline contracts (ISSUE acceptance):
  * `run_spmd` / `coreness(backend="ell_spmd")` is bit-identical to the
    single-device path on ≥ 2 generated graphs with P ∈ {2, 4, 8};
  * executed W2W inter/intra counts equal `halo_slot_counts` metering.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    BladygEngine, CorenessProgram, build_blocks, coreness,
    coreness_via_engine, coreness_via_spmd, halo_pair_counts,
    halo_slot_counts, maintain_batch,
)
from repro.core.partition import node_bfs_partition, node_random_partition
from repro.graphgen import barabasi_albert, erdos_renyi
from repro.kernels import ops, ref
from repro.runtime import (
    SpmdCorenessProgram, SpmdEngine, SpmdExecutor, best_worker_count,
    build_halo_plan, make_worker_mesh,
)

PS = (2, 4, 8)


def _graphs():
    """Two generated graphs (the acceptance floor) with distinct structure."""
    ba = barabasi_albert(180, 4, seed=11)
    er = erdos_renyi(150, 450, seed=5)
    return [("ba", ba, int(ba.max()) + 1), ("er", er, 150)]


def _blocks(edges, n, P, seed=2):
    assign = node_random_partition(n, P, seed=seed)
    return build_blocks(edges, n, assign, P=P, deg_slack=48)


def _worker_counts(P):
    """W options available on this host: always 1; plus any divisor of P
    that fits the device count (covers fold B > 1 whenever possible)."""
    ndev = len(jax.devices())
    return sorted({w for w in (1, 2, P) if w <= ndev and P % w == 0})


def _clone(g):
    return jax.tree.map(
        lambda x: jnp.copy(x) if hasattr(x, "dtype") else x, g)


# ---------------------------------------------------------------------------
# mesh geometry
# ---------------------------------------------------------------------------


def test_best_worker_count_divisor_rule():
    assert best_worker_count(8, 8) == 8
    assert best_worker_count(8, 5) == 4   # largest divisor that fits
    assert best_worker_count(6, 4) == 3
    assert best_worker_count(4, 1) == 1
    assert best_worker_count(1, 16) == 1
    with pytest.raises(ValueError):
        best_worker_count(0, 4)


def test_worker_mesh_fold_geometry():
    g = _blocks(*_graphs()[0][1:], P=4)
    wm = make_worker_mesh(g, W=1)
    assert (wm.W, wm.B, wm.S) == (1, 4, 4 * g.Cn)
    assert wm.N == g.N and wm.worker_of(g.N - 1) == 0
    with pytest.raises(ValueError):
        make_worker_mesh(g, W=3)  # 3 does not divide P=4


# ---------------------------------------------------------------------------
# halo plan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("P", PS)
def test_plan_slot_counts_match_metering(P):
    for _, edges, n in _graphs():
        g = _blocks(edges, n, P)
        for W in _worker_counts(P):
            plan = build_halo_plan(g, W=W)
            assert plan.slot_counts() == halo_slot_counts(g)


def test_plan_pair_matrix_consistent_with_graph_matrix():
    _, edges, n = _graphs()[0]
    g = _blocks(edges, n, 4)
    pm = halo_pair_counts(g)
    intra, inter = halo_slot_counts(g)
    assert int(np.trace(pm)) == intra
    assert int(pm.sum() - np.trace(pm)) == inter
    # executed pair matrix: deduplicated, so bounded by the slot matrix
    plan = build_halo_plan(g, W=len(jax.devices()) > 1 and 2 or 1)
    assert plan.device_elems <= inter
    # every off-diagonal device element corresponds to a boundary vertex
    assert (plan.pair_elems >= 0).all()


def test_plan_local_frame_covers_every_slot():
    _, edges, n = _graphs()[1]
    g = _blocks(edges, n, 4)
    plan = build_halo_plan(g, W=1)
    nbrl = plan.nbr_local
    valid = np.asarray(g.nbr) >= 0
    S = plan.wm.S
    # valid slots index local rows or halo entries, PAD slots the sentinel
    assert (nbrl[valid] < S + plan.H).all()
    assert (nbrl[~valid] == plan.pad_slot).all()


def test_plan_build_under_jit_raises():
    _, edges, n = _graphs()[0]
    g = _blocks(edges, n, 2)

    @jax.jit
    def bad(g):
        return ops.hindex_blocks(
            g, jnp.zeros(g.N, jnp.int32), backend="ell_spmd")

    with pytest.raises(TypeError, match="concrete"):
        bad(g)


# ---------------------------------------------------------------------------
# bit-identical execution (the acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("P", PS)
def test_coreness_spmd_bit_identical(P):
    for name, edges, n in _graphs():
        g = _blocks(edges, n, P)
        ref_core = np.asarray(ops.coreness_blocks(g, backend="jnp"))
        for W in _worker_counts(P):
            got = np.asarray(coreness(g, backend="ell_spmd")) if W == 1 \
                else np.asarray(
                    SpmdExecutor(g, W=W).coreness()[0])
            assert (ref_core == got).all(), (name, P, W)


def test_executor_gathers_degree_bucket_columns():
    """The mesh supersteps gather only the pow2 bucket (>= 8) above the
    widest row, not all Cd slots, and stay exact."""
    for name, edges, n in _graphs():
        g = _blocks(edges, n, 4)
        want = np.asarray(ops.coreness_blocks(g, backend="jnp"))
        width = int(np.asarray(g.deg).max())
        for W in _worker_counts(4):
            ex = SpmdExecutor(g, W=W)
            cols = ex._nbrl.shape[1]
            assert cols == min(g.Cd, ops._pow2_bucket(width, floor=8))
            np.testing.assert_array_equal(
                np.asarray(ex.coreness()[0]), want, err_msg=f"{name} W={W}")


def test_session_graph_split_over_mesh():
    """A mesh session's graph (the apply path's copy) is split over the
    worker mesh like the plan tables, before and after a window."""
    from repro.runtime import StreamSession

    _, edges, n = _graphs()[1]
    g = _blocks(edges, n, 4)
    core = ops.coreness_blocks(g, backend="jnp")
    W = _worker_counts(4)[-1]
    sess = StreamSession(_clone(g), core, R=4, backend="ell_spmd", W=W)

    def shards(x):
        got = [(s.device, s.data.nbytes) for s in x.addressable_shards]
        assert len({d for d, _ in got}) == W
        assert all(b * W == x.nbytes for _, b in got), got

    for a in (sess.g.nbr, sess.g.deg, sess.g.node_mask):
        shards(a)
    nb = np.asarray(g.nbr)
    u = int(np.flatnonzero(np.asarray(g.node_mask))[0])
    sess.apply_window([(u, int(nb[u][nb[u] >= 0][0]), -1)])
    shards(sess.g.nbr)
    np.testing.assert_array_equal(
        np.asarray(sess.core),
        np.asarray(ops.coreness_blocks(sess.g, backend="jnp")))


def test_hindex_and_frontier_dispatch_parity():
    _, edges, n = _graphs()[0]
    g = _blocks(edges, n, 4)
    est = jnp.where(g.node_mask, g.deg, 0).astype(jnp.int32)
    h_ref = np.asarray(ref.ell_hindex_ref(g.nbr, est))
    h_spmd = np.asarray(ops.hindex_blocks(g, est, backend="ell_spmd"))
    assert (h_ref == h_spmd).all()

    rng = np.random.default_rng(0)
    R = 3
    f = jnp.asarray(rng.random((g.N, R)) < 0.05)
    elig = jnp.asarray(rng.random((g.N, R)) < 0.8)
    vis = jnp.zeros((g.N, R), bool)
    hop_ref = np.asarray(ref.ell_frontier_hop_ref(g.nbr, f, elig, vis))
    hop_spmd = np.asarray(
        ops.frontier_blocks(g, f, elig, vis, backend="ell_spmd"))
    assert (hop_ref == hop_spmd).all()
    # shared (N,) eligibility broadcast path
    elig1 = jnp.asarray(rng.random(g.N) < 0.8)
    hop_ref1 = np.asarray(ref.ell_frontier_hop_ref(
        g.nbr, f, jnp.broadcast_to(elig1[:, None], f.shape), vis))
    hop_spmd1 = np.asarray(
        ops.frontier_blocks(g, f, elig1, vis, backend="ell_spmd"))
    assert (hop_ref1 == hop_spmd1).all()


# ---------------------------------------------------------------------------
# engine traces: executed vs metered accounting
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("P", (2, 4))
def test_run_spmd_traces_match_metered_w2w(P):
    _, edges, n = _graphs()[0]
    g = _blocks(edges, n, P)
    core_m, eng_m = coreness_via_engine(g)
    core_x, eng_x = coreness_via_spmd(g)
    assert (np.asarray(core_m) == np.asarray(core_x)).all()
    assert len(eng_m.traces) == len(eng_x.traces)
    tm, tx = eng_m.message_totals(), eng_x.message_totals()
    # the acceptance contract: executed == metered, both splits
    assert (tm.w2w_intra, tm.w2w_inter) == (tx.w2w_intra, tx.w2w_inter)
    # per-superstep too, since the plan is static across the run
    for a, b in zip(eng_m.traces, eng_x.traces):
        assert (a.stats.w2w_intra, a.stats.w2w_inter) == \
               (b.stats.w2w_intra, b.stats.w2w_inter)
    # the SPMD engine's W2M carries per-*block* flags (P per superstep)
    assert tx.w2m == P * len(eng_x.traces)


def test_engine_w2w_override_stamps_executed_counts():
    _, edges, n = _graphs()[0]
    g = _blocks(edges, n, 2)
    plan = build_halo_plan(g, W=1)
    est0 = jnp.where(g.node_mask, g.deg, 0).astype(jnp.int32)
    eng = BladygEngine(g)
    eng.run(CorenessProgram(), est0, None, w2w_override=plan.slot_counts())
    t = eng.message_totals()
    intra, inter = plan.slot_counts()
    assert t.w2w_intra == intra * len(eng.traces)
    assert t.w2w_inter == inter * len(eng.traces)


# ---------------------------------------------------------------------------
# maintenance routed through the mesh
# ---------------------------------------------------------------------------


def test_maintain_batch_ell_spmd_bit_identical():
    from repro.core.updates import sample_deletions, sample_insertions

    _, edges, n = _graphs()[0]
    g = _blocks(edges, n, 4)
    core0 = coreness(g, backend="jnp")
    ups = (sample_insertions(g, 2, "inter", seed=2)
           + sample_insertions(g, 2, "intra", seed=3)
           + sample_deletions(g, 2, "intra", seed=4))
    g_a, core_a, st_a = maintain_batch(
        _clone(g), jnp.asarray(core0), ups, R=3, backend="jnp")
    g_b, core_b, st_b = maintain_batch(
        _clone(g), jnp.asarray(core0), ups, R=3, backend="ell_spmd")
    assert (np.asarray(core_a) == np.asarray(core_b)).all()
    assert (np.asarray(g_a.nbr) == np.asarray(g_b.nbr)).all()
    assert st_b.updates == len(ups)
    # and the maintained result equals recompute-from-scratch on the mesh
    assert (np.asarray(coreness(g_b, backend="ell_spmd"))
            == np.asarray(core_b)).all()
