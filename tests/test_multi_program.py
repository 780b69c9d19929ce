"""MultiProgram: fused multi-field supersteps off one adjacency gather.

Three contracts under test:

  * parity — a fused coreness+CC+PageRank run is bit-identical, per
    field, to the standalone programs run for the same superstep count,
    on every backend (jnp / ell / dense / ell_spmd);
  * one gather — tracing the fused superstep loop dispatches exactly ONE
    adjacency gather where k standalone programs dispatch k
    (`ops.gather_trace_count`, bumped per `red_of` trace; asserted via
    explicit `.lower()` calls since jit cache hits never retrace);
  * validation — non-fusable sub-combines ("count_common") and unknown
    combines are rejected at construction/dispatch.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    MultiProgram, build_blocks, build_ell_random, fused_analytics,
)
from repro.core.algorithms import (
    ConnectedComponentsProgram, CorenessBlockProgram, PageRankProgram,
    TriangleCountProgram, connected_components, pagerank,
)
from repro.kernels import ops

from _road import road_graph

STEPS = 30


def _programs():
    return (CorenessBlockProgram(), ConnectedComponentsProgram(),
            PageRankProgram(tol=None, max_steps=STEPS))


@pytest.fixture(scope="module")
def g():
    return build_ell_random(192, Cd=16, seed=5)


@pytest.mark.parametrize("backend", ["jnp", "ell", "dense", "ell_spmd"])
def test_fused_matches_standalone(g, backend):
    core, lab, rank = fused_analytics(g, steps=STEPS, backend=backend)
    core_ref = ops.run_block_program(
        g, CorenessBlockProgram(), backend=backend)
    lab_ref = connected_components(g, backend=backend)
    rank_ref = pagerank(g, tol=None, max_steps=STEPS, backend=backend)
    np.testing.assert_array_equal(np.asarray(core), np.asarray(core_ref))
    np.testing.assert_array_equal(np.asarray(lab), np.asarray(lab_ref))
    np.testing.assert_array_equal(np.asarray(rank), np.asarray(rank_ref))


def test_fused_runs_exactly_steps_supersteps(g):
    (_, _, _), n = fused_analytics(g, steps=STEPS, backend="jnp",
                                   with_steps=True)
    assert int(n) == STEPS  # fixed-iteration PageRank pins the loop length


def _hybrid_adj(g):
    """The `ell` loop operand `run_block_program` builds for `g`."""
    split = ops.hybrid_split(g.nbr)
    return ops._hybrid_ell(g.nbr, split.cols, split.head_cols,
                           split.tail_padded)


def _lower(g, program, b):
    """Force a fresh trace of the fused superstep loop (no jit cache)."""
    state0 = program.init(g)
    adj = _hybrid_adj(g) if b == "ell" else None
    ops._block_program_fused.lower(
        g, state0, adj, None, program=program, b=b, interpret=True,
        max_steps=5, n_real=int(g.n_real))


@pytest.mark.parametrize("b", ["jnp", "ell"])
def test_fused_traces_one_gather_where_standalone_trace_three(g, b):
    before = ops.gather_trace_count()
    _lower(g, MultiProgram(_programs(), max_steps=5), b)
    assert ops.gather_trace_count() - before == 1
    before = ops.gather_trace_count()
    for p in _programs():
        _lower(g, p, b)
    assert ops.gather_trace_count() - before == 3


@pytest.mark.parametrize("b", ["jnp", "ell"])
def test_hybrid_fused_traces_one_gather(b):
    """A head and a tail are still ONE gather dispatch per superstep."""
    road = road_graph(768, 4, 12, Cn=384)
    assert b == "jnp" or _hybrid_adj(road).tail is not None
    before = ops.gather_trace_count()
    _lower(road, MultiProgram(_programs(), max_steps=5), b)
    assert ops.gather_trace_count() - before == 1


def test_multi_kernel_direct_parity(g):
    """ops.neighbor_multi_ell == the three standalone combines, bit-exact."""
    est = jnp.asarray(g.deg, jnp.int32)
    lab = jnp.arange(g.N, dtype=jnp.int32)
    contrib = jnp.where(g.deg > 0, 1.0 / jnp.maximum(g.deg, 1),
                        0.0).astype(jnp.float32)
    fused = ops.neighbor_multi_ell(
        g.nbr, (est, lab, contrib), ("hindex", "min", "sum"),
        interpret=True)
    np.testing.assert_array_equal(
        np.asarray(fused[0]), np.asarray(ops.hindex_ell(g.nbr, est)))
    np.testing.assert_array_equal(
        np.asarray(fused[1]), np.asarray(ops.neighbor_min_ell(g.nbr, lab)))
    np.testing.assert_array_equal(
        np.asarray(fused[2]), np.asarray(ops.neighbor_sum_ell(g.nbr, contrib)))


def test_count_common_not_fusable():
    with pytest.raises(ValueError, match="not fusable"):
        MultiProgram((ConnectedComponentsProgram(), TriangleCountProgram()))


def test_empty_multi_rejected():
    with pytest.raises(ValueError, match="at least one"):
        MultiProgram(())


def test_unknown_combine_rejected(g):
    class Bad(CorenessBlockProgram):
        combine = "nonsense"

    with pytest.raises(ValueError, match="unknown combine"):
        ops.run_block_program(g, Bad(), backend="jnp")


# ---------------------------------------------------------------------------
# auto backend crossover (measured table, TPU only)
# ---------------------------------------------------------------------------


def test_auto_crossover_table(monkeypatch):
    # off-TPU (this container): always jnp — Pallas would run interpreted
    assert ops.resolve_backend("auto", 256) == "jnp"
    assert ops.resolve_backend("auto", 1 << 20) == "jnp"
    # on TPU: the measured N crossovers of AUTO_CROSSOVER
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ops.resolve_backend("auto", 1) == "jnp"
    assert ops.resolve_backend("auto", ops.JNP_AUTO_MAX) == "jnp"
    assert ops.resolve_backend("auto", ops.JNP_AUTO_MAX + 1) == "dense"
    assert ops.resolve_backend("auto", ops.DENSE_AUTO_MAX) == "dense"
    assert ops.resolve_backend("auto", ops.DENSE_AUTO_MAX + 1) == "ell"
    # explicit names pass through untouched on every platform
    for b in ("jnp", "dense", "ell", "ell_spmd"):
        assert ops.resolve_backend(b, 17) == b


# ---------------------------------------------------------------------------
# hybrid ELL: every row gathers a narrow head, the few wide rows a tail
# ---------------------------------------------------------------------------


#: (n, hubs, hub width, Cn) -> the expected split: full width C, head W,
#: real and padded tail rows, and slots per field per superstep
HYBRID_CASES = {
    # no row wider than 4: a 4-column head would have no tail, so C = 8
    "tail0": ((700, 0, 12, 350), (8, 8, 0, 0, 768 * 8)),
    # one hub; N = 700 is not a multiple of the 256-row tile
    "tail1": ((700, 1, 12, 350), (16, 4, 1, 256, 768 * 4 + 256 * 16)),
    # roadNet-CA's shape; N = 768 fills its tiles, so row N - 1 is real
    # and the tail's pad entries (row id N) must write nowhere
    "road_aligned": ((768, 4, 12, 384), (16, 4, 4, 256, 768 * 4 + 256 * 16)),
    # 257 wide rows: just over one tail bucket
    "tail257": ((2000, 257, 6, 1000), (8, 4, 257, 512, 2048 * 4 + 512 * 8)),
}


@pytest.mark.parametrize("case", sorted(HYBRID_CASES))
def test_hybrid_fused_matches_jnp(case):
    (n, hubs, width, Cn), want = HYBRID_CASES[case]
    road = road_graph(n, hubs, width, Cn=Cn)
    assert road.N == 2 * Cn
    core, lab, rank = fused_analytics(road, steps=STEPS, backend="ell")
    assert ops.last_hybrid_split() == ops.HybridSplit(*want)
    core_ref, lab_ref, rank_ref = fused_analytics(road, steps=STEPS,
                                                  backend="jnp")
    np.testing.assert_array_equal(np.asarray(core), np.asarray(core_ref))
    np.testing.assert_array_equal(np.asarray(lab), np.asarray(lab_ref))
    np.testing.assert_allclose(np.asarray(rank), np.asarray(rank_ref),
                               rtol=1e-6, atol=0)


def test_hybrid_regular_graph_keeps_one_adjacency():
    """Rows of one width: W = C and no tail, today's (Np, C) operand."""
    n = 512
    u = np.arange(n)
    edges = np.concatenate([np.stack([u, (u + k) % n], 1) for k in (1, 2, 3)])
    reg = build_blocks(edges, n, u % 2, P=2)
    split = ops.hybrid_split(reg.nbr)
    assert split == ops.HybridSplit(8, 8, 0, 0, 512 * 8)
    adj = _hybrid_adj(reg)
    assert adj.tail is None and adj.rows is None
    assert adj.head.shape == (512, 8)
    np.testing.assert_array_equal(
        np.asarray(adj.head), np.asarray(ops._pad_ell(reg.nbr, 6, 256)[0]))
    core, lab, rank = fused_analytics(reg, steps=STEPS, backend="ell")
    assert ops.last_hybrid_split() == split
    core_ref, lab_ref, rank_ref = fused_analytics(reg, steps=STEPS,
                                                  backend="jnp")
    np.testing.assert_array_equal(np.asarray(core), np.asarray(core_ref))
    np.testing.assert_array_equal(np.asarray(lab), np.asarray(lab_ref))
    np.testing.assert_allclose(np.asarray(rank), np.asarray(rank_ref),
                               rtol=1e-6, atol=0)


def test_hybrid_operand_rows():
    """The tail holds the wide rows whole, in row order; its pad entries
    hold no slots and name row N."""
    road = road_graph(768, 4, 12, Cn=384)
    adj = _hybrid_adj(road)
    nbr = np.asarray(road.nbr)
    wide = np.flatnonzero((nbr >= 0).sum(1) > 4)
    assert len(wide) == 4
    rows = np.asarray(adj.rows)
    np.testing.assert_array_equal(rows[:4], wide)
    assert (rows[4:] == road.N).all()
    tail = np.asarray(adj.tail)
    np.testing.assert_array_equal(tail[:4, :12], nbr[wide, :12])
    assert (tail[:4, 12:] == -1).all() and (tail[4:] == -1).all()
    np.testing.assert_array_equal(np.asarray(adj.head), nbr[:, :4])
