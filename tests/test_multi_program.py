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

from repro.core import MultiProgram, build_ell_random, fused_analytics
from repro.core.algorithms import (
    ConnectedComponentsProgram, CorenessBlockProgram, PageRankProgram,
    TriangleCountProgram, connected_components, pagerank,
)
from repro.kernels import ops

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


def _lower(g, program, b):
    """Force a fresh trace of the fused superstep loop (no jit cache)."""
    state0 = program.init(g)
    adj = ops._pad_ell(g.nbr, None, 256)[0] if b == "ell" else None
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
