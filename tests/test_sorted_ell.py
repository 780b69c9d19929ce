"""Property tests: the sorted-ELL invariant and the merge intersection.

The canonical row form every kernel optimization of this PR leans on:
valid slots ascending, pads (-1) on the right.  Every construction and
mutation path of `core.graph` must preserve it — `build_blocks`,
`build_ell_random`, the jitted `insert_edge`/`delete_edge`, the host
`apply_updates_host`, and `migrate_vertices` — and the host and jitted
update paths must produce bit-identical canonical rows.

The sorted-merge triangle kernel (`ell_triangles` variant "merge") must
match the all-pairs oracle bit-for-bit on ragged inputs: Cd not a lane
multiple (e.g. 130), all-pad rows, duplicate ids in raw fields.
"""
import numpy as np
import jax.numpy as jnp

from _hyp import given, settings, st

from repro.core import (
    build_blocks, build_ell_random, insert_edge, delete_edge,
    migrate_vertices,
)
from repro.core.partition import node_random_partition
from repro.core.updates import (
    apply_updates_host, sample_deletions, sample_insertions,
)
from repro.graphgen import barabasi_albert
from repro.kernels import ops, ref


def assert_sorted_ell(nbr, deg=None):
    """Every row: valid slots first, strictly ascending, pads right."""
    nbr = np.asarray(nbr)
    for i, row in enumerate(nbr):
        vals = row[row >= 0]
        # left-filled: the valid slots are exactly the row's prefix
        np.testing.assert_array_equal(
            row[: len(vals)], vals, err_msg=f"row {i} not left-filled")
        assert np.all(row[len(vals):] == -1), f"row {i} pads not -1"
        assert np.all(np.diff(vals) > 0), f"row {i} not ascending: {vals}"
        if deg is not None:
            assert len(vals) == int(np.asarray(deg)[i]), f"row {i} deg"


def _random_graph(n, seed, P=4, m=3):
    edges = barabasi_albert(n, m, seed=seed)
    nn = int(edges.max()) + 1
    return build_blocks(edges, nn, node_random_partition(nn, P, seed=seed),
                        P=P, deg_slack=16)


@settings(max_examples=10, deadline=None)
@given(st.integers(10, 60), st.integers(0, 10_000))
def test_build_blocks_sorted(n, seed):
    g = _random_graph(n, seed)
    assert_sorted_ell(g.nbr, g.deg)


@settings(max_examples=6, deadline=None)
@given(st.integers(32, 200), st.integers(0, 10_000))
def test_build_ell_random_sorted(N, seed):
    g = build_ell_random(N, Cd=16, seed=seed)
    assert_sorted_ell(g.nbr, g.deg)


@settings(max_examples=8, deadline=None)
@given(st.integers(16, 50), st.integers(0, 10_000),
       st.sampled_from(["intra", "inter"]))
def test_mutations_preserve_invariant_and_host_jit_parity(n, seed, scen):
    """Jitted insert/delete keep rows canonical, bit-equal to the host path."""
    g = _random_graph(n, seed)
    ups = (sample_insertions(g, 3, scen, seed=seed)
           + sample_deletions(g, 3, scen, seed=seed + 1))
    g_host = apply_updates_host(g, ups)
    g_jit = g
    for u, v, op in ups:
        g_jit = (insert_edge if op > 0 else delete_edge)(
            g_jit, jnp.int32(u), jnp.int32(v))
    assert_sorted_ell(g_jit.nbr, g_jit.deg)
    # canonical form == the two update paths agree bit-for-bit
    np.testing.assert_array_equal(np.asarray(g_jit.nbr),
                                  np.asarray(g_host.nbr))
    np.testing.assert_array_equal(np.asarray(g_jit.deg),
                                  np.asarray(g_host.deg))


@settings(max_examples=6, deadline=None)
@given(st.integers(20, 60), st.integers(0, 10_000))
def test_migration_preserves_invariant(n, seed):
    g = _random_graph(n, seed)
    rng = np.random.default_rng(seed)
    mask = np.asarray(g.node_mask)
    pad_free = np.array([int(np.sum(~mask[b * g.Cn:(b + 1) * g.Cn]))
                         for b in range(g.P)])
    reals = np.flatnonzero(mask)
    moves = []
    for u in rng.permutation(reals)[:3]:
        dests = [b for b in range(g.P) if b != u // g.Cn and pad_free[b] > 0]
        if not dests:
            continue
        b = int(rng.choice(dests))
        pad_free[b] -= 1
        moves.append((int(u), b))
    if not moves:
        return
    g2, _perm = migrate_vertices(g, moves)
    assert_sorted_ell(g2.nbr, g2.deg)


# ---------------------------------------------------------------------------
# merge-intersection parity on ragged inputs
# ---------------------------------------------------------------------------


def _ragged_rows(n, cd, seed):
    """Raw (n, cd) int32 field: duplicates legal, ~25% all-pad rows."""
    rng = np.random.default_rng(seed)
    deg = rng.integers(0, cd + 1, size=n)
    deg[rng.random(n) < 0.25] = 0
    nbr = np.full((n, cd), -1, np.int32)
    for i in range(n):
        nbr[i, : deg[i]] = rng.integers(0, n, size=deg[i])  # with replacement
    return jnp.asarray(nbr)


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 50), st.integers(1, 12), st.integers(0, 10_000))
def test_merge_matches_oracle_ragged(n, cd, seed):
    nbr = _ragged_rows(n, cd, seed)
    want = np.asarray(ref.ell_common_ref(nbr, nbr))
    for variant in ("merge", "allpairs"):
        got = ops.neighbor_common_ell(nbr, nbr, interpret=True,
                                      variant=variant)
        np.testing.assert_array_equal(np.asarray(got), want,
                                      err_msg=f"variant={variant}")


def test_merge_matches_oracle_cd130():
    """Cd=130: column padding crosses a lane boundary (130 % 128 != 0)."""
    nbr = _ragged_rows(40, 130, seed=7)
    want = np.asarray(ref.ell_common_ref(nbr, nbr))
    got = ops.neighbor_common_ell(nbr, nbr, interpret=True, variant="merge")
    np.testing.assert_array_equal(np.asarray(got), want)


def test_merge_all_pad_rows():
    """An entirely empty adjacency reduces to zeros (early exit at 0 trips)."""
    nbr = jnp.full((12, 8), -1, jnp.int32)
    got = ops.neighbor_common_ell(nbr, nbr, interpret=True, variant="merge")
    np.testing.assert_array_equal(np.asarray(got), np.zeros(12, np.int32))


def test_merge_on_real_graph_matches_allpairs():
    g = build_ell_random(320, Cd=24, seed=3)
    want = np.asarray(ref.ell_common_ref(g.nbr, g.nbr))
    for variant in ("merge", "allpairs"):
        got = ops.neighbor_common_ell(g.nbr, g.nbr, interpret=True,
                                      variant=variant)
        np.testing.assert_array_equal(np.asarray(got), want)
