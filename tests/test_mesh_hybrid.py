"""The mesh superstep's hybrid gather: a head of every row, plus the few
wide rows whole.

`SpmdExecutor` splits its local-frame adjacency by `ops.split_widths`
(the rule the single-device `ell` fixpoint uses), and an unmirrored
`SpmdBlockProgram` reads a pow2 head of every row plus each worker's
rows wider than the head at full width, the tail's reductions
overwriting the head's.  Contracts:

  * exactness — the fused coreness + CC + PageRank refresh on
    `ell_spmd` equals the `jnp` backend (coreness and labels bit for
    bit, PageRank within 1e-6 relative) on ragged graphs with and
    without a tail, under both reads, at W = 1 here and at W = 2 and 4
    on forced host devices (one subprocess), where the wide rows'
    neighbours sit in the halo;
  * the layout counter — `last_mesh_layout` reports the head, the tail
    and S·Wh + Tw·C slots on an ego-like degree law, the fewest any
    pow2 head gives; a mirrored program keeps the whole row;
  * the width rule — `split_widths` keeps one width on ties.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.core import build_blocks, coreness
from repro.core.algorithms import fused_analytics
from repro.core.hub_split import build_split_blocks
from repro.graphgen import barabasi_albert, erdos_renyi, grid_like
from repro.kernels import ops
from repro.runtime import spmd

REPO = Path(__file__).resolve().parents[1]
P = 4
STEPS = 30


def _hubs(n=240, hubs=3, hub_deg=70, seed=5):
    """A sparse random graph (mean degree 3) plus ``hubs`` rows joined
    to ``hub_deg`` random others each: a few rows far wider than the
    rest, whose neighbours lie in every block."""
    rng = np.random.default_rng(seed)
    extra = [(h, int(v)) for h in range(hubs)
             for v in rng.choice(np.arange(hubs, n), hub_deg, replace=False)]
    e = np.concatenate([erdos_renyi(n, 3 * n // 2, seed=seed),
                        np.asarray(extra)])
    return np.unique(np.sort(e, axis=1), axis=0), n


GRAPHS = {
    # a few hubs: the split finds a narrow head and a tail
    "tail": _hubs,
    # a lattice of degree <= 8: one width, no tail
    "no_tail": lambda: (grid_like(240, seed=3), 240),
}


def _blocks(name):
    edges, n = GRAPHS[name]()
    assign = np.random.default_rng(2).integers(0, P, n)
    return build_blocks(edges, n, assign, P=P, deg_slack=8)


def run_case(name: str, W: int, overlap: bool) -> dict:
    """The refresh on ``W`` workers against the jnp backend, and where
    the split's tail reads its neighbours from."""
    g = _blocks(name)
    ex = spmd.SpmdExecutor(g, W=W, overlap=overlap)
    want = fused_analytics(g, steps=STEPS, backend="jnp")
    got = fused_analytics(g, steps=STEPS, backend="ell_spmd", executor=ex)
    lay = spmd.last_mesh_layout()
    m = np.asarray(g.node_mask)
    w_rank, g_rank = np.asarray(want[2])[m], np.asarray(got[2])[m]
    halo = 0
    if ex.split.tail_padded:
        tail = np.asarray(ex.hybrid_tables().tail)
        halo = int(((tail >= ex.wm.S) & (tail < ex.wm.S + ex.plan.H)).sum())
    return {
        "devices": len(jax.devices()),
        "core_equal": bool((np.asarray(got[0]) == np.asarray(want[0])).all()),
        "labels_equal": bool(
            (np.asarray(got[1]) == np.asarray(want[1])).all()),
        "rank_err": float((np.abs(g_rank - w_rank) / w_rank).max()),
        "head_cols": lay.head_cols, "cols": lay.cols,
        "tail_rows": lay.tail_rows, "tail_padded": lay.tail_padded,
        "halo_tail_slots": halo,
    }


MESH_SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {tests!r})
    from test_mesh_hybrid import run_case
    print(json.dumps({{f"{{g}}-{{w}}-{{ov}}": run_case(g, w, ov)
                      for g in ("tail", "no_tail") for w in (2, 4)
                      for ov in (True, False)}}))
""")


@pytest.fixture(scope="module")
def mesh_results(tmp_path_factory):
    """Every W = 2 and W = 4 case, run once on 4 forced host devices."""
    script = tmp_path_factory.mktemp("mesh") / "hybrid.py"
    script.write_text(MESH_SCRIPT.format(tests=str(REPO / "tests")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    p = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "strict"])
@pytest.mark.parametrize("W", [1, 2, 4])
@pytest.mark.parametrize("name", ["tail", "no_tail"])
def test_hybrid_refresh_matches_jnp(name, W, overlap, request):
    if W == 1:
        out = run_case(name, W, overlap)
    else:
        out = request.getfixturevalue("mesh_results")[f"{name}-{W}-{overlap}"]
        assert out["devices"] == 4
    assert out["core_equal"] and out["labels_equal"], out
    assert out["rank_err"] < 1e-6, out
    if name == "tail":
        assert out["head_cols"] < out["cols"] and out["tail_rows"] > 0, out
        if W > 1:  # the wide rows' neighbours on other workers
            assert out["halo_tail_slots"] > 0, out
    else:
        assert out["head_cols"] == out["cols"], out
        assert out["tail_rows"] == out["tail_padded"] == 0, out


def _ego_like(n=1200, seed=4):
    """A BA graph plus ten egos of falling degree, as ego-Facebook's
    degree law has them: a long narrow body and a few very wide rows."""
    rng = np.random.default_rng(seed)
    e = [barabasi_albert(n, 4, seed=seed)]
    for ego, d in enumerate((400, 300, 250, 180, 120, 90, 70, 60, 40, 30)):
        others = rng.choice(np.setdiff1d(np.arange(n), [ego]), d,
                            replace=False)
        e.append(np.stack([np.full(d, ego), others], 1))
    e = np.concatenate(e)
    e = e[e[:, 0] != e[:, 1]]
    return np.unique(np.sort(e, axis=1), axis=0), n


def test_layout_reports_head_and_tail_on_an_ego_like_graph():
    edges, n = _ego_like()
    g = build_blocks(edges, n, np.random.default_rng(1).integers(0, 8, n),
                     P=8, deg_slack=8)
    ex = spmd.SpmdExecutor(g, W=1)
    coreness_mesh, labels_mesh, _ = fused_analytics(
        g, steps=STEPS, backend="ell_spmd", executor=ex)
    lay = spmd.last_mesh_layout()
    S, C = lay.S, lay.cols
    deg = np.asarray(g.deg)
    assert C == min(g.Cd, ops._pow2_bucket(int(deg.max()), floor=8))
    # the oracle: every pow2 head, its tail counted from the degrees
    slots = {}
    Wh = 8
    while Wh < C:
        tail = int((deg > Wh).sum())
        if tail:
            slots[Wh] = (tail, S * Wh + ops._pow2_bucket(tail, 8) * C)
        Wh *= 2
    best = min(slots, key=lambda k: slots[k][1])
    assert (lay.head_cols, lay.tail_rows) == (best, slots[best][0])
    assert lay.tail_padded == ops._pow2_bucket(lay.tail_rows, 8)
    assert lay.slots == S * lay.head_cols + lay.tail_padded * C
    assert lay.slots == slots[best][1] < S * C
    np.testing.assert_array_equal(
        np.asarray(coreness_mesh), np.asarray(coreness(g, backend="jnp")))
    np.testing.assert_array_equal(
        np.asarray(labels_mesh),
        np.asarray(fused_analytics(g, steps=STEPS, backend="jnp")[1]))


def test_mirrored_program_reads_the_whole_row():
    """A hub-split graph whose split would find a tail: the mirrored
    refresh still gathers every row whole (its h-index merge reads whole
    gathered replica rows), and the counter says so."""
    edges, n = _ego_like(600)
    assign = np.random.default_rng(3).integers(0, 8, n)
    g2, plan = build_split_blocks(edges, n, assign, 8, 32)
    ex = spmd.SpmdExecutor(g2, W=1)
    assert ex.split.tail_padded > 0
    got = fused_analytics(g2, steps=STEPS, backend="ell_spmd", executor=ex,
                          mirror=plan)
    lay = spmd.last_mesh_layout()
    assert lay.head_cols == lay.cols == ex.cols
    assert lay.tail_rows == lay.tail_padded == 0
    assert lay.slots == lay.S * lay.cols and lay.groups > 0
    g = build_blocks(edges, n, assign, P=8, Cn=g2.Cn)
    want = np.asarray(coreness(g, backend="jnp"))[np.asarray(g.node_mask)]
    np.testing.assert_array_equal(
        np.asarray(got[0])[np.asarray(plan.primary_mask)], want)


@pytest.mark.parametrize("wider,rows,cols,want", [
    # one hub among 100 rows of 16: head 8, a tail of one padded to 8
    ([100, 100, 60, 1, 0], 100, 16, (8, 1, 8, 100 * 8 + 8 * 16)),
    # every row full: a narrower head never pays, keep one width
    ([100, 100, 100, 100, 0], 100, 16, (16, 0, 0, 100 * 16)),
    # a tie (16·8 + 8·16 = 16·16) keeps one width
    ([16, 16, 16, 8, 0], 16, 16, (16, 0, 0, 16 * 16)),
], ids=["hub", "full", "tie"])
def test_split_widths_rule(wider, rows, cols, want):
    sp = ops.split_widths(wider, rows, cols, tail_floor=8)
    assert (sp.head_cols, sp.tail_rows, sp.tail_padded, sp.slots) == want
    assert sp.cols == cols
