"""The split loader and the bounded h-index merge.

`build_split_blocks` loads a hub-split graph straight from its edge list;
it and `split_hubs` share one split rule, and both must give what the
per-hub loop of the original `split_hubs` gave (`_split_hubs_loop`
below, kept here as the oracle).  `merged_hindex` finds each replica
group's h-index by bisection; it must equal the count-histogram merge it
replaced (`_histogram_hindex`), on one device and on a 4-device mesh,
without building any array as wide as the h-index bound.
"""
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core import build_blocks, coreness
from repro.core.algorithms import (ConnectedComponentsProgram,
                                   CorenessBlockProgram, PageRankProgram,
                                   connected_components)
from repro.core.engine import MultiProgram
from repro.core.graph import CapacityError
from repro.core.hub_split import build_split_blocks, groups_of, split_hubs
from repro.graphgen import barabasi_albert
from repro.kernels import ops
from repro.runtime import spmd
from repro.runtime.halo import build_halo_plan

REPO = Path(__file__).resolve().parents[1]
P = 8


def _powerlaw(n, seed, hub=0):
    """A BA graph with random blocks, plus a planted hub of degree ``hub``
    (when given) that every other node befriends."""
    edges = {(min(u, v), max(u, v)) for u, v in barabasi_albert(n, 3,
                                                                 seed=seed)
             if u != v}
    if hub:
        edges |= {(0, v) for v in range(1, hub + 1)}
    assign = np.random.default_rng(seed).integers(0, P, n)
    return np.array(sorted(edges)), assign


def _split_hubs_loop(g, t):
    """The original per-hub split, kept as the oracle of the split rule:
    (nbr, deg, mask, orig, {primary: [rows]})."""
    nbr = np.asarray(g.nbr, np.int64)
    deg = np.asarray(g.deg, np.int64)
    mask = np.asarray(g.node_mask).copy()
    orig = np.asarray(g.orig_id, np.int64).copy()
    N, Cn, Cd = g.N, g.Cn, g.Cd
    free = {b: list(np.flatnonzero(~mask[b * Cn:(b + 1) * Cn]) + b * Cn)
            for b in range(g.P)}

    def alloc(pref, own):
        for b in [pref, own] + sorted(free):
            if free[b]:
                return free[b].pop(0)
        raise CapacityError("no free padding rows")

    rew = np.repeat(np.arange(N, dtype=np.int64), Cd).reshape(N, Cd)
    rew2 = nbr.copy()
    groups = {}
    for h in np.flatnonzero(mask & (deg > t)):
        d, own = int(deg[h]), h // Cn
        nb = nbr[h, :d]
        blk = nb // Cn
        nb_o = nb[np.lexsort((nb, np.where(blk == own, -1, blk)))]
        rows_h = [int(h)]
        for ci in range(1, -(-d // t)):
            r = alloc(int(nb_o[ci * t] // Cn), int(own))
            rows_h.append(r)
            mask[r], orig[r] = True, orig[h]
        groups[int(h)] = rows_h
        for ci, r in enumerate(rows_h):
            chunk = nb_o[ci * t:(ci + 1) * t]
            rew[h, np.searchsorted(nb, chunk)] = r
            for w in chunk:
                rew2[w, np.searchsorted(nbr[w, :deg[w]], h)] = r
    valid = nbr >= 0
    src, dst = rew[valid], rew2[valid]
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    out = np.full((N, t), -1, np.int64)
    start = np.searchsorted(src, src)
    out[src, np.arange(len(src)) - start] = dst
    return out, np.bincount(src, minlength=N), mask, orig, groups


def _same_split(a, b):
    (ga, pa), (gb, pb) = a, b
    assert (ga.P, ga.Cn, ga.Cd) == (gb.P, gb.Cn, gb.Cd)
    for f in ("nbr", "deg", "node_mask", "orig_id"):
        np.testing.assert_array_equal(getattr(ga, f), getattr(gb, f), f)
    for f in ("primary_row", "ldeg", "primary_mask", "grp_rows", "grp_gid",
              "row_gid"):
        np.testing.assert_array_equal(getattr(pa, f), getattr(pb, f), f)
    assert (pa.Gmax, pa.Km, pa.threshold, pa.n_logical) == \
        (pb.Gmax, pb.Km, pb.threshold, pb.n_logical)


def _matches_loop(g, split):
    g2, plan = split
    nbr, deg, mask, orig, groups = _split_hubs_loop(g, plan.threshold)
    np.testing.assert_array_equal(np.asarray(g2.nbr), nbr)
    np.testing.assert_array_equal(np.asarray(g2.deg), deg)
    np.testing.assert_array_equal(np.asarray(g2.node_mask), mask)
    np.testing.assert_array_equal(np.asarray(g2.orig_id), orig)
    assert groups_of(plan) == {h: sorted(rs, key=lambda r: (r != h, r))
                               for h, rs in groups.items()}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("T", [2, 4, 8])
def test_loader_equals_split_hubs(T, seed):
    """Field for field, with a hub wider than any block's padding: the
    loader sizes each block's padding for the replicas that prefer it."""
    n = 240
    edges, assign = _powerlaw(n, seed, hub=150)
    loaded = build_split_blocks(edges, n, assign, P, T)
    g = build_blocks(edges, n, assign, P=P, Cn=loaded[0].Cn)
    _same_split(loaded, split_hubs(g, T))
    _matches_loop(g, loaded)
    assert loaded[0].Cd == T and loaded[1].n_logical == n


@pytest.mark.parametrize("slack", [56, 72, 80])
def test_split_hubs_walks_the_rule_when_blocks_run_dry(slack):
    """A graph built with little padding: 5 (slack 72) or 2 (slack 80)
    preferred blocks run dry, so replicas fall back to the hub's block
    and then the lowest with room, as the original loop allocated them;
    with too little in all (slack 56), both raise."""
    edges, assign = _powerlaw(300, 3)
    g = build_blocks(edges, 300, assign, P=P, node_slack=slack)
    try:
        _split_hubs_loop(g, 2)
    except CapacityError:
        with pytest.raises(CapacityError):
            split_hubs(g, 2)
        return
    _matches_loop(g, split_hubs(g, 2))


def test_hub_wider_than_the_free_rows_raises():
    """A hub needing more replicas than all padding rows hold raises when
    the padding is given; the loader sizes its padding to fit."""
    n = 200
    edges, assign = _powerlaw(n, 5, hub=199)
    g = build_blocks(edges, n, assign, P=P)  # Cn: the largest block, to 8
    with pytest.raises(CapacityError):
        split_hubs(g, 2)
    g2, _ = build_split_blocks(edges, n, assign, P, 2)
    assert g2.Cn > g.Cn


def _histogram_hindex(vals, live, gid, G, Km):
    """The count-histogram merge `merged_hindex` replaced: per entry the
    counts of values >= t for t = 1..Km, added per group."""
    t = np.arange(1, Km + 1)
    hist = ((vals[:, :, None] >= t).sum(1)) * live[:, None]
    cnt = np.zeros((G + 1, Km), np.int64)
    np.add.at(cnt, gid, hist)
    return (cnt >= t).sum(1)[gid]


@pytest.mark.parametrize("capped", [False, True])
@pytest.mark.parametrize("Km", [8, 64, 1024])
def test_merged_hindex_equals_the_histogram_merge(Km, capped):
    """The histogram's h of each group; with a cap drawn per group (the
    same on each of its entries, as a min-H estimate is), min(h, cap)."""
    rng = np.random.default_rng(Km)
    R, C, G = 300, 8, 40
    vals = rng.integers(-1, Km + 3, (R, C))
    vals[rng.random((R, C)) < 0.3] = -1
    live = rng.random(R) < 0.9
    gid = np.where(live, rng.integers(0, G, R), G)
    want = _histogram_hindex(vals, live, gid, G, Km)
    cap = np.full(R, Km)
    if capped:
        # per group: its h itself, past Km, or drawn in [0, Km]
        h = np.zeros(G + 1, np.int64)
        h[gid[live]] = want[live]
        kind = rng.integers(0, 3, G + 1)
        cap = np.where(kind == 0, h, np.where(
            kind == 1, Km + 5, rng.integers(0, Km + 1, G + 1)))[gid]
        want = np.minimum(want, cap)
    got = ops.merged_hindex(jnp.asarray(vals, jnp.int32), jnp.asarray(live),
                            jnp.asarray(gid, jnp.int32),
                            jnp.asarray(cap, jnp.int32), G, Km)
    np.testing.assert_array_equal(np.asarray(got)[live], want[live])


def _dims(text):
    """Every tensor shape in a lowered module's text."""
    return [tuple(int(d) for d in m.split("x"))
            for m in re.findall(r"tensor<((?:\d+x)+)\w+>", text)
            for m in [m.rstrip("x")]]


def test_no_array_is_as_wide_as_the_hindex_bound():
    """On a split graph with Km = 4096 (a hub of degree 3,000 among 400
    nodes), neither the single-device nor the mesh superstep holds an
    array with a Km-long axis; the exact merge still gives the unsplit
    coreness."""
    n = 400
    edges, assign = _powerlaw(n, 7, hub=3000 // 8)
    edges = np.concatenate([edges, np.stack(
        [np.zeros(2625, np.int64), np.arange(n, n + 2625)], 1)])
    n += 2625
    assign = np.random.default_rng(7).integers(0, P, n)
    g2, plan = build_split_blocks(edges, n, assign, P, 16)
    assert plan.Km == 4096 and max(g2.N, plan.Gmax + 1) < 4096
    prog = MultiProgram((CorenessBlockProgram(), ConnectedComponentsProgram(),
                         PageRankProgram(tol=None, max_steps=3)), max_steps=3)
    st = prog.init(ops._mirror_init_view(g2, plan))
    one = ops._block_program_fused.lower(
        g2, st, None, plan, program=prog, b="jnp", interpret=True,
        max_steps=3, n_real=n).as_text()
    ex = spmd.SpmdExecutor(g2, W=1)
    sp = spmd.SpmdBlockProgram(prog, n, mirror=plan)
    mesh = spmd.SpmdEngine(g2, executor=ex)._fused_fn(sp).lower(
        st, ex.deg, ex.node_mask, sp.operands(ex), None, jnp.int32(0),
        jnp.int32(3), *ex._tables).as_text()
    for text in (one, mesh):
        shapes = _dims(text)
        assert shapes and not [s for s in shapes if 4096 in s]
    pm = np.asarray(plan.primary_mask)
    got = np.asarray(coreness(g2, backend="ell_spmd", mirror=plan))[pm]
    g = build_blocks(edges, n, assign, P=P, Cn=g2.Cn)
    want = np.asarray(coreness(g, backend="jnp"))[np.asarray(g.node_mask)]
    np.testing.assert_array_equal(got, want)


MESH_SCRIPT = textwrap.dedent("""
    import json, sys
    import numpy as np
    import jax, jax.numpy as jnp
    sys.path.insert(0, {tests!r})
    from test_split_loader import _histogram_hindex, _powerlaw
    from repro.core import build_blocks, coreness
    from repro.core.algorithms import fused_analytics
    from repro.core.hub_split import build_split_blocks
    from repro.runtime import spmd
    from repro.runtime.mesh import AXIS
    from repro.kernels import ops
    from jax.sharding import PartitionSpec
    out = {{"devices": len(jax.devices())}}
    n = 500
    edges, assign = _powerlaw(n, 11, hub=300)
    g2, plan = build_split_blocks(edges, n, assign, 8, 8)
    g = build_blocks(edges, n, assign, P=8, Cn=g2.Cn)
    pm, m = np.asarray(plan.primary_mask), np.asarray(g.node_mask)
    ex = spmd.SpmdExecutor(g2, W=4)
    want = np.asarray(coreness(g, backend="jnp"))[m]
    got = np.asarray(coreness(g2, backend="ell_spmd", executor=ex,
                              mirror=plan))[pm]
    out["core_equal"] = bool((got == want).all())
    c, l, r = fused_analytics(g2, steps=40, backend="ell_spmd", executor=ex,
                              mirror=plan)
    c0, l0, r0 = fused_analytics(g, steps=40, backend="jnp")
    out["fused_core_equal"] = bool((np.asarray(c)[pm] == np.asarray(c0)[m]).all())
    out["fused_labels_equal"] = bool(
        (np.asarray(l)[pm] == np.asarray(l0)[m]).all())
    out["rank_err"] = float(np.abs(np.asarray(r)[pm] - np.asarray(r0)[m]).max())
    # the merge alone, its table summed across the 4 devices
    rng = np.random.default_rng(0)
    R, C, G, Km = 4 * 64, 8, 30, 512
    vals = rng.integers(-1, Km, (R, C))
    live = rng.random(R) < 0.9
    gid = np.where(live, rng.integers(0, G, R), G)
    mesh = ex.wm.mesh
    f = jax.jit(jax.shard_map(
        lambda v, l, i, c: ops.merged_hindex(
            v, l, i, c, G, Km, total=lambda x: jax.lax.psum(x, AXIS)),
        mesh=mesh, in_specs=(PartitionSpec(AXIS),) * 4,
        out_specs=PartitionSpec(AXIS), check_vma=False))
    want = _histogram_hindex(vals, live, gid, G, Km)
    # uncapped (a cap of Km), and capped at a per-group estimate: the
    # early stop agrees across devices
    for name, cap in (("merge_equal", np.full(R, Km)),
                      ("capped_merge_equal",
                       rng.integers(0, Km + 1, G + 1)[gid])):
        h = np.asarray(f(jnp.asarray(vals, jnp.int32), jnp.asarray(live),
                         jnp.asarray(gid, jnp.int32),
                         jnp.asarray(cap, jnp.int32)))
        out[name] = bool((h[live] == np.minimum(want, cap)[live]).all())
    print(json.dumps(out))
""")


def test_mesh_merge_on_four_devices(tmp_path):
    """The replica merges cross real device boundaries: coreness, labels
    and PageRank of the split graph on a 4-device mesh equal the unsplit
    graph's, and the bisection's psum'd table gives the histogram's h."""
    script = tmp_path / "mesh.py"
    script.write_text(MESH_SCRIPT.format(tests=str(REPO / "tests")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    p = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out.pop("devices") == 4
    assert out.pop("rank_err") < 1e-6
    assert all(out.values()), out


def test_split_load_and_halo_build_spans(tmp_path):
    """Both open on the profiler's clock, the halo build inside a mesh
    executor's construction, and do nothing without a profiler."""
    edges, assign = _powerlaw(120, 2, hub=60)
    with tracing.span("outer"):
        g2, _ = build_split_blocks(edges, 120, assign, P, 4)
        assert tracing.current() == "outer"
    jax.profiler.start_trace(str(tmp_path))
    build_split_blocks(edges, 120, assign, P, 4)
    spmd.SpmdExecutor(g2, W=1)
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = [ev.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events]
    assert names.count("bladyg.graph.split_load") == 1
    assert names.count("bladyg.halo.build") == 1
    build_halo_plan(g2, W=1)  # outside a profile: inert


def test_mesh_layout_counter_reports_a_split_run():
    n = 300
    edges, assign = _powerlaw(n, 4, hub=100)
    g2, plan = build_split_blocks(edges, n, assign, P, 8)
    ex = spmd.SpmdExecutor(g2, W=1)
    coreness(g2, backend="ell_spmd", executor=ex, mirror=plan)
    lay = spmd.last_mesh_layout()
    assert lay.S == g2.N and lay.cols == 8 and lay.slots == g2.N * 8
    assert (lay.H, lay.K) == (ex.plan.H, ex.plan.K)
    assert lay.groups == len(groups_of(plan)) == plan.n_groups
    assert lay.Rp == len(np.asarray(plan.grp_rows))
    assert lay.Rw >= int((np.asarray(plan.grp_gid) < plan.Gmax).sum())
    assert lay.Km == plan.Km and lay.rounds == plan.Km.bit_length()
    connected_components(build_blocks(edges, n, assign, P=P),
                         backend="ell_spmd")
    lay = spmd.last_mesh_layout()
    assert (lay.groups, lay.Rp, lay.Rw, lay.Km, lay.rounds) == (0,) * 5
