"""The 4-chip mesh cell (``livejournal.refresh-mesh4``) on the CPU.

Its tiny copy runs through the cell's own driver on 4 CPU devices, in a
process of its own (``bench_tiny_mesh``): clean, traced, and under
faults of the merge, the exchange and the checks' inputs, and the
bfloat16 control, each of which must make ``correct`` false.  The com-LiveJournal stand-in is checked
at a fiftieth of its size.
"""
import json

import numpy as np
import pytest

import bench_tiny
import bench_tiny_mesh

#: faults (``mesh_rehearsal.py``) and the checks that must catch them
FAULTS = {
    "merge_off_by_one": {"core_wrong", "repair_core_wrong"},
    "halo_dropped": {"core_wrong", "rank_rel_err", "repair_core_wrong"},
    "start_kept": {"repair_core_wrong", "repair_labels_wrong"},
    "rank_unmerged": {"rank_rel_err"},
    "degree_of_a_slice": {"degree_wrong"},
    "control": {"rank_rel_err"},
}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = bench_tiny_mesh.make_root(tmp_path_factory.mktemp("checkout"))
    return bench_tiny_mesh.measure_mesh(
        root, ["clean", "traced", *sorted(FAULTS)])


def test_mesh_cell_runs_correct_on_four_devices(runs):
    """The hub-split refresh over a 4-worker mesh: exact against the
    reference, through the cell's own driver."""
    out = runs["clean"]
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert sorted(out["metrics"]) == ["refresh_s", "setup_s"]
    assert all(m["value"] > 0 for m in out["metrics"].values())
    c = out["counters"]
    assert c["chips"] == 4 and c["replicas"] > 0 and c["mesh_groups"] > 0
    assert c["mesh_S"] * 4 == c["rows"] and c["mesh_Km"] >= 512
    assert c["mesh_rounds"] == c["mesh_Km"].bit_length()
    assert c["compiles_in_window"] == 0
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_mesh_cell_traced_run_reads_its_counter(runs):
    """A traced run reads the layout counter; the device-trace readers
    find no device plane on the CPU and report nothing."""
    out = runs["traced"]
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["slots_per_superstep.mesh4"]["value"] == \
        out["counters"]["slots_per_superstep"] > 0
    assert not {"superstep_ms.mesh4", "superstep_roofline.mesh4",
                "collective_share.mesh4", "gather_share.mesh4",
                "idle_share.mesh4"} & set(m)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_mesh_fault_makes_run_incorrect(runs, fault):
    out = runs[fault]
    failed = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert out["correct"] is False
    assert FAULTS[fault] <= failed, out["checks"]


def test_livejournal_stand_in_hits_the_source_sizes():
    """At a fiftieth of its nodes and edges the stand-in keeps the
    source's largest degree, and the degeneracy its planted core sets at
    any size; one component."""
    from scipy.sparse.csgraph import connected_components

    from bench import graphs, reference
    conf = json.loads((bench_tiny.REPO / "bench/configs/livejournal.json")
                      .read_text())
    spec, want = dict(conf["graph"]), conf["source_sizes"]
    spec["n"], spec["edges"] = (int(0.02 * want["nodes"]),
                                int(0.02 * want["edges"]))
    e = graphs.generate(spec)
    deg = np.bincount(e.ravel(), minlength=spec["n"])
    assert len(e) == spec["edges"] and (deg > 0).all()
    assert deg.max() == want["max_degree"]
    assert np.sort(deg)[-2] < want["max_degree"]
    A = reference.adjacency(e, spec["n"])
    core = reference.coreness(A, np.ones(spec["n"], bool))
    assert core.max() == want["degeneracy"]
    assert connected_components(A, directed=False)[0] == 1
    assert (graphs.generate(spec) == e).all()  # one fixed data set


def _reader(name):
    from bench import run
    return run.reader(name, bench_tiny.REPO)


#: op texts as a TPU trace names them: the opcode, not the instruction's
#: name, says what a collective is
TEXTS = {
    "%psum.27 = s32[1048577]{0} all-reduce(s32[1048577]{0} %fusion.127)": 1,
    "%all_to_all.33 = f32[4,1,64]{2,1,0} all-to-all(f32[4,1,64]{2,1,0} %b)": 2,
    "%all-reduce-start.4 = (f32[8]{0}, f32[8]{0}) all-reduce-start(f32[8]{0}"
    " %x)": 3,
    "%all-reduce-done.4 = f32[8]{0} all-reduce-done((f32[8]{0}, f32[8]{0})"
    " %all-reduce-start.4)": 4,
    "%ag = s32[4]{0} all-gather(s32[1]{0} %f)": 5,
    "%cp.2 = s32[4]{0} collective-permute-done(s32[4]{0} %c)": 6,
    "%reduce-scatter = f32[2]{0} reduce-scatter(f32[8]{0} %y)": 7,
    "%fusion.3 = f32[64]{0} fusion(f32[8]{0} %t, s32[64]{0} %i), kind=kCustom":
        10,
    "%all-reduce.9 = f32[] fusion(f32[8]{0} %z), kind=kLoop": 20,
    "%copy-start.1 = (s32[8]{0}, s32[8]{0}, u32[]) copy-start(s32[8]{0} %q)":
        30,
}


def test_collectives_are_classed_by_opcode():
    from bench import collectives as C
    got = {t for t in TEXTS if C.is_collective(t)}
    assert {TEXTS[t] for t in got} == {1, 2, 3, 4, 5, 6, 7}
    # two chips; ops clipped to the window [5, 45] ns
    planes = {"/device:TPU:0": [(t, 0.0, float(10 * v))
                                for t, v in TEXTS.items()],
              "/device:TPU:1": [(t, 40.0, 50.0) for t in TEXTS],
              "/device:TPU:2": []}
    want = (sum(min(10 * v, 45) - 5 for v in range(1, 8))
            + 7 * 5) / 2 / 1e9
    assert C.collective_seconds(planes, (5.0, 45.0)) == pytest.approx(want)
    assert C.collective_seconds({"/device:TPU:0": []}, (0, 1)) is None


def test_mesh_trace_readers_on_hand_made_ops():
    """The collective share reads the driver's opcode-classed seconds;
    the superstep's time and roofline share are per chip of the 4; the
    gather and idle shares read the reduction as on one chip."""
    from types import SimpleNamespace

    from bench import peaks, roofline, trace as T
    red = T.Reduction("TPU v5 lite", window_s=200.0, busy_s=198.0,
                      class_s={"gather": 99.0, "other": 99.0})
    counters = {"supersteps": 60, "chips": 4, "n_real": 3_997_962,
                "n_edges": 34_681_189, "collective_s": 28.0}
    run = SimpleNamespace(trace=red, counters=counters)
    assert _reader("collective_share.mesh4")(run) == \
        pytest.approx(100 * 28 / 198)
    assert _reader("superstep_ms.mesh4")(run) == pytest.approx(3300.0)
    least = (roofline.superstep_bytes(3_997_962, 34_681_189)
             / (4 * peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"]))
    assert _reader("superstep_roofline.mesh4")(run) == \
        pytest.approx(100 * least / 3.3)
    assert _reader("gather_share.mesh4")(run) == pytest.approx(50.0)
    assert _reader("idle_share.mesh4")(run) == pytest.approx(1.0)
    names = ("collective_share.mesh4", "superstep_ms.mesh4",
             "superstep_roofline.mesh4", "gather_share.mesh4",
             "idle_share.mesh4")
    for name in names:
        assert _reader(name)(SimpleNamespace(trace=None,
                                             counters=counters)) is None
    # a program without the trace's collective time reports no share
    del counters["collective_s"]
    assert _reader("collective_share.mesh4")(run) is None


def test_control_rank_is_the_logical_power_iteration():
    """The control's PageRank of a split graph is the reference's PageRank
    of the unsplit graph at every row of a vertex, only coarser."""
    import jax.numpy as jnp

    from bench import control_mesh, graphs, reference
    from repro.core.hub_split import build_split_blocks
    spec = dict(bench_tiny_mesh.MESH["graph"], n=600, edges=3000,
                max_degree=150, cap=60, dense=[20, 0.8])
    e = graphs.generate(spec)
    n, P = spec["n"], 8
    assign = np.random.default_rng(0).integers(0, P, n)
    g, plan = build_split_blocks(e, n, assign, P, 16)
    assert int(np.asarray(plan.primary_mask).sum()) < int(
        np.asarray(g.node_mask).sum())  # some hubs split
    got = np.asarray(control_mesh.rank_bf16_split(
        g.nbr, plan.primary_row, plan.ldeg, plan.primary_mask, steps=30,
        alpha=0.85))
    orig = np.asarray(g.orig_id)
    mask = np.asarray(g.node_mask)
    want = reference.pagerank(reference.adjacency(e, n), np.ones(n, bool),
                              30, 0.85)[orig[mask]]
    rel = np.abs(got[mask] - want) / want
    assert 1e-3 < rel.max() < 0.2
