"""The benchmark end to end on the CPU at a tiny size.

Each mix runs through the harness's discovery against configurations,
a mix and a metric reader that exist only in the test's checkout
(``bench_tiny``); the check for a TPU is skipped here, in the test only.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import bench_tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell", sorted(bench_tiny.CELLS))
def test_cell_runs_correct_on_cpu(root, cell):
    out = bench_tiny.measure(root, cell)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    want = [m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]
    assert out["correct"], out["checks"]
    assert sorted(out["metrics"]) == sorted(want)
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0


def test_traced_run_reads_the_new_metric(root):
    out = bench_tiny.measure(root, "tiny-social.ingest_small", trace=1)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["windows_applied"]["value"] > 0
    assert m["supersteps_per_window"]["value"] > 0
    assert m["apply_ms"]["value"] > 0 and m["refresh_ms.ingest"]["value"] > 0
    # no device plane in a CPU trace: the device readers find nothing
    assert "idle_share.ingest" not in m
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_seed_fixes_inputs_and_keeps_sizes(root):
    """The same seed gives the same inputs; another seed draws the same
    counts of each update and read kind, on other edges and nodes."""
    from bench import harness, traffic
    conf = json.loads((root / "bench/configs/tiny-social.json").read_text())
    spec = traffic.load("ingest_small", root)

    def plan(seed):
        g = harness.build_graph(conf, seed, root)
        same = lambda u, v: u // g.Cn == v // g.Cn  # noqa: E731
        kinds = sorted((op, same(u, v)) for u, v, op in
                       traffic.generate(spec, g, seed, 8).updates)
        return traffic.generate(spec, g, seed, 8), kinds

    (a, ka), (b, kb), (c, kc) = plan(2**31 + 5), plan(2**31 + 5), plan(9)
    assert a.updates == b.updates and a.reads == b.reads
    assert ka == kc and len(a.updates) == len(c.updates) == 256
    assert sorted(r[0] for r in a.reads) == sorted(r[0] for r in c.reads)
    assert a.updates != c.updates and a.reads != c.reads


def test_config_brings_its_own_generator_and_partitioner(root):
    """Generator and partitioner are found by the names the
    configuration gives, as files of their own."""
    from bench import harness
    conf = json.loads((root / "bench/configs/tiny-ring.json").read_text())
    g = harness.build_graph(conf, 3, root)
    assert (g.assign == np.arange(1000) * 4 // 1000).all()
    assert g.edges0.shape[0] >= 1000
    conf["partitioner"] = "bfs"
    with pytest.raises(SystemExit, match="no partitioner 'bfs'"):
        harness.build_graph(conf, 3, root)
    conf["partitioner"], conf["graph"]["generator"] = "random", "snap_file"
    with pytest.raises(SystemExit, match="no generator 'snap_file'"):
        harness.build_graph(conf, 3, root)


@pytest.mark.parametrize("name,scale", [("ego-facebook", 1.0),
                                        ("roadnet-ca", 0.02)])
def test_stand_in_hits_the_source_sizes(name, scale):
    """A configuration's generator gives the source's node and edge
    counts and its largest degree (road network: at a fiftieth of its
    nodes and edges, the width kept)."""
    from bench import graphs, reference
    conf = json.loads(
        (bench_tiny.REPO / "bench/configs" / f"{name}.json").read_text())
    spec, want = dict(conf["graph"]), conf["source_sizes"]
    spec["n"], spec["edges"] = (int(scale * want["nodes"]),
                                int(scale * want["edges"]))
    e = graphs.generate(spec)
    deg = np.bincount(e.ravel(), minlength=spec["n"])
    assert len(e) == spec["edges"] and (deg > 0).all()
    assert deg.max() == want["max_degree"]
    if scale == 1.0 and "degeneracy" in want:
        A = reference.adjacency(e, spec["n"])
        core = reference.coreness(A, np.ones(spec["n"], bool))
        assert core.max() == want["degeneracy"]


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(bench_tiny.REPO / "bench" / "run.py"),
         "--workload", "ego-facebook.ingest", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=bench_tiny.REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_shed_reads_fail_without_breaking_correct(root, monkeypatch):
    """Admission control may shed a read: it counts as failed, while an
    admitted read left unanswered would make the run incorrect."""
    from repro.service.server import QueryServer
    submit, calls = QueryServer.submit, [0]

    def shed_some(self, query):
        calls[0] += 1
        return None if calls[0] % 5 == 0 else submit(self, query)
    monkeypatch.setattr(QueryServer, "submit", shed_some)
    out = bench_tiny.measure(root, "tiny-social.ingest_small")
    assert out["failed"] > 0 and out["counters"]["shed"] == out["failed"]
    assert out["correct"], out["checks"]
    assert out["checks"]["unanswered"]["value"] == 0
