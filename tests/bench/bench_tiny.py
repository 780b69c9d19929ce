"""A tiny checkout for the benchmark's CPU tests.

Its configurations, a traffic mix, a metric reader, and one
configuration's generator and partitioner exist only here: they are
picked up by name from their own files and their own BENCHMARK.json
entries, beside copies of the repository's files, with no edit to any
file of the benchmark.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SOCIAL = {
    "name": "tiny-social", "source": "test only",
    "graph": {"generator": "ego_circles", "n": 200, "edges": 900,
              "egos": [40, 30, 20, 10], "intra": [300, 200, 150, 50],
              "gamma": 2.2, "dense": [0, 12, 0.8], "bridges": 40,
              "seed": 0},
    "blocks": 4, "partitioner": "random", "deg_slack": 16,
    "backend": "ell_spmd", "workers": 1, "window": 8,
    "service": {"refresh_every": 1, "pr_steps": 30, "alpha": 0.85,
                "max_queue": 1024, "max_batch": 64},
}
ROAD = {
    "name": "tiny-road", "source": "test only",
    "graph": {"generator": "road_lattice", "n": 3000, "edges": 4200,
              "max_degree": 12, "hubs": 2, "seed": 0},
    "blocks": 8, "partitioner": "random", "deg_slack": 64,
    "backend": "auto", "refresh": {"pr_steps": 30, "alpha": 0.85},
}
#: a configuration that brings its own generator and partitioner
RING = {
    "name": "tiny-ring", "source": "test only",
    "graph": {"generator": "ring_chords", "n": 1000, "chords": 300,
              "seed": 0},
    "blocks": 4, "partitioner": "stripes", "deg_slack": 16,
    "backend": "auto", "refresh": {"pr_steps": 30, "alpha": 0.85},
}
RING_GENERATOR = '''"""A ring with random chords."""
import numpy as np

from bench.graphs import canonical


def generate(n, chords, seed):
    rng = np.random.default_rng(seed)
    ring = np.stack([np.arange(n), (np.arange(n) + 1) % n], 1)
    return canonical(np.concatenate([ring, rng.integers(0, n, (chords, 2))]))
'''
STRIPES_PARTITIONER = '''"""Contiguous id ranges, one per block."""
import numpy as np


def partition(edges, n, P, rng):
    return np.arange(n) * P // n
'''
#: a new mix: the ingest protocol with a backlog the tiny graph can hold
INGEST_SMALL = {
    "driver": "served",
    "updates": {"count": 256, "warm_windows": 2},
    "reads": {"topk_k": [10, 10],
              "per_window": {"core": 2, "degree": 2, "nbr_max_core": 2,
                             "same_component": 2, "topk_pagerank": 1}},
}
#: a new per-layer metric reader
WINDOWS_READER = '''"""Stream windows applied in the measured window."""


def read(run):
    return run.counters.get("windows")
'''
CELLS = {
    "tiny-social.ingest_small": ("tiny-social", "ingest_small"),
    "tiny-road.refresh": ("tiny-road", "refresh"),
    "tiny-ring.refresh": ("tiny-ring", "refresh"),
}
#: the repository's cells each tiny cell stands in for, for metric lists
STANDS_FOR = {
    "tiny-social.ingest_small": "ego-facebook.ingest",
    "tiny-road.refresh": "roadnet-ca.refresh",
    "tiny-ring.refresh": "roadnet-ca.refresh",
}
#: the repository's configuration whose limits each tiny one borrows
LIMITS_OF = {"tiny-social": "ego-facebook", "tiny-road": "roadnet-ca",
             "tiny-ring": "roadnet-ca"}


def make_root(tmp: Path) -> Path:
    """A checkout under ``tmp``: BENCHMARK.json and the data files."""
    b = tmp / "bench"
    for d in ("traffic", "metrics", "generators", "partitioners"):
        shutil.copytree(REPO / "bench" / d, b / d)
    (b / "configs").mkdir()
    (b / "limits").mkdir()
    for conf in (SOCIAL, ROAD, RING):
        (b / "configs" / f"{conf['name']}.json").write_text(json.dumps(conf))
        shutil.copy(REPO / "bench" / "limits"
                    / f"{LIMITS_OF[conf['name']]}.json",
                    b / "limits" / f"{conf['name']}.json")
    (b / "traffic" / "ingest_small.json").write_text(json.dumps(INGEST_SMALL))
    (b / "metrics" / "windows_applied.py").write_text(WINDOWS_READER)
    (b / "generators" / "ring_chords.py").write_text(RING_GENERATOR)
    (b / "partitioners" / "stripes.py").write_text(STRIPES_PARTITIONER)

    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [
        {"name": c["name"], "source": "test only", "reduced": [],
         "file": f"bench/configs/{c['name']}.json", "why": "test only"}
        for c in (SOCIAL, ROAD, RING)]
    bench["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": 1, "why": "test"}
        for n, (c, t) in CELLS.items()]
    inverse = {}
    for tiny, real in STANDS_FOR.items():
        inverse.setdefault(real, []).append(tiny)

    def remap(m):
        if "workloads" in m:
            m["workloads"] = [t for w in m["workloads"]
                              for t in inverse.get(w, [])]
        return m

    bench["end_to_end"] = [remap(m) for m in bench["end_to_end"]]
    bench["per_layer"] = [remap(m) for m in bench["per_layer"]] + [
        {"name": "windows_applied", "unit": "windows", "better": "higher",
         "source": "program_counter", "layer": "stream",
         "moves": "update_rate", "workloads": ["tiny-social.ingest_small"]}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def measure(root: Path, cell: str, seed: int = 2**31 + 17,
            seconds: float = 1.0, trace: int = 0) -> dict:
    """One run of ``cell`` on the CPU, the chip check skipped and the
    persistent compile cache left off (it is for the chip)."""
    import jax

    from bench import run
    args = run.parse(["--workload", cell, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
    cache = run.enable_compile_cache
    run.enable_compile_cache = lambda: ""
    try:
        return run.measure(args, devices=jax.devices(), root=root)
    finally:
        run.enable_compile_cache = cache
