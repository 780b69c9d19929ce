"""A tiny copy of the 4-chip mesh cell for the benchmark's CPU tests.

`make_root` lays out `bench_tiny`'s checkout and adds a power-law graph
with a few hubs, split at 16 and spread over 4 workers, under the mesh
cell's traffic (``refresh-mesh``), with the metric lists of
``livejournal.refresh-mesh4``.  JAX fixes its device count when it
starts, so the cell runs in a process of its own with 4 CPU devices
(`measure_mesh`, which runs ``mesh_rehearsal.py``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import bench_tiny

CELL = "tiny-mesh.refresh-mesh"
STANDS_FOR = "livejournal.refresh-mesh4"
MESH = {
    "name": "tiny-mesh", "source": "test only",
    "graph": {"generator": "powerlaw_core", "n": 3000, "edges": 21000,
              "max_degree": 600, "gamma": 2.5, "cap": 200,
              "dense": [40, 0.8], "seed": 0},
    "blocks": 8, "workers": 4, "partitioner": "random",
    "split_threshold": 16, "backend": "ell_spmd",
    "executor": {"overlap": False},
    "refresh": {"pr_steps": 30, "alpha": 0.85},
}


def make_root(tmp: Path) -> Path:
    """`bench_tiny`'s checkout under ``tmp`` plus the tiny mesh cell."""
    root = bench_tiny.make_root(tmp)
    b = root / "bench"
    (b / "configs" / "tiny-mesh.json").write_text(json.dumps(MESH))
    (b / "limits" / "tiny-mesh.json").write_text(
        (bench_tiny.REPO / "bench" / "limits" / "livejournal.json")
        .read_text())
    real = json.loads((bench_tiny.REPO / "BENCHMARK.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "tiny-mesh", "source": "test only", "reduced": [],
         "file": "bench/configs/tiny-mesh.json", "why": "test only"})
    bench["workloads"].append(
        {"name": CELL, "config": "tiny-mesh", "traffic": "refresh-mesh",
         "chips": 4, "why": "test"})
    ours = {m["name"]: m for m in real["end_to_end"] + real["per_layer"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if STANDS_FOR in ours.get(m["name"], {}).get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def measure_mesh(root: Path, cases) -> dict:
    """Each case of ``mesh_rehearsal.py`` (clean, traced, or a fault) on
    the tiny mesh cell, in one process with 4 CPU devices: case ->
    result line."""
    here = Path(__file__).parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(bench_tiny.REPO / "src"),
                                           str(here)]))
    p = subprocess.run(
        [sys.executable, str(here / "mesh_rehearsal.py"), str(root),
         *cases], env=env, capture_output=True, text=True, timeout=900)
    if p.returncode:
        raise RuntimeError(p.stderr[-4000:])
    lines = [json.loads(x) for x in p.stdout.splitlines()]
    return {x["case"]: x["out"] for x in lines}
