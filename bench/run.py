#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload ego-facebook.ingest --seed 7 \\
        --seconds 30 --trace 0

A cell (``BENCHMARK.json`` ``workloads``) is a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``) whose ``driver`` names the module in
``bench/drivers/`` that runs it.  With ``--trace 0`` the last line of
standard output reports the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the line reports the
per-layer metrics, each read by ``bench/metrics/<metric>.py``.  Each
number that decides ``correct`` is printed beside its limit, last on
standard error and last in the result line.

Runs only on a TPU: without one, or with fewer chips than the cell asks
for, it exits non-zero before any work.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chip(chips: int):
    """The devices to run on; exits 2 without a TPU or enough chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        sys.exit(f"the cell needs {chips} TPU chips, JAX found "
                 f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache, every program in it, at a fixed path:
    ``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def per_layer_for(bench: dict, cell: str) -> list:
    """Per-layer metrics this cell reports (see the contract's rule for a
    metric without ``workloads``)."""
    e2e = end_to_end_for(bench, cell)
    return [m["name"] for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def end_to_end_for(bench: dict, cell: str) -> list:
    return [m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str, root: Path = ROOT):
    """The per-layer reader ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def measure(args, devices=None, root: Path = ROOT) -> dict:
    """Run the cell; return the result line's object.  ``devices`` and
    ``root`` are for tests: the chip check is skipped when ``devices`` is
    given, and BENCHMARK.json and the files it names are read under
    ``root``."""
    import jax

    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = harness.load_cell(bench, args.workload, args.seed, args.seconds,
                             bool(args.trace), root)
    if devices is None:
        devices = require_chip(cell.chips)
    enable_compile_cache()
    compiles = harness.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    jax.monitoring.register_event_listener(compiles.event)
    driver = importlib.import_module(
        f"bench.drivers.{cell.traffic['driver']}")
    run = driver.run(cell, harness.Run(cell, t_start=T_START,
                                       compiles=compiles))

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": all(c.ok for c in run.checks) and bool(run.checks),
           "attempted": run.attempted, "failed": run.failed}
    if cell.trace:
        from bench import trace
        run.trace = trace.reduce_dir(run.trace_dir, dev.device_kind)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        metrics = {}
        for name in per_layer_for(bench, cell.name):
            value = reader(name, root)(run)
            if value is not None:
                metrics[name] = {"value": value,
                                 "unit": _unit(bench, name)}
        out["metrics"] = metrics
        out["breakdown"] = run.trace.breakdown()
    else:
        out["metrics"] = {name: {"value": run.e2e[name],
                                 "unit": _unit(bench, name)}
                          for name in end_to_end_for(bench, cell.name)}
    out["device"] = device
    out["counters"] = run.counters
    out["notes"] = run.notes
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in run.checks}
    return out


def _unit(bench: dict, name: str) -> str:
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == name:
            return m["unit"]
    raise KeyError(name)


def main(argv=None) -> int:
    args = parse(argv)
    out = measure(args)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
