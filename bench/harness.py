"""What every driver shares: the cell's graph, spans, checks.

A driver (``drivers/<name>.py``) gets a `Cell` and fills a `Run`:
end-to-end values, the checks that decide ``correct``, host spans,
program counters, and what the per-layer readers (``metrics/<name>.py``)
read.  Nothing here imports the program; drivers do.
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from . import graphs, traffic

ROOT = Path(__file__).resolve().parents[1]


@dataclass
class Cell:
    """One cell of BENCHMARK.json with its files loaded."""

    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    root: Path = ROOT      # the checkout its files were found in


def load_cell(bench: dict, name: str, seed: int, seconds: float,
              trace: bool, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, mix and limits, each
    found by name under the checkout ``root``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, config=json.loads((root / conf["file"]).read_text()),
        traffic=traffic.load(w["traffic"], root),
        limits=json.loads((root / "bench" / "limits" / f"{w['config']}.json")
                          .read_text()),
        chips=int(w["chips"]), seed=int(seed), seconds=float(seconds),
        trace=bool(trace), root=root)


@dataclass
class Graph:
    """The benchmark's own copy of a configuration's graph."""

    n: int                 # original nodes
    P: int
    Cn: int
    N: int                 # padded nodes
    edges0: np.ndarray     # (m, 2) original ids, as handed to the program
    assign: np.ndarray     # (n,) block of each original node
    new: np.ndarray        # (n,) padded id of each original node
    edges: np.ndarray      # (m, 2) padded ids, lo < hi, sorted
    mask: np.ndarray       # (N,) real node
    real: np.ndarray       # sorted padded ids of real nodes
    deg: np.ndarray        # (N,) degree


def build_graph(config: dict, seed: int, root: Path = ROOT) -> Graph:
    """The configuration's stand-in graph, partitioned from ``seed``.

    The graph itself is the configuration's fixed data set (its own
    generator seed); ``seed`` draws the partition and, in the traffic
    module, the stream and the reads.  Generator and partitioner are
    the files the configuration names under ``root``.
    """
    edges0 = graphs.generate(config["graph"], root)
    n, P = int(config["graph"]["n"]), int(config["blocks"])
    assign = graphs.partition(config["partitioner"], edges0, n, P,
                              traffic.rng_for(seed, 0), root)
    Cn = graphs.block_capacity(assign, P)
    new = graphs.layout(assign, P, Cn)
    N = P * Cn
    edges = graphs.canonical(new[edges0])
    mask = np.zeros(N, bool)
    mask[new] = True
    deg = np.bincount(edges.ravel(), minlength=N)
    return Graph(n=n, P=P, Cn=Cn, N=N, edges0=edges0, assign=assign,
                 new=new, edges=edges, mask=mask,
                 real=np.flatnonzero(mask), deg=deg)


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


class CompileCounter:
    """Counts what JAX compiles once armed (the measured window opens):
    backend compiles, and how many of them the persistent cache served.
    A program that retraces an identical function gets a cache load."""

    def __init__(self):
        self.armed, self.count, self.cache_hits = False, 0, 0

    def __call__(self, event, duration, **_):
        if self.armed and event.startswith("/jax/core/compile/backend"):
            self.count += 1

    def event(self, event, **_):
        if self.armed and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclass
class Run:
    """What one run measured; drivers fill it, readers read it."""

    cell: Cell
    t_start: float = 0.0                  # perf_counter at process start
    compiles: CompileCounter = field(default_factory=CompileCounter)
    e2e: Dict[str, float] = field(default_factory=dict)
    checks: List[Check] = field(default_factory=list)
    spans: Dict[str, List[float]] = field(default_factory=dict)
    counters: Dict[str, float] = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    window: Optional[tuple] = None        # perf_counter start, end
    memory_peak_bytes: Optional[int] = None
    trace_dir: Optional[str] = None       # the profiler's output
    trace: Optional[object] = None        # trace.Reduction when traced
    notes: Dict[str, object] = field(default_factory=dict)

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append(Check(name, float(value), float(limit)))

    @contextmanager
    def span(self, name: str):
        """Host span around a call into the program; on the profiler's
        clock too when the run is traced."""
        ann = None
        if self.cell.trace:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.setdefault(name, []).append(time.perf_counter() - t0)
            if ann is not None:
                ann.__exit__(None, None, None)

    def mean_ms(self, name: str) -> Optional[float]:
        xs = self.spans.get(name)
        return 1e3 * float(np.mean(xs)) if xs else None


def ready(tree) -> None:
    """Wait for every array of ``tree``."""
    import jax
    jax.block_until_ready([x for x in jax.tree_util.tree_leaves(tree)
                           if isinstance(x, jax.Array)])


def peak_bytes() -> Optional[int]:
    """Peak device bytes of the fullest local device, as JAX reports it."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Window:
    """The measured window: host clock, and the profiler when traced."""

    def __init__(self, run: Run):
        self.run = run
        self.logdir = None
        self._ann = None

    def __enter__(self):
        if self.run.cell.trace:
            import tempfile

            import jax
            self.logdir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # spans and device ops only
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        self.run.e2e["setup_s"] = self.t0 - self.run.t_start
        self.run.compiles.armed = True
        return self

    def close(self) -> float:
        """End the window now (idempotent); returns its end."""
        if self.run.window is None:
            t1 = time.perf_counter()
            self.run.window = (self.t0, t1)
            self.run.compiles.armed = False
            c = self.run.compiles
            self.run.counters["compiles_in_window"] = c.count - c.cache_hits
            self.run.counters["cache_loads_in_window"] = c.cache_hits
            if self._ann is not None:
                import jax
                self._ann.__exit__(None, None, None)
                jax.profiler.stop_trace()
                self.run.trace_dir = self.logdir
        return self.run.window[1]

    def __exit__(self, *exc):
        self.close()
