"""The one traffic generator: reads a mix's parameters, draws its events.

A mix is a JSON file ``traffic/<name>.json``.  Keys:

``driver``  which driver runs the cell (``drivers/<driver>.py``).
``updates`` null, or the update stream, a backlog that is all due at the
            start (a queue that never empties):
    ``count``    backlog length (a multiple of 4);
    ``warm_windows`` leading windows applied in set-up, which compile
                 the maintenance paths (taken from the same stream).
    The mix of kinds is the paper's: insert/delete x inter/intra block,
    equal shares, interleaved.
``reads``   null, or the reads submitted before every update window:
    ``per_window`` kind -> how many of that kind;
    ``topk_k``   [lo, hi]: k of a top-k PageRank read, uniform.
    Nodes are uniform over the real nodes.

Every seed draws the same counts of each kind, on other edges and nodes.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import List

import numpy as np

from . import graphs

ROOT = Path(__file__).resolve().parents[1]


def load(name: str, root: Path = ROOT) -> dict:
    return json.loads((root / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng([seed & ((1 << 64) - 1), stream])


@dataclass
class Plan:
    """The events of one run, in padded node ids."""

    updates: List[tuple] = field(default_factory=list)  # (u, v, op)
    reads: List[tuple] = field(default_factory=list)    # (kind, u, v, k)
    reads_per_window: int = 0
    warm_windows: int = 0   # leading update windows applied in set-up


def generate(spec: dict, graph, seed: int, R: int) -> Plan:
    """Draw the events of ``spec`` for one run."""
    plan = Plan()
    up, rd = spec.get("updates"), spec.get("reads")
    if up:
        plan.warm_windows = int(up.get("warm_windows", 0))
        plan.updates = graphs.sample_updates(
            graph.edges, graph.real, graph.Cn, graph.N, int(up["count"]),
            rng_for(seed, 1))
    if rd:
        rng = rng_for(seed, 3)
        windows = -(-len(plan.updates) // R)
        kinds = [kd for kd, c in rd["per_window"].items()
                 for _ in range(c)] * windows
        plan.reads_per_window = len(kinds) // max(windows, 1)
        n = len(kinds)
        us, vs = rng.choice(graph.real, n), rng.choice(graph.real, n)
        lo, hi = rd.get("topk_k", [1, 1])
        ks = rng.integers(lo, hi + 1, n)
        plan.reads = [(kd, int(u), int(v), int(k))
                      for kd, u, v, k in zip(kinds, us, vs, ks)]
    return plan
