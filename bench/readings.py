#!/usr/bin/env python3
"""Readings for the limits: the compared numbers of a cell over many
seeds in one process, with the program or with the control.

    python3 bench/readings.py --workload roadnet-ca.refresh \\
        --seeds 11,12,13 --seconds 5 [--control 1]

One JSON line per seed (its checks), then one line with each number's
largest reading over the seeds.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    if a.control:
        from bench import control
        control.install()
    worst = {}
    for seed in a.seeds.split(","):
        out = run.measure(run.parse(["--workload", a.workload, "--seed",
                                     seed, "--seconds", str(a.seconds)]))
        vals = {k: c["value"] for k, c in out["checks"].items()}
        for k, v in vals.items():
            worst[k] = max(worst.get(k, v), v)
        print(json.dumps({"seed": int(seed), "control": a.control,
                          "correct": out["correct"], "checks": vals,
                          "metrics": out["metrics"]}), flush=True)
    print(json.dumps({"workload": a.workload, "control": a.control,
                      "seeds": a.seeds, "largest": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
