"""Per-kernel roofline points (the ``benchmarks.run --profile`` payload).

For each ELL kernel of the registry this module pairs an analytic
operation model — FLOPs (integer compares count as ops) and HBM bytes
moved per dispatch, derived from the kernel's loop structure — with a
measured wall-clock time, and emits one roofline point per kernel:

  intensity          = flops / bytes            [ops per byte]
  roofline_bound_us  = max(flops/peak_flops, bytes/peak_bw)
  achieved_fraction  = roofline_bound_us / measured_us   (1.0 = on the
                       roofline)

The points land in ``PROFILE_kernels.json`` next to the BENCH_*.json
trajectory files (the distinct prefix keeps ``check_regression``'s
``BENCH_*`` glob away from them — profile points carry device peaks,
not comparable row timings) and ride the same CI artifact upload.

Peaks come from `PEAKS`, keyed by the device's `device_kind`; a device
the table does not know (the CPU included) is an error, so a roofline
share is only ever computed against a chip's published peaks.  The
triangle kernel has no compiled TPU lowering (see `ell_triangles.py`)
and is not profiled.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.core import build_ell_random
from repro.kernels import ops

#: (peak_flops/s, peak_bytes/s) per `device_kind`.  TPU v5e: Google Cloud
#: documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM per chip.
PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
}


def device_peaks(device) -> tuple:
    """(peak_flops/s, peak_bytes/s) of `device`; raises for unknown kinds."""
    kind = device.device_kind
    if kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device_kind {kind!r}; known: "
            f"{sorted(PEAKS)}")
    return PEAKS[kind]


def _timed_us(fn, reps: int = 3) -> float:
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / max(1, reps) * 1e6


def _pad128(x: int) -> int:
    return -(-x // 128) * 128


def kernel_models(N: int, Cd: int) -> List[Dict]:
    """Analytic (flops, bytes) per dispatch for each profiled kernel.

    C is the padded column count the kernels actually sweep; int32
    everywhere (4 bytes).  Compares/selects count as 1 op.
    """
    C = _pad128(Cd)
    Np = _pad128(N)
    lg = max(1, math.ceil(math.log2(C)))
    nbr_bytes = Np * C * 4             # one adjacency sweep
    vec_bytes = Np * 4                 # one (N,) field or output
    gather_bytes = Np * C * 4          # one (N, C) gathered value matrix
    return [
        dict(name=f"hindex_bisect/N{N}/Cd{Cd}",
             flops=Np * C * 2 * (lg + 1),      # compare + count per round
             bytes=nbr_bytes + gather_bytes + vec_bytes * 2),
        dict(name=f"cc_min/N{N}/Cd{Cd}",
             flops=Np * C,                     # row min
             bytes=nbr_bytes + gather_bytes + vec_bytes * 2),
        dict(name=f"pagerank_sum/N{N}/Cd{Cd}",
             flops=Np * C,                     # row sum
             bytes=nbr_bytes + gather_bytes + vec_bytes * 2),
        dict(name=f"multi_fused/N{N}/Cd{Cd}",
             flops=Np * C * (2 * lg + 4),      # bisect + min + sum
             bytes=nbr_bytes + 3 * (gather_bytes + vec_bytes * 2)),
    ]


def profile_points(seed: int = 0, N: int = 320, Cd: int = 24,
                   reps: int = 3) -> Dict:
    """Measure every modeled kernel once and attach roofline terms."""
    device = jax.devices()[0]
    platform = device.platform
    peak_f, peak_b = device_peaks(device)
    g = build_ell_random(N, Cd=Cd, seed=seed, m_factor=Cd / 3)
    est = jnp.asarray(g.deg, jnp.int32)
    lab = jnp.arange(g.N, dtype=jnp.int32)
    contrib = jnp.where(g.deg > 0, 1.0 / jnp.maximum(g.deg, 1),
                        0.0).astype(jnp.float32)
    dispatch = {
        "hindex_bisect": lambda: ops.hindex_ell(g.nbr, est),
        "cc_min": lambda: ops.neighbor_min_ell(g.nbr, lab),
        "pagerank_sum": lambda: ops.neighbor_sum_ell(g.nbr, contrib),
        "multi_fused": lambda: ops.neighbor_multi_ell(
            g.nbr, (est, lab, contrib), ("hindex", "min", "sum")),
    }
    points = []
    for model in kernel_models(g.N, g.Cd):
        key = model["name"].split("/")[0]
        us = _timed_us(dispatch[key], reps)
        bound_us = max(model["flops"] / peak_f,
                       model["bytes"] / peak_b) * 1e6
        points.append({
            **model,
            "us_per_call": round(us, 1),
            "intensity_flops_per_byte": round(
                model["flops"] / model["bytes"], 3),
            "roofline_bound_us": round(bound_us, 3),
            "achieved_fraction": round(bound_us / max(us, 1e-9), 6),
        })
    return {
        "profile": "kernels",
        "platform": {
            "jax_backend": platform,
            "device_kind": device.device_kind,
            "device_count": len(jax.devices()),
        },
        "peaks": {"flops_per_s": peak_f, "bytes_per_s": peak_b},
        "points": points,
    }


if __name__ == "__main__":
    import json

    print(json.dumps(profile_points(), indent=2))
