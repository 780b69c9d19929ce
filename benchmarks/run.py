"""Benchmark harness entry: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  table2  — AIT/ADT inter- vs intra-partition k-core maintenance (Table 2)
            plus batched-maintenance rows when --batch-sizes is given
  fig7    — incremental maintenance vs naive full recompute    (Figure 7)
  table3/4/5 — dynamic partitioning PT/UT hash/random/DFEP     (Tables 3-5)
  kcore_static — static decomposition time + supersteps        (§4.1 step 1)
  backends — jnp vs dense vs ELL registry sweep incl. the >4 GiB dense-
             infeasible N (EXPERIMENTS.md §Backends)
  kernels  — h-index kernel variants (bisect vs count) + fused-vs-host-loop
             fixpoint latency (EXPERIMENTS.md §Kernels)
  runtime  — mesh (ell_spmd) coreness parity/time + metered vs executed
             W2W accounting (EXPERIMENTS.md §Runtime)
  stream   — incremental vs full halo-plan maintenance, executor-reuse
             stream pass, §4.2 live rebalancing (EXPERIMENTS.md §Stream)
  workloads — BlockProgram workload sweep: CC / PageRank / triangles per
             backend, superstep counts + parity (EXPERIMENTS.md §Workloads)
  service  — query service qps + p50/p99 under concurrent update load,
             sweeping query mix × window width R (EXPERIMENTS.md §Service)
  roofline — three-term roofline per (arch × shape) from the dry-run JSONs

The `kernels`, `stream`, `workloads`, and `service` rows are additionally
written to ``BENCH_kernels.json`` / ``BENCH_stream.json`` /
``BENCH_workloads.json`` / ``BENCH_service.json``
under --out-dir: the machine-readable perf trajectory (committed
baselines at the repo root, fresh points uploaded as CI artifacts and
soft-checked by ``benchmarks.check_regression``).

``--profile`` additionally measures the per-kernel roofline points
(analytic FLOPs/bytes + achieved fraction, `benchmarks.profile_kernels`)
and writes them to ``PROFILE_kernels.json`` under --out-dir — a distinct
prefix, so the BENCH_* regression glob never compares profile payloads.

Usage: PYTHONPATH=src python -m benchmarks.run [--full] [--updates N]
       [--backends jnp,dense,ell] [--batch-sizes 1,4,8] [--smoke]
       [--profile] [--out-dir DIR]

--smoke is the CI gate: tiny graphs, every backend, a few updates — fails
fast on kernel parity regressions without the full table runtime.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import platform
import sys
import traceback

#: benches whose rows feed the machine-readable perf trajectory
JSON_BENCHES = ("kernels", "stream", "workloads", "service", "skew",
                "elastic")


def write_bench_json(out_dir: str, bench: str, rows) -> pathlib.Path:
    """Write one bench's rows as BENCH_<name>.json (NaN -> null)."""
    import jax

    payload = {
        "bench": bench,
        "schema": ["name", "us_per_call", "derived"],
        "platform": {
            "jax_backend": jax.devices()[0].platform,
            "device_count": len(jax.devices()),
            "python": platform.python_version(),
        },
        "rows": [
            {
                "name": name,
                "us_per_call": round(us, 1) if math.isfinite(us) else None,
                "derived": derived,
            }
            for name, us, derived in rows
        ],
    }
    path = pathlib.Path(out_dir) / f"BENCH_{bench}.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(payload, indent=2, allow_nan=False) + "\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale datasets (slow; CI default is scaled)")
    ap.add_argument("--updates", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backends", default="jnp",
                    help="comma list for the static sweep: jnp,dense,ell")
    ap.add_argument("--batch-sizes", default="",
                    help="comma list of maintain_batch R values for table2")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI pass: backend parity + a few updates")
    ap.add_argument("--only", default=None,
                    help="comma list: table2,fig7,partitioning,static,"
                         "backends,kernels,runtime,stream,workloads,"
                         "service,skew,roofline")
    ap.add_argument("--profile", action="store_true",
                    help="also dump per-kernel roofline points "
                         "(PROFILE_kernels.json under --out-dir)")
    ap.add_argument("--out-dir", default=".",
                    help="directory for the BENCH_*.json trajectory files")
    args = ap.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()

    from . import (bench_backends, bench_elastic, bench_kcore_maintenance,
                   bench_kernels, bench_vs_naive_kcore, bench_partitioning,
                   bench_runtime, bench_service, bench_skew,
                   bench_static_kcore, bench_stream, bench_workloads,
                   roofline)

    backends = tuple(b for b in args.backends.split(",") if b)
    batch_sizes = tuple(int(r) for r in args.batch_sizes.split(",") if r)

    if args.smoke:
        # shrink the Table-1 stand-ins to a fast sanity scale and force the
        # full backend sweep + a batched-maintenance pass
        from . import common
        small = {"DS1": 0.02, "ego-Facebook": 0.10}
        common.CI_SCALES.clear()
        common.CI_SCALES.update(small)
        args.updates = min(args.updates, 6)
        backends = ("jnp", "dense", "ell")
        batch_sizes = batch_sizes or (4,)

    benches = {
        "table2": lambda: bench_kcore_maintenance.run(
            updates=args.updates, full=args.full, seed=args.seed,
            batch_sizes=batch_sizes),
        "fig7": lambda: bench_vs_naive_kcore.run(
            updates=max(5, args.updates // 4), full=args.full, seed=args.seed),
        "partitioning": lambda: bench_partitioning.run(
            full=args.full, seed=args.seed),
        "static": lambda: bench_static_kcore.run(
            full=args.full, seed=args.seed, backends=backends),
        "backends": lambda: bench_backends.run(
            seed=args.seed, smoke=args.smoke),
        "kernels": lambda: bench_kernels.run(
            seed=args.seed, smoke=args.smoke),
        "runtime": lambda: bench_runtime.run(
            seed=args.seed, smoke=args.smoke),
        "stream": lambda: bench_stream.run(
            seed=args.seed, smoke=args.smoke),
        "workloads": lambda: bench_workloads.run(
            seed=args.seed, smoke=args.smoke),
        "service": lambda: bench_service.run(
            seed=args.seed, smoke=args.smoke),
        "skew": lambda: bench_skew.run(
            seed=args.seed, smoke=args.smoke),
        "elastic": lambda: bench_elastic.run(
            seed=args.seed, smoke=args.smoke),
        "roofline": lambda: roofline.run(full=args.full, seed=args.seed),
    }
    if args.smoke:
        for excluded in ("roofline", "fig7"):
            benches.pop(excluded)  # roofline needs dry-run JSONs; fig7
            # adds minutes without touching the kernel/backend surface
            # (partitioning stays: it is pure numpy and fast at CI scale,
            # and gates the §4.2 IncrementalPart/NaivePart protocol)
    only = set(args.only.split(",")) if args.only else set(benches)
    unknown = only - set(benches)
    if unknown:
        raise SystemExit(
            f"--only {','.join(sorted(unknown))}: not available"
            + (" under --smoke" if args.smoke else "")
            + f"; choose from {','.join(sorted(benches))}"
        )

    print("name,us_per_call,derived")
    failed = 0
    for name, fn in benches.items():
        if name not in only:
            continue
        try:
            rows = list(fn())
            for r in rows:
                print(f"{r[0]},{r[1]:.1f},{r[2]}")
            sys.stdout.flush()
            if name in JSON_BENCHES:
                path = write_bench_json(args.out_dir, name, rows)
                print(f"# wrote {path}", file=sys.stderr)
        except Exception:
            failed += 1
            print(f"{name},nan,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if args.profile:
        from . import profile_kernels

        payload = profile_kernels.profile_points(seed=args.seed)
        path = pathlib.Path(args.out_dir) / "PROFILE_kernels.json"
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(payload, indent=2) + "\n")
        print(f"# wrote {path}", file=sys.stderr)
        for p in payload["points"]:
            print(f"profile/{p['name']},{p['us_per_call']:.1f},"
                  f"achieved={p['achieved_fraction']};"
                  f"intensity={p['intensity_flops_per_byte']}")
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
