"""The tiny mesh cell on 4 CPU devices, clean, traced or under faults.

    python mesh_rehearsal.py <checkout> <case> [<case> ...]

The device count is fixed when JAX starts, so the tests run this in a
process of its own with ``XLA_FLAGS=--xla_force_host_platform_device_
count=4``.  Each case prints one JSON line: the case and the run's
result.  A fault breaks the timed path underneath the harness, as
``test_bench_faults.py`` does in process; the control puts the logical
graph's PageRank in bfloat16 in the program's place.  The checkout is
``bench_tiny_mesh.make_root``'s.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import bench_tiny
from bench_tiny_mesh import CELL


_UNDO = []


def _patch(obj, name, value):
    _UNDO.append((obj, name, getattr(obj, name)))
    setattr(obj, name, value)


def _merge_off_by_one():
    """Each replica group's merged h-index one too small."""
    from repro.runtime import spmd
    merged = spmd.merged_hindex
    _patch(spmd, "merged_hindex", lambda *a, **kw: merged(*a, **kw) - 1)


def _halo_dropped():
    """The halo exchange delivers nothing: every remote neighbour reads
    the field's fill."""
    import jax.numpy as jnp

    from repro.runtime import spmd

    def dropped(x_local, send_idx, recv_pos, H, fill):
        return jnp.full((H + 2,) + x_local.shape[1:], fill, x_local.dtype)
    _patch(spmd, "_halo_exchange", dropped)


def _start_kept():
    """Coreness and labels handed back as they came in."""
    import repro.core.algorithms as alg
    fused = alg.fused_analytics

    def kept(*a, init=None, **kw):
        _, _, rank = fused(*a, init=init, **kw)
        return init[0], init[1], rank
    _patch(alg, "fused_analytics", kept)


def _rank_unmerged():
    """PageRank read at a hub's primary row from its own slice alone."""
    import repro.core.algorithms as alg
    fused = alg.fused_analytics

    def unmerged(g, *a, mirror=None, **kw):
        core, labels, _ = fused(g, *a, mirror=mirror, **kw)
        return core, labels, fused(g, *a, **kw)[2]
    _patch(alg, "fused_analytics", unmerged)


def _degree_of_a_slice():
    """The plan reports a hub's slice degree as its degree."""
    import dataclasses

    import jax.numpy as jnp

    import repro.core.hub_split as hs
    load = hs.build_split_blocks

    def sliced(*a, **kw):
        g, plan = load(*a, **kw)
        return g, dataclasses.replace(plan, ldeg=jnp.minimum(plan.ldeg,
                                                             g.Cd))
    _patch(hs, "build_split_blocks", sliced)


def _control():
    """The control: the logical graph's PageRank in bfloat16
    (`bench/control_mesh.py`) in place of the program's."""
    import repro.core.algorithms as alg
    from bench import control_mesh
    _patch(alg, "fused_analytics", alg.fused_analytics)
    control_mesh.install()


FAULTS = {f.__name__[1:]: f for f in (
    _merge_off_by_one, _halo_dropped, _start_kept, _rank_unmerged,
    _degree_of_a_slice, _control)}


def main(root: str, cases) -> None:
    from repro.runtime import spmd
    for case in cases:
        if case in FAULTS:
            FAULTS[case]()
        spmd.SpmdEngine._step_cache.clear()  # compile what runs now
        out = bench_tiny.measure(Path(root), CELL,
                                 trace=int(case == "traced"))
        while _UNDO:
            setattr(*_UNDO.pop())
        print(json.dumps({"case": case, "out": out}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2:])
