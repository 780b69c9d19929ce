"""Distributed k-core decomposition — BLADYG application #1 (paper §4.1).

Algorithm: the locality-based distributed coreness computation of
[Montresor, De Pellegrini, Miorandi, TPDS'13], expressed as BLADYG
supersteps.  Each node keeps a coreness *estimate*; one superstep applies

    est' = min(est, H(est))        H(est)(u) = h-index of {est(v) : v ~ u}

Correctness (why the fixpoint is exactly the coreness):
  * est starts at deg >= core and H is monotone, H(core) = core, so est >= core
    is invariant under est' = min(est, H(est)).
  * the sequence is pointwise non-increasing and integral -> converges to
    some x with H(x) >= x.
  * for such x, every u with x(u) = k has >= k neighbors with x >= k, so each
    level set S_k = {v : x(v) >= k} induces a subgraph of min degree >= k,
    i.e. S_k is inside the k-core and x <= core pointwise.  Hence x = core.

The same argument is *local*: clamping any set of nodes at their true
coreness and iterating only on the rest still converges to the true
coreness of the rest — that is what makes the incremental maintenance in
`kcore_dynamic.py` exact.

Execution: the H(est) primitive is obtained *only* through the kernel
backend registry (`repro.kernels.ops`) — `backend="jnp"|"dense"|"ell"`
selects pure-jnp, dense-tile Pallas, or ELL block-sparse Pallas, all exact;
"auto" resolves by platform and graph size; the explicit `"ell_spmd"`
backend runs the same supersteps sharded over the `workers` device mesh
with a real halo exchange (`repro.runtime`).  See EXPERIMENTS.md
§Backends and §Runtime.

Communication pattern (BLADYG modes): the gather of neighbor estimates is
the W2W halo exchange; the convergence test is a W2M reduction; the loop
continuation is the master's M2W broadcast.  Under `jit` with sharded
arrays, XLA emits exactly those collectives (all-gather for the halo,
all-reduce for the flag) — see EXPERIMENTS.md §Dry-run.  `CorenessProgram`
runs the same superstep through `BladygEngine` with the halo payload
declared, so the engine's per-mode message metering reproduces the paper's
inter- vs intra-partition accounting.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from ..kernels.ref import ell_gather, hindex_rows  # noqa: F401 (re-export)
from .engine import BladygProgram, Mode
from .graph import GraphBlocks, halo_slot_counts


def neighbor_estimates(g: GraphBlocks, est: jax.Array) -> jax.Array:
    """Gather est over the ELL adjacency; PAD slots -> -1 (ignored by hindex)."""
    return ell_gather(g.nbr, est)


def coreness_step(
    g: GraphBlocks, est: jax.Array, active: jax.Array, backend: str = "jnp"
) -> Tuple[jax.Array, jax.Array]:
    """One BLADYG superstep on an `active` node mask; returns (est', changed)."""
    h = ops.hindex_blocks(g, est, backend=backend)
    new = jnp.where(active & g.node_mask, jnp.minimum(est, h), est)
    return new, jnp.any(new != est)


def coreness(
    g: GraphBlocks, max_steps: int = 10_000, backend: str = "auto",
    executor=None, mirror=None,
) -> jax.Array:
    """Coreness of every node (0 on padding rows), via the chosen backend.

    Every backend runs the whole min-H fixpoint as a single fused
    `lax.while_loop` (Pallas kernels inside the body on dense/ell, the
    shard_map'd halo loop on ell_spmd) — zero per-superstep host syncs.
    All backends return identical integers.  On the mesh backend pass a
    long-lived `SpmdExecutor` via `executor=` to skip the per-call halo
    plan build.

    `mirror` (a `core.hub_split.MirrorPlan` for a split `g`) routes
    through the generic `CorenessBlockProgram` under the vertex-cut
    dataflow: the slices' counts merge per replica group and a bisection
    finds the group's h-index (`ops.merged_hindex`), so every row of a replica group carries the hub's exact coreness —
    bit-identical at primaries to the unsplit run.
    """
    if mirror is not None:
        from .algorithms import CorenessBlockProgram

        est = ops.run_block_program(
            g, CorenessBlockProgram(), backend=backend, executor=executor,
            max_steps=max_steps, mirror=mirror)
        return jnp.where(g.node_mask, est, 0)
    return ops.coreness_blocks(g, backend=backend, max_steps=max_steps,
                               executor=executor)


def coreness_with_stats(
    g: GraphBlocks, max_steps: int = 10_000, backend: str = "jnp"
):
    """Coreness plus the superstep count (host int, for benchmarks).

    Same fused fixpoint as `coreness`; the step count comes back as a
    device scalar and is fetched in one transfer at the end — the old
    host-driven loop (one transfer per superstep) is gone.
    """
    est, steps = ops.coreness_blocks(
        g, backend=backend, max_steps=max_steps, with_steps=True)
    return est, int(jax.device_get(steps))


def max_coreness(g: GraphBlocks) -> int:
    return int(jax.device_get(jnp.max(coreness(g))))


class CorenessProgram(BladygProgram):
    """min-H coreness as an engine program (paper §4.1 step 1).

    Worker state is the estimate vector; each superstep gathers the neighbor
    halo (W2W — the payload is one estimate per valid neighbor slot, intra or
    inter depending on the slot's block), applies min-H, and reports the
    changed flag (W2M).  The master broadcasts continue/halt (M2W).
    """

    modes = Mode.LOCAL | Mode.M2W | Mode.W2M | Mode.W2W

    def __init__(self, backend: str = "jnp"):
        self.backend = backend

    def worker_compute(self, g: GraphBlocks, est, directive):
        new, changed = coreness_step(g, est, g.node_mask, backend=self.backend)
        return new, changed

    def master_compute(self, mstate, summary):
        return mstate, None, jnp.logical_not(summary)

    def w2w_payload(self, g: GraphBlocks) -> Tuple[int, int]:
        # one estimate flows across every valid neighbor slot per superstep
        return halo_slot_counts(g)


def coreness_via_engine(g: GraphBlocks, backend: str = "jnp"):
    """Run CorenessProgram through BladygEngine; returns (core, engine).

    The engine's traces carry the metered message counts per superstep —
    the benchmark hook for the paper's message accounting.
    """
    from .engine import BladygEngine

    est0 = jnp.where(g.node_mask, g.deg, 0).astype(jnp.int32)
    eng = BladygEngine(g)
    est, _ = eng.run(CorenessProgram(backend=backend), est0, None)
    return jnp.where(g.node_mask, est, 0), eng


def coreness_via_spmd(g: GraphBlocks, W=None):
    """CorenessProgram routed through the distributed runtime.

    Runs the same min-H supersteps under `runtime.SpmdEngine.run_spmd`:
    the neighbor gather is an executed halo exchange on the `workers`
    mesh and the returned engine's traces carry the *executed* W2W
    counts (`HaloPlan.slot_counts`) instead of the declared payload.
    Returns (core, SpmdEngine); core is bit-identical to
    `coreness_via_engine`'s.
    """
    from ..runtime.spmd import SpmdCorenessProgram, SpmdEngine

    est0 = jnp.where(g.node_mask, g.deg, 0).astype(jnp.int32)
    eng = SpmdEngine(g, W=W)
    est, _ = eng.run_spmd(SpmdCorenessProgram(), est0, None)
    return jnp.where(g.node_mask, est, 0), eng
