"""BLADYG computational model: master/worker supersteps + messaging modes.

The paper's abstractions, mapped to SPMD JAX:

  workerCompute()  — a pure function applied to the block-sharded arrays
                     (all blocks advance together; on hardware each device
                     holds one block via the `workers` mesh axis).
  masterCompute()  — a pure function of per-block summaries; its result is
                     replicated (broadcast) to all workers.
  M2W / W2M        — the broadcast of the master directive / the all-gather
                     of per-block summaries around each superstep.
  W2W              — any neighbor-state exchange inside workerCompute (halo
                     gathers across the block boundary).
  Local            — block-local compute, no collectives.

A BLADYG *computation* (paper §3.1) = input graph + incremental changes +
a sequence of worker/master operations + output.  `BladygEngine.run`
executes that sequence; `run_jit` fuses it into a single `lax.while_loop`
when both operations are jittable.

Two program notions live here:

  `BladygProgram`  — the free-form worker/master contract (any pytree
                     state, any collective inside workerCompute).  Coreness
                     uses it for the paper's message-accounting runs.
  `BlockProgram`   — the *structured* superstep contract every workload in
                     `core.algorithms` is written against: init state →
                     per-node halo field → named neighbor combine →
                     block-local update → halt reduction.  Because the
                     neighbor access is declared (not hidden inside
                     workerCompute), one runner per backend executes any
                     BlockProgram: `kernels.ops.run_block_program` fuses
                     the whole fixpoint into a single `lax.while_loop` on
                     the jnp/dense/ell backends and routes `ell_spmd`
                     through the worker mesh with a real halo exchange
                     (`runtime.spmd.SpmdBlockProgram`).

The engine also meters messages per mode — this is how the benchmarks
reproduce the paper's inter- vs intra-partition accounting.  The W2W
numbers here are *declared* (shape-reconstructed) because the halo gather
fuses inside jit; the distributed runtime (`repro.runtime.SpmdEngine`)
executes the same supersteps over the `workers` device mesh and records
the counts of its executed `HaloPlan` instead — `w2w_override` lets a
caller stamp those executed counts into this engine's traces when
cross-checking the two (EXPERIMENTS.md §Runtime).
"""
from __future__ import annotations

import dataclasses
import enum
import weakref
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.ops import BlockCtx  # noqa: F401  (re-export: contract type)
from .graph import GraphBlocks


class Mode(enum.Flag):
    LOCAL = enum.auto()
    M2W = enum.auto()
    W2M = enum.auto()
    W2W = enum.auto()


class MessageStats(NamedTuple):
    m2w: int = 0
    w2m: int = 0
    w2w_intra: int = 0
    w2w_inter: int = 0

    def __add__(self, o):  # type: ignore[override]
        return MessageStats(*(a + b for a, b in zip(self, o)))


@dataclasses.dataclass
class SuperstepTrace:
    step: int
    mode: Mode
    stats: MessageStats
    #: collective phases the step's compute had to WAIT on before touching
    #: neighbor values: 1 under the strict-ordered halo exchange, 0 when the
    #: runtime overlapped the exchange with block-local gathers
    #: (`runtime.spmd.SpmdExecutor(overlap=True)`).
    serialized_collectives: int = 0


class BladygProgram:
    """Base class for user programs (paper's workerCompute/masterCompute).

    Subclasses override `worker_compute` and `master_compute`.  Both must be
    pure (jit-safe) if the program is run through `run_jit`.
    """

    #: modes this program is allowed to activate (checked by the engine)
    modes: Mode = Mode.LOCAL | Mode.M2W | Mode.W2M | Mode.W2W

    def w2w_payload(self, g: GraphBlocks) -> Tuple[int, int]:
        """(intra, inter) W2W halo element counts moved per superstep.

        The engine cannot see inside `worker_compute` (under jit the halo
        gather is a fused XLA collective), so programs *declare* their halo
        payload — e.g. via `graph.halo_slot_counts` for a one-value-per-
        neighbor-slot exchange.  Default: no W2W traffic.
        """
        return (0, 0)

    def worker_compute(
        self, g: GraphBlocks, wstate: Any, directive: Any
    ) -> Tuple[Any, Any]:
        """(graph, worker state, master directive) -> (worker state', summary).

        `summary` is the W2M payload: any pytree whose leaves have a leading
        P axis (one row per block) or are global reductions.
        """
        raise NotImplementedError

    def master_compute(
        self, mstate: Any, summary: Any
    ) -> Tuple[Any, Any, jax.Array]:
        """(master state, summaries) -> (master state', directive, halt)."""
        raise NotImplementedError


class BlockProgram:
    """The structured BLADYG superstep contract (tentpole abstraction).

    A BlockProgram factors one superstep into four declared phases, which
    is exactly what lets a single runner execute it on every backend of
    the kernel registry:

      1. **init state**     — `init(g)`: whole-graph worker state (a
         pytree whose array leaves all carry the leading node axis, so
         the state shards over the `workers` mesh axis unchanged).
      2. **halo exchange**  — `halo_field(state)`: the (n, ...) per-node
         values neighbors read this superstep, plus `halo_fill`, the
         value PAD neighbor slots (and, on the mesh, halo dump slots)
         read as.  This *declares* the W2W payload instead of hiding it
         inside workerCompute.
      3. **kernel step**    — `combine` names the neighbor reduction
         (see `kernels.ops.COMBINES`: "min" | "sum" | "hindex" |
         "count_common"); each backend supplies its own execution of it
         (pure-jnp gather, dense-adjacency form, ELL Pallas kernel, or
         halo-exchange + local reduce on the mesh).  `update(ctx, state,
         red)` is then pure block-local math on the reduced (n, ...)
         values.
      4. **halt reduction** — `changed(old, new)`: the local
         convergence verdict; the runner reduces it globally (a `psum`
         on the mesh) and stops when no worker changed or `max_steps`
         supersteps ran.  Fixed-iteration programs return True
         unconditionally and bound the loop with `max_steps`.

    Programs must be *hashable statics*: instances ride into `jax.jit` as
    static arguments and into the per-(mesh, H) compiled-step caches, so
    equality/hash derive from `(type, _key())` — include every
    behavior-changing constructor parameter in `_key()`.

    See `core.algorithms` for the shipped workloads (connected
    components, PageRank, triangle counting, coreness) and
    `kernels.ops.run_block_program` for the runner.
    """

    #: neighbor combine name, resolved per backend by `kernels.ops`
    combine: str = "min"
    #: value PAD slots read as; must be absorbing for `combine` and match
    #: the halo field dtype (e.g. int32 max for "min", 0.0 for "sum")
    halo_fill: Any = -1
    #: superstep bound (the whole loop is device-resident; the bound is a
    #: loop-carried operand, never a host decision)
    max_steps: int = 10_000
    #: the program's name in compiled-program and named-scope names (a
    #: profile's ops read ``.../<name>/...``); not part of its identity
    name: str = "program"

    def _key(self) -> Tuple:
        """Static identity: every parameter that changes traced behavior."""
        return ()

    def __hash__(self):
        return hash((type(self), self._key()))

    def __eq__(self, other):
        return type(other) is type(self) and other._key() == self._key()

    def init(self, g: GraphBlocks) -> Any:
        """Whole-graph initial worker state (host boundary, pre-shard).

        Every array leaf must have the padded node count N as its leading
        axis so the ell_spmd backend can shard the state over workers.
        """
        raise NotImplementedError

    def halo_field(self, state: Any) -> jax.Array:
        """The (n, ...) per-node array whose values neighbors read (W2W)."""
        raise NotImplementedError

    def update(self, ctx: BlockCtx, state: Any, red: jax.Array) -> Any:
        """One block-local step: (ctx, state, reduced neighbor values) ->
        state'.  Must be jit-pure and elementwise over the node axis."""
        raise NotImplementedError

    def mirror_state(self, state: Any, primary_row: jax.Array) -> Any:
        """Replicate per-vertex state onto hub mirror rows (vertex cut).

        Under a hub-split graph (`core.hub_split`) every mirror row must
        carry its primary's state so neighbors reading a replica see the
        logical value and replicas advance in lockstep through `update`.
        The default gathers every array leaf through `primary_row` —
        correct whenever all leaves are per-VERTEX (N-leading) values.
        Programs with per-ROW state (e.g. triangle counting's neighbor-
        row field) override this to protect those leaves.  Must be
        idempotent: the runner applies it to caller warm starts too.
        """
        return jax.tree_util.tree_map(lambda a: a[primary_row], state)

    def changed(self, old: Any, new: Any) -> jax.Array:
        """Local convergence verdict (device bool scalar); the runner
        halts when no worker reports a change.  Default: any array leaf
        differs bit-wise."""
        leaves_o = jax.tree_util.tree_leaves(old)
        leaves_n = jax.tree_util.tree_leaves(new)
        flags = [jnp.any(a != b) for a, b in zip(leaves_o, leaves_n)]
        out = jnp.bool_(False)
        for f in flags:
            out = out | f
        return out


class MultiProgram(BlockProgram):
    """Several BlockPrograms advancing in lockstep off ONE neighbor gather.

    Run separately, k programs cost k adjacency sweeps per superstep —
    and the (N, Cd) neighbor matrix is the roofline-dominant operand of
    every sweep.  A MultiProgram declares the fusion instead: its state,
    halo field, and fill are *tuples* (one leaf per sub-program), its
    combine is the sentinel ``"multi"`` with the per-field names in
    `combines`, and the runners (`kernels.ops.run_block_program`, the
    ell_spmd mesh path) read the neighbor slots ONCE per superstep and
    serve every field's gather + reduce off the shared index matrix.
    Each fused reduce reproduces its standalone formulation exactly, so
    per-field results are bit-identical to running the sub-programs
    alone for the same superstep count.

    Sub-program combines must come from `kernels.ops.MULTI_COMBINES`
    ("min" | "sum" | "hindex" — "count_common" exchanges whole rows,
    which would defeat the shared gather).  Halting: a fused step runs
    until EVERY sub-program's `changed` goes quiet (OR reduction) or
    `max_steps` supersteps ran; include a fixed-iteration sub-program
    (e.g. `PageRankProgram(tol=None)`) and the loop runs exactly
    `max_steps` supersteps, during which already-converged min-style
    sub-programs idle at their fixpoints (their updates are idempotent).
    """

    combine = "multi"

    def __init__(self, programs: Tuple[BlockProgram, ...],
                 max_steps: int = 10_000):
        from ..kernels.ops import MULTI_COMBINES  # cycle-free late import
        programs = tuple(programs)
        if not programs:
            raise ValueError("MultiProgram needs at least one sub-program")
        for p in programs:
            if p.combine not in MULTI_COMBINES:
                raise ValueError(
                    f"sub-program combine {p.combine!r} not fusable; "
                    f"expected one of {MULTI_COMBINES}")
        self.programs = programs
        self.combines: Tuple[str, ...] = tuple(p.combine for p in programs)
        self.name = "_".join(p.name for p in programs)
        self.halo_fill = tuple(p.halo_fill for p in programs)
        self.max_steps = int(max_steps)

    def _key(self):
        return (self.programs, self.max_steps)

    def init(self, g: GraphBlocks) -> Tuple[Any, ...]:
        return tuple(p.init(g) for p in self.programs)

    def halo_field(self, state: Tuple[Any, ...]) -> Tuple[jax.Array, ...]:
        return tuple(p.halo_field(s) for p, s in zip(self.programs, state))

    def update(self, ctx: "BlockCtx", state: Tuple[Any, ...],
               red: Tuple[jax.Array, ...]) -> Tuple[Any, ...]:
        out = []
        for p, s, r in zip(self.programs, state, red):
            with jax.named_scope(p.name):
                out.append(p.update(ctx, s, r))
        return tuple(out)

    def changed(self, old: Tuple[Any, ...],
                new: Tuple[Any, ...]) -> jax.Array:
        out = jnp.bool_(False)
        for p, o, n in zip(self.programs, old, new):
            out = out | p.changed(o, n)
        return out

    def mirror_state(self, state: Tuple[Any, ...],
                     primary_row: jax.Array) -> Tuple[Any, ...]:
        return tuple(p.mirror_state(s, primary_row)
                     for p, s in zip(self.programs, state))


# One jitted wrapper per program INSTANCE, kept for the instance's
# lifetime: a fresh `jax.jit(...)` per run() would discard the compile
# cache and retrace every call (tracelint: retrace-hazard).
_JIT_WORKERS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _jitted_worker(program: BladygProgram) -> Callable:
    """Memoized `jax.jit(program.worker_compute)` keyed on the instance."""
    fn = _JIT_WORKERS.get(program)
    if fn is None:
        fn = _JIT_WORKERS[program] = jax.jit(program.worker_compute)
    return fn


class BladygEngine:
    """Superstep scheduler over a block-partitioned graph."""

    def __init__(self, g: GraphBlocks):
        self.g = g
        self.traces: list[SuperstepTrace] = []

    # -- host-driven loop (flexible; each superstep individually jitted) ----
    def run(
        self,
        program: BladygProgram,
        wstate: Any,
        mstate: Any,
        directive: Any = None,
        max_supersteps: int = 10_000,
        jit_steps: bool = True,
        w2w_override: Optional[Tuple[int, int]] = None,
    ) -> Tuple[Any, Any]:
        worker = _jitted_worker(program) if jit_steps \
            else program.worker_compute
        master = program.master_compute
        step = 0
        g = self.g
        w2w = w2w_override if w2w_override is not None else program.w2w_payload(g)
        while step < max_supersteps:
            wstate, summary = worker(g, wstate, directive)          # Local/W2W
            mstate, directive, halt = master(mstate, summary)        # W2M+M2W
            self.traces.append(
                SuperstepTrace(
                    step, program.modes, self._meter(summary, directive, w2w)
                )
            )
            step += 1
            if bool(halt):
                break
        return wstate, mstate

    # -- fully-jitted loop ---------------------------------------------------
    def run_jit(
        self,
        program: BladygProgram,
        wstate: Any,
        mstate: Any,
        directive: Any,
        max_supersteps: int = 10_000,
        w2w_override: Optional[Tuple[int, int]] = None,
    ) -> Tuple[Any, Any]:
        g = self.g

        def cond(c):
            _, _, _, halt, it = c
            return (~halt) & (it < max_supersteps)

        def body(c):
            wstate, mstate, directive, _, it = c
            wstate, summary = program.worker_compute(g, wstate, directive)
            mstate, directive, halt = program.master_compute(mstate, summary)
            return wstate, mstate, directive, halt, it + 1

        # Per-superstep message sizes are static (jit-shaped pytrees), so the
        # trace can be reconstructed after the fused loop: abstract-eval the
        # worker for the summary shape, use the declared W2W payload, and
        # multiply by the executed superstep count.
        _, summary_shape = jax.eval_shape(
            program.worker_compute, g, wstate, directive
        )
        w2w = w2w_override if w2w_override is not None else program.w2w_payload(g)

        wstate, mstate, _, _, n = jax.lax.while_loop(
            cond, body, (wstate, mstate, directive, jnp.bool_(False), jnp.int32(0))
        )
        stats = self._meter(summary_shape, directive, w2w)
        # ONE host transfer for the whole run: the superstep count rides the
        # same device_get that blocks on the final state; the traces are then
        # reconstructed in a single bulk extend (per-superstep stats are
        # static, so no per-step host work remains).  wstate/mstate stay on
        # device for the caller.
        (n_steps,) = jax.device_get((n,))
        self.traces.extend(
            SuperstepTrace(step, program.modes, stats)
            for step in range(int(n_steps))
        )
        return wstate, mstate

    @staticmethod
    def _meter(
        summary: Any, directive: Any, w2w: Tuple[int, int] = (0, 0)
    ) -> MessageStats:
        def count(tree):
            tot = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                tot += int(getattr(leaf, "size", 1))
            return tot

        return MessageStats(
            m2w=count(directive),
            w2m=count(summary),
            w2w_intra=int(w2w[0]),
            w2w_inter=int(w2w[1]),
        )

    def message_totals(self) -> MessageStats:
        tot = MessageStats()
        for t in self.traces:
            tot = tot + t.stats
        return tot
