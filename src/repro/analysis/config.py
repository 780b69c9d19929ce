"""tracelint configuration: scopes, boundary whitelists, and key schemas.

Everything rule-specific but repo-specific lives here, so the rules
themselves stay mechanical and this file reads as the *inventory of
sanctioned exceptions* to the device-loop invariants:

* `HOST_BOUNDARIES` — the functions allowed to synchronize with the
  device (`jax.device_get` / `np.asarray` / `int()` on arrays).  Every
  entry is a documented host boundary: graph construction, stream
  validation, the ONE bundled transfer per window/batch/fixpoint.
* `CACHE_SCHEMAS` — every known compiled-function cache and the names
  its key must contain.  A cache site detected by pattern (an
  `lru_cache` in scope, or a `*cache*` dict) that is not registered
  here is itself a finding — new caches must declare their key.
* `SEED_PREFIXES` — quarantined seed-substrate packages (LLM configs,
  models/optim/data, launch/distributed).  They are kept as fixtures
  (see the `seed_fixtures` notes in their package `__init__`) and are
  excluded from the sync/retrace rules; the dead-seed import audit
  (`repro.analysis.imports`) is what keeps the quarantine honest.

Paths are POSIX-relative to the scan root (the directory containing the
`repro` package), e.g. ``repro/runtime/spmd.py``.
"""
from __future__ import annotations

from typing import Dict, FrozenSet, Tuple

#: packages whose device loops the host-sync / retrace rules protect
SYNC_SCOPE: Tuple[str, ...] = (
    "repro/core/",
    "repro/kernels/",
    "repro/runtime/",
    "repro/service/",
)

#: quarantined seed substrate — excluded from every AST rule; the
#: dead-seed audit checks these carry a `seed_fixtures` note instead
SEED_PREFIXES: Tuple[str, ...] = (
    "repro/models/",
    "repro/optim/",
    "repro/data/",
    "repro/launch/",
    "repro/distributed/",
    "repro/configs/",
)

#: reachability roots for the dead-seed import audit: everything in
#: these packages is product surface; modules *outside* them must be
#: imported (transitively) by them or carry the seed_fixtures marker
REACHABILITY_ROOTS: Tuple[str, ...] = (
    "repro.core",
    "repro.kernels",
    "repro.runtime",
    "repro.service",
    "repro.graphgen",
    # repro.checkpoint is no longer a root of its own: the recovery
    # coordinator (repro.runtime.recovery) imports it, so it is regular
    # product surface reached from repro.runtime
)

#: the literal token a quarantined package's `__init__` docstring must
#: contain for the dead-seed audit to accept it
SEED_MARKER = "seed_fixtures"

#: the pow2 bucket helpers — the ONLY sanctioned way a data/shape-derived
#: host scalar may reach a jit static argument or compiled-cache key.
#: Functions named here are also exempt from the shape-derived check on
#: their own bodies (they ARE the helpers).
BUCKET_HELPERS: FrozenSet[str] = frozenset({
    "_pow2_bucket",
    "_pad_to",
    "_tile_dims",
    "degree_bound",
    "hybrid_split",
    "batch_bucket",
    "topk_bucket",
})

#: functions allowed to build a fresh jit wrapper inside another
#: function body without an enclosing lru_cache (they memoize by hand
#: or are themselves called only from memoized sites)
JIT_FACTORIES: FrozenSet[str] = frozenset({
    "_smap",
    "_jitted_worker",  # core/engine.py: WeakKeyDictionary memo per program
})

#: host-boundary whitelist for the host-sync rule.
#:
#: Maps file -> set of function names (innermost OR any enclosing def)
#: allowed to synchronize, or "*" for a whole host-side module.  Every
#: entry documents WHY it is a boundary; anything not listed that pulls
#: from device in SYNC_SCOPE is a finding.
HOST_BOUNDARIES: Dict[str, FrozenSet[str]] = {
    # graph construction / host-side accessors (np arrays in, np out);
    # the jitted mutation path (insert_edge/delete_edge/_sorted_*) is
    # deliberately NOT whitelisted.
    "repro/core/graph.py": frozenset({
        "build_blocks", "build_ell_random", "sort_nbr_rows",
        "n_real", "m_real", "halo_slot_counts", "halo_pair_counts",
        "to_networkx_edges", "migrate_vertices", "edge_exists_host",
        "degree_host", "orig_of",
        # capacity escalation: pad-and-rekey relocation is host numpy on
        # the concrete adjacency, like build_blocks / migrate_vertices
        "grow_blocks", "grow", "relocate_rows", "add_vertices_host",
    }),
    # host splice/validation module: the sanctioned numpy twin of the
    # jitted update path
    "repro/core/updates.py": frozenset({"*"}),
    # hub splitting / mirror-plan maintenance: split planning, replica
    # allocation, and per-edit slice splices are host-boundary work on
    # the concrete adjacency (like halo-plan builds) — the per-superstep
    # merge stage lives in kernels/ops.py and runtime/spmd.py, protected
    "repro/core/hub_split.py": frozenset({"*"}),
    # host-side partitioners (numpy throughout)
    "repro/core/partition.py": frozenset({"*"}),
    "repro/core/partition_dynamic.py": frozenset({"*"}),
    # host Bron-Kerbosch / degree summaries (numpy throughout)
    "repro/core/cliques.py": frozenset({"*"}),
    "repro/core/degree.py": frozenset({"*"}),
    # engine host drivers: one transfer per run / per trace flush
    "repro/core/engine.py": frozenset({"run", "run_jit", "_flush_traces"}),
    # coreness host wrappers: documented host-int returns
    "repro/core/kcore.py": frozenset({
        "coreness_with_stats", "max_coreness",
    }),
    # maintenance host drivers: stream validation + the bundled
    # per-chunk verdict pull; the jitted maintain path stays protected
    "repro/core/kcore_dynamic.py": frozenset({
        "maintain_batch", "maintain_batch_host", "_maintain_one",
        "_maintain_one_spmd", "_validate_updates_host",
        "_independent_prefix", "_spmd_executor",
    }),
    # backend resolution (platform query) + the sanctioned ONE-transfer
    # sites: degree_bound and hybrid_split (per fixpoint),
    # run_block_program (n_real at entry), coreness_dense/coreness_blocks
    # (bucketed K pull)
    "repro/kernels/ops.py": frozenset({
        "resolve_backend", "degree_bound", "hybrid_split",
        "run_block_program",
        "coreness_dense", "coreness_blocks", "dense_adj", "_pad_ell",
        "ell_lanes",
    }),
    # reference oracles are host-side by design
    "repro/kernels/ref.py": frozenset({"*"}),
    # halo plans are BUILT on host from the concrete adjacency (at open /
    # apply_updates time, never per superstep)
    "repro/runtime/halo.py": frozenset({"*"}),
    "repro/runtime/mesh.py": frozenset({"*"}),
    # executor/engine host shell: plan (re)builds + the one fused-run
    # transfer; compiled supersteps live in _compiled_* (protected)
    "repro/runtime/spmd.py": frozenset({
        "__init__", "apply_updates", "rebuild", "run_spmd", "run",
        "_plan_arrays", "_halo_args", "k_reachable_batch",
        "restricted_recompute", "step_build_count",
        # capacity escalation: full plan rebuild at the new (Cn, Cd),
        # same boundary as rebuild
        "grow", "refresh_fields",
        # a hub-split plan's tables laid out per worker, once per plan
        "mirror_tables",
    }),
    # stream host driver: window padding (np), the ONE bundled verdict
    # pull per window, and host routing arithmetic; _route_window and
    # the jitted maintain path are NOT whitelisted
    "repro/runtime/stream.py": frozenset({
        "apply_window", "stats", "_owner_blocks", "owner_block",
        "route_updates", "__init__",
        # elasticity + snapshots: grow/add_vertices/migrate mutate the
        # concrete host graph (like migrate_vertices); state_dict /
        # from_state are the checkpoint boundary (one bundled transfer
        # per snapshot); _cur/_compose_perm are host id arithmetic
        "grow", "add_vertices", "migrate", "state_dict", "from_state",
        "_cur", "_compose_perm",
    }),
    # crash-recovery coordinator: evacuation planning, window-log replay
    # and the kill/restore drill are host protocol work by construction
    "repro/runtime/recovery.py": frozenset({"*"}),
    # the ONE device_get per answered batch + host padding
    "repro/service/queries.py": frozenset({"run_batch", "_pad_ids"}),
    # snapshot cut/publish: host boundary between stream and serving
    "repro/service/state.py": frozenset({"refresh", "__init__"}),
    "repro/service/metrics.py": frozenset({"*"}),
}

#: every known compiled-function cache and the names its key carries.
#: lru_cache sites key on their parameter list; dict caches key on the
#: tuple expression stored/looked up.  Adding a cache without
#: registering it here is a cache-key finding.
CACHE_SCHEMAS: Dict[str, Tuple[str, ...]] = {
    "repro/runtime/spmd.py::_compiled_hindex": ("mesh", "H", "overlap"),
    "repro/runtime/spmd.py::_compiled_frontier": ("mesh", "H", "overlap"),
    "repro/runtime/spmd.py::_compiled_coreness": ("mesh", "H", "overlap"),
    "repro/runtime/spmd.py::_compiled_reach": ("mesh", "H", "overlap"),
    "repro/runtime/spmd.py::_compiled_recompute": ("mesh", "H", "overlap"),
    "repro/runtime/spmd.py::_step_cache": (
        "mesh", "H", "B", "Cn", "widths", "overlap", "program", "mirror"),
}

#: approved sorted-ELL splice/sort helpers: a `nbr` write whose value
#: routes through one of these calls preserves the invariant
SORTED_ELL_HELPERS: FrozenSet[str] = frozenset({
    "sort_nbr_rows",
    "_sorted_insert_row",
    "_sorted_delete_row",
    "_insert_sorted",
    "_delete_sorted",
    # hub-split slice splices (host numpy, in-place on one (Cd,) row
    # slice, shift-based like their jnp row twins above)
    "_sorted_slice_insert",
    "_sorted_slice_delete",
})

#: functions allowed to write `nbr` raw: the helpers themselves plus
#: the constructors that establish the invariant with a terminal
#: `sort_nbr_rows` pass and the host applier that splices via the
#: approved helpers row by row
SORTED_ELL_WRITERS: FrozenSet[str] = SORTED_ELL_HELPERS | frozenset({
    "build_blocks",
    "build_ell_random",
    "apply_updates_host",
    # split_hubs rewires slot-by-slot into fresh replica rows, then
    # re-establishes the invariant with a terminal sort_nbr_rows pass;
    # apply_mirrored_edits splices via the approved slice helpers;
    # run_common_mirror's canonicalized view routes through
    # sort_nbr_rows too (the jnp.asarray dtype wrapper hides the call
    # from the value-flow check)
    "split_hubs",
    "apply_mirrored_edits",
    # the split rule's assembly writes each row's slots from one sorted
    # (row, neighbour) key array, so rows arrive ascending, pads right
    "_assemble",
    "run_common_mirror",
    # grow_blocks value-remaps nbr through a MONOTONE rekey (row slots
    # keep their relative order, pads stay right-justified), so the
    # sorted-ELL invariant survives without a re-sort — the property
    # tests/test_growth.py checks against a from-scratch rebuild
    "grow_blocks",
    # snapshot restore re-adopts arrays saved from an invariant-holding
    # graph verbatim (checkpoints are bit-exact copies)
    "from_state",
})


def in_sync_scope(path: str) -> bool:
    """True if `path` (root-relative POSIX) is protected by the
    host-sync / retrace rules."""
    return path.startswith(SYNC_SCOPE) and not is_seed(path)


def is_seed(path: str) -> bool:
    """True if `path` lies in a quarantined seed-substrate package."""
    return path.startswith(SEED_PREFIXES)


def boundary_functions(path: str) -> FrozenSet[str]:
    """Whitelisted host-boundary function names for `path`."""
    return HOST_BOUNDARIES.get(path, frozenset())
