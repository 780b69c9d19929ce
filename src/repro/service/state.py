"""Device-resident analytics state, published as versioned epoch snapshots.

The serving layer never reads the stream's live arrays: between the
moment a window is applied and the moment its analytics are refreshed,
`session.core`/`session.labels` and the graph describe DIFFERENT epochs,
and the apply path donates graph buffers besides.  `AnalyticsState` is
the consistency boundary — after any prefix of windows it cuts an
`EpochSnapshot`: one immutable record of (coreness, CC labels, PageRank,
degrees, adjacency) all describing the same graph, copied out of the
donation-recycled buffers.

Snapshot refresh is ONE fused superstep loop, not three recomputes: the
stream hooks already keep coreness and CC labels exact, and both are
fixpoints of their own monotone updates (min-H of true coreness is the
coreness; min-label of canonical labels is the labels) — so
`fused_analytics(init=(session.core, session.labels))` warm-starts them
AT the fixpoint, where they ride through bit-unchanged, while the
fixed-iteration PageRank sub-program does the actual work off the same
shared adjacency gather.  Every field of the published snapshot is
therefore bit-identical to a from-scratch recompute on that epoch's
graph (`coreness`, `connected_components`,
`pagerank(tol=None, max_steps=pr_steps)`) — the parity contract
`tests/test_service.py` enforces per backend.

Double buffering: snapshots are immutable NamedTuples, so "front" and
"back" collapse to an attribute swap — queries in flight keep whatever
snapshot record they started with; `refresh()` builds the next epoch's
record off to the side and publishes it by a single assignment.

Hub-split sessions (`runtime.stream.MirrorStream`, or any session whose
`.mirror` is a `core.hub_split.MirrorPlan`) refresh through the same
fused loop under the vertex-cut dataflow: coreness/CC stay bit-exact at
primaries, PageRank is allclose (float slice partials re-associate),
and the snapshot gains the `primary`/`nbr_max` resolution fields the
query layer uses (see `EpochSnapshot`).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..core.algorithms import fused_analytics


class EpochSnapshot(NamedTuple):
    """One epoch's consistent, immutable analytics + topology record.

    All arrays are device-resident COPIES (the stream's apply path
    donates the live graph buffers, so shared references would be
    invalidated mid-epoch).  Node addressing is the session's padded id
    space at this epoch; `orig_id` maps back to pre-partition input ids
    (stable across §4.2 migrations).

    Hub-split sessions (`MirrorStream` / any session exposing a
    `core.hub_split.MirrorPlan` on `.mirror`) publish two extra fields:
    `primary` — the host-side row -> primary-row map queries resolve
    through (replica-row ids answer with the hub's values), and
    `nbr_max` — the group-merged neighbor-max-coreness field (a hub's
    neighbors are sharded across its replica slices, so the plain
    (N, Cd) gather on one row would see only one slice).  `deg` then
    holds LOGICAL degrees and `rank` is masked to primaries (replica
    rows read 0.0, so `topk_pagerank` never lists a hub twice).

    Padded row ids are only comparable between two snapshots whose
    `(Cn, grows)` match: a capacity escalation (`StreamSession.grow`)
    re-keys every padded id monotonically, so a row id cached from an
    older epoch silently points at a different vertex afterwards.
    Cross-epoch joins must go through `orig_id`, the stable key.
    """

    epoch: int               # snapshot version, 0 at session open
    windows: int             # stream windows ingested when this was cut
    core: jax.Array          # (N,) int32 coreness (0 on padding)
    labels: jax.Array        # (N,) int32 CC labels (-1 on padding)
    rank: jax.Array          # (N,) float32 PageRank (0.0 on padding)
    deg: jax.Array           # (N,) int32 degrees (logical under mirror)
    nbr: jax.Array           # (N, Cd) int32 sorted-ELL adjacency
    node_mask: jax.Array     # (N,) bool real-node mask
    orig_id: jax.Array       # (N,) int32 original input ids
    primary: Optional[np.ndarray] = None   # (N,) host row->primary map
    nbr_max: Optional[jax.Array] = None    # (N,) group-merged nbr max core
    Cn: int = 0              # per-block node capacity at this epoch
    Cd: int = 0              # degree capacity at this epoch
    grows: int = 0           # capacity escalations before this epoch


class AnalyticsState:
    """Maintained analytics over a `StreamSession`, read via snapshots.

    Requires the session to be tracking CC labels (open it with
    `cc_labels=connected_components(g)`): label maintenance is what lets
    the refresh warm-start at the fixpoint instead of budgeting its own
    convergence supersteps.  The session's executor (if any) serves the
    refresh too — one device program, updates and analytics alike.
    """

    def __init__(self, session, alpha: float = 0.85, pr_steps: int = 30):
        if session.labels is None:
            raise ValueError(
                "AnalyticsState needs a label-tracking session: open "
                "StreamSession with cc_labels=connected_components(g) "
                "(or MirrorStream with cc_labels=True) so the refresh "
                "can warm-start CC at its maintained fixpoint.")
        self._session = session
        self.alpha = float(alpha)
        self.pr_steps = int(pr_steps)
        self.refreshes = 0
        self._front: Optional[EpochSnapshot] = None
        self.refresh()  # epoch 0: serve from the open-time graph

    @property
    def snapshot(self) -> EpochSnapshot:
        """The published (front) snapshot — what queries read."""
        return self._front

    @property
    def epoch(self) -> int:
        return self._front.epoch

    def staleness(self) -> int:
        """Stream windows applied since the published snapshot was cut."""
        return self._session.windows_applied - self._front.windows

    @tracing.span("service.refresh")
    def refresh(self) -> EpochSnapshot:
        """Cut + publish the next epoch's snapshot from the session head.

        One fused-analytics pass (see module docstring) plus one copy of
        the topology arrays; the publish itself is a reference swap, so
        a reader can never observe a half-built snapshot.
        """
        sess = self._session
        g = sess.g
        mirror = getattr(sess, "mirror", None)
        core, labels, rank = fused_analytics(
            g, alpha=self.alpha, steps=self.pr_steps,
            backend=sess.backend, executor=sess.executor,
            init=(sess.core, sess.labels), mirror=mirror)
        if mirror is None:
            deg, primary, nbr_max = g.deg, None, None
        else:
            # hub-split session: publish logical degrees, resolve queries
            # through the primary map, mask replica ranks out of top-k,
            # and pre-merge neighbor-max-coreness across replica slices
            # (one (N, Cd) gather + scatter-max per refresh — a single
            # row's slice would see only part of a hub's neighborhood)
            prow = jnp.asarray(mirror.primary_row, jnp.int32)
            deg = jnp.asarray(mirror.ldeg, jnp.int32)
            rank = jnp.where(jnp.asarray(mirror.primary_mask), rank, 0.0)
            row_max = jnp.max(
                jnp.where(g.nbr >= 0, core[jnp.clip(g.nbr, 0)], -1),
                axis=1).astype(jnp.int32)
            grp_max = jnp.full(g.N, -1, jnp.int32).at[prow].max(row_max)
            nbr_max = grp_max[prow]
            primary = np.asarray(mirror.primary_row, np.int32)
        back = EpochSnapshot(
            epoch=0 if self._front is None else self._front.epoch + 1,
            windows=sess.windows_applied,
            core=jnp.copy(core),
            labels=jnp.copy(labels),
            rank=jnp.copy(rank),
            deg=jnp.copy(deg),
            nbr=jnp.copy(g.nbr),
            node_mask=jnp.copy(g.node_mask),
            orig_id=jnp.copy(g.orig_id),
            primary=primary,
            nbr_max=None if nbr_max is None else jnp.copy(nbr_max),
            Cn=int(g.Cn),
            Cd=int(g.Cd),
            grows=int(getattr(sess, "_grows", 0)),
        )
        self._front = back  # publish
        self.refreshes += 1
        return back
