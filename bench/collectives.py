"""Device time in cross-chip collectives, classed by HLO opcode.

`bench/trace.py` keeps an op's instruction name, and on the TPU the
instruction names of collectives do not say what they are: a
``jax.lax.psum`` compiles to ``%psum.27 = s32[...] all-reduce(...)`` and
the halo exchange to ``%all_to_all.33 = ... all-to-all(...)``.  The
opcode, read from each op's HLO text (`trace.parse_op`), does.  A run
that uses this reads its trace with `seconds` before the harness reduces
(and deletes) it.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

from . import trace

#: collective opcodes; their async ``-start``/``-done`` halves count too
OPCODES = ("all-to-all", "ragged-all-to-all", "all-reduce", "all-gather",
           "collective-permute", "collective-broadcast", "reduce-scatter")


def is_collective(text: str) -> bool:
    """Whether an op's HLO text is a collective or one of its halves."""
    op = trace.parse_op(text)[1]
    for half in ("-start", "-done"):
        if op.endswith(half):
            op = op[:-len(half)]
    return op in OPCODES


def collective_seconds(planes: Dict[str, List[Tuple[str, float, float]]],
                       window: Tuple[float, float]) -> Optional[float]:
    """Seconds a chip (the mean over the planes that ran ops) spent in
    collective ops inside ``window`` (ns); ``planes`` maps a device plane
    to its ops as (HLO text, start_ns, end_ns).  None without ops."""
    w0, w1 = window
    total, chips = 0.0, 0
    for ops in planes.values():
        if not ops:
            continue
        chips += 1
        total += sum(max(0.0, min(e, w1) - max(s, w0))
                     for text, s, e in ops if is_collective(text))
    return total / 1e9 / chips if chips else None


def seconds(logdir: str) -> Optional[float]:
    """`collective_seconds` of the trace a run wrote under ``logdir``,
    inside its ``bench.window`` span; None where it has no device ops."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    planes, window = {}, None
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:"):
            planes[plane.name] = [(ev.name, ev.start_ns, ev.end_ns)
                                  for ln in plane.lines
                                  if ln.name == trace.OPS_LINE
                                  for ev in ln.events]
        elif plane.name.startswith("/host:") and window is None:
            window = next(((ev.start_ns, ev.end_ns) for ln in plane.lines
                           for ev in ln.events if ev.name == trace.WINDOW),
                          None)
    if window is None:
        return None
    return collective_seconds(planes, window)
