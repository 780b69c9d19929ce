"""Reduce a profiler trace to device busy time, idle share and op totals.

Read with ``jax.profiler.ProfileData`` (nothing but JAX).  What a TPU v5e
trace of this benchmark holds (looked at by hand first):

* a plane ``/device:TPU:<i>`` per chip.  Its line ``XLA Modules`` has one
  event per executed program (``jit__block_program_fused(<hash>)``); its
  line ``XLA Ops`` has one event per executed HLO instruction, named by
  the instruction's HLO text (``%fusion.8 = f32[2097152]{...} fusion(...),
  kind=kCustom, calls=...``).  Loops are ops too: a ``while`` event spans
  its whole loop and the ops of its body lie inside it, so op times nest.
* the host plane ``/host:CPU`` with the benchmark's spans
  (``jax.profiler.TraceAnnotation`` named ``bench.<span>``), among them
  ``bench.window`` around the measured window.  Host and device events
  share one clock.

Busy time is the union of a chip's op intervals inside the window,
averaged over the chips used; the idle share is 1 - busy / window.  Op
and class totals leave out the container ops (``while``, ``conditional``,
``call``), whose time is their body's.  Classes:

* ``gather``: an HLO ``gather``, or what XLA makes of the neighbour
  gather on the TPU, a ``kind=kCustom`` fusion of a 1-D table and a 1-D
  s32 index vector whose output has the index vector's length
  (``fusion(f32[N] %table, s32[M] %idx)`` -> ``f32[M]``);
* ``pallas``: a ``custom-call`` to ``tpu_custom_call`` (the ELL kernels);
* ``other``: the rest.

Each idle gap between busy intervals is put down to the innermost
benchmark span covering its midpoint (``host`` where none does), so the
breakdown says what the host was doing while the chip idled.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from bisect import bisect_right
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
CONTAINERS = ("while", "conditional", "call")

_SHAPE = r"(\w+)\[(\d+)\]\{[^}]*\}"
_GATHER_FUSION = re.compile(
    rf"^{_SHAPE} fusion\({_SHAPE} %[\w.\-]+, s32\[(\d+)\]\{{[^}}]*\}} "
    r"%[\w.\-]+\), kind=kCustom")


@dataclass
class Op:
    """One device op: its short name, class, and interval (ns)."""

    name: str
    cls: str
    start_ns: float
    end_ns: float


@dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclass
class Trace:
    """What a reduction needs, as plain data (also the test fixture)."""

    device_kind: str
    devices: Dict[str, List[Op]]          # plane name -> ops
    spans: List[Span]                     # bench.* host spans


def parse_op(text: str) -> Tuple[str, str, str]:
    """(instruction, opcode, rest) of an op's HLO text; rest starts at
    the result type."""
    instr, _, rest = text.partition(" = ")
    body = rest
    if body.startswith("("):  # a tuple type: skip to its close
        depth = 0
        for i, ch in enumerate(body):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                body = body[i + 1:]
                break
        else:
            return instr.lstrip("%"), "", rest
    else:
        body = body.partition(" ")[2]
    return instr.lstrip("%"), body.strip().partition("(")[0], rest


def classify(text: str) -> str:
    """``container``, ``gather``, ``pallas`` or ``other`` (see above)."""
    _, opcode, rest = parse_op(text)
    if opcode in CONTAINERS:
        return "container"
    if opcode == "gather":
        return "gather"
    if opcode == "fusion":
        m = _GATHER_FUSION.match(rest)
        if m and m.group(1) == m.group(3) and m.group(2) == m.group(5):
            return "gather"
    if opcode == "custom-call" and "tpu_custom_call" in rest:
        return "pallas"
    return "other"


def build(device_kind: str, planes: Dict[str, tuple],
          spans: List[Span]) -> Trace:
    """A `Trace` from raw events: ``planes`` maps a device plane to its
    (modules, ops), each a list of (name, start_ns, end_ns)."""
    devices = {}
    for plane, (modules, raw_ops) in planes.items():
        mods = sorted((s, e, name.partition("(")[0]) for name, s, e in modules)
        starts = [m[0] for m in mods]
        ops = []
        for text, s, e in raw_ops:
            i = bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            ops.append(Op(f"{mod}/{parse_op(text)[0]}", classify(text), s, e))
        if ops:
            devices[plane] = ops
    return Trace(device_kind, devices, spans)


def load(path: str, device_kind: str) -> Trace:
    """Read an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    planes, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: [(ev.name, ev.start_ns, ev.end_ns)
                               for ev in ln.events] for ln in plane.lines}
            planes[plane.name] = (lines.get(MODULES_LINE, []),
                                  lines.get(OPS_LINE, []))
        elif plane.name.startswith("/host:"):
            spans += [Span(ev.name, ev.start_ns, ev.end_ns)
                      for line in plane.lines for ev in line.events
                      if ev.name.startswith("bench.")]
    return build(device_kind, planes, spans)


def union(intervals) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class Reduction:
    device_kind: str
    window_s: float
    busy_s: float                                            # per chip
    op_s: Dict[str, float] = field(default_factory=dict)     # per chip
    class_s: Dict[str, float] = field(default_factory=dict)  # per chip
    gap_s: Dict[str, float] = field(default_factory=dict)    # per chip

    def idle_share(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def share(self, cls: str) -> float:
        """Share of busy time in ops of class ``cls``, in %."""
        return 100.0 * self.class_s.get(cls, 0.0) / self.busy_s

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.op_s), "idle_gaps": top(self.gap_s)}


def reduce(tr: Trace) -> Reduction:
    win = [s for s in tr.spans if s.name == WINDOW]
    if not win:
        raise ValueError("the trace has no bench.window span")
    w0, w1 = win[0].start_ns, win[0].end_ns
    spans = sorted((s for s in tr.spans if s.name != WINDOW),
                   key=lambda s: s.start_ns)
    starts = [s.start_ns for s in spans]
    n = max(1, len(tr.devices))
    busy = 0.0
    op_s: Dict[str, float] = {}
    class_s: Dict[str, float] = {}
    gap_s: Dict[str, float] = {}
    for ops in tr.devices.values():
        clipped = []
        for op in ops:
            s, e = max(op.start_ns, w0), min(op.end_ns, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            if op.cls != "container":
                d = (e - s) / 1e9 / n
                op_s[op.name] = op_s.get(op.name, 0.0) + d
                class_s[op.cls] = class_s.get(op.cls, 0.0) + d
        merged = union(clipped)
        busy += sum(e - s for s, e in merged) / 1e9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                label = _covering(spans, starts, (s + e) / 2)
                gap_s[label] = gap_s.get(label, 0.0) + (e - s) / 1e9 / n
    return Reduction(tr.device_kind, (w1 - w0) / 1e9, busy / n, op_s,
                     class_s, gap_s)


def _covering(spans: List[Span], starts: List[float], t: float) -> str:
    """The innermost span holding ``t``, else ``host``; ``spans`` sorted
    by start (``starts``).  The benchmark's spans do not nest, apart
    from the window, so the nearest few that start before ``t`` hold
    the answer."""
    best = None
    i = bisect_right(starts, t)
    for s in spans[max(0, i - 64):i]:
        if s.end_ns >= t and (best is None or s.end_ns - s.start_ns
                              < best.end_ns - best.start_ns):
            best = s
    return best.name[len("bench."):] if best else "host"


def reduce_dir(logdir: str, device_kind: str) -> Reduction:
    """Reduce the trace a run wrote, then delete it."""
    try:
        path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        return reduce(load(path, device_kind))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
