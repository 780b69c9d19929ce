"""Program spans on the profiler's clock, and an always-on compile counter.

`span(name)` marks a phase of the served path where the work happens
(``stream.route``, ``halo.update``, ``service.refresh``, ...).  It opens
a `jax.profiler.TraceAnnotation` named ``bladyg.<name>``: a TraceMe on
the host plane of a profiler trace, on the same clock as the device
planes, so an idle gap on the chip can be put down to the innermost
span around it.  With no profiler session running the annotation is
inert; the span then only pushes and pops its name on a per-thread
stack.  Tracing is on exactly when a profiler session runs: there is no
flag.

The compile counter listens to JAX's three compile-path events (tracing
to a jaxpr, lowering to MLIR, backend compile; the last includes a load
from the persistent compilation cache) and adds their seconds to the
innermost open span (``"-"`` outside any span).  An event nested in
another (a jit traced while an outer function is traced) counts once,
inside the outermost.  `compile_seconds` and `compile_counts` (backend
compiles or cache loads) give the totals since import; `compile_log`
the most recent compile intervals with the time each ended.

    >>> from repro import tracing
    >>> with tracing.span("doc.outer"), tracing.span("doc.inner"):
    ...     tracing.current()
    'doc.inner'
    >>> tracing.current()
    '-'
"""
from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Deque, Dict, List, NamedTuple

import jax

#: the compile-path events of `jax._src.dispatch`, in the order a fresh
#: program meets them
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = (TRACE_EVENT, LOWER_EVENT, BACKEND_EVENT)

#: compile intervals `compile_log` keeps
LOG_LENGTH = 4096

#: span name outside any span
NO_SPAN = "-"


class CompileInterval(NamedTuple):
    """One outermost compile-path event: when it ended (`time.perf_counter`),
    the span it ran in, its seconds, and the backend compiles (or cache
    loads) inside it."""

    t_end: float
    span: str
    seconds: float
    backend: int


_local = threading.local()
_lock = threading.Lock()
_seconds: Dict[str, float] = {}
_counts: Dict[str, int] = {}
_log: Deque[CompileInterval] = deque(maxlen=LOG_LENGTH)


def _state():
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
        _local.depth = 0
        _local.backend = 0
    return st


@contextmanager
def span(name: str):
    """A host span ``bladyg.<name>`` (see the module docstring)."""
    stack = _state()
    stack.append(name)
    try:
        with jax.profiler.TraceAnnotation(f"bladyg.{name}"):
            yield
    finally:
        stack.pop()


def current() -> str:
    """The innermost open span of this thread, or ``"-"``."""
    stack = _state()
    return stack[-1] if stack else NO_SPAN


def compile_seconds() -> Dict[str, float]:
    """Compile-path seconds since import, by innermost span (a copy)."""
    with _lock:
        return dict(_seconds)


def compile_counts() -> Dict[str, int]:
    """Backend compiles (persistent-cache loads included) since import,
    by innermost span (a copy)."""
    with _lock:
        return dict(_counts)


def compile_log() -> List[CompileInterval]:
    """The last `LOG_LENGTH` compile intervals, oldest first (a copy)."""
    with _lock:
        return list(_log)


def _on_start(event: str, value: float, **_) -> None:
    if event in COMPILE_EVENTS:
        _state()
        _local.depth += 1


def _on_duration(event: str, seconds: float, **_) -> None:
    if event not in COMPILE_EVENTS:
        return
    name = current()
    _local.depth = max(0, _local.depth - 1)
    _local.backend += event == BACKEND_EVENT
    if _local.depth:
        return  # nested: its outermost event holds its time
    n, _local.backend = _local.backend, 0
    with _lock:
        _seconds[name] = _seconds.get(name, 0.0) + seconds
        _counts[name] = _counts.get(name, 0) + n
        _log.append(CompileInterval(time.perf_counter(), name, seconds, n))


# each compile-path event records its start as a scalar, then its duration
jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
