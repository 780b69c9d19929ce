"""The reader of the program's compile counter (`compile_ms.ingest`), on a
hand-made run and in a traced CPU rehearsal of the ingest mix."""
import sys
import time
import types

import pytest

import bench_tiny
from bench import harness, run as bench_run

from repro import tracing

READER = bench_run.reader("compile_ms.ingest")


def _run(window, windows):
    r = harness.Run(cell=types.SimpleNamespace(trace=False))
    r.window = window
    r.counters["windows"] = windows
    return r


def _compile(tag: float):
    import jax
    import jax.numpy as jnp

    x = jnp.full(3, tag).block_until_ready()
    return jax.jit(lambda y: y * tag + 1)(x).block_until_ready()


def test_reader_sums_the_compiles_inside_the_window():
    _compile(1.5)                           # before the window: left out
    t0 = time.perf_counter()
    with tracing.span("stream.window"):
        _compile(2.5)
    with tracing.span("service.refresh"):
        _compile(3.5)
    t1 = time.perf_counter()
    _compile(4.5)                           # after it: left out
    inside = [iv for iv in tracing.compile_log() if t0 <= iv.t_end <= t1]
    assert {iv.span for iv in inside} == {"stream.window",
                                          "service.refresh"}
    assert sum(iv.backend for iv in inside) == 2
    got = READER(_run((t0, t1), 4))
    assert got == pytest.approx(1e3 * sum(iv.seconds for iv in inside) / 4)
    assert 0 < got <= 1e3 * (t1 - t0) / 4


def test_reader_reads_nothing_without_windows_or_counter(monkeypatch):
    t = time.perf_counter()
    assert READER(_run(None, 3)) is None
    assert READER(_run((t, t), 0)) is None
    assert READER(_run((t, t), 3)) == 0.0
    # the parent program has no `repro.tracing`
    import repro
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert READER(_run((t, t), 3)) is None


def test_traced_rehearsal_counts_what_the_harness_counts(tmp_path,
                                                         monkeypatch):
    """In a traced ingest run the program's backend compiles inside the
    window are the harness's compiles plus cache loads (both count the
    same JAX event), and the reader reports their time."""
    runs = []
    close = harness.Window.close

    def keep(self):
        runs.append(self.run)
        return close(self)

    monkeypatch.setattr(harness.Window, "close", keep)
    root = bench_tiny.make_root(tmp_path)
    out = bench_tiny.measure(root, "tiny-social.ingest_small", trace=1)
    assert out["correct"], out["checks"]
    t0, t1 = runs[-1].window
    backend = sum(iv.backend for iv in tracing.compile_log()
                  if t0 <= iv.t_end <= t1)
    c = out["counters"]
    assert backend == c["compiles_in_window"] + c["cache_loads_in_window"]
    got = out["metrics"]["compile_ms.ingest"]["value"]
    assert got >= 0 and (got > 0) == (backend > 0)
    assert got <= out["metrics"]["apply_ms"]["value"] + (
        out["metrics"]["refresh_ms.ingest"]["value"])
