"""The program's own spans on a recorded v5e trace of the ingest cell.

The piece is one stream window of `ego-facebook.ingest` traced on the
chip: the benchmark's `bench.apply` span, the program's `bladyg.*` spans
inside it, and the device's modules and ops.  `bench/trace.py` reads the
benchmark's spans only; the reference below puts each idle gap down to
the innermost of all spans, nested ones included, and the two have to
agree on the idle inside `bench.apply`.
"""
import json
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the repository on the path)
from bench import trace as T

FIXTURE = Path(__file__).parent / "fixtures" / "tpu_v5e_ingest_trace.json"


@pytest.fixture(scope="module")
def recorded():
    raw = json.loads(FIXTURE.read_text())
    texts = raw["texts"]
    spans = [T.Span(*s) for s in raw["spans"]]
    tr = T.build(raw["device_kind"], {"/device:TPU:0": (
        [tuple(m) for m in raw["modules"]],
        [(texts[i], s, e) for i, s, e in raw["ops"]])},
        [s for s in spans if s.name.startswith("bench.")])
    return raw, tr, spans


def _innermost(spans, t):
    """The shortest span other than the window holding ``t``."""
    best = None
    for s in spans:
        if (s.name != T.WINDOW and s.start_ns <= t <= s.end_ns
                and (best is None or s.end_ns - s.start_ns
                     < best.end_ns - best.start_ns)):
            best = s
    return best.name if best else "host"


def _gaps(tr, w0, w1):
    ops = tr.devices["/device:TPU:0"]
    merged = T.union((max(o.start_ns, w0), min(o.end_ns, w1)) for o in ops
                     if min(o.end_ns, w1) > max(o.start_ns, w0))
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def test_program_spans_take_the_idle_of_the_apply(recorded):
    raw, tr, spans = recorded
    red = T.reduce(tr)
    on_apply = red.gap_s["apply"]
    assert on_apply > 0
    (win,) = [s for s in spans if s.name == T.WINDOW]
    bench = [s for s in spans if s.name.startswith("bench.")]
    by = {}
    for s, e in _gaps(tr, win.start_ns, win.end_ns):
        mid = (s + e) / 2
        if _innermost(bench, mid) != "bench.apply":
            continue
        label = _innermost(spans, mid)
        by[label] = by.get(label, 0.0) + (e - s) / 1e9
    program = sum(v for k, v in by.items() if k.startswith("bladyg."))
    assert program + by.get("bench.apply", 0.0) == pytest.approx(
        on_apply, rel=1e-3)
    assert program >= 0.9 * on_apply
    # the window's own phases hold it: nothing falls to the service span
    assert all(k.startswith(("bladyg.stream.", "bladyg.halo.", "bench."))
               for k in by), by


def test_program_spans_nest_inside_the_window(recorded):
    _, _, spans = recorded
    inside = lambda a, b: b.start_ns <= a.start_ns and a.end_ns <= b.end_ns  # noqa: E731
    (apply_,) = [s for s in spans if s.name == "bench.apply"]
    (window,) = [s for s in spans if s.name == "bladyg.stream.window"]
    assert inside(window, apply_)
    phases = [s for s in spans if s.name.startswith("bladyg.stream.")
              and s is not window]
    assert {s.name for s in phases} >= {
        "bladyg.stream.validate", "bladyg.stream.candidates",
        "bladyg.stream.route", "bladyg.stream.apply",
        "bladyg.stream.coordinator", "bladyg.stream.labels"}
    assert all(inside(s, window) for s in phases)
    coordinator = [s for s in phases if s.name == "bladyg.stream.coordinator"]
    halo = [s for s in spans if s.name == "bladyg.halo.update"]
    assert len(coordinator) >= 1
    # every escalated update keeps the halo plan up to date inside it
    assert all(any(inside(h, c) for h in halo) for c in coordinator)


def test_mesh_steps_are_told_apart_by_module(recorded):
    raw, tr, _ = recorded
    modules = {m[0].partition("(")[0] for m in raw["modules"]}
    assert {"jit_spmd_reach", "jit_spmd_recompute"} <= modules
    assert not any(m == "jit_local" for m in modules)
    names = {op.name.partition("/")[0] for op in tr.devices["/device:TPU:0"]}
    assert "jit_local" not in names and "jit_spmd_reach" in names


def test_ops_carry_the_scope_path_of_their_step(recorded):
    """The v5e keeps each op's scope path in its event metadata (stat
    ``tf_op``): the compiled step's name, then the program's named
    scopes, so the mesh's gathers can be told from its halo exchange."""
    raw, _, _ = recorded
    texts, paths = raw["texts"], raw["tf_op"]
    reach = {texts[int(i)].partition(" = ")[0].lstrip("%"): p
             for i, p in paths.items() if p.startswith("jit(spmd_reach)/")}
    assert all(p.startswith("jit(spmd_reach)/reach/") for p in reach.values())
    assert reach["fusion.10"].startswith(
        "jit(spmd_reach)/reach/while/body/gather/")
    steps = {p.partition("/")[0] for p in paths.values()}
    assert {"jit(spmd_recompute)", "jit(spmd_fused_cc)"} <= steps
    assert any("/while/body/halo/" in p for p in paths.values())
