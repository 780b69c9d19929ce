"""The trace reduction, on a small recorded TPU trace and on hand-made
intervals."""
import json
from pathlib import Path

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the repository on the path)
from bench import trace as T

FIXTURE = Path(__file__).parent / "fixtures" / "tpu_v5e_refresh_trace.json"


@pytest.fixture(scope="module")
def recorded():
    raw = json.loads(FIXTURE.read_text())
    texts = raw["texts"]
    tr = T.build(raw["device_kind"], {"/device:TPU:0": (
        [tuple(m) for m in raw["modules"]],
        [(texts[i], s, e) for i, s, e in raw["ops"]])},
        [T.Span(*s) for s in raw["spans"]])
    return raw, tr


def test_classes_of_recorded_ops(recorded):
    raw, tr = recorded
    by_instr = {T.parse_op(t)[0]: T.classify(t) for t in raw["texts"]}
    assert by_instr["while.32"] == "container"
    assert by_instr["fusion.6"] == by_instr["fusion.7"] == "gather"
    assert by_instr["closed_call.12"] == "pallas"
    assert by_instr["broadcast_select_fusion.6"] == "other"
    names = {op.name for op in tr.devices["/device:TPU:0"]}
    assert "jit__block_program_fused/fusion.6" in names


def test_busy_union_matches_a_timeline(recorded):
    raw, tr = recorded
    red = T.reduce(tr)
    w0, w1 = raw["spans"][0][1], raw["spans"][0][2]
    # an independent count: a 100 ns timeline of the window
    grid = np.zeros(int((w1 - w0) // 100) + 1, bool)
    for _, s, e in raw["ops"]:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            grid[int((s - w0) // 100):int(np.ceil((e - w0) / 100))] = True
    assert red.window_s == pytest.approx((w1 - w0) / 1e9)
    assert red.busy_s == pytest.approx(grid.sum() * 100 / 1e9, rel=2e-3)
    assert 0 < red.idle_share() < 100
    assert sum(red.gap_s.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)
    # containers do not count twice: class totals stay within busy time
    assert sum(red.class_s.values()) <= red.busy_s * (1 + 1e-9)
    assert 0 < red.share("gather") < 100
    bd = red.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_gaps_go_to_the_span_that_covers_them():
    op = lambda s, e: T.Op("m/op", "other", s, e)  # noqa: E731
    tr = T.Trace("TPU v5 lite", {"/device:TPU:0": [op(0, 10), op(30, 40),
                                                    op(35, 50), op(90, 100)]},
                 [T.Span("bench.window", 0, 100),
                  T.Span("bench.apply", 8, 32),
                  T.Span("bench.refresh", 60, 95)])
    red = T.reduce(tr)
    assert red.busy_s == pytest.approx(40e-9)       # 10 + 20 + 10
    assert red.idle_share() == pytest.approx(60.0)
    assert red.gap_s == pytest.approx({"apply": 20e-9, "refresh": 40e-9})


def test_load_reads_spans_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    tr = T.load(str(path), "cpu")
    assert [s.name for s in tr.spans] == ["bench.window"]
    assert tr.devices == {}  # a CPU trace has no device plane
