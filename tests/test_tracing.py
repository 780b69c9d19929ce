"""Program spans and the compile counter (`repro.tracing`), the spans the
served path writes into a profile, and the names of the compiled mesh
steps."""
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core import build_blocks, coreness
from repro.core import kcore_dynamic as kd
from repro.core.algorithms import (ConnectedComponentsProgram,
                                   CorenessBlockProgram, PageRankProgram,
                                   connected_components)
from repro.core.engine import MultiProgram
from repro.runtime import StreamSession
from repro.runtime import spmd

P, COMMUNITY = 4, 12


def _community_graph():
    """P disjoint communities, one per block (a cycle with chords each)."""
    edges = []
    for b in range(P):
        base = b * COMMUNITY
        for i in range(COMMUNITY):
            edges.append((base + i, base + (i + 1) % COMMUNITY))
            edges.append((base + i, base + (i + 2) % COMMUNITY))
    n = P * COMMUNITY
    return build_blocks(np.array(edges), n, np.arange(n) // COMMUNITY, P=P,
                        deg_slack=16)


def _pad_id(g, b, i):
    orig = np.asarray(g.orig_id)
    return int(np.flatnonzero(orig == b * COMMUNITY + i)[0])


def test_span_nests_and_pops_on_exceptions():
    assert tracing.current() == tracing.NO_SPAN
    with tracing.span("a"):
        assert tracing.current() == "a"
        with pytest.raises(RuntimeError):
            with tracing.span("b"):
                assert tracing.current() == "b"
                raise RuntimeError("inside b")
        assert tracing.current() == "a"

    @tracing.span("c")
    def f():
        return tracing.current()

    assert f() == "c"
    assert tracing.current() == tracing.NO_SPAN


def test_forced_compile_lands_in_its_span():
    fn = jax.jit(lambda x: jnp.cos(x) * 3 + 1)
    x = jnp.arange(5.0).block_until_ready()
    secs0, n0 = tracing.compile_seconds(), tracing.compile_counts()
    t0 = time.perf_counter()  # the log is bounded: find entries by time
    with tracing.span("x"):
        fn(x).block_until_ready()
    secs, n = tracing.compile_seconds(), tracing.compile_counts()
    assert secs["x"] > secs0.get("x", 0.0)
    assert n["x"] == n0.get("x", 0) + 1   # one backend compile
    new = [iv for iv in tracing.compile_log() if iv.t_end >= t0]
    assert {iv.span for iv in new} == {"x"}
    assert sum(iv.backend for iv in new) == 1
    # a second call hits jit's cache: nothing is added
    with tracing.span("x"):
        fn(x).block_until_ready()
    assert tracing.compile_counts()["x"] == n["x"]


def test_nested_compile_events_count_once():
    """Tracing an outer jit traces the inner one inside it: the seconds
    added never exceed the wall time of the call."""
    inner = jax.jit(lambda x: jnp.sin(x) + 2)
    outer = jax.jit(lambda x: inner(x) * inner(x + 1))
    x = jnp.arange(7.0).block_until_ready()
    before = tracing.compile_seconds().get("nested", 0.0)
    t0 = time.perf_counter()
    with tracing.span("nested"):
        outer(x).block_until_ready()
    wall = time.perf_counter() - t0
    added = tracing.compile_seconds()["nested"] - before
    assert 0 < added <= wall


def _host_spans(logdir):
    from jax.profiler import ProfileData

    path = next(logdir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            out += [(ev.name, ev.start_ns, ev.end_ns)
                    for line in plane.lines for ev in line.events
                    if ev.name.startswith("bladyg.")]
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_stream_window_writes_its_spans_into_a_profile(tmp_path):
    g = _community_graph()
    core = coreness(g, backend="jnp")
    labels = connected_components(g, backend="jnp")
    sess = StreamSession(g, core, R=4, backend="ell_spmd", W=1,
                         cc_labels=labels)
    # one block-local insert (accepted) and one across blocks (escalated)
    local = (_pad_id(g, 0, 0), _pad_id(g, 0, 5), +1)
    cross = (_pad_id(g, 1, 0), _pad_id(g, 2, 0), +1)
    sess.apply_window([local, (_pad_id(g, 3, 0), _pad_id(g, 3, 5), +1)])
    jax.block_until_ready(sess.core)  # compiled outside the trace
    jax.profiler.start_trace(str(tmp_path))
    sess.apply_window([local[:2] + (-1,), cross])
    jax.block_until_ready((sess.core, sess.labels))
    jax.profiler.stop_trace()
    st = sess.stats()
    assert st.escalated_cross_block == 1 and st.block_local == 3

    spans = _host_spans(tmp_path)
    names = [s[0] for s in spans]
    (window,) = [s for s in spans if s[0] == "bladyg.stream.window"]
    for name in ("bladyg.stream.validate", "bladyg.stream.candidates",
                 "bladyg.stream.route", "bladyg.stream.apply",
                 "bladyg.stream.coordinator", "bladyg.stream.labels"):
        assert names.count(name) == 1, (name, names)
        (s,) = [s for s in spans if s[0] == name]
        assert _inside(s, window), name
    # the halo plan upkeep runs inside the apply and the coordinator
    updates = [s for s in spans if s[0] == "bladyg.halo.update"]
    assert len(updates) == 2
    for name in ("bladyg.stream.apply", "bladyg.stream.coordinator"):
        (outer,) = [s for s in spans if s[0] == name]
        assert sum(_inside(u, outer) for u in updates) == 1


def test_stream_stats_count_candidates():
    """`StreamStats.candidates` sums each update's candidate set, on the
    block-local path and on the coordinator path alike."""
    g = _community_graph()
    core = coreness(g, backend="jnp")
    u, v = _pad_id(g, 1, 0), _pad_id(g, 1, 5)
    k = jnp.minimum(core[u], core[v])
    roots = jnp.zeros(g.N, bool).at[u].set(True).at[v].set(True)
    reach, _ = kd.k_reachable(g, core, roots, k)
    want = int(jnp.sum(reach | roots))
    got = {}
    for backend in ("jnp", "ell_spmd"):
        sess = StreamSession(jax.tree.map(jnp.copy, g), core, R=4,
                             backend=backend, W=1)
        sess.apply_window([(u, v, +1)])
        assert sess.stats().block_local == 1
        assert sess.stats().candidates == want
        # an update across blocks escalates to the coordinator path
        sess.apply_window([(_pad_id(g, 1, 0), _pad_id(g, 2, 0), +1)])
        st = sess.stats()
        assert st.escalated_cross_block == 1
        got[backend] = st.candidates
    assert got["jnp"] == got["ell_spmd"] > want > 2


def test_mesh_steps_lower_with_distinct_module_names():
    g = _community_graph()
    ex = spmd.SpmdExecutor(g, W=1)
    mesh, H = ex.wm.mesh, ex.plan.H
    R = 4
    core = jnp.zeros(g.N, jnp.int32)
    mask = ex.node_mask
    tabs = ex._tables
    lowered = {
        "hindex": spmd._compiled_hindex(mesh, H, True).lower(core, *tabs),
        "frontier": spmd._compiled_frontier(mesh, H, True).lower(
            *(jnp.zeros((g.N, R), bool),) * 3, *tabs),
        "coreness": spmd._compiled_coreness(mesh, H, True).lower(
            core, mask, jnp.int32(5), *tabs),
        "reach": spmd._compiled_reach(mesh, H, True).lower(
            core, mask, jnp.zeros((g.N, R), bool), jnp.zeros(R, jnp.int32),
            jnp.int32(5), *tabs),
        "recompute": spmd._compiled_recompute(mesh, H, True).lower(
            core, mask, mask, jnp.int32(5), *tabs),
    }
    eng = spmd.SpmdEngine(g, executor=ex)
    multi = MultiProgram((CorenessBlockProgram(),
                          ConnectedComponentsProgram(),
                          PageRankProgram(tol=None, max_steps=3)),
                         max_steps=3)
    n_real = int(g.n_real)
    for prog, state in [
            (multi, (core, core, (jnp.zeros(g.N), jnp.zeros(g.N)))),
            (ConnectedComponentsProgram(), core)]:
        sp = spmd.SpmdBlockProgram(prog, n_real)
        lowered["fused_" + sp.name] = eng._fused_fn(sp).lower(
            state, ex.deg, mask, sp.operands(ex), jnp.int32(0),
            jnp.int32(0), jnp.int32(3), *tabs)
    modules = {k: re.match(r"module @(\S+)", low.as_text()).group(1)
               for k, low in lowered.items()}
    assert modules == {
        "hindex": "jit_spmd_hindex", "frontier": "jit_spmd_frontier",
        "coreness": "jit_spmd_coreness", "reach": "jit_spmd_reach",
        "recompute": "jit_spmd_recompute",
        "fused_coreness_cc_pagerank": "jit_spmd_fused_coreness_cc_pagerank",
        "fused_cc": "jit_spmd_fused_cc"}
    # the sub-programs' fields gather under their own named scopes
    text = lowered["fused_coreness_cc_pagerank"].as_text(debug_info=True)
    for name in ("coreness", "cc", "pagerank"):
        assert f"/{name}/gather/" in text, name
    assert "/reach/while/body/gather/" in (
        lowered["reach"].as_text(debug_info=True))
