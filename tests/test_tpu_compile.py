"""Compile the chip's kernels for a described TPU v5e — no chip needed.

Every Pallas kernel that `ops.resolve_backend` can route to on a TPU is
lowered and compiled with `interpret=False` for a described `v5e:2x2`
topology at the widths of the roadNet-CA full-scale deployment that
`chip_smoke.py` runs (N = 1,965,248 nodes padded to 256-row tiles; an
8-slot column bucket stands for a low-degree graph, the 128-lane kernels
take a whole lane chunk, and a 256-lane row stands for a wider graph),
plus the smoke's whole fused static pass over its hybrid adjacency (a
4-column head, one tail bucket of 16-column rows), one jitted
`ell_spmd` superstep on a 4-device mesh built from the described
devices, and the hub-split com-LiveJournal refresh's whole fused loop on
that mesh at its full size.  Nothing runs: the TPU compiler
refuses here what it would refuse on the chip (unsupported primitives,
misaligned tiles, fast-memory overflow), at no chip time.

The topology is described only inside the module fixture below — never
at import — so every pytest-xdist worker collects the same tests and only
the worker that runs this file loads the TPU compiler library.
"""
import re
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.algorithms import (ConnectedComponentsProgram,
                                   CorenessBlockProgram, PageRankProgram)
from repro.core.engine import MultiProgram
from repro.core.graph import GraphBlocks
from repro.core.hub_split import MirrorPlan
from repro.kernels import ops
from repro.kernels.ell_cc import neighbor_min_ell
from repro.kernels.ell_frontier import frontier_step_ell
from repro.kernels.ell_hindex import ROW_CHUNK, hindex_ell
from repro.kernels.ell_multi import neighbor_multi_ell
from repro.kernels.ell_pagerank import neighbor_sum_ell
from repro.kernels.ell_triangles import neighbor_common_ell
from repro.kernels.frontier import frontier_step
from repro.kernels.kcore_hindex import hindex_counts
from repro.kernels.ops import DENSE_AUTO_MAX
from repro.runtime import spmd
from repro.runtime.mesh import AXIS

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import collectives  # noqa: E402
from bench.trace import parse_op  # noqa: E402

#: roadNet-CA at scale 1.0, 8 random blocks: P * Cn rows (N once padded to
#: the 256-row tile); Cd = max degree 12 + deg_slack 64; C8 = an 8-column
#: bucket (a graph of max degree 5-8); C = a 256-lane row; R = the
#: stream's window
P, CN, CD = 8, 245_656, 76
N, C, C8, T, R = 1_965_312, 256, 8, 256, 8
#: the fused pass's hybrid adjacency at roadNet-CA's widths: a 4-column
#: head for every row, and its 17 rows wider than 4 whole (the 16-column
#: bucket of max degree 12) in one tail bucket of a tile
W4, C16, TAIL = 4, 16, 256
#: HBM of one TPU v5e chip
HBM_BYTES = 16 * 10**9
#: the dense backend's largest graph under "auto"
ND = DENSE_AUTO_MAX


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described device cannot be read back from the
    # persistent cache: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)


def _cases(S):
    nbr, i32, f32 = S((N, C), jnp.int32), S((N,), jnp.int32), S((N,), jnp.float32)
    fb = S((N, R), jnp.bool_)
    return {
        "ell_hindex": (lambda a, b: hindex_ell(a, b, K=C, T=T), (nbr, i32)),
        "ell_multi_narrow": (
            lambda a, b, c, d: neighbor_multi_ell(
                a, (b, c, d), ("hindex", "min", "sum"), K=C8, T=T),
            (S((N, C8), jnp.int32), i32, i32, f32)),
        "ell_min": (lambda a, b: neighbor_min_ell(a, b, K=C, T=T), (nbr, i32)),
        "ell_sum": (lambda a, b: neighbor_sum_ell(a, b, K=C, T=T), (nbr, f32)),
        "ell_multi": (
            lambda a, b, c, d: neighbor_multi_ell(
                a, (b, c, d), ("hindex", "min", "sum"), K=C, T=T),
            (nbr, i32, i32, f32)),
        "ell_frontier": (
            lambda a, b, c, d: frontier_step_ell(a, b, c, d, K=C, T=T),
            (nbr, fb, fb, fb)),
        "dense_hindex": (
            lambda a, b: hindex_counts(a, b, K=256, T=T),
            (S((ND, ND), jnp.bfloat16), S((ND,), jnp.int32))),
        "dense_frontier": (
            lambda a, b, c, d: frontier_step(a, b, c, d, T=T),
            (S((ND, ND), jnp.bfloat16), S((ND, 128), jnp.bfloat16),
             S((ND,), jnp.int8), S((ND, 128), jnp.int8))),
    }


@pytest.mark.parametrize("name", [
    "ell_hindex", "ell_multi_narrow", "ell_min", "ell_sum", "ell_multi",
    "ell_frontier", "dense_hindex", "dense_frontier"])
def test_kernel_compiles_for_v5e(shape, name):
    fn, args = _cases(shape)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_triangle_kernel_refuses_to_compile(shape):
    """No TPU lowering exists: the entry point raises instead of
    interpreting or falling back."""
    nbr = shape((N, C), jnp.int32)
    with pytest.raises(NotImplementedError, match="no compiled TPU lowering"):
        jax.jit(lambda a: neighbor_common_ell(a, a, K=C, T=T)).lower(nbr)


def test_spmd_superstep_compiles_on_4_chip_mesh(topo):
    """One halo-exchange h-index superstep of the ell_spmd backend, the
    node axis sharded over a 4-device worker mesh (P = 8 blocks fold as
    B = 2 per device), with representative halo capacities."""
    W, Nn = 4, P * CN
    H, K = 1 << 19, 1 << 18  # pow2 halo / per-pair send capacities
    mesh = Mesh(np.asarray(topo.devices[:W]), (AXIS,))
    sh = NamedSharding(mesh, PartitionSpec(AXIS))
    est = jax.ShapeDtypeStruct((Nn,), jnp.int32, sharding=sh)
    nbrl = jax.ShapeDtypeStruct((Nn, C8), jnp.int32, sharding=sh)
    send = jax.ShapeDtypeStruct((W, W, K), jnp.int32, sharding=sh)
    recv = jax.ShapeDtypeStruct((W, W, K), jnp.int32, sharding=sh)
    fn = spmd._compiled_hindex(mesh, H, True)
    compiled = fn.lower(est, nbrl, send, recv).compile()
    assert "all-to-all" in compiled.as_text()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < Nn * C8 * 4 // 2  # each device holds its shard only


def test_static_fused_pass_compiles_and_fits(shape):
    """The smoke's static phase as one program: the fused coreness + CC +
    PageRank `while_loop` on the ELL kernels at full scale, within HBM,
    over the hybrid adjacency `ops.hybrid_split` picks for roadNet-CA."""
    n = P * CN
    g = GraphBlocks(nbr=shape((n, CD), jnp.int32), deg=shape((n,), jnp.int32),
                    node_mask=shape((n,), jnp.bool_),
                    orig_id=shape((n,), jnp.int32), P=P, Cn=CN, Cd=CD)
    steps = 2048
    prog = MultiProgram(
        (CorenessBlockProgram(), ConnectedComponentsProgram(),
         PageRankProgram(tol=None, max_steps=steps)), max_steps=steps)
    vec = shape((n,), jnp.int32)
    state0 = (vec, vec, (shape((n,), jnp.float32), shape((n,), jnp.float32)))
    adj = ops.HybridEll(shape((N, W4), jnp.int32),
                        shape((TAIL, C16), jnp.int32),
                        shape((TAIL,), jnp.int32))
    compiled = ops._block_program_fused.lower(
        g, state0, adj, None, program=prog, b="ell", interpret=False,
        max_steps=steps, n_real=n).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
    # every field's head and tail gather reads its (N,) table from VMEM
    # (memory space S(1)); side by side XLA staged only one and the others
    # read HBM.  The head gathers a row chunk's W4 columns, the tail its
    # whole rows
    gathers = []
    for comp in text.split("\n\n"):
        m = re.search(r" = \w+\[([\d,]+)\]\S* gather\(%(\S+), ", comp)
        if m:
            decl = re.search("%" + re.escape(m.group(2)) + r" = (\S+) ", comp)
            gathers.append(("S(1)" in decl.group(1), m.group(1)))
    chunk = ROW_CHUNK // T * T
    assert sorted(gathers) == sorted(
        [(True, f"{chunk},{W4}")] * 3 + [(True, f"{TAIL},{C16}")] * 3), gathers


#: com-LiveJournal at full scale, hubs split at 16 over 4 workers: rows
#: a block (3,997,962 nodes, 2,144,251 replicas and the padding of the
#: fullest block, rounded up), pow2 halo, pair, group-row and group
#: capacities at or above the stand-in's, and the h-index bound 16,384
LJ_CN, LJ_H, LJ_K, LJ_RW, LJ_G, LJ_KM = (876_544, 1 << 23, 1 << 21, 1 << 20,
                                        1 << 20, 1 << 14)


def test_split_mesh_refresh_compiles_and_fits(topo):
    """The served refresh of the hub-split graph (coreness + CC +
    PageRank, with the replica merges) as one fused loop on a 4-chip
    mesh, with the benchmark cell's read (``overlap=False``): it
    compiles, moves its halo by all-to-all, merges by all-reduce (as
    `bench/collectives.py` classes them), and fits a chip's HBM."""
    W, C = 4, 16
    Nn = P * LJ_CN
    mesh = Mesh(np.asarray(topo.devices[:W]), (AXIS,))
    sh = NamedSharding(mesh, PartitionSpec(AXIS))
    rep = NamedSharding(mesh, PartitionSpec())
    S = lambda s, dt, sd=sh: jax.ShapeDtypeStruct(s, dt, sharding=sd)  # noqa
    z = jnp.zeros(1, jnp.int32)
    plan = MirrorPlan(primary_row=z, ldeg=z, primary_mask=z, grp_rows=z,
                      grp_gid=z, row_gid=z, Gmax=LJ_G, Km=LJ_KM,
                      threshold=C, n_logical=3_997_962, uid=0)
    prog = MultiProgram((CorenessBlockProgram(), ConnectedComponentsProgram(),
                         PageRankProgram(tol=None, max_steps=30)),
                        max_steps=30)
    sp = spmd.SpmdBlockProgram(prog, 3_997_962, mirror=plan)
    vec = S((Nn,), jnp.int32)
    state = (vec, vec, (S((Nn,), jnp.float32), S((Nn,), jnp.float32)))
    aux = (vec, S((W * LJ_RW,), jnp.int32), S((W * LJ_RW,), jnp.int32))
    fn = spmd.fused_step(mesh, LJ_H, P // W, LJ_CN, C, False, sp)
    compiled = fn.lower(
        state, vec, S((Nn,), jnp.bool_), aux, None,
        S((), jnp.int32, rep), S((), jnp.int32, rep), S((Nn, C), jnp.int32),
        S((W, W, LJ_K), jnp.int32), S((W, W, LJ_K), jnp.int32)).compile()
    text = compiled.as_text()
    ops = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
           if " = " in ln]
    found = [parse_op(op)[:2] for op in ops if collectives.is_collective(op)]
    # the halo of three fields by all-to-all, the merges by all-reduce;
    # named after the jax op (``all_to_all``, ``psum``, ``pmin``), so the
    # benchmark classes them by opcode
    assert sorted(c for _, c in found).count("all-to-all") == 3
    assert {c for _, c in found} == {"all-to-all", "all-reduce"}
    assert any(not name.startswith(c) for name, c in found)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES // 4
