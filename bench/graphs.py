"""Graphs, partitions, the program's node layout and the update sampler.

A configuration's ``graph`` entry names its generator, the file
``bench/generators/<generator>.py``, and its ``partitioner`` the file
``bench/partitioners/<partitioner>.py``; both are found by name, so a
later configuration brings its own as new files.  The update sampler
follows the paper's §5.2.1 protocol (inter/intra-partition insertions of
absent edges and deletions of present ones) and is vectorized.

Node ids: generators give original ids ``0..n-1``.  The program lays
block ``b``'s nodes out at padded ids ``b*Cn + r`` in increasing original
id (`layout`); updates and queries are expressed in padded ids.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def _plugin(kind: str, name: str, root: Path):
    """``bench/<kind>/<name>.py`` under the checkout ``root``."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generate(spec: dict, root: Path = ROOT) -> np.ndarray:
    """(m, 2) original-id edges of a configuration's ``graph`` entry,
    drawn by ``bench/generators/<generator>.py``."""
    kw = {k: v for k, v in spec.items() if k != "generator"}
    return _plugin("generators", spec["generator"], root).generate(**kw)


def partition(name: str, edges: np.ndarray, n: int, P: int,
              rng: np.random.Generator, root: Path = ROOT) -> np.ndarray:
    """(n,) block of each node, by ``bench/partitioners/<name>.py``."""
    return _plugin("partitioners", name, root).partition(edges, n, P, rng)


def block_capacity(assign: np.ndarray, P: int) -> int:
    """Nodes per block as the loader sizes it: the largest block, to 8."""
    return int(-(-int(np.bincount(assign, minlength=P).max()) // 8) * 8)


def layout(assign: np.ndarray, P: int, Cn: int) -> np.ndarray:
    """Padded id of each original node: block-contiguous, id order kept."""
    order = np.argsort(assign, kind="stable")
    blocks = assign[order]
    starts = np.searchsorted(blocks, np.arange(P))
    new = np.empty(len(assign), np.int64)
    new[order] = blocks * Cn + (np.arange(len(order)) - starts[blocks])
    return new


def canonical(edges: np.ndarray) -> np.ndarray:
    """Sorted unique (lo, hi) rows without self loops."""
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    lo, hi = np.minimum(e[:, 0], e[:, 1]), np.maximum(e[:, 0], e[:, 1])
    keep = lo != hi
    span = np.int64(hi.max() + 1) if hi.size else np.int64(1)
    k = np.unique(lo[keep] * span + hi[keep])
    return np.stack([k // span, k % span], 1)


def keys(edges: np.ndarray, N: int) -> np.ndarray:
    return edges[:, 0] * np.int64(N) + edges[:, 1]


def _absent_pairs(present: np.ndarray, real: np.ndarray, Cn: int, N: int,
                  per: int, intra: bool, rng: np.random.Generator):
    """``per`` distinct absent pairs, both ends in one block or in two."""
    P = N // Cn
    start = np.searchsorted(real, np.arange(P) * Cn)
    count = np.searchsorted(real, (np.arange(P) + 1) * Cn) - start
    got = np.empty(0, np.int64)
    while got.size < per:
        m = 4 * (per - got.size) + 64
        a = rng.choice(real, m)
        if intra:  # the partner is drawn from a's own block
            blk = a // Cn
            b = real[start[blk] + rng.integers(0, count[blk])]
        else:
            b = rng.choice(real, m)
        ok = (a != b) & (((a // Cn) == (b // Cn)) == intra)
        lo, hi = np.minimum(a[ok], b[ok]), np.maximum(a[ok], b[ok])
        k = lo * np.int64(N) + hi
        pos = np.searchsorted(present, k).clip(0, len(present) - 1)
        k = k[present[pos] != k]
        cat = np.concatenate([got, k])
        _, first = np.unique(cat, return_index=True)
        got = cat[np.sort(first)]
    return got[:per]


def sample_updates(edges: np.ndarray, real: np.ndarray, Cn: int, N: int,
                   count: int, rng: np.random.Generator) -> list:
    """The paper's mixed protocol: ``count`` updates (a multiple of 4),
    interleaved insert-inter, insert-intra, delete-inter, delete-intra.

    ``edges`` are the padded-id edges at stream start and ``real`` the
    sorted padded ids of real nodes.  Insertions are pairs absent from
    that graph and deletions are edges of it, all distinct, so the stream
    is valid in any order.
    """
    if count % 4:
        raise ValueError(f"update count {count} is not a multiple of 4")
    per = count // 4
    present = np.sort(keys(edges, N))
    same = (edges[:, 0] // Cn) == (edges[:, 1] // Cn)
    out = []
    for intra in (False, True):
        k = _absent_pairs(present, real, Cn, N, per, intra, rng)
        out.append([(int(x // N), int(x % N), +1) for x in k])
    for intra in (False, True):
        pick = np.flatnonzero(same if intra else ~same)
        if pick.size < per:
            raise ValueError(f"only {pick.size} edges to delete, need {per}")
        sel = edges[rng.choice(pick, per, replace=False)]
        out.append([(int(u), int(v), -1) for u, v in sel])
    return [u for quad in zip(*out) for u in quad]
