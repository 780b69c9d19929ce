"""Road-like graphs for the hybrid ELL tests: many narrow rows and a few
wide ones, the shape of roadNet-CA (widths 1-4 and a handful of hubs)."""
import numpy as np

from repro.core import build_blocks


def road_graph(n, hubs, hub_deg, P=2, Cn=None, seed=0, **kw):
    """Nodes `hubs`.. form a path plus a random matching, and each hub
    (nodes 0..hubs-1) has `hub_deg` leaves, no leaf shared, so every
    other row has width 1-4 and exactly the hubs are wider.  Nodes spread
    evenly over the P blocks of `Cn` rows; `kw` goes to `build_blocks`."""
    rng = np.random.default_rng(seed)
    rest = np.arange(hubs, n)
    edges = set(zip(rest[:-1].tolist(), rest[1:].tolist()))
    pairs = rng.permutation(rest)[:len(rest) // 2 * 2].reshape(-1, 2)
    edges |= {(min(u, v), max(u, v)) for u, v in pairs[::2].tolist()}
    leaves = rng.choice(rest, hubs * hub_deg, replace=False)
    edges |= {(h, int(v)) for h, ls in
              enumerate(leaves.reshape(hubs, hub_deg)) for v in ls}
    return build_blocks(np.array(sorted(edges)), n, np.arange(n) % P, P=P,
                        Cn=Cn, **kw)
