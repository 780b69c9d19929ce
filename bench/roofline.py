"""The least bytes a fused analytics superstep must move.

One superstep of the fused refresh (coreness, CC labels and PageRank off
one neighbour gather) has to read every neighbour id once (2|E| ids),
gather the three per-node fields over those 2|E| slots, read the three
fields and the degree of each node, and write the three fields back:
int32/float32 throughout.  Counted from the graph's real edges and
nodes, not from ELL slots, so the count is the same whatever layout
implements the superstep and a change that drops pads raises the share.
"""
from __future__ import annotations

WORD = 4  # bytes of an int32 / float32


def superstep_bytes(n_real: int, n_edges: int) -> int:
    ids = 2 * n_edges
    gathered = 3 * 2 * n_edges
    node_fields = n_real * (3 + 1 + 3)  # 3 fields and degree read, 3 written
    return WORD * (ids + gathered + node_fields)
