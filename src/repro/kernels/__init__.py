"""Pallas TPU kernels for the BLADYG hot loops, behind a backend registry.

Two kernel families — dense-tile (O(N^2) adjacency, MXU matmuls) and ELL
block-sparse (O(N*Cd), consumes `GraphBlocks.nbr` tiles directly) — plus the
pure-jnp oracles in `ref.py`.  Core code selects between them only through
`ops` (`backend="auto"|"jnp"|"dense"|"ell"`).

Validated in interpret mode against the oracles on the CPU, and compiled
for TPU v5e at the full roadNet-CA widths by `tests/test_tpu_compile.py`
(explicit BlockSpec VMEM tiling, MXU-aligned); `ell_triangles` has no TPU
lowering.
"""
from . import ops, ref
from .kcore_hindex import hindex_counts
from .frontier import frontier_step
from .ell_hindex import hindex_ell
from .ell_frontier import frontier_step_ell
from .ell_cc import neighbor_min_ell
from .ell_pagerank import neighbor_sum_ell
from .ell_triangles import neighbor_common_ell
from .ell_multi import neighbor_multi_ell

__all__ = [
    "ops", "ref", "hindex_counts", "frontier_step",
    "hindex_ell", "frontier_step_ell",
    "neighbor_min_ell", "neighbor_sum_ell", "neighbor_common_ell",
    "neighbor_multi_ell",
]
