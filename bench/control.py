"""The control: a run whose PageRank is the reference's, in bfloat16.

The configurations state float32 PageRank; bfloat16 is the precision
below it, the step that would tempt a later change.  `install` puts the
reference's power iteration, computed in bfloat16 on the device from the
program's own adjacency, in place of the PageRank of every refresh
(coreness and labels are left as the program computes them).  A limit on
``rank_rel_err`` that such a run passes is too loose.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("steps",))
def rank_bf16(nbr, deg, mask, steps: int, alpha: float):
    """``steps`` power-iteration steps, every value rounded to bfloat16."""
    bf = jnp.bfloat16
    n = jnp.maximum(mask.sum(), 1).astype(bf)
    degb = jnp.maximum(deg, 1).astype(bf)
    r0 = jnp.where(mask, (1 / n).astype(bf), bf(0))

    def step(_, r):
        contrib = jnp.where(deg > 0, r / degb, bf(0))
        vals = jnp.where(nbr >= 0, contrib[jnp.clip(nbr, 0)], bf(0))
        s = jnp.sum(vals, axis=1, dtype=bf)
        new = ((1 - alpha) / n).astype(bf) + bf(alpha) * s
        return jnp.where(mask, new, bf(0)).astype(bf)

    return jax.lax.fori_loop(0, steps, step, r0).astype(jnp.float32)


def _used(nbr):
    """``nbr`` cut to its columns that hold a neighbour somewhere, rounded
    up to a power of two (at least 8): the same sums, a narrower gather."""
    used = jax.device_get((nbr >= 0).any(axis=0)).nonzero()[0]
    width = int(used.max()) + 1 if used.size else 1
    cols = 8
    while cols < width:
        cols *= 2
    return nbr[:, :min(cols, nbr.shape[1])]


def install() -> None:
    """Swap every refresh's PageRank for the control's (this process)."""
    import repro.core.algorithms as alg
    import repro.service.state as state

    orig = alg.fused_analytics

    def fused_analytics(g, alpha=0.85, steps=30, **kw):
        core, labels, _ = orig(g, alpha=alpha, steps=steps, **kw)
        return core, labels, rank_bf16(_used(g.nbr), g.deg, g.node_mask,
                                       steps=steps, alpha=alpha)

    alg.fused_analytics = fused_analytics
    state.fused_analytics = fused_analytics
