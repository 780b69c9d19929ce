"""The control of a hub-split cell: the reference's PageRank of the
logical graph, in bfloat16, put in the program's place.

`bench/control.py` runs its bfloat16 power iteration over the rows it is
handed.  On a hub-split graph a row holds one slice of a hub, so that
would be a PageRank of the split rows, wrong for a reason other than
precision.  This control sums each logical vertex's slices at its
primary row and divides by the logical degree (the plan's ``ldeg``), as
the reference does on the unsplit graph, with every value rounded to
bfloat16; replica rows read their primary's value.  `install` swaps it
for the PageRank of every mirrored refresh (coreness and labels are left
as the program computes them).  A limit on ``rank_rel_err`` that such a
run passes is too loose.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("steps",))
def rank_bf16_split(nbr, primary_row, ldeg, primary_mask, steps: int,
                    alpha: float):
    """``steps`` power-iteration steps of the logical graph, every value
    rounded to bfloat16: (N,) float32, a logical vertex's value at each
    of its rows."""
    bf = jnp.bfloat16
    n = jnp.maximum(primary_mask.sum(), 1).astype(bf)
    degb = jnp.maximum(ldeg, 1).astype(bf)
    src = primary_row[jnp.clip(nbr, 0)]  # the neighbour's primary row
    r0 = jnp.where(primary_mask, (1 / n).astype(bf), bf(0))

    def step(_, r):
        contrib = jnp.where(ldeg > 0, r / degb, bf(0))
        vals = jnp.where(nbr >= 0, contrib[src], bf(0))
        s = jnp.sum(vals, axis=1, dtype=bf)
        s = jnp.zeros_like(s).at[primary_row].add(s)  # slices to primary
        new = ((1 - alpha) / n).astype(bf) + bf(alpha) * s
        return jnp.where(primary_mask, new, bf(0)).astype(bf)

    r = jax.lax.fori_loop(0, steps, step, r0)
    return r[primary_row].astype(jnp.float32)


def install() -> None:
    """Swap every mirrored refresh's PageRank for the control's (this
    process)."""
    import repro.core.algorithms as alg

    orig = alg.fused_analytics

    def fused_analytics(g, alpha=0.85, steps=30, *, mirror, **kw):
        core, labels, _ = orig(g, alpha=alpha, steps=steps, mirror=mirror,
                               **kw)
        return core, labels, rank_bf16_split(
            g.nbr, mirror.primary_row, mirror.ldeg, mirror.primary_mask,
            steps=steps, alpha=alpha)

    alg.fused_analytics = fused_analytics
