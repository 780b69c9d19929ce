"""`gather_share.mesh4`: share, in %, of a chip's busy time on the mesh
spent in the XLA neighbour gathers (ops classed ``gather`` by
``bench/trace.py``): each field's reads of the local and the halo
tables, and the exchange's send gathers."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return run.trace.share("gather")
