"""`idle_share.mesh4`: share, in %, of the traced window in which no
operation ran on a chip of the mesh (1 - busy union / window, the mean
over the chips)."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return run.trace.idle_share()
