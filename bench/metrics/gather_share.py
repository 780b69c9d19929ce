"""`gather_share`: share, in %, of the device's busy time spent in the
XLA neighbour gathers (ops classed ``gather`` by ``bench/trace.py``)."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return run.trace.share("gather")
