"""`idle_share.ingest`: share, in %, of the traced window in which no
operation ran on the device (1 - busy union / window)."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return run.trace.idle_share()
