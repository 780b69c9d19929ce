"""`superstep_ms.mesh4`: device busy time a chip of the traced refreshes
on the mesh (the mean over the chips) divided by the supersteps they
ran, in ms."""


def read(run):
    steps = run.counters.get("supersteps")
    if run.trace is None or not steps or run.trace.busy_s <= 0:
        return None
    return 1e3 * run.trace.busy_s / steps
