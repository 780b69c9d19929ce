"""`collective_share.mesh4`: share, in %, of the device's busy time spent
in cross-chip collectives: the halo exchange's all-to-all, the replica
merges' all-reduces and the halt flag's all-reduce.  An op counts when
its HLO opcode is a collective's, its async start and done halves
included (`bench/collectives.py`); the driver reads that time from the
trace into the ``collective_s`` counter."""


def read(run):
    secs = run.counters.get("collective_s")
    if run.trace is None or secs is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * secs / run.trace.busy_s
