"""`superstep_roofline.mesh4`: the least time a fused superstep could
take with every chip of the mesh at its peak HBM bandwidth, for the
bytes the superstep must move (``bench/roofline.py``, from the real
nodes and edges, whatever the layout), as a share of the traced
``superstep_ms.mesh4``."""
from bench import peaks, roofline


def read(run):
    steps = run.counters.get("supersteps")
    chips = run.counters.get("chips")
    if run.trace is None or not steps or not chips or run.trace.busy_s <= 0:
        return None
    least_s = (roofline.superstep_bytes(run.counters["n_real"],
                                        run.counters["n_edges"])
               / (chips * peaks.peaks(run.trace.device_kind)
                  ["hbm_bytes_per_s"]))
    return 100.0 * least_s / (run.trace.busy_s / steps)
