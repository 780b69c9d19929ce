"""`superstep_roofline`: the least time a fused superstep could take at
the chip's peak HBM bandwidth, for the bytes it must move
(``bench/roofline.py``), as a share of the traced ``superstep_ms``."""
from bench import peaks, roofline


def read(run):
    steps = run.counters.get("supersteps")
    if run.trace is None or not steps or run.trace.busy_s <= 0:
        return None
    least_s = (roofline.superstep_bytes(run.counters["n_real"],
                                        run.counters["n_edges"])
               / peaks.peaks(run.trace.device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / (run.trace.busy_s / steps)
