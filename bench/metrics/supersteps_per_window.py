"""`supersteps_per_window`: frontier (BFS) plus clamped-recompute
supersteps of the stream's maintenance per window, from the program's
``StreamStats`` counters over the measured window."""


def read(run):
    windows = run.counters.get("windows")
    if not windows:
        return None
    return run.counters["supersteps"] / windows
