"""`compile_ms.ingest`: time the program spent on JAX's compile path
inside the measured window, per update window, in ms.  The program's
own counter (`repro.tracing`) times tracing, lowering and backend
compiles (a persistent-cache load included) and logs each interval
with the time it ended; the intervals that ended inside the window are
summed.  A program without that counter reads nothing."""


def read(run):
    windows = run.counters.get("windows")
    if not windows or run.window is None:
        return None
    try:
        from repro import tracing
    except ImportError:
        return None
    t0, t1 = run.window
    secs = sum(iv.seconds for iv in tracing.compile_log()
               if t0 <= iv.t_end <= t1)
    return 1e3 * secs / windows
