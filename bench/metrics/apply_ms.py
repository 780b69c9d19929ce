"""`apply_ms`: mean host span, in ms, around each stream window's
``StreamSession.apply_window`` up to the graph, coreness and labels
being ready."""


def read(run):
    return run.mean_ms("apply")
