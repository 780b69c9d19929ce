"""`refresh_ms.ingest`: mean host span, in ms, around each snapshot
refresh (``AnalyticsState.refresh``) up to its snapshot being ready."""


def read(run):
    return run.mean_ms("refresh")
