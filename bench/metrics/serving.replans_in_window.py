"""Re-plans the capacity re-estimator started inside the window: the change
of ``CapacityReestimator.stats()["replans"]`` across it.  Nothing to read
on a path without a re-estimator.  Moves ``served_queries_per_s``."""


def read(ctx):
    return ctx["counters"]["replans_in_window"]
