"""Device milliseconds of XLA operations (everything but Pallas kernels:
Morton sort, candidate gather, ring search, padding) per 1,000 queries of
the traced window.  Moves ``served_queries_per_s``."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t["xla_s"] <= 0:
        return None
    return 1e3 * t["xla_s"] / (ctx["counters"]["queries"] / 1e3)
