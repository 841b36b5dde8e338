"""Device milliseconds of the quadtree arm's near-field kernels (the group
``bench/kernels/phase2_near.json``) per 1,000 queries of the traced
window.  Nothing to read where no such kernel ran.  Moves
``served_queries_per_s``."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t["kernel_s"].get("phase2_near", 0.0) <= 0:
        return None
    return 1e3 * t["kernel_s"]["phase2_near"] / (ctx["counters"]["queries"] / 1e3)
