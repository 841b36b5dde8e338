"""Device milliseconds of the quadtree arm's far-node kernels (the group
``bench/kernels/phase2_far_nodes.json``, every level's launch) per 1,000
queries of the traced window.  Nothing to read where no such kernel ran.
Moves ``served_queries_per_s``."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t["kernel_s"].get("phase2_far_nodes", 0.0) <= 0:
        return None
    return 1e3 * t["kernel_s"]["phase2_far_nodes"] / (ctx["counters"]["queries"] / 1e3)
