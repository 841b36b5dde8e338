"""Device milliseconds of the Phase-1 kNN/alpha kernels (the group
``bench/kernels/phase1_knn.json``) per 1,000 queries of the traced window.
Moves ``served_queries_per_s``
(as ``tiled.kernel.phase1_knn.ms_per_kquery``, ``queries_per_s``)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t["kernel_s"].get("phase1_knn", 0.0) <= 0:
        return None
    return 1e3 * t["kernel_s"]["phase1_knn"] / (ctx["counters"]["queries"] / 1e3)
