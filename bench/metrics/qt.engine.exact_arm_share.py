"""Share of the window's queries, in %, that an exact fallback arm
answered: the ring search (their block overflowed Phase 1's capacity) or
the masked exact Phase-2 sweep (their block's near field overflowed).  It sums what the quadtree path's ``call`` marks,
``exact_arm_mask`` of each served call.  Nothing to read on a path that
marks nothing.  Moves ``served_queries_per_s``."""


def read(ctx):
    c = ctx["counters"]
    if c["marked"] is None:
        return None
    return 100.0 * c["marked"] / c["queries"]
