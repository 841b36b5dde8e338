"""Share of the window's queries, in %, whose block overflowed the Phase-1
candidate capacity and took the ring-search arm (the sum of each served
call's ``overflow_query_mask``).  Nothing to read on a path without that
arm.  Moves ``served_queries_per_s``."""


def read(ctx):
    c = ctx["counters"]
    if c["marked"] is None:
        return None
    return 100.0 * c["marked"] / c["queries"]
