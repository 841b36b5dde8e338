"""Seconds to build the cell's first plan: host clock around ``build_plan``
and ``block_until_ready``.  Moves ``setup_s``."""


def read(ctx):
    return ctx["counters"]["plan_build_s"]
