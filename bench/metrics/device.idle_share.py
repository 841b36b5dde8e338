"""Share of the traced window, in %, in which no operation ran on the
device: 1 - busy / window, busy being the union of the device's operation
intervals.  Moves ``served_queries_per_s``
(as ``tiled.device.idle_share``, ``queries_per_s``)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
