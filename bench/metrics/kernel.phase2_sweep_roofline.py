"""The exact Phase-2 sweep's share of its roofline, in %: the least time
the chip could take for the window's sweep work over the device time of
the sweep kernels (the group ``bench/kernels/phase2_sweep.json``).

The work is counted by ``bench/roofline.phase2_work`` from the AIDW
formula: every (query, data point) pair of every call, whatever kernel or
block sizes compute it.  The least time takes the published peaks of the
device kind.  Moves ``served_queries_per_s``
(as ``tiled.kernel.phase2_sweep_roofline``, ``queries_per_s``)."""

from bench import roofline


def read(ctx):
    t, peak = ctx["trace"], ctx["peaks"]
    if t is None or peak is None or t["kernel_s"].get("phase2_sweep", 0.0) <= 0:
        return None
    c = ctx["counters"]
    ops = nbytes = 0.0
    for n in c["sizes"]:
        o, b = roofline.phase2_work(n, c["m"])
        ops += o
        nbytes += b
    least, _bound = roofline.least_time(ops, nbytes, peak)
    return 100.0 * least / t["kernel_s"]["phase2_sweep"]
