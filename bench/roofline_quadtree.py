"""The least work of the quadtree arm's two Phase-2 kernels, for their
roofline shares.

Counted from the formula each kernel evaluates, not from its blocks or
tiles, with each transcendental one operation:

* a near pair (query, point in its block's near rectangle): the exact
  sweep's 11 operations (``bench/roofline.OPS_PER_PAIR``);
* a far term (query, closed quadtree node): the squared distance (5:
  2 subtractions, 2 multiplications, 1 addition), the weight
  ``exp(-alpha/2 log d^2)`` (3), the dipole factor ``2 a w / max(d^2,
  tiny)`` (3: a maximum, a multiplication, a division), its dot product
  with the node's moment (4: 2 multiplications, an addition, a
  multiplication) and the two accumulations ``count w`` and ``z_sum w +
  dipole`` (5: 2 multiplications, 3 additions): 20.

The least traffic reads every point (x, y, z) or node (x, y, count, z-sum
and the two moments) a call touches once, and the queries and alpha in
and the accumulators out once, all float32.

The counts come from the program's own statistics
(``execute_with_stats``): ``near_points_mean`` and ``far_cells_mean`` per
block of ``block_q`` queries, so a call of ``n`` queries has about ``n *
near_points_mean`` near pairs and ``n * far_cells_mean`` far terms.
``bench/run.py`` passes its readers no program statistics, so no metric
reads these yet: they set the shares ``PERF.md`` gives from the program's
logged statistics.
"""

from __future__ import annotations

from bench import roofline

NEAR_OPS_PER_PAIR = roofline.OPS_PER_PAIR
FAR_OPS_PER_TERM = 5 + 3 + 3 + 4 + 5
F32 = roofline.F32


def near_work(n: int, near_points_mean: float, points_read: int) -> tuple[float, float]:
    """``(operations, bytes)`` of one call's near sweep: ``n`` queries,
    each against ``near_points_mean`` points; ``points_read`` distinct
    points read."""
    ops = float(NEAR_OPS_PER_PAIR) * n * near_points_mean
    nbytes = float(F32) * (3 * points_read + 3 * n + 4 * n)
    return ops, nbytes


def far_node_work(n: int, far_terms_mean: float, nodes_read: int) -> tuple[float, float]:
    """``(operations, bytes)`` of one call's far-node sweep: ``n`` queries,
    each against ``far_terms_mean`` closed nodes; ``nodes_read`` distinct
    nodes read."""
    ops = float(FAR_OPS_PER_TERM) * n * far_terms_mean
    nbytes = float(F32) * (6 * nodes_read + 3 * n + 2 * n)
    return ops, nbytes


def share(ops: float, nbytes: float, kernel_s: float, peak: dict) -> float:
    """The kernel's share of its roofline, in %: least time over kernel time."""
    least, _bound = roofline.least_time(ops, nbytes, peak)
    return 100.0 * least / kernel_s
