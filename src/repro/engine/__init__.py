"""Plan/execute engine — the serving-shaped front door for every AIDW/IDW
implementation (DESIGN.md §6).

``build_plan`` runs ONCE per dataset, eagerly, and captures everything
shape- and occupancy-dependent (padded data layouts, the grid's CSR
snapshot, the static candidate capacity, autotuned block sizes).
``execute(plan, qx, qy)`` is a pure, jit-compatible function for *all*
impls — including ``grid``, which was eager-only before this engine — so a
plan is built once and reused across query batches with zero retraces:

    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid")
    z1, a1 = execute(plan, qx1, qy1)     # compiles
    z2, a2 = execute(plan, qx2, qy2)     # cache hit (same shapes)
"""

from repro.engine.plan import InterpolationPlan, build_plan, replan_with_capacity
from repro.engine.execute import exact_arm_mask, execute, execute_with_stats

__all__ = ["InterpolationPlan", "build_plan", "exact_arm_mask", "execute",
           "execute_with_stats", "replan_with_capacity"]
