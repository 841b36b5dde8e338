"""Served grid path with the quadtree far field:
``build_plan(impl="grid", phase2="quadtree", farfield_rtol=<config>)`` held
by a ``PlanRegistry`` and served through ``CapacityReestimator.execute``.

The configuration states the guarantee: every answer within the plan's
proved bound ``farfield_rtol`` of exact AIDW (DESIGN.md §8).  A plan that
cannot prove it is refused here, at the first build and after every
re-plan: ``UnprovableRtolWarning`` is an error while the plan is built, and
``plan.farfield_bound <= farfield_rtol`` is asserted.  The re-estimator
heals Phase-1 and Phase-2 overflow alike; warm-up is the exact path's
(``served-grid-exact.py``).  ``call`` marks the answers an exact fallback
arm gave: the ring search (Phase-1 overflow) or the masked exact sweep
(Phase-2 overflow), so the check's marked stratum samples both.
"""

from __future__ import annotations

import time
import warnings

import jax

from bench.manifest import path_module
from bench.program import aidw_params
from repro.engine import build_plan, exact_arm_mask
from repro.errors import UnprovableRtolWarning
from repro.serving import CapacityReestimator, PlanRegistry

_exact = path_module("served-grid-exact")
MAX_WARM_CALLS = _exact.MAX_WARM_CALLS


def _held_to(plan, rtol: float):
    if not plan.farfield_bound <= rtol:
        raise RuntimeError(f"the plan proves farfield_bound {plan.farfield_bound!r}, "
                           f"above the configuration's farfield_rtol {rtol!r}")
    return plan


class Server(_exact.Server):
    def __init__(self, config, data, batches, log):
        params = aidw_params(config)
        self.rtol = float(config["farfield_rtol"])
        radius = config.get("farfield_radius")
        t0 = time.perf_counter()
        with warnings.catch_warnings():
            warnings.simplefilter("error", UnprovableRtolWarning)
            plan = build_plan(*data, params=params, area=params.area, impl="grid",
                              phase2="quadtree", farfield_rtol=self.rtol,
                              farfield_radius=None if radius is None else int(radius))
        jax.block_until_ready(plan)
        self.build_s = time.perf_counter() - t0
        self.plan = _held_to(plan, self.rtol)
        self.log = log
        self.registry = PlanRegistry()
        self.reest = CapacityReestimator(self.registry, "bench", plan, warmup=batches[0])
        log(f"plan: grid {plan.grid.gx}x{plan.grid.gy} cell cap {plan.grid.cap} "
            f"cand_capacity {plan.cand_capacity} seam_level {plan.seam_level} "
            f"quadtree radius {plan.farfield_radius} bound {plan.farfield_bound!r} "
            f"tau {plan.qt_tau!r} p2_capacity {plan.p2_capacity} tile {plan.p2_block_d} "
            f"levels {plan.qt_levels} interpret {plan.interpret} built in {self.build_s:.3f}s")

    def call(self, qx, qy):
        plan = self.reest.plan
        if plan is not self.plan:  # a re-plan landed: held to the same bound
            self.plan = _held_to(plan, self.rtol)
        z, a, stats = self.reest.execute(qx, qy)
        self.last_stats = stats
        return z, a, exact_arm_mask(stats)

    def warm(self, batches):
        super().warm(batches)
        s = self.last_stats
        self.log("warm: last call near_points_mean {} far_cells_mean {} cells_per_level {} "
                 "p2_need_max {} overflow {} p2_overflow {}".format(
                     float(s["near_points_mean"]), float(s["far_cells_mean"]),
                     [round(float(v), 1) for v in s["cells_per_level"]],
                     int(s["p2_need_max"]), int(s["overflow_queries"]),
                     int(s["p2_overflow_queries"])))

    def counters(self) -> dict:
        s = self.reest.stats()
        return {**super().counters(), "p2_overflow_queries": s["p2_overflow_queries"]}
