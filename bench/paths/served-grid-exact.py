"""Served grid path: ``build_plan(impl="grid", phase2="exact")`` held by a
``PlanRegistry`` and served through ``CapacityReestimator.execute``.

The re-estimator decides the width of the Phase-1 candidate rows: a
persistent Phase-1 overflow streak re-plans at a larger capacity in the
background and swaps the new plan in, warmed on a batch first.  Warm-up
serves calls until the re-estimator is healthy and its last
``PERSISTENT_OVERFLOW_BATCHES`` + 1 calls ran on one plan without a
trigger, so the window starts on the plan the traffic settles on, with
that plan's serving program compiled; a warm-up that does not settle
within ``MAX_WARM_CALLS`` calls ends the run with no result.  Queries
that overflowed Phase 1 took the ring-search arm; ``call`` marks them.
"""

from __future__ import annotations

import time

import jax

from bench.program import aidw_params
from repro.engine import build_plan
from repro.engine.execute import PERSISTENT_OVERFLOW_BATCHES
from repro.serving import CapacityReestimator, PlanRegistry

MAX_WARM_CALLS = 48


class Server:
    def __init__(self, config, data, batches, log):
        params = aidw_params(config)
        t0 = time.perf_counter()
        plan = build_plan(*data, params=params, area=params.area, impl="grid", phase2="exact")
        jax.block_until_ready(plan)
        self.build_s = time.perf_counter() - t0
        self.log = log
        self.registry = PlanRegistry()
        self.reest = CapacityReestimator(self.registry, "bench", plan, warmup=batches[0])
        log(f"plan: grid {plan.grid.gx}x{plan.grid.gy} cell cap {plan.grid.cap} "
            f"cand_capacity {plan.cand_capacity} seam_level {plan.seam_level} "
            f"interpret {plan.interpret} built in {self.build_s:.3f}s")

    def call(self, qx, qy):
        z, a, stats = self.reest.execute(qx, qy)
        return z, a, stats["overflow_query_mask"]

    def warm(self, batches):
        # settled: the last PERSISTENT_OVERFLOW_BATCHES + 1 calls all ran on
        # one plan (the first call on a swapped-in plan compiles its serving
        # program) and none of them triggered a re-plan
        seen = []
        for i in range(MAX_WARM_CALLS):
            plan = self.reest.plan
            z, a, _ = self.call(*batches[i % len(batches)])
            jax.block_until_ready((z, a))
            s = self.reest.stats()
            seen.append((id(plan), s["triggers"]))
            recent = seen[-(PERSISTENT_OVERFLOW_BATCHES + 1):]
            # a swap may land while a call runs: the plan the next call
            # will get must be the one the recent calls ran on
            if (len(recent) == PERSISTENT_OVERFLOW_BATCHES + 1 and len(set(recent)) == 1
                    and self.reest.join(timeout=0.0) == "healthy"
                    and id(self.reest.plan) == recent[0][0]):
                self.log(f"warm: {i + 1} calls; {s}")
                return
        # a re-plan, swap or compile would land in the window: no run
        raise RuntimeError(f"warm: not settled after {MAX_WARM_CALLS} calls; {self.reest.stats()}")

    def counters(self) -> dict:
        s = self.reest.stats()
        return {"plan_build_s": self.build_s, "replans": s["replans"], "swaps": s["swaps"],
                "state": s["state"], "cand_capacity": s["cand_capacity"]}

    def close(self):
        state = self.reest.join(timeout=120.0)
        if state == "degraded":
            raise RuntimeError(f"the re-estimator degraded: {self.reest.last_error}")
        self.reest = self.registry = None
