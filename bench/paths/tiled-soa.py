"""The paper's tiled kernel: ``build_plan(impl="tiled", layout="soa")``,
then ``repro.engine.execute`` per call.  No grid, no serving layer: both
phases sweep every data point for every query."""

from __future__ import annotations

import time

import jax

from bench.program import aidw_params
from repro.engine import build_plan, execute


class Server:
    def __init__(self, config, data, batches, log):
        params = aidw_params(config)
        t0 = time.perf_counter()
        self.plan = build_plan(*data, params=params, area=params.area, impl="tiled", layout="soa")
        jax.block_until_ready(self.plan)
        self.build_s = time.perf_counter() - t0
        log(f"plan: tiled soa block_q {self.plan.block_q} block_d {self.plan.block_d} "
            f"interpret {self.plan.interpret} built in {self.build_s:.3f}s")

    def call(self, qx, qy):
        z, a = execute(self.plan, qx, qy)
        return z, a, None

    def warm(self, batches):
        # every call has one shape: two calls compile it and run it warm
        for qx, qy in batches[:2]:
            jax.block_until_ready(self.call(qx, qy)[:2])

    def counters(self) -> dict:
        return {"plan_build_s": self.build_s}

    def close(self):
        self.plan = None
