"""The one place the paths turn a configuration into the program's AIDW
parameters."""

from __future__ import annotations

from repro.core.aidw import AIDWParams


def aidw_params(config: dict) -> AIDWParams:
    a = config["aidw"]
    return AIDWParams(k=int(a["k"]), alpha_levels=tuple(a["alpha_levels"]),
                      r_min=float(a["r_min"]), r_max=float(a["r_max"]),
                      area=float(a["area"]), exact_hit_eps=float(a["exact_hit_eps"]))
