"""The comparison that decides ``correct``.

After the window has closed, a sample of the answers the window produced,
drawn from the seed, is recomputed by the plain reference and compared:

* ``z_rel_gap``: the largest ``|z - z_ref| / |z_ref|`` over the sample
  (Phase 2, the exact sweep, and Phase 1 through alpha);
* ``alpha_abs_gap``: the largest ``|alpha - alpha_ref|`` (Phase 1).

Where the path marks answers that took another arm (the grid path's
ring-search overflow arm), part of the sample is drawn from those, so the
arm is checked whenever it ran; and part from the answers whose alpha
lies below the top level, the only ones whose alpha Phase 1's neighbours
move (above the saturation radius every alpha is the top level).  Each number has its own limit, kept per
cell in ``bench/limits/<workload>.json`` with the readings it was set from.
"""

from __future__ import annotations

import numpy as np

from bench.gen import rng_for

NUMBERS = ("z_rel_gap", "alpha_abs_gap")


def draw_sample(n_total: int, seed: int, size: int, strata=()) -> np.ndarray:
    """Indices into the window's answers: ``size`` uniform draws, plus, for
    each ``(mask, k)`` of ``strata``, up to ``k`` among the answers the mask
    marks (a mask of ``None`` marks none); sorted, without repeats."""
    rng = rng_for(seed, 4)
    idx = [rng.choice(n_total, min(size, n_total), replace=False)]
    for mask, k in strata:
        where = np.flatnonzero(mask) if mask is not None else np.empty(0, np.int64)
        if where.size and k > 0:
            idx.append(rng.choice(where, min(k, where.size), replace=False))
    return np.unique(np.concatenate(idx))


def gaps(z, alpha, z_ref, alpha_ref) -> dict:
    z, alpha = np.asarray(z, np.float64), np.asarray(alpha, np.float64)
    bad = ~(np.isfinite(z) & np.isfinite(alpha))
    if bad.any():
        return {"z_rel_gap": float("inf"), "alpha_abs_gap": float("inf")}
    return {
        "z_rel_gap": float(np.max(np.abs(z - z_ref) / np.maximum(np.abs(z_ref), 1e-30))),
        "alpha_abs_gap": float(np.max(np.abs(alpha - alpha_ref))),
    }


def verdict(measured: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, checks)``: every number at or under its limit; ``checks``
    maps each name to its number and limit, for the result line."""
    checks = {name: {"value": measured[name], "limit": limits[name]} for name in NUMBERS}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return bool(ok), checks
