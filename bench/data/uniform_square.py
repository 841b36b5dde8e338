"""The paper's data (arXiv 1511.02186 §4): ``m`` points uniform in the unit
square, ``z = sin(6x) cos(6y) + 2``.  Positions lie on a ``2**24``
lattice, the finest on which ``1 - x`` is exact in float32."""

from __future__ import annotations

import numpy as np

from bench.gen import rng_for, seeded_layout


def make(spec: dict, seed: int):
    m = int(spec["m"])
    lattice = 1 << 24
    base = rng_for(spec["base_seed"], 0)
    ix = base.integers(1, lattice - 1, m, dtype=np.int64)
    iy = base.integers(1, lattice - 1, m, dtype=np.int64)
    ix, iy, which = seeded_layout(ix, iy, lattice, seed, int(spec["orientations"]))
    x = (ix.astype(np.float64) / lattice).astype(np.float32)
    y = (iy.astype(np.float64) / lattice).astype(np.float32)
    z = (np.sin(6.0 * x.astype(np.float64)) * np.cos(6.0 * y.astype(np.float64)) + 2.0)
    return x, y, z.astype(np.float32), {"symmetry": which}
