"""An airborne LiDAR tile at USGS Lidar Base Specification QL2 density.

Parallel flight lines run along y.  Inside each swath an oscillating
mirror draws zig-zag scan lines across the track; pulses along a scan
line and scan lines along the track are both ``pulse_spacing_m`` apart on
average, with Gaussian positional jitter.  Where swaths overlap (sidelap)
the density adds up.  Points inside a few elliptic voids (water, no
returns) are dropped, and a random share of the rest (returns lost) so
that exactly ``m`` remain.  Positions are quantised to ``2**-quant_bits``
m, as LAS files store them on a fixed scale.

The terrain z is drawn from ``seed``: a base elevation plus smooth
sinusoidal relief and a few Gaussian hills.
"""

from __future__ import annotations

import math

import numpy as np

from bench.gen import rng_for, seeded_layout


def make(spec: dict, seed: int):
    m = int(spec["m"])
    side = float(spec["tile_m"])
    q = 2.0 ** -int(spec["quant_bits"])
    lattice = int(round(side / q))
    spacing = float(spec["pulse_spacing_m"])
    width = float(spec["swath_width_m"])
    base = rng_for(spec["base_seed"], 0)

    xs, ys = [], []
    n_scan = int(math.ceil(side / spacing)) + 2
    per_scan = int(round(width / spacing))
    u = (np.arange(per_scan, dtype=np.float64) + 0.5) / per_scan
    for centre in spec["line_centres_m"]:
        scan = np.arange(n_scan, dtype=np.float64)[:, None]
        y0 = float(base.uniform(-spacing, 0.0))
        # zig-zag: even scans sweep left to right, odd ones back, while the
        # aircraft moves one spacing along the track per scan
        frac = np.where(scan % 2 == 0, u[None, :], 1.0 - u[None, :])
        x = centre - width / 2 + width * frac
        y = y0 + spacing * (scan + u[None, :])
        xs.append(x.ravel())
        ys.append(y.ravel())
    x = np.concatenate(xs)
    y = np.concatenate(ys)
    x += base.normal(0.0, spec["jitter_m"], x.shape)
    y += base.normal(0.0, spec["jitter_m"], y.shape)
    keep = (x >= 0.0) & (x < side) & (y >= 0.0) & (y < side)
    for cx, cy, ax, ay in spec["voids_m"]:
        keep &= ((x - cx) / ax) ** 2 + ((y - cy) / ay) ** 2 > 1.0
    x, y = x[keep], y[keep]
    if x.shape[0] < m:
        raise ValueError(f"lidar_swaths: the pattern gives {x.shape[0]} returns, "
                         f"fewer than m={m}; lower pulse_spacing_m")
    pick = np.sort(base.choice(x.shape[0], m, replace=False))
    ix = np.clip(np.floor(x[pick] / q), 0, lattice - 1).astype(np.int64)
    iy = np.clip(np.floor(y[pick] / q), 0, lattice - 1).astype(np.int64)
    ix, iy, which = seeded_layout(ix, iy, lattice, seed, int(spec["orientations"]))
    x = (ix * q).astype(np.float32)
    y = (iy * q).astype(np.float32)
    return x, y, terrain(x, y, seed, side), {"symmetry": which,
                                             "returns_before_dropout": int(keep.sum())}


def terrain(x, y, seed: int, side: float) -> np.ndarray:
    """Smooth synthetic elevations in metres, from ``seed``."""
    rng = rng_for(seed, 2)
    xd, yd = x.astype(np.float64), y.astype(np.float64)
    z = np.full(xd.shape, 250.0)
    for _ in range(6):
        wavelength = rng.uniform(150.0, 1000.0)
        theta = rng.uniform(0.0, 2.0 * math.pi)
        amp = rng.uniform(3.0, 20.0)
        phase = rng.uniform(0.0, 2.0 * math.pi)
        k = 2.0 * math.pi / wavelength
        z += amp * np.sin(k * (xd * math.cos(theta) + yd * math.sin(theta)) + phase)
    for _ in range(3):
        hx, hy = rng.uniform(0.0, side, 2)
        r = rng.uniform(40.0, 200.0)
        z += rng.uniform(-15.0, 30.0) * np.exp(-((xd - hx) ** 2 + (yd - hy) ** 2) / (2 * r * r))
    return z.astype(np.float32)
