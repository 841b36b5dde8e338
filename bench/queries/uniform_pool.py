"""A pool of ``pool`` queries uniform over the data extent, cut into calls
of ``batch``; calls cycle over the pool."""

from __future__ import annotations

from bench.gen import rng_for


def make(spec: dict, config: dict, seed: int):
    pool, batch = int(spec["pool"]), int(spec["batch"])
    if pool % batch:
        raise ValueError(f"uniform_pool: pool {pool} is not a multiple of batch {batch}")
    x0, x1, y0, y1 = config["extent"]
    rng = rng_for(seed, 3)
    qx = rng.uniform(x0, x1, pool).astype("float32")
    qy = rng.uniform(y0, y1, pool).astype("float32")
    return [(qx[i:i + batch], qy[i:i + batch]) for i in range(0, pool, batch)]
