"""Plain AIDW reference (Lu & Wong 2008, as in arXiv 1511.02186 §2).

Written from the equations, in plain ``jax.numpy``, and importing nothing
of the program under test.  For each query:

* Phase 1: the ``k`` nearest data points by brute force over all ``m``;
  ``r_obs`` is the mean of their distances, ``r_exp = 1 / (2 sqrt(m/A))``
  (Eq. 2), ``R = r_obs / r_exp`` (Eq. 3), the fuzzy membership
  ``mu = 0.5 - 0.5 cos(pi/r_max (R - r_min))`` clamped to [0, 1] (Eq. 5),
  and ``alpha`` the piecewise-linear map through (0.1, a1) ... (0.9, a5),
  constant outside (Eq. 6).
* Phase 2: ``z = sum_i d_i^-alpha z_i / sum_i d_i^-alpha`` over all ``m``
  points (Eq. 1); a query closer than ``sqrt(exact_hit_eps)`` to a data
  point takes that point's z.

``dtype`` is the precision of every input and every operation.  The
benchmark's check runs it in float32, the precision the configurations
state; the control runs it in bfloat16, the next precision down.  Queries
go in blocks and data in chunks, so the working set stays small at any m.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MU_KNOTS = (0.1, 0.3, 0.5, 0.7, 0.9)


@partial(jax.jit, static_argnames=("k", "levels", "r_min", "r_max", "area", "eps", "m_real", "chunk"))
def _block(qx, qy, dx, dy, dz, *, k, levels, r_min, r_max, area, eps, m_real, chunk):
    """One block of queries against chunked data; data is padded with +inf
    coordinates (distance inf, weight 0) to a multiple of ``chunk``."""
    dtype = qx.dtype
    n = qx.shape[0]
    tiles = (dx.reshape(-1, chunk), dy.reshape(-1, chunk), dz.reshape(-1, chunk))

    def d2_of(tx, ty):
        ddx = qx[:, None] - tx[None, :]
        ddy = qy[:, None] - ty[None, :]
        return ddx * ddx + ddy * ddy

    def knn_step(best, tile):
        tx, ty, _ = tile
        both = jnp.concatenate([best, d2_of(tx, ty)], axis=1)
        return -jax.lax.top_k(-both, k)[0], None

    best, _ = jax.lax.scan(knn_step, jnp.full((n, k), jnp.inf, dtype), tiles)
    r_obs = jnp.mean(jnp.sqrt(best), axis=1)
    r_exp = jnp.asarray(1.0 / (2.0 * np.sqrt(m_real / area)), dtype)
    big_r = r_obs / r_exp
    mu = 0.5 - 0.5 * jnp.cos(jnp.asarray(np.pi / r_max, dtype) * (big_r - r_min))
    mu = jnp.where(big_r <= r_min, 0.0, jnp.where(big_r >= r_max, 1.0, mu)).astype(dtype)
    # Eq. (6): knots 0.2 apart from 0.1; segment i runs from a_i to a_(i+1)
    pos = jnp.clip((mu - MU_KNOTS[0]) / (MU_KNOTS[1] - MU_KNOTS[0]), 0, len(levels) - 1)
    seg = jnp.minimum(jnp.floor(pos).astype(jnp.int32), len(levels) - 2)
    lv = jnp.asarray(levels, dtype)
    alpha = (lv[seg] + (pos - seg).astype(dtype) * (lv[seg + 1] - lv[seg])).astype(dtype)

    def weight_step(carry, tile):
        sw, swz, dmin, zmin = carry
        tx, ty, tz = tile
        d2 = d2_of(tx, ty)
        w = jnp.power(d2, -alpha[:, None] / 2)
        w = jnp.where(d2 > 0, w, 0).astype(dtype)
        near = jnp.argmin(d2, axis=1)
        tmin = jnp.min(d2, axis=1)
        closer = tmin < dmin
        return (sw + jnp.sum(w, axis=1), swz + jnp.sum(w * tz[None, :], axis=1),
                jnp.where(closer, tmin, dmin), jnp.where(closer, tz[near], zmin)), None

    zero = jnp.zeros((n,), dtype)
    init = (zero, zero, jnp.full((n,), jnp.inf, dtype), zero)
    (sw, swz, dmin, zmin), _ = jax.lax.scan(weight_step, init, tiles)
    z = jnp.where(dmin <= eps, zmin, swz / sw)
    return z, alpha


def aidw(dx, dy, dz, qx, qy, aidw_cfg: dict, *, dtype=jnp.float32,
         q_block: int = 512, chunk: int = 8192):
    """AIDW ``(z, alpha)`` at the queries, as float64 numpy arrays.

    ``aidw_cfg`` is a configuration's ``aidw`` block: ``k``,
    ``alpha_levels``, ``r_min``, ``r_max``, ``area``, ``exact_hit_eps``.
    """
    m = int(np.shape(dx)[0])
    chunk = min(chunk, int(2 ** np.ceil(np.log2(max(m, 128)))))
    pad = (-m) % chunk
    inf = np.full(pad, np.inf, np.float32)
    data = [jnp.asarray(np.concatenate([np.asarray(a, np.float32), fill]), dtype)
            for a, fill in ((dx, inf), (dy, inf), (dz, np.zeros(pad, np.float32)))]
    n = int(np.shape(qx)[0])
    npad = (-n) % q_block
    qxp = np.concatenate([np.asarray(qx, np.float32), np.zeros(npad, np.float32)])
    qyp = np.concatenate([np.asarray(qy, np.float32), np.zeros(npad, np.float32)])
    kw = dict(k=int(aidw_cfg["k"]), levels=tuple(float(a) for a in aidw_cfg["alpha_levels"]),
              r_min=float(aidw_cfg["r_min"]), r_max=float(aidw_cfg["r_max"]),
              area=float(aidw_cfg["area"]), eps=float(aidw_cfg["exact_hit_eps"]),
              m_real=m, chunk=chunk)
    zs, alphas = [], []
    for i in range(0, n + npad, q_block):
        z, a = _block(jnp.asarray(qxp[i:i + q_block], dtype), jnp.asarray(qyp[i:i + q_block], dtype),
                      *data, **kw)
        zs.append(z)
        alphas.append(a)
    z = np.concatenate([np.asarray(v, np.float64) for v in jax.device_get(zs)])[:n]
    alpha = np.concatenate([np.asarray(v, np.float64) for v in jax.device_get(alphas)])[:n]
    return z, alpha
