"""Adaptive Inverse Distance Weighting (AIDW) — Lu & Wong (2008), as
GPU-accelerated by Mei, Xu & Xu (2015).

This module is the *mathematical* core: Eq. (2)-(6) of the paper plus a
vectorised pure-JAX interpolator that serves as the oracle for every Pallas
kernel in ``repro.kernels`` and as the single-host execution path.

Conventions
-----------
* Points are 2-D ``(x, y)`` with a scalar attribute ``z`` (the paper's
  setting; elevations etc.).
* All distances inside the hot path are *squared* distances; the paper's
  ``alpha *= 0.5`` trick (Fig. 3 line 49) is applied so weights are
  ``(d^2)^(-alpha/2) = d^(-alpha)`` without a sqrt in the weighting pass.
* The piecewise-linear alpha map implements Eq. (6) — NOT the paper's CUDA
  listing, which has a typo in the 0.3-0.5 branch (uses ``a1`` where Eq. (6)
  has ``a2``).  Eq. (6) is the continuous piecewise-linear map through
  (0.1, a1), (0.3, a2), (0.5, a3), (0.7, a4), (0.9, a5).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.knn import running_k_best

# Knots of the Eq. (6) triangular-membership map.
MU_KNOTS = (0.0, 0.1, 0.3, 0.5, 0.7, 0.9, 1.0)

# Default five decay levels a1..a5.  The paper does not publish its values;
# these follow Lu & Wong's "categories of distance-decay value" spanning the
# usual IDW powers 0.5..4 and are configurable everywhere.
DEFAULT_ALPHA_LEVELS = (0.5, 1.0, 2.0, 3.0, 4.0)


@dataclasses.dataclass(frozen=True)
class AIDWParams:
    """Static configuration of an AIDW interpolation.

    Attributes:
      k: number of nearest neighbours entering ``r_obs`` (paper Fig. 1: 10).
      alpha_levels: the five decay levels ``a1..a5`` of Eq. (6).
      r_min, r_max: bounds of the fuzzy membership function, Eq. (5)
        ("in general ... 0.0 and 2.0").
      area: area ``A`` of the study region for Eq. (2).  ``None`` derives the
        bounding-box area of the data points (paper: unit square test data).
      exact_hit_eps: squared-distance threshold below which a query point is
        declared coincident with a data point and returns that ``z`` exactly
        (the paper's kernel would produce inf/nan here; production guard).
    """

    k: int = 10
    alpha_levels: Sequence[float] = DEFAULT_ALPHA_LEVELS
    r_min: float = 0.0
    r_max: float = 2.0
    area: float | None = None
    exact_hit_eps: float = 1e-18

    def resolve_area(self, dx, dy) -> float:
        if self.area is not None:
            return float(self.area)
        spanx = float(jnp.max(dx) - jnp.min(dx))
        spany = float(jnp.max(dy) - jnp.min(dy))
        return max(spanx * spany, 1e-30)


def expected_nn_distance(m: int, area: float):
    """Eq. (2): expected NN distance of a random pattern, r_exp = 1/(2 sqrt(m/A))."""
    return 1.0 / (2.0 * math.sqrt(m / area))


def fuzzy_membership(r_stat, r_min: float, r_max: float):
    """Eq. (5): normalise the NN statistic R(S0) to [0, 1].

    mu_R = 0 for R <= r_min; 1 for R >= r_max;
    0.5 - 0.5 cos(pi / r_max * (R - r_min)) in between.
    """
    mu = 0.5 - 0.5 * jnp.cos(jnp.pi / r_max * (r_stat - r_min))
    mu = jnp.where(r_stat <= r_min, 0.0, mu)
    mu = jnp.where(r_stat >= r_max, 1.0, mu)
    return mu


def alpha_from_mu(mu, levels: Sequence[float] = DEFAULT_ALPHA_LEVELS):
    """Eq. (6): piecewise-linear (triangular membership) map mu -> alpha.

    Linear through (0.1, a1), (0.3, a2), (0.5, a3), (0.7, a4), (0.9, a5),
    constant a1 below 0.1 and a5 above 0.9.  Equivalent to
    ``jnp.interp(mu, MU_KNOTS, [a1, a1, a2, a3, a4, a5, a5])`` but written as
    a clamped-lerp chain so the identical expression is reusable inside
    Pallas kernel bodies (jnp.interp does not lower in Mosaic).
    """
    a1, a2, a3, a4, a5 = [jnp.asarray(a, dtype=mu.dtype) for a in levels]
    alpha = a1
    for lo, aa, bb in (
        (0.1, a1, a2),
        (0.3, a2, a3),
        (0.5, a3, a4),
        (0.7, a4, a5),
    ):
        t = jnp.clip((mu - lo) * 5.0, 0.0, 1.0)  # each segment spans 0.2
        alpha = alpha * (1.0 - t) + bb * t
    return alpha


def adaptive_alpha(r_obs, m: int, area: float, params: AIDWParams):
    """Steps 1-3 of §2.2: observed-NN-mean -> R(S0) -> mu_R -> alpha."""
    r_exp = expected_nn_distance(m, area)
    r_stat = r_obs / jnp.asarray(r_exp, dtype=r_obs.dtype)
    mu = fuzzy_membership(r_stat, params.r_min, params.r_max)
    return alpha_from_mu(mu, params.alpha_levels)


def _sq_dists(qx, qy, dx, dy):
    """Pairwise squared distances, (n, 1) queries x (1, m) data -> (n, m)."""
    ddx = qx[:, None] - dx[None, :]
    ddy = qy[:, None] - dy[None, :]
    return ddx * ddx + ddy * ddy


def _weighted_average(d2, dz, alpha_half, exact_hit_eps):
    """Phase 2 (Eq. 1): w = (d^2)^(-alpha/2); exact-hit override."""
    dtype = d2.dtype
    # (d2)^(-alpha_half) via exp/log; d2 clamped away from 0 (hits handled below)
    tiny = jnp.asarray(1e-30 if dtype == jnp.float32 else 1e-290, dtype)
    w = jnp.exp(-alpha_half[:, None] * jnp.log(jnp.maximum(d2, tiny)))
    sum_w = jnp.sum(w, axis=1)
    sum_wz = jnp.sum(w * dz[None, :], axis=1)
    zhat = sum_wz / sum_w
    # exact-hit guard: query coincides with a data point
    min_d2 = jnp.min(d2, axis=1)
    hit_z = dz[jnp.argmin(d2, axis=1)]
    return jnp.where(min_d2 <= exact_hit_eps, hit_z, zhat)


def aidw_reference(dx, dy, dz, qx, qy, params: AIDWParams = AIDWParams(), *, area: float | None = None):
    """Memory-naive oracle: materialises the full (n, m) distance matrix.

    The ground truth for all kernels and the distributed path.  O(n*m) memory —
    use :func:`aidw_interpolate` for large inputs.
    Returns ``(z_hat, alpha)`` with shapes ``(n,)``.
    """
    m = dx.shape[0]
    a = area if area is not None else params.resolve_area(dx, dy)
    d2 = _sq_dists(qx, qy, dx, dy)  # (n, m)
    # k smallest squared distances per row -> r_obs over true distances
    neg_topk = jax.lax.top_k(-d2, params.k)[0]
    knn_d = jnp.sqrt(-neg_topk)
    r_obs = jnp.mean(knn_d, axis=1)
    alpha = adaptive_alpha(r_obs, m, a, params)
    zhat = _weighted_average(d2, dz, alpha * 0.5, params.exact_hit_eps)
    return zhat, alpha


def aidw_interpolate(
    dx,
    dy,
    dz,
    qx,
    qy,
    params: AIDWParams = AIDWParams(),
    *,
    area: float | None = None,
    q_chunk: int = 1024,
    d_chunk: int = 4096,
    knn: str = "brute",
    grid=None,
):
    """Production single-host AIDW: O(q_chunk * d_chunk) peak memory.

    Mirrors the two-pass structure of the paper's kernels (distances are
    computed twice) with the data-point axis tiled — this is the pure-jnp
    twin of the *tiled* kernel and the building block of the distributed
    ring version.  Returns ``(z_hat, alpha)``.

    ``knn="grid"`` replaces the Phase-1 brute-force k-best scan with the
    uniform-grid ring search of ``repro.core.grid`` (near-O(k) per query);
    Phase 2 (weights over ALL m points) is identical either way.

    This is a convenience over the plan/execute engine (``repro.engine``,
    impl="chunked"): each call builds a chunked plan and runs the jitted
    execute step.  Grid building is the one eager step (concrete occupancy);
    pass a prebuilt ``grid=`` — or hold the plan yourself — to amortise
    across query batches.  The ``knn="brute"`` path plans traceably, which
    is how the distributed sharded path reuses it inside ``shard_map``.
    """
    from repro.engine import build_plan, execute

    if knn == "brute" and grid is not None:
        raise ValueError("grid= is only meaningful with knn='grid'")
    if area is None and params.area is None:
        raise ValueError("jit path requires a static area; pass area= or set params.area")
    plan = build_plan(
        dx, dy, dz, params=params, area=area, impl="chunked", knn=knn,
        q_chunk=q_chunk, d_chunk=d_chunk, grid=grid,
    )
    return execute(plan, qx, qy)


@partial(jax.jit, static_argnames=("k", "q_chunk", "d_chunk"))
def brute_r_obs(dx, dy, qx, qy, k: int, *, q_chunk: int = 1024, d_chunk: int = 4096):
    """Phase 1, brute force: chunked running-k-best scan over ALL m data
    points -> mean k-nearest distance per query, shape ``(n,)``.

    The single implementation behind ``aidw_interpolate(knn="brute")``, and
    the pure-jnp twin of the grid path's ``grid_r_obs``."""
    n = qx.shape[0]
    dtype = qx.dtype
    big = jnp.asarray(jnp.finfo(dtype).max / 4, dtype)
    m_pad = (-dx.shape[0]) % d_chunk
    d_tiles = jnp.concatenate([dx, jnp.full((m_pad,), big, dtype)]).reshape(-1, d_chunk)
    dy_tiles = jnp.concatenate([dy, jnp.full((m_pad,), big, dtype)]).reshape(-1, d_chunk)
    n_pad = (-n) % q_chunk
    qxp = jnp.concatenate([qx, jnp.zeros((n_pad,), dtype)])
    qyp = jnp.concatenate([qy, jnp.zeros((n_pad,), dtype)])

    def per_q_chunk(q):
        qcx, qcy = q

        def knn_step(best, tile):
            tx, ty = tile
            return running_k_best(best, _sq_dists(qcx, qcy, tx, ty)), None

        best0 = jnp.full((q_chunk, k), jnp.inf, dtype)
        best, _ = jax.lax.scan(knn_step, best0, (d_tiles, dy_tiles))
        return jnp.mean(jnp.sqrt(best), axis=1)

    q_tiles = (qxp.reshape(-1, q_chunk), qyp.reshape(-1, q_chunk))
    return jax.lax.map(per_q_chunk, q_tiles).reshape(-1)[:n]


@partial(jax.jit, static_argnames=("params", "area", "q_chunk", "d_chunk"))
def _interpolate_pass2(
    dx, dy, dz, qx, qy, alpha,
    params: AIDWParams,
    *,
    area: float,
    q_chunk: int = 1024,
    d_chunk: int = 4096,
):
    """Phase 2 — the chunked weighted-average sweep with a precomputed
    per-query ``alpha``.  Shared by both knn paths (``brute_r_obs`` and
    ``grid_r_obs`` only differ in how Phase 1 finds the neighbours), so the
    Phase-2 numerics are identical by construction."""
    m = dx.shape[0]
    n = qx.shape[0]
    dtype = qx.dtype

    m_pad = (-m) % d_chunk
    big = jnp.asarray(jnp.finfo(dtype).max / 4, dtype)
    dxp = jnp.concatenate([dx, jnp.full((m_pad,), big, dtype)])
    dyp = jnp.concatenate([dy, jnp.full((m_pad,), big, dtype)])
    dzp = jnp.concatenate([dz, jnp.zeros((m_pad,), dtype)])
    n_pad = (-n) % q_chunk
    qxp = jnp.concatenate([qx, jnp.zeros((n_pad,), dtype)])
    qyp = jnp.concatenate([qy, jnp.zeros((n_pad,), dtype)])
    alphap = jnp.concatenate([alpha.astype(dtype), jnp.ones((n_pad,), dtype)])

    d_tiles = dxp.reshape(-1, d_chunk)
    dy_tiles = dyp.reshape(-1, d_chunk)
    dz_tiles = dzp.reshape(-1, d_chunk)

    def per_q_chunk(q):
        qcx, qcy, alpha_half = q

        def w_step(carry, tile):
            sum_w, sum_wz, min_d2, hit_z = carry
            tx, ty, tz = tile
            d2 = _sq_dists(qcx, qcy, tx, ty)
            tiny = jnp.asarray(1e-30 if dtype == jnp.float32 else 1e-290, dtype)
            w = jnp.exp(-alpha_half[:, None] * jnp.log(jnp.maximum(d2, tiny)))
            tile_min = jnp.min(d2, axis=1)
            tile_hit_z = tz[jnp.argmin(d2, axis=1)]
            better = tile_min < min_d2
            return (
                sum_w + jnp.sum(w, axis=1),
                sum_wz + jnp.sum(w * tz[None, :], axis=1),
                jnp.where(better, tile_min, min_d2),
                jnp.where(better, tile_hit_z, hit_z),
            ), None

        zeros = jnp.zeros((q_chunk,), dtype)
        carry0 = (zeros, zeros, jnp.full((q_chunk,), jnp.inf, dtype), zeros)
        (sum_w, sum_wz, min_d2, hit_z), _ = jax.lax.scan(
            w_step, carry0, (d_tiles, dy_tiles, dz_tiles)
        )
        return jnp.where(min_d2 <= params.exact_hit_eps, hit_z, sum_wz / sum_w)

    q_tiles = (
        qxp.reshape(-1, q_chunk),
        qyp.reshape(-1, q_chunk),
        (alphap * 0.5).reshape(-1, q_chunk),
    )
    zhat = jax.lax.map(per_q_chunk, q_tiles)
    return zhat.reshape(-1)[:n]
