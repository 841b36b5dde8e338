"""Accuracy tooling — beyond-paper f32 error control (EXPERIMENTS §Accuracy).

The paper's answer to f32 rounding is "use f64", which costs 1/24 rate on its
GPU and has NO native support on TPU.  The dominant f32 error source in AIDW
is the long accumulation chain of Σw and Σw·z over m data points (w spans
many orders of magnitude near the query).  Kahan-compensated accumulation of
the cross-tile partials recovers ~f64 accuracy at f32 cost — the TPU-native
replacement for the paper's double-precision variant.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.aidw import AIDWParams, adaptive_alpha, _sq_dists
from repro.core.knn import running_k_best


def kahan_add(s, c, x):
    """One compensated accumulation step: returns (new_sum, new_compensation)."""
    y = x - c
    t = s + y
    c_new = (t - s) - y
    return t, c_new


@partial(jax.jit, static_argnames=("params", "area", "q_chunk", "d_chunk"))
def aidw_interpolate_kahan(
    dx, dy, dz, qx, qy,
    params: AIDWParams = AIDWParams(),
    *,
    area: float,
    q_chunk: int = 1024,
    d_chunk: int = 4096,
):
    """Tiled AIDW with Kahan-compensated cross-tile Σw / Σw·z accumulators.

    Same structure as :func:`repro.core.aidw.aidw_interpolate`; only the
    weight-pass carry differs.  Returns ``(z_hat, alpha)``.
    """
    m, n = dx.shape[0], qx.shape[0]
    dtype = qx.dtype
    big = jnp.asarray(jnp.finfo(dtype).max / 4, dtype)
    m_pad = (-m) % d_chunk
    dxp = jnp.concatenate([dx, jnp.full((m_pad,), big, dtype)])
    dyp = jnp.concatenate([dy, jnp.full((m_pad,), big, dtype)])
    dzp = jnp.concatenate([dz, jnp.zeros((m_pad,), dtype)])
    n_pad = (-n) % q_chunk
    qxp = jnp.concatenate([qx, jnp.zeros((n_pad,), dtype)])
    qyp = jnp.concatenate([qy, jnp.zeros((n_pad,), dtype)])
    tiles = (dxp.reshape(-1, d_chunk), dyp.reshape(-1, d_chunk), dzp.reshape(-1, d_chunk))

    def per_q(q):
        qcx, qcy = q

        def knn_step(best, tile):
            tx, ty, _ = tile
            return running_k_best(best, _sq_dists(qcx, qcy, tx, ty)), None

        best0 = jnp.full((q_chunk, params.k), jnp.inf, dtype)
        best, _ = jax.lax.scan(knn_step, best0, tiles)
        alpha = adaptive_alpha(jnp.mean(jnp.sqrt(best), axis=1), m, area, params)
        ah = alpha * 0.5

        def w_step(carry, tile):
            sw, cw, swz, cwz, min_d2, hit_z = carry
            tx, ty, tz = tile
            d2 = _sq_dists(qcx, qcy, tx, ty)
            tiny = jnp.asarray(1e-30 if dtype == jnp.float32 else 1e-290, dtype)
            w = jnp.exp(-ah[:, None] * jnp.log(jnp.maximum(d2, tiny)))
            sw, cw = kahan_add(sw, cw, jnp.sum(w, axis=1))
            swz, cwz = kahan_add(swz, cwz, jnp.sum(w * tz[None, :], axis=1))
            tmin = jnp.min(d2, axis=1)
            thz = tz[jnp.argmin(d2, axis=1)]
            better = tmin < min_d2
            return (sw, cw, swz, cwz, jnp.where(better, tmin, min_d2), jnp.where(better, thz, hit_z)), None

        zeros = jnp.zeros((q_chunk,), dtype)
        carry0 = (zeros, zeros, zeros, zeros, jnp.full((q_chunk,), jnp.inf, dtype), zeros)
        (sw, _, swz, _, min_d2, hit_z), _ = jax.lax.scan(w_step, carry0, tiles)
        zhat = jnp.where(min_d2 <= params.exact_hit_eps, hit_z, swz / sw)
        return zhat, alpha

    zhat, alpha = jax.lax.map(per_q, (qxp.reshape(-1, q_chunk), qyp.reshape(-1, q_chunk)))
    return zhat.reshape(-1)[:n], alpha.reshape(-1)[:n]


def relative_rmse(approx, exact):
    """RMS of (approx-exact) normalised by RMS(exact) — the §Accuracy metric."""
    approx = jnp.asarray(approx, jnp.float64) if approx.dtype != jnp.float64 else approx
    e = jnp.asarray(exact, approx.dtype)
    return float(jnp.sqrt(jnp.mean((approx - e) ** 2)) / jnp.sqrt(jnp.mean(e**2)))


# Floating-point slack separating the far-field *model* bound (exact
# arithmetic) from a measured comparison of two finite-precision pipelines:
# both the approximated path and the Kahan oracle round, so a mathematically
# 0-error case (one point per far node — the aggregate IS the point; or a
# phase2="exact" plan, bound 0.0) still measures O(eps).  The slack scales
# with sqrt(m) because the compared path accumulates plain-dtype partial
# sums over m terms (random-rounding growth); at 64 ulps * sqrt(m) it stays
# orders of magnitude below any useful farfield_rtol (2.4e-3 at f32/m=100K)
# while covering the measured drift of the exact impls vs the Kahan oracle
# (the golden gate observes ~1e-4 relative at m=900).
FP_SLACK_ULPS = 64


def farfield_error_report(plan, qx, qy, *, q_chunk: int = 1024, d_chunk: int = 4096):
    """Measure a plan's Phase-2 approximation error against the Kahan oracle.

    The verification half of the quadtree contract (the other half is the
    plan-time model in ``engine.plan._choose_quadtree_radius``): runs
    ``execute(plan, qx, qy)``, recomputes the exact interpolant with the
    Kahan-compensated oracle (:func:`aidw_interpolate_kahan` — ~f64-quality
    accumulation at the data dtype), and reports the measured relative
    error on the same scale the bound is stated on, ``max|z_data|``.

    Returns a dict: ``max_rel_err`` / ``rms_rel_err`` / ``max_abs_err``
    (diffs in f64), ``scale``, ``phase2`` (which Phase-2 arm the plan runs
    — the report covers both: "exact" plans measure pure fp drift
    against bound 0.0, "quadtree" the multi-level dipole bound of
    DESIGN.md §8), ``bound``
    (the plan's ``farfield_bound``), ``fp_slack`` (see
    :data:`FP_SLACK_ULPS`), and ``within_bound`` — ``max_rel_err <= bound
    + fp_slack``, the predicate the error-budget tests
    (``tests/engine/test_quadtree.py``) enforce.
    """
    import numpy as np

    from repro.engine import execute  # lazy: core <-> engine

    if plan.impl != "grid":
        raise ValueError("farfield_error_report expects an impl='grid' plan "
                         f"(got impl={plan.impl!r})")
    dxp, dyp, dzp = plan.data
    dx, dy, dz = dxp[0, :plan.m], dyp[0, :plan.m], dzp[0, :plan.m]
    z_approx, _ = execute(plan, qx, qy)
    z_exact, _ = aidw_interpolate_kahan(
        dx, dy, dz, qx, qy, plan.params,
        area=plan.area, q_chunk=q_chunk, d_chunk=d_chunk,
    )
    za = np.asarray(z_approx, np.float64)
    ze = np.asarray(z_exact, np.float64)
    scale = max(float(np.max(np.abs(np.asarray(dz, np.float64)))), 1e-300)
    diff = np.abs(za - ze)
    bound = float(plan.farfield_bound)
    fp_slack = (FP_SLACK_ULPS * float(jnp.finfo(dx.dtype).eps)
                * max(1.0, float(np.sqrt(plan.m))))
    max_rel = float(diff.max() / scale) if diff.size else 0.0
    return {
        "max_rel_err": max_rel,
        "rms_rel_err": float(np.sqrt(np.mean(diff**2)) / scale) if diff.size else 0.0,
        "max_abs_err": float(diff.max()) if diff.size else 0.0,
        "scale": scale,
        "phase2": plan.phase2,
        "bound": bound,
        "fp_slack": fp_slack,
        "within_bound": max_rel <= bound + fp_slack,
        "n_queries": int(np.asarray(qx).shape[0]),
    }
