"""Shared Pallas kernel-body helpers for the AIDW/IDW kernels.

Two in-kernel orientations (see DESIGN.md §2):

* ``data_axis=1`` (SoA family): queries vary along sublanes, data points along
  lanes — distance tile ``D`` is ``(bn, bm)``, per-query reductions run along
  axis 1.
* ``data_axis=0`` (AoaS family): the ``(bm, 4)`` aligned-struct tile puts data
  points on sublanes, so queries live on lanes — ``D`` is ``(bm, bn)`` and
  per-query reductions run along axis 0.

All helpers are pure jnp on values (not refs) so they lower identically in
Mosaic and in interpret mode, and can be unit-tested directly.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.aidw import AIDWParams, adaptive_alpha
from repro.core.knn import first_true


def sq_dist_tile(qx, qy, dx, dy):
    """Squared-distance tile via VPU broadcast (see DESIGN.md: beats the
    K=2 MXU matmul form at 1.6% MXU utilisation)."""
    ddx = qx - dx
    ddy = qy - dy
    return ddx * ddx + ddy * ddy


def alpha_from_best(best, m_real: int, area: float, params: AIDWParams, data_axis: int):
    """r_obs -> R(S0) -> mu -> alpha (Eq. 2-6), per query column/row.

    Returns alpha with keepdims (``(bn, 1)`` or ``(1, bn)``).
    """
    r_obs = jnp.mean(jnp.sqrt(best), axis=data_axis, keepdims=True)
    return adaptive_alpha(r_obs, m_real, area, params)


def pow_weight(d2, alpha_half):
    """The AIDW weight ``(d^2)^(-alpha/2) = d^(-alpha)`` from a squared
    distance, with the dtype-dependent tiny clamp (exact hits are handled by
    the callers' min-d² guard; sentinel distances overflow to +inf and yield
    weight 0).  The ONE kernel-side definition — the far-field aggregate arm
    must weigh centroids exactly as the near/full sweeps weigh points, or
    the proved error budget silently breaks."""
    dtype = d2.dtype
    tiny = jnp.asarray(1e-30 if dtype == jnp.float32 else 1e-290, dtype)
    return jnp.exp(-alpha_half * jnp.log(jnp.maximum(d2, tiny)))


def weight_tile(d2, dz, alpha_half, data_axis: int):
    """One tile of the weighting pass: returns (sum_w, sum_wz, tile_min, tile_hit_z),
    all keepdims along ``data_axis``.

    ``dz`` must broadcast against ``d2`` ( (1, bm) or (bm, 1) ), ``alpha_half``
    is the per-query half-power ((bn,1)/(1,bn)).
    """
    ax = data_axis
    w = pow_weight(d2, alpha_half)
    sum_w = jnp.sum(w, axis=ax, keepdims=True)
    sum_wz = jnp.sum(w * dz, axis=ax, keepdims=True)
    tile_min = jnp.min(d2, axis=ax, keepdims=True)
    first = first_true(d2 == tile_min, ax)
    zeros = jnp.zeros_like(w)
    tile_hit_z = jnp.sum(jnp.where(first, dz + zeros, zeros), axis=ax, keepdims=True)
    return sum_w, sum_wz, tile_min, tile_hit_z
