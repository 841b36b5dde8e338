"""Shared Pallas kernel-body helpers for the AIDW/IDW kernels.

Two in-kernel orientations (see DESIGN.md §2):

* ``data_axis=1`` (SoA family): queries vary along sublanes, data points along
  lanes — distance tile ``D`` is ``(bn, bm)``, per-query reductions run along
  axis 1.
* ``data_axis=0`` (AoaS family): the ``(bm, 4)`` aligned-struct tile puts data
  points on sublanes, so queries live on lanes — ``D`` is ``(bm, bn)`` and
  per-query reductions run along axis 0.

The tile helpers are pure jnp on values (not refs) so they lower identically
in Mosaic and in interpret mode, and can be unit-tested directly.  The SoA
weight sweep is the exception: :func:`sweep_step` folds a row of points into
lane-dense accumulator refs (DESIGN.md §2), and every SoA Phase-2 kernel
sweeps through it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.aidw import AIDWParams, adaptive_alpha
from repro.core.knn import first_true


def sq_dist_tile(qx, qy, dx, dy):
    """Squared-distance tile via VPU broadcast (DESIGN.md §2: beats the K=2
    MXU matmul form at 1.6% MXU utilisation; the weight sweep calls it per
    ``(strip, 128)`` chunk in :func:`sweep_step`)."""
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
    weight 0).  The ONE kernel-side definition — the quadtree's far-node sweep
    must weigh centroids exactly as the near/full sweeps weigh points, or
    the proved error budget silently breaks."""
    dtype = d2.dtype
    tiny = jnp.asarray(1e-30 if dtype == jnp.float32 else 1e-290, dtype)
    return jnp.exp(-alpha_half * jnp.log(jnp.maximum(d2, tiny)))


def weight_tile(d2, dz, alpha_half, data_axis: int):
    """One tile of the AoaS weighting pass: returns (sum_w, sum_wz, tile_min,
    tile_hit_z), all keepdims along ``data_axis``.  Five reductions a tile:
    the SoA kernels sweep with :func:`sweep_step` instead.

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


# The SoA weight sweep (DESIGN.md §2).  Its accumulators are lane-dense
# ``(block_q, L)`` VMEM refs, ``L = min(_SWEEP_LANES, block_d)``: lane ``l``
# of a query's row holds the points of sweep positions ``= l (mod L)``.  A
# step walks its query rows ``_SWEEP_STRIP`` at a time and its points ``L``
# at a time, so no cross-lane reduction runs per tile; :func:`sweep_finish`
# reduces each query block once.  A loop iteration folds whole strips until
# it holds about ``_SWEEP_ITER_PAIRS`` pairs: on a TPU v5e each iteration
# pays about 0.1 us of exposed latency (its first chunk's log/exp chain,
# the accumulators' loads and stores) beyond about 3 ns a 1,024 pairs, so
# short iterations waste the chip (PERF.md §6).
_SWEEP_LANES = 128
_SWEEP_STRIP = 32
_SWEEP_ITER_PAIRS = 256 * 1024


def sweep_scratch(block_q: int, block_d: int, dtype):
    """VMEM scratch of one sweep: ``(sum_w, sum_wz, min_d2, hit_z)`` in
    ``dtype`` and ``hit_chunk`` in int32, each ``(block_q, L)``."""
    shape = (block_q, min(_SWEEP_LANES, block_d))
    return [pltpu.VMEM(shape, dtype) for _ in range(4)] + [pltpu.VMEM(shape, jnp.int32)]


def sweep_init(acc):
    """Empty accumulators: zero sums, ``min_d2 = +inf``, no hit."""
    for ref, value in zip(acc, (0, 0, jnp.inf, 0, 0)):
        ref[...] = jnp.full(ref.shape, value, ref.dtype)


def sweep_step(qx_ref, qy_ref, ah_ref, x_ref, y_ref, z_ref, acc, step):
    """Fold one ``(1, bd)`` row of points into the lane-dense accumulators.

    ``qx_ref/qy_ref/ah_ref``: the block's ``(block_q, 1)`` query columns and
    half-powers; ``x_ref/y_ref/z_ref``: the points (sentinel coordinates
    weigh 0 and never hit); ``acc``: the :func:`sweep_scratch` refs;
    ``step``: the row's index in the sweep (every row of a sweep has the
    same width), so its ``c``-th ``L``-point chunk is chunk ``step * bd //
    L + c`` of the sweep order.

    Per pair: the distance, :func:`pow_weight`, ``sum_w += w``, ``sum_wz +=
    w * z``, and a strict ``d2 < min_d2`` that takes the point's z and chunk
    into its lane, so each lane keeps its first least-``d2`` point.  A
    strip's accumulators stay in registers across the row's chunks.
    """
    bq, lanes = acc[0].shape
    strip = math.gcd(_SWEEP_STRIP, bq)
    chunks = x_ref.shape[-1] // lanes
    first_chunk = step * chunks
    strips = bq // strip
    group = min(strips, max(1, _SWEEP_ITER_PAIRS // (strip * lanes * chunks)))
    while strips % group:
        group -= 1

    def fold(rows):
        qx, qy, ah = (jnp.broadcast_to(r[rows, :], (strip, lanes))
                      for r in (qx_ref, qy_ref, ah_ref))
        sw, swz, md, hz, hc = (r[rows, :] for r in acc)
        for c in range(chunks):
            cols = pl.ds(c * lanes, lanes)
            d2 = sq_dist_tile(qx, qy, x_ref[:, cols], y_ref[:, cols])
            w = pow_weight(d2, ah)
            z = z_ref[:, cols]
            sw = sw + w
            # product first: a CPU compiler that contracts the multiply-add
            # into an FMA then fuses this chunk's product whether or not the
            # zero init is folded into the first add, so a block sums the
            # same bits in a per-block and a whole-batch interpret call
            swz = w * z + swz
            better = d2 < md
            hz = jnp.where(better, z, hz)
            hc = jnp.where(better, first_chunk + c, hc)
            md = jnp.minimum(md, d2)
        for r, v in zip(acc, (sw, swz, md, hz, hc)):
            r[rows, :] = v

    def body(s, carry):
        for k in range(group):
            fold(pl.ds(pl.multiple_of((s * group + k) * strip, strip), strip))
        return carry

    jax.lax.fori_loop(0, strips // group, body, 0)


def sweep_finish(acc):
    """The block's one cross-lane reduction: ``(sum_w, sum_wz, min_d2,
    hit_z)``, each ``(block_q, 1)``.  ``hit_z`` is the z of the first point
    in sweep order (chunk ``* L +`` lane) whose ``d2`` is the least, the
    rule of a sequential sweep with a strict ``<``."""
    acc_w, acc_wz, min_d2, hit_z, hit_chunk = acc
    md = min_d2[...]
    least = jnp.min(md, axis=1, keepdims=True)
    pos = hit_chunk[...] * md.shape[1] + jax.lax.broadcasted_iota(jnp.int32, md.shape, 1)
    pos = jnp.where(md == least, pos, jnp.iinfo(jnp.int32).max)
    first = pos == jnp.min(pos, axis=1, keepdims=True)
    hz = jnp.sum(jnp.where(first, hit_z[...], 0), axis=1, keepdims=True)
    return (jnp.sum(acc_w[...], axis=1, keepdims=True),
            jnp.sum(acc_wz[...], axis=1, keepdims=True), least, hz)


def sweep_z(acc, eps):
    """Interpolated z of the block, ``(block_q, 1)``: the hit's z where the
    least ``d2 <= eps`` (an exact hit), else ``sum_wz / sum_w``."""
    sw, swz, md, hz = sweep_finish(acc)
    return jnp.where(md <= eps, hz, swz / sw)
