"""Naive AIDW Pallas kernels — the paper's no-shared-memory version, TPU-native.

The CUDA naive kernel has every thread stream all m data-point coordinates
from *global memory*.  The closest faithful TPU analogue: the whole data
array is mapped into VMEM as a single (untiled) block that is re-materialised
for every query-block grid step, and — like the paper's kernel — the
distances are computed twice (kNN pass and weight pass) with no reuse.

TPU-honest consequence (see EXPERIMENTS §Perf): without tiling, the working
set is O(m + block_q * m), so the naive kernel stops being schedulable once
3*4*m + 4*block_q*(k+m) bytes approach the ~16 MiB of VMEM — around m≈300K
for block_q=8.  On the GPU the naive kernel merely got slower; on TPU the
untiled formulation hits a hard capacity wall.  This is the strongest
argument for the paper's tiling strategy on this hardware.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.aidw import AIDWParams
from repro.core.knn import running_k_best
from repro.kernels._common import (
    alpha_from_best,
    sq_dist_tile,
    sweep_init,
    sweep_scratch,
    sweep_step,
    sweep_z,
    weight_tile,
)

_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel",))
# The SoA weight pass walks the row in windows of at most this many points
# (a divisor of the padded m), each one unrolled sweep step.
_SWEEP_WINDOW = 2048


def _naive_kernel_soa(qx_ref, qy_ref, dx_ref, dy_ref, dz_ref, out_ref, alpha_ref, ah, *acc,
                      m_real, area, params, window):
    qx, qy = qx_ref[...], qy_ref[...]
    # --- pass 1: distances + kNN (paper Fig. 3 lines 11-34) ---
    d2 = sq_dist_tile(qx, qy, dx_ref[...], dy_ref[...])  # (bn, m)
    k = params.k
    best0 = jnp.full((qx.shape[0], k), jnp.inf, d2.dtype)
    best = running_k_best(best0, d2, axis=1)
    alpha = alpha_from_best(best, m_real, area, params, data_axis=1)
    alpha_ref[...] = alpha
    ah[...] = alpha * 0.5
    # --- pass 2: distances AGAIN + weighting (paper lines 52-58), the
    # weight sweep step over the row ``window`` points at a time ---
    sweep_init(acc)

    def body(t, carry):
        cols = pl.ds(pl.multiple_of(t * window, window), window)
        sweep_step(qx_ref, qy_ref, ah, dx_ref.at[:, cols], dy_ref.at[:, cols],
                   dz_ref.at[:, cols], acc, t)
        return carry

    jax.lax.fori_loop(0, dx_ref.shape[1] // window, body, 0)
    out_ref[...] = sweep_z(acc, params.exact_hit_eps)


def _naive_kernel_aoas(qx_ref, qy_ref, d_ref, out_ref, alpha_ref, *, m_real, area, params):
    qx, qy = qx_ref[...], qy_ref[...]
    dxc, dyc, dzc = d_ref[:, 0:1], d_ref[:, 1:2], d_ref[:, 2:3]
    d2 = sq_dist_tile(qx, qy, dxc, dyc)  # (m, bn)
    k = params.k
    best0 = jnp.full((k, qx.shape[1]), jnp.inf, d2.dtype)
    best = running_k_best(best0, d2, axis=0)
    alpha = alpha_from_best(best, m_real, area, params, data_axis=0)
    alpha_ref[...] = alpha
    d2b = sq_dist_tile(qx, qy, dxc, dyc)
    sw, swz, tmin, thz = weight_tile(d2b, dzc, alpha * 0.5, data_axis=0)
    out_ref[...] = jnp.where(tmin <= params.exact_hit_eps, thz, swz / sw)


def aidw_naive_soa(
    dx, dy, dz, qx, qy, *, params: AIDWParams, area: float, m_real: int,
    block_q: int = 64, interpret: bool = False,
):
    """Inputs pre-padded: qx/qy (n,1), dx/dy/dz (1,m). Returns (z_hat, alpha), (n,1) each."""
    n, m = qx.shape[0], dx.shape[1]
    dtype = qx.dtype
    grid = (n // block_q,)
    window = math.gcd(m, _SWEEP_WINDOW)
    q_spec = pl.BlockSpec((block_q, 1), lambda i: (i, 0))
    d_spec = pl.BlockSpec((1, m), lambda i: (0, 0))  # full array, re-fetched per block
    o_spec = pl.BlockSpec((block_q, 1), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_naive_kernel_soa, m_real=m_real, area=area, params=params,
                          window=window),
        grid=grid,
        in_specs=[q_spec, q_spec, d_spec, d_spec, d_spec],
        out_specs=[o_spec, o_spec],
        out_shape=[jax.ShapeDtypeStruct((n, 1), dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((block_q, 1), dtype)] + sweep_scratch(block_q, window, dtype),
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="_naive_kernel_soa",
    )(qx, qy, dx, dy, dz)


def aidw_naive_aoas(
    data, qx, qy, *, params: AIDWParams, area: float, m_real: int,
    block_q: int = 64, interpret: bool = False,
):
    """Inputs pre-padded: data (m,4), qx/qy (1,n). Returns (z_hat, alpha), (1,n) each."""
    n, m = qx.shape[1], data.shape[0]
    dtype = qx.dtype
    grid = (n // block_q,)
    q_spec = pl.BlockSpec((1, block_q), lambda i: (0, i))
    d_spec = pl.BlockSpec((m, 4), lambda i: (0, 0))
    o_spec = pl.BlockSpec((1, block_q), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_naive_kernel_aoas, m_real=m_real, area=area, params=params),
        grid=grid,
        in_specs=[q_spec, q_spec, d_spec],
        out_specs=[o_spec, o_spec],
        out_shape=[jax.ShapeDtypeStruct((1, n), dtype)] * 2,
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="_naive_kernel_aoas",
    )(qx, qy, data)
