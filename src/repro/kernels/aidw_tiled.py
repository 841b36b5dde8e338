"""Tiled AIDW Pallas kernels — the paper's shared-memory version, TPU-native.

The CUDA tiled kernel stages blockDim-sized tiles of data-point coordinates
through shared memory.  Here the data-point axis is the *inner grid
dimension* of a ``pallas_call``: Pallas pipelines each ``(1, bm)`` (SoA) or
``(bm, 4)`` (AoaS) tile HBM→VMEM (double-buffered), while the query block
stays pinned in VMEM across the inner loop — the explicit TPU analogue of
"coordinates in shared memory, reused by every thread in the block".

Two kernels, matching the paper's two distance sweeps:
  1. knn pass  → per-query adaptive alpha (Eq. 2-6), running k-best in VMEM
     scratch (the vectorised replacement for the per-thread insertion sort).
  2. weight pass → accumulates Σw, Σw·z in VMEM scratch; exact-hit guard via
     running (min d², z_at_min).  The SoA pass keeps them lane-dense and
     reduces across lanes once per query block (``_common.sweep_step``).
"""

from __future__ import annotations

import functools

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

_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


# ---------------------------------------------------------------- SoA family
def _knn_kernel_soa(qx_ref, qy_ref, dx_ref, dy_ref, alpha_ref, best, *, m_real, area, params, nbins=0):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best[...] = jnp.full(best.shape, jnp.inf, best.dtype)

    d2 = sq_dist_tile(qx_ref[...], qy_ref[...], dx_ref[...], dy_ref[...])  # (bn, bm)
    if nbins:
        # beyond-paper "binned" prefilter (§Perf-AIDW iteration 3): reduce the
        # tile to nbins contiguous bin-minima (1 op/pair) before the k-pass
        # merge — cuts merge cost ~bm/nbins-fold; mildly approximate (drops a
        # true neighbour only when two of a query's top-k land in the SAME
        # bin of the SAME tile; r_obs feeds a smooth map, error bounded in
        # tests/kernels).
        bm = d2.shape[1]
        sub = bm // nbins
        cands = jnp.concatenate(
            [jnp.min(d2[:, i * sub : (i + 1) * sub], axis=1, keepdims=True) for i in range(nbins)],
            axis=1,
        )
    else:
        cands = d2
    best[...] = running_k_best(best[...], cands, axis=1)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        alpha_ref[...] = alpha_from_best(best[...], m_real, area, params, data_axis=1)


def _weight_kernel_soa(qx_ref, qy_ref, ah_ref, dx_ref, dy_ref, dz_ref, out_ref, *acc, eps):
    """Exact weight sweep: step ``j`` folds data tile ``j`` into the block's
    lane-dense accumulators (:func:`sweep_step`), the last step reduces them
    once and applies the exact-hit guard."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        sweep_init(acc)

    sweep_step(qx_ref, qy_ref, ah_ref, dx_ref, dy_ref, dz_ref, acc, j)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        out_ref[...] = sweep_z(acc, eps)


def weight_sweep_soa(qx, qy, alpha_half, dx, dy, dz, *, eps: float, block_q: int,
                     block_d: int, interpret: bool):
    """Phase 2 over every point: qx/qy/alpha_half (n, 1), dx/dy/dz (1, m)
    sentinel-padded, ``n % block_q == m % block_d == 0``.  Returns z (n, 1)."""
    n, m = qx.shape[0], dx.shape[1]
    q_spec = pl.BlockSpec((block_q, 1), lambda i, j: (i, 0))
    d_spec = pl.BlockSpec((1, block_d), lambda i, j: (0, j))
    return pl.pallas_call(
        functools.partial(_weight_kernel_soa, eps=eps),
        grid=(n // block_q, m // block_d),
        in_specs=[q_spec, q_spec, q_spec, d_spec, d_spec, d_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1), qx.dtype),
        scratch_shapes=sweep_scratch(block_q, block_d, qx.dtype),
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="_weight_kernel_soa",
    )(qx, qy, alpha_half, dx, dy, dz)


def aidw_tiled_soa(
    dx, dy, dz, qx, qy, *, params: AIDWParams, area: float, m_real: int,
    block_q: int = 256, block_d: int = 512, interpret: bool = False, nbins: int = 0,
):
    """Run both tiled passes. Inputs pre-padded: qx/qy (n,1), dx/dy/dz (1,m),
    n % block_q == 0, m % block_d == 0. Returns (z_hat (n,1), alpha (n,1)).
    nbins > 0 enables the approximate binned-prefilter kNN pass."""
    n = qx.shape[0]
    m = dx.shape[1]
    dtype = qx.dtype
    grid = (n // block_q, m // block_d)
    k = params.k

    q_spec = pl.BlockSpec((block_q, 1), lambda i, j: (i, 0))
    d_spec = pl.BlockSpec((1, block_d), lambda i, j: (0, j))
    o_spec = pl.BlockSpec((block_q, 1), lambda i, j: (i, 0))

    with jax.named_scope("aidw.phase1"):
        alpha = pl.pallas_call(
            functools.partial(_knn_kernel_soa, m_real=m_real, area=area, params=params, nbins=nbins),
            grid=grid,
            in_specs=[q_spec, q_spec, d_spec, d_spec],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((n, 1), dtype),
            scratch_shapes=[pltpu.VMEM((block_q, k), dtype)],
            compiler_params=_SEMANTICS,
            interpret=interpret,
            name="_knn_kernel_soa",
        )(qx, qy, dx, dy)

    with jax.named_scope("aidw.phase2"):
        zhat = weight_sweep_soa(qx, qy, alpha * 0.5, dx, dy, dz, eps=params.exact_hit_eps,
                                block_q=block_q, block_d=block_d, interpret=interpret)
    return zhat, alpha


# -------------------------------------------------------------- AoaS family
def _knn_kernel_aoas(qx_ref, qy_ref, d_ref, alpha_ref, best, *, m_real, area, params):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best[...] = jnp.full(best.shape, jnp.inf, best.dtype)

    # (bm, 4) aligned structs: data points on sublanes -> D is (bm, bn)
    dxc = d_ref[:, 0:1]
    dyc = d_ref[:, 1:2]
    d2 = sq_dist_tile(qx_ref[...], qy_ref[...], dxc, dyc)  # (bm, bn)
    best[...] = running_k_best(best[...], d2, axis=0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        alpha_ref[...] = alpha_from_best(best[...], m_real, area, params, data_axis=0)


def _weight_kernel_aoas(qx_ref, qy_ref, ah_ref, d_ref, out_ref, acc_w, acc_wz, min_d2, hit_z, *, eps):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_w[...] = jnp.zeros(acc_w.shape, acc_w.dtype)
        acc_wz[...] = jnp.zeros(acc_wz.shape, acc_wz.dtype)
        min_d2[...] = jnp.full(min_d2.shape, jnp.inf, min_d2.dtype)
        hit_z[...] = jnp.zeros(hit_z.shape, hit_z.dtype)

    dxc = d_ref[:, 0:1]
    dyc = d_ref[:, 1:2]
    dzc = d_ref[:, 2:3]
    d2 = sq_dist_tile(qx_ref[...], qy_ref[...], dxc, dyc)  # (bm, bn)
    sw, swz, tmin, thz = weight_tile(d2, dzc, ah_ref[...], data_axis=0)
    acc_w[...] += sw
    acc_wz[...] += swz
    better = tmin < min_d2[...]
    hit_z[...] = jnp.where(better, thz, hit_z[...])
    min_d2[...] = jnp.where(better, tmin, min_d2[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        out_ref[...] = jnp.where(min_d2[...] <= eps, hit_z[...], acc_wz[...] / acc_w[...])


def aidw_tiled_aoas(
    data, qx, qy, *, params: AIDWParams, area: float, m_real: int,
    block_q: int = 256, block_d: int = 512, interpret: bool = False,
):
    """AoaS twin. Inputs pre-padded: data (m, 4) structs, qx/qy (1, n).
    Returns (z_hat (1, n), alpha (1, n))."""
    n = qx.shape[1]
    m = data.shape[0]
    dtype = qx.dtype
    grid = (n // block_q, m // block_d)
    k = params.k

    q_spec = pl.BlockSpec((1, block_q), lambda i, j: (0, i))
    d_spec = pl.BlockSpec((block_d, 4), lambda i, j: (j, 0))
    o_spec = pl.BlockSpec((1, block_q), lambda i, j: (0, i))

    with jax.named_scope("aidw.phase1"):
        alpha = pl.pallas_call(
            functools.partial(_knn_kernel_aoas, m_real=m_real, area=area, params=params),
            grid=grid,
            in_specs=[q_spec, q_spec, d_spec],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((1, n), dtype),
            scratch_shapes=[pltpu.VMEM((k, block_q), dtype)],
            compiler_params=_SEMANTICS,
            interpret=interpret,
            name="_knn_kernel_aoas",
        )(qx, qy, data)

    with jax.named_scope("aidw.phase2"):
        zhat = pl.pallas_call(
            functools.partial(_weight_kernel_aoas, eps=params.exact_hit_eps),
            grid=grid,
            in_specs=[q_spec, q_spec, q_spec, d_spec],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((1, n), dtype),
            scratch_shapes=[pltpu.VMEM((1, block_q), dtype) for _ in range(4)],
            compiler_params=_SEMANTICS,
            interpret=interpret,
            name="_weight_kernel_aoas",
        )(qx, qy, alpha * 0.5, data)
    return zhat, alpha
