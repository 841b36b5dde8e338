"""Standard-IDW tiled Pallas kernel — the paper's §5.3.1 comparison baseline.

One distance sweep (constant alpha, no kNN pass): half the data traffic and
roughly half the FLOPs of AIDW, quantified in benchmarks/fig_speedups.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._common import sq_dist_tile, weight_tile

_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _idw_kernel(qx_ref, qy_ref, dx_ref, dy_ref, dz_ref, out_ref, acc_w, acc_wz, min_d2, hit_z, *, alpha_half, eps):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_w[...] = jnp.zeros(acc_w.shape, acc_w.dtype)
        acc_wz[...] = jnp.zeros(acc_wz.shape, acc_wz.dtype)
        min_d2[...] = jnp.full(min_d2.shape, jnp.inf, min_d2.dtype)
        hit_z[...] = jnp.zeros(hit_z.shape, hit_z.dtype)

    d2 = sq_dist_tile(qx_ref[...], qy_ref[...], dx_ref[...], dy_ref[...])
    ah = jnp.asarray(alpha_half, d2.dtype)
    sw, swz, tmin, thz = weight_tile(d2, dz_ref[...], ah, data_axis=1)
    acc_w[...] += sw
    acc_wz[...] += swz
    better = tmin < min_d2[...]
    hit_z[...] = jnp.where(better, thz, hit_z[...])
    min_d2[...] = jnp.where(better, tmin, min_d2[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        out_ref[...] = jnp.where(min_d2[...] <= eps, hit_z[...], acc_wz[...] / acc_w[...])


def idw_tiled_soa(
    dx, dy, dz, qx, qy, *, alpha: float = 2.0, exact_hit_eps: float = 1e-18,
    block_q: int = 256, block_d: int = 512, interpret: bool = False,
):
    """Inputs pre-padded: qx/qy (n,1), dx/dy/dz (1,m). Returns z_hat (n,1)."""
    n, m = qx.shape[0], dx.shape[1]
    dtype = qx.dtype
    grid = (n // block_q, m // block_d)
    q_spec = pl.BlockSpec((block_q, 1), lambda i, j: (i, 0))
    d_spec = pl.BlockSpec((1, block_d), lambda i, j: (0, j))
    o_spec = pl.BlockSpec((block_q, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_idw_kernel, alpha_half=alpha * 0.5, eps=exact_hit_eps),
        grid=grid,
        in_specs=[q_spec, q_spec, d_spec, d_spec, d_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1), dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 1), dtype) for _ in range(4)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="_idw_kernel",
    )(qx, qy, dx, dy, dz)
