"""Standard-IDW tiled Pallas kernel — the paper's §5.3.1 comparison baseline.

One distance sweep (constant alpha, no kNN pass): half the data traffic and
roughly half the FLOPs of AIDW.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._common import sweep_init, sweep_scratch, sweep_step, sweep_z

_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _idw_kernel(qx_ref, qy_ref, dx_ref, dy_ref, dz_ref, out_ref, ah, *acc, alpha_half, eps):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ah[...] = jnp.full(ah.shape, alpha_half, ah.dtype)
        sweep_init(acc)

    sweep_step(qx_ref, qy_ref, ah, dx_ref, dy_ref, dz_ref, acc, j)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        out_ref[...] = sweep_z(acc, eps)


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
        scratch_shapes=[pltpu.VMEM((block_q, 1), dtype)] + sweep_scratch(block_q, block_d, dtype),
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="_idw_kernel",
    )(qx, qy, dx, dy, dz)
