"""Fused AIDW kernel — beyond-paper optimisation (EXPERIMENTS §Perf).

The paper launches two kernels (kNN pass, weight pass) and streams the data
points from HBM twice *and* re-reads the query block twice.  Here both phases
live in ONE ``pallas_call`` with grid ``(nq_blocks, 2, m_tiles)``: the middle
"phase" axis walks the data tiles twice while

  * the query block is fetched once per (i) and pinned in VMEM,
  * the per-query alpha produced by phase 0 is handed to phase 1 through VMEM
    scratch — it never round-trips to HBM,
  * one kernel launch instead of two (and no intermediate (n,1) alpha array
    written+read from HBM).

HBM traffic saved vs. tiled: n*4 B (alpha write) + n*4 B (alpha read)
+ one extra query sweep; data-point traffic is identical (2 sweeps — the
algorithm fundamentally needs alpha before weighting).
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
)

_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _fused_kernel(
    qx_ref, qy_ref, dx_ref, dy_ref, dz_ref, out_ref, alpha_ref,
    best, ah, *acc, m_real, area, params,
):
    phase = pl.program_id(1)
    j = pl.program_id(2)
    last_j = pl.num_programs(2) - 1

    @pl.when(phase == 0)
    def _knn_phase():
        @pl.when(j == 0)
        def _init():
            best[...] = jnp.full(best.shape, jnp.inf, best.dtype)

        d2 = sq_dist_tile(qx_ref[...], qy_ref[...], dx_ref[...], dy_ref[...])  # (bn, bm)
        best[...] = running_k_best(best[...], d2, axis=1)

        @pl.when(j == last_j)
        def _finish():
            alpha = alpha_from_best(best[...], m_real, area, params, data_axis=1)
            alpha_ref[...] = alpha
            ah[...] = alpha * 0.5

    @pl.when(phase == 1)
    def _weight_phase():
        @pl.when(j == 0)
        def _init():
            sweep_init(acc)

        sweep_step(qx_ref, qy_ref, ah, dx_ref, dy_ref, dz_ref, acc, j)

        @pl.when(j == last_j)
        def _finish():
            out_ref[...] = sweep_z(acc, params.exact_hit_eps)


def aidw_fused_soa(
    dx, dy, dz, qx, qy, *, params: AIDWParams, area: float, m_real: int,
    block_q: int = 256, block_d: int = 512, interpret: bool = False,
):
    """Inputs pre-padded: qx/qy (n,1), dx/dy/dz (1,m). Returns (z_hat, alpha), (n,1) each."""
    n, m = qx.shape[0], dx.shape[1]
    dtype = qx.dtype
    grid = (n // block_q, 2, m // block_d)
    k = params.k
    q_spec = pl.BlockSpec((block_q, 1), lambda i, p, j: (i, 0))
    d_spec = pl.BlockSpec((1, block_d), lambda i, p, j: (0, j))
    o_spec = pl.BlockSpec((block_q, 1), lambda i, p, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_fused_kernel, m_real=m_real, area=area, params=params),
        grid=grid,
        in_specs=[q_spec, q_spec, d_spec, d_spec, d_spec],
        out_specs=[o_spec, o_spec],
        out_shape=[jax.ShapeDtypeStruct((n, 1), dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((block_q, k), dtype), pltpu.VMEM((block_q, 1), dtype)]
        + sweep_scratch(block_q, block_d, dtype),
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="_fused_kernel",
    )(qx, qy, dx, dy, dz)
