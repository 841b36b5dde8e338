"""Beyond-paper hillclimb #2 (EXPERIMENTS §Perf-AIDW): threshold-skip kNN.

Napkin math (v5e, k=10, bm=512): the baseline tiled kernel's vectorised
k-pass merge costs ~3k = 30 flop/pair — 58% of the kNN pass.  But once the
running k-best has seen t >> k*bm points, the probability a NEW TILE contains
any top-k candidate is ~bm*k/t; summed over tiles that is ~k*ln(m/(k*bm))
merging tiles out of m/bm — ~3% for m = 1M.  So: keep the k-best SORTED, test
the tile against the per-row threshold tau = kth-best (1 cmp/pair), and run
the merge under a ``pl.when(any-candidate)`` guard at query-block
granularity (branch-free per lane, one scalar branch per tile — exactly what
the TPU can do cheaply, unlike the CUDA per-thread early-out which diverges).

Expected kNN-pass cost: 7 + 1 + p_merge * 3k ~ 9 flop/pair vs 37 baseline.
The kernel also emits a per-block merge counter so interpret-mode runs can
MEASURE p_merge (``engine.execute_with_stats``'s ``merge_fraction``).
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
)
from repro.kernels.aidw_tiled import weight_sweep_soa

_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _knn_kernel_v2(qx_ref, qy_ref, dx_ref, dy_ref, alpha_ref, nmerge_ref, best, *, m_real, area, params):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best[...] = jnp.full(best.shape, jnp.inf, best.dtype)
        nmerge_ref[...] = jnp.zeros(nmerge_ref.shape, nmerge_ref.dtype)

    d2 = sq_dist_tile(qx_ref[...], qy_ref[...], dx_ref[...], dy_ref[...])  # (bn, bm)
    tau = best[:, -1:]  # kth best per row (best kept ascending by merge)
    has_candidate = jnp.any(d2 < tau)

    @pl.when(has_candidate)
    def _merge():
        best[...] = running_k_best(best[...], d2, axis=1)
        nmerge_ref[...] += 1

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        alpha_ref[...] = alpha_from_best(best[...], m_real, area, params, data_axis=1)


def aidw_knn_v2(
    dx, dy, qx, qy, *, params: AIDWParams, area: float, m_real: int,
    block_q: int = 256, block_d: int = 512, interpret: bool = False,
):
    """Threshold-skip kNN pass.  Inputs pre-padded like aidw_tiled_soa.
    Returns (alpha (n,1), merges_per_block (n_blocks, 1) int32)."""
    n, m = qx.shape[0], dx.shape[1]
    dtype = qx.dtype
    grid = (n // block_q, m // block_d)
    k = params.k
    q_spec = pl.BlockSpec((block_q, 1), lambda i, j: (i, 0))
    d_spec = pl.BlockSpec((1, block_d), lambda i, j: (0, j))
    o_spec = pl.BlockSpec((block_q, 1), lambda i, j: (i, 0))
    c_spec = pl.BlockSpec((1, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_knn_kernel_v2, m_real=m_real, area=area, params=params),
        grid=grid,
        in_specs=[q_spec, q_spec, d_spec, d_spec],
        out_specs=[o_spec, c_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), dtype),
            jax.ShapeDtypeStruct((n // block_q, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, k), dtype)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="_knn_kernel_v2",
    )(qx, qy, dx, dy)


def aidw_tiled_v2_soa(
    dx, dy, dz, qx, qy, *, params: AIDWParams, area: float, m_real: int,
    block_q: int = 256, block_d: int = 512, interpret: bool = False,
):
    """Full v2 AIDW: threshold-skip kNN pass + the baseline weight pass.
    Returns (z_hat (n,1), alpha (n,1), merges (n_blocks,1))."""
    with jax.named_scope("aidw.phase1"):
        alpha, merges = aidw_knn_v2(
            dx, dy, qx, qy, params=params, area=area, m_real=m_real,
            block_q=block_q, block_d=block_d, interpret=interpret,
        )
    with jax.named_scope("aidw.phase2"):
        zhat = weight_sweep_soa(qx, qy, alpha * 0.5, dx, dy, dz, eps=params.exact_hit_eps,
                                block_q=block_q, block_d=block_d, interpret=interpret)
    return zhat, alpha, merges
