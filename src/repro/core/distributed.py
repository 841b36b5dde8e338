"""Multi-device / multi-pod AIDW — beyond the paper's single GPU.

Sharding scheme (DESIGN.md §2, last row):

* **Query points** are embarrassingly parallel (the paper's own observation)
  → sharded over every mesh axis, no communication.
* **Data points** at production scale (10^8+) no longer fit one chip →
  sharded too.  The kNN phase and the Σw/Σw·z phase are both *associative*
  reductions over data shards, so a **ring** of ``lax.ppermute`` steps rotates
  the data shards around the mesh axis while each query shard folds the
  visiting shard into its running state (k-best merge / weight partials).

Communication/compute overlap: the next shard's ppermute is issued *before*
the local fold, so XLA's async collective-permute runs concurrently with the
distance computation — the TPU analogue of CUDA stream overlap, and the same
schedule ring-attention uses.

Exactness: k-best merge and compensated sums are associative up to fp
rounding — results match the single-device kernels to tolerance (tested with
8 simulated devices in ``tests/distributed``).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.aidw import AIDWParams, adaptive_alpha, _sq_dists
from repro.core.knn import running_k_best


def _pvary(x, axes):
    """Mark a fresh (unvarying) carry as device-varying for shard_map's vma
    typing, so it can join the ring's per-device state."""
    return jax.lax.pcast(x, axes, to="varying")


def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def _fold_knn(best, qx_l, qy_l, cx, cy, q_chunk, d_chunk):
    """Merge the visiting data shard into the running per-query k-best,
    bounded-memory: queries mapped in q_chunk rows, data scanned in d_chunk
    columns -> peak temp (q_chunk, d_chunk)."""
    k = best.shape[1]
    dxt = (cx.reshape(-1, d_chunk), cy.reshape(-1, d_chunk))

    def per_q(args):
        qcx, qcy, b0 = args

        def step(b, tile):
            tx, ty = tile
            return running_k_best(b, _sq_dists(qcx, qcy, tx, ty)), None

        b, _ = jax.lax.scan(step, b0, dxt)
        return b

    out = jax.lax.map(
        per_q,
        (qx_l.reshape(-1, q_chunk), qy_l.reshape(-1, q_chunk), best.reshape(-1, q_chunk, k)),
    )
    return out.reshape(-1, k)


def _fold_weights(carry, ah, qx_l, qy_l, cx, cy, cz, q_chunk, d_chunk):
    """Accumulate this shard's weight partials (sum_w, sum_wz, min_d2, hit_z)."""
    sw, swz, min_d2, hit_z = carry
    dtype = qx_l.dtype
    tiles = (cx.reshape(-1, d_chunk), cy.reshape(-1, d_chunk), cz.reshape(-1, d_chunk))

    def per_q(args):
        qcx, qcy, ahc, swc, swzc, mdc, hzc = args

        def step(c, tile):
            s, z, md, hz = c
            tx, ty, tz = tile
            d2 = _sq_dists(qcx, qcy, tx, ty)
            tiny = jnp.asarray(1e-30 if dtype == jnp.float32 else 1e-290, dtype)
            w = jnp.exp(-ahc[:, None] * jnp.log(jnp.maximum(d2, tiny)))
            tmin = jnp.min(d2, axis=1)
            thz = tz[jnp.argmin(d2, axis=1)]
            better = tmin < md
            return (
                s + jnp.sum(w, axis=1),
                z + jnp.sum(w * tz[None, :], axis=1),
                jnp.where(better, tmin, md),
                jnp.where(better, thz, hz),
            ), None

        c, _ = jax.lax.scan(step, (swc, swzc, mdc, hzc), tiles)
        return c

    r = lambda a: a.reshape(-1, q_chunk)
    out = jax.lax.map(per_q, (r(qx_l), r(qy_l), r(ah), r(sw), r(swz), r(min_d2), r(hit_z)))
    return tuple(a.reshape(-1) for a in out)


def ring_aidw(
    mesh: Mesh,
    dx, dy, dz, qx, qy,
    *,
    params: AIDWParams,
    area: float,
    axis_names: Sequence[str] | str | None = None,
    q_chunk: int = 1024,
    d_chunk: int = 2048,
):
    """Fully-sharded AIDW over ``mesh``.

    Queries AND data are sharded over the flattened ``axis_names`` (default:
    all mesh axes).  Global sizes must divide the total device count (the
    launcher pads).  Per-device temp memory is bounded by the
    (q_chunk, d_chunk) distance tile regardless of shard sizes.
    Returns ``(z_hat, alpha)`` sharded like the queries.
    """
    if axis_names is None:
        axis_names = tuple(mesh.axis_names)
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    axes = tuple(axis_names)
    nshards = 1
    for a in axes:
        nshards *= mesh.shape[a]
    m_total = dx.shape[0]
    k = params.k
    spec = P(axes)
    qc = min(q_chunk, qx.shape[0] // nshards)
    dc = min(d_chunk, dx.shape[0] // nshards)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec),
        out_specs=(spec, spec),
    )
    def body(dx_l, dy_l, dz_l, qx_l, qy_l):
        nq_l = qx_l.shape[0]
        dtype = qx_l.dtype
        perm = _ring_perm(nshards)

        # ---- phase 1: ring kNN ----
        def knn_step(i, carry):
            best, cx, cy = carry
            # issue the rotation first so the collective-permute overlaps the fold
            nx = jax.lax.ppermute(cx, axes, perm)
            ny = jax.lax.ppermute(cy, axes, perm)
            best = _fold_knn(best, qx_l, qy_l, cx, cy, qc, dc)
            return best, nx, ny

        best0 = _pvary(jnp.full((nq_l, k), jnp.inf, dtype), axes)
        best, _, _ = jax.lax.fori_loop(0, nshards, knn_step, (best0, dx_l, dy_l))
        alpha = adaptive_alpha(jnp.mean(jnp.sqrt(best), axis=1), m_total, area, params)
        ah = alpha * 0.5

        # ---- phase 2: ring weighting ----
        def w_step(i, carry):
            acc, cx, cy, cz = carry
            nx = jax.lax.ppermute(cx, axes, perm)
            ny = jax.lax.ppermute(cy, axes, perm)
            nz = jax.lax.ppermute(cz, axes, perm)
            acc = _fold_weights(acc, ah, qx_l, qy_l, cx, cy, cz, qc, dc)
            return acc, nx, ny, nz

        zeros = _pvary(jnp.zeros((nq_l,), dtype), axes)
        inf0 = _pvary(jnp.full((nq_l,), jnp.inf, dtype), axes)
        acc0 = (zeros, zeros, inf0, zeros)
        (sw, swz, min_d2, hit_z), _, _, _ = jax.lax.fori_loop(
            0, nshards, w_step, (acc0, dx_l, dy_l, dz_l)
        )
        zhat = jnp.where(min_d2 <= params.exact_hit_eps, hit_z, swz / sw)
        return zhat, alpha

    return body(dx, dy, dz, qx, qy)


def ring_aidw_rotate_queries(
    mesh: Mesh,
    dx, dy, dz, qx, qy,
    *,
    params: AIDWParams,
    area: float,
    axis_names: Sequence[str] | str | None = None,
    q_chunk: int = 1024,
    d_chunk: int = 2048,
):
    """§Perf-AIDW hillclimb: rotate the QUERIES (with their running state)
    around the ring instead of the data shards.

    Ring payload per step: phase 1 moves (qx, qy, k-best) = (2+k)*4 B/query;
    phase 2 moves (qx, qy, alpha, sum_w, sum_wz, min_d2, hit_z) = 7*4 B/query.
    The data-rotating baseline moves 8 B/point (phase 1) + 12 B/point
    (phase 2).  For the production workload (n = 2^24 queries, m = 2^27
    points) that is a ~4.6x collective-byte reduction — data points never
    leave their shard.  Exactness is unchanged (same folds, different hand).
    Results return in the ORIGINAL query sharding (the ring walks each query
    slab through every shard and back home: nshards rotations = identity).
    """
    if axis_names is None:
        axis_names = tuple(mesh.axis_names)
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    axes = tuple(axis_names)
    nshards = 1
    for a in axes:
        nshards *= mesh.shape[a]
    m_total = dx.shape[0]
    k = params.k
    spec = P(axes)
    qc = min(q_chunk, qx.shape[0] // nshards)
    dc = min(d_chunk, dx.shape[0] // nshards)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec),
        out_specs=(spec, spec),
    )
    def body(dx_l, dy_l, dz_l, qx_l, qy_l):
        nq_l = qx_l.shape[0]
        dtype = qx_l.dtype
        perm = _ring_perm(nshards)

        # ---- phase 1: queries + k-best circulate ----
        def knn_step(i, carry):
            cqx, cqy, best = carry
            nqx = jax.lax.ppermute(cqx, axes, perm)
            nqy = jax.lax.ppermute(cqy, axes, perm)
            best = _fold_knn(best, cqx, cqy, dx_l, dy_l, qc, dc)
            nbest = jax.lax.ppermute(best, axes, perm)
            return nqx, nqy, nbest

        best0 = _pvary(jnp.full((nq_l, k), jnp.inf, dtype), axes)
        qx_r, qy_r, best = jax.lax.fori_loop(0, nshards, knn_step, (qx_l, qy_l, best0))
        # after nshards rotations every slab is home again
        alpha = adaptive_alpha(jnp.mean(jnp.sqrt(best), axis=1), m_total, area, params)
        ah = alpha * 0.5

        # ---- phase 2: queries + weight partials circulate ----
        def w_step(i, carry):
            cqx, cqy, cah, acc = carry
            nqx = jax.lax.ppermute(cqx, axes, perm)
            nqy = jax.lax.ppermute(cqy, axes, perm)
            nah = jax.lax.ppermute(cah, axes, perm)
            acc = _fold_weights(acc, cah, cqx, cqy, dx_l, dy_l, dz_l, qc, dc)
            nacc = jax.tree.map(lambda a: jax.lax.ppermute(a, axes, perm), acc)
            return nqx, nqy, nah, nacc

        zeros = _pvary(jnp.zeros((nq_l,), dtype), axes)
        inf0 = _pvary(jnp.full((nq_l,), jnp.inf, dtype), axes)
        acc0 = (zeros, zeros, inf0, zeros)
        _, _, _, (sw, swz, min_d2, hit_z) = jax.lax.fori_loop(
            0, nshards, w_step, (qx_r, qy_r, ah, acc0)
        )
        zhat = jnp.where(min_d2 <= params.exact_hit_eps, hit_z, swz / sw)
        return zhat, alpha

    return body(dx, dy, dz, qx, qy)


def sharded_queries_aidw(
    mesh: Mesh, dx, dy, dz, qx, qy, *, params: AIDWParams, area: float,
    q_chunk: int = 1024, d_chunk: int = 8192,
):
    """Simpler production mode when the data set fits per-chip: data points
    replicated, queries sharded over all axes — zero communication (the
    paper's "naturally parallel" observation, lifted to a pod).  The local
    solve goes through the plan/execute engine (a chunked-brute plan builds
    traceably, so each shard plans *inside* ``shard_map``), which keeps the
    padding/sentinel/chunking logic identical to the single-host path."""
    from repro.engine import build_plan, execute

    axes = tuple(mesh.axis_names)
    qspec = P(axes)
    nshards = 1
    for a in axes:
        nshards *= mesh.shape[a]
    qc = min(q_chunk, qx.shape[0] // nshards)
    dc = min(d_chunk, dx.shape[0])

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(), qspec, qspec),
        out_specs=(qspec, qspec),
        check_vma=False,  # collective-free body; the tiled interpolator's
        # scan carries are created unvarying and trip the vma typing
    )
    def body(dx_r, dy_r, dz_r, qx_l, qy_l):
        plan = build_plan(
            dx_r, dy_r, dz_r, params=params, area=area, impl="chunked",
            q_chunk=qc, d_chunk=dc,
        )
        return execute(plan, qx_l, qy_l)

    return body(dx, dy, dz, qx, qy)
