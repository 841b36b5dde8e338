"""Execute — the traced half of the plan/execute engine.

``execute(plan, qx, qy)`` is a pure function of its arguments for every
impl.  All shapes inside are fixed by (plan statics, query count), so the
jitted entry points compile once per (plan configuration, query shape) and
hit the cache on every further batch — the "build once, execute per
request" serving shape.

The grid path (DESIGN.md §6) runs entirely under the trace: Morton sort,
seam-split block layout, per-query safe radii from the plan's
``required_radius`` table (closed form — no while-loop), the
Phase 1 reading each block's CSR row runs in place (or, for
``pipeline="dense"``, over candidate rows gathered to the static capacity)
and the full-data Phase 2 — or, for ``build_plan(phase2="quadtree")``
plans, an exact near field plus the multi-level Barnes–Hut far field
whose per-node opening criterion and dipole correction give a plan-proved,
second-order error bound (DESIGN.md §8).  Exactness is unconditional and
now *per block*: the kernel result is kept wherever a block's candidates
fit the plan's capacity, and queries in overflowing blocks (far out-of-bbox
queries, query distributions unlike the data) get their alpha from the
exact expanding-ring search run *only for them* (masked) — the worst case
is O(overflowed queries), never the whole batch.
"""

from __future__ import annotations

import threading
import warnings
import weakref

import jax
import jax.numpy as jnp

from repro.core.aidw import _interpolate_pass2, adaptive_alpha, brute_r_obs
from repro.core.grid import (
    cell_of,
    grid_r_obs,
    morton_ids,
    safe_radius_from_need,
    seam_layout,
    seam_segment_ids,
)
from repro.core.layouts import coord_sentinel, pad_tail, pad_to
from repro.engine.plan import InterpolationPlan
from repro.errors import CapacityOverflowWarning
from repro.kernels.aidw_fused import aidw_fused_soa
from repro.kernels.aidw_grid import (
    block_rectangles,
    gather_candidates_csr,
    phase1_alpha_from_candidates,
    phase1_alpha_row_runs,
    phase2_far_nodes,
    phase2_near_row_runs,
    phase2_near_weights,
    phase2_weights_full,
    rectangle_need,
    row_run_max_tiles,
    row_run_tiles,
)
from repro.kernels.aidw_naive import aidw_naive_aoas, aidw_naive_soa
from repro.kernels.aidw_tiled import aidw_tiled_aoas, aidw_tiled_soa
from repro.kernels.aidw_tiled_v2 import aidw_tiled_v2_soa
from repro.kernels.idw_tiled import idw_tiled_soa


def _seam_split_layout(plan: InterpolationPlan, qx_s, qy_s, cx_s, cy_s):
    """Regroup the Morton-sorted batch so no block straddles a Morton seam.

    The plan's ``seam_level`` is capped per batch so the worst-case block
    padding (one block per occupied quadrant) stays small relative to the
    batch; everything is static given the query shape.  Returns the blocked
    view ``(qx_v, qy_v, cx_v, cy_v)`` plus ``src`` (slot -> sorted index:
    maps per-query arrays like the blended alpha INTO the view) and ``dest``
    (sorted index -> slot: maps per-slot results back); both ``None`` when
    splitting is off — the view IS the sorted layout.  The exact Phase 2
    never sees the split layout (alpha is gathered back through ``dest``,
    so its full-data sweep cost is untouched); the quadtree Phase 2 runs
    in the view, whose per-block rectangles it shares with Phase 1.
    """
    n_tot = qx_s.shape[0]
    level = plan.seam_level
    while level > 0 and (4 ** level) * plan.block_q > n_tot:
        level -= 1
    if level == 0:
        return qx_s, qy_s, cx_s, cy_s, None, None
    seg = seam_segment_ids(plan.grid, cx_s, cy_s, level)
    n_slots = n_tot + (4 ** level) * plan.block_q
    src, dest = seam_layout(seg, 4 ** level, plan.block_q, n_slots)
    return qx_s[src], qy_s[src], cx_s[src], cy_s[src], src, dest


def _tile_table(need, capacity: int, block_d: int, pipeline: str):
    """Per-block real-tile counts for the scalar-prefetch pipelines — the ONE
    place the "dense walk is bit-identical because skipped tiles are
    all-sentinel" invariant is encoded.  "prefetch" clamps each block to the
    tiles its (capacity-covered) candidates occupy; "dense" walks every
    static tile."""
    if pipeline == "prefetch":
        covered = jnp.minimum(need, capacity)
        return (covered + block_d - 1) // block_d
    return jnp.full(need.shape, capacity // block_d, jnp.int32)


def _quadtree_walk(plan: InterpolationPlan, hxlo, hxhi, hylo, hyhi):
    """Barnes–Hut walk over the plan's quadtree, one mask per level.

    Per query block (home rectangle ``hxlo..hxhi x hylo..hyhi``, inclusive
    cell coords) and per level, every node gets the OPENING criterion: a
    node is CLOSED — swept as one aggregate+dipole term — iff its
    Chebyshev cell gap from the home rectangle clears ``radius + 1`` (its
    cells are all outside the near rectangle, and the ring invariant gives
    every point distance ``>= (gap-1) * cell_min``) and its stored
    dispersion fits the plan's opening ratio, ``e <= tau * (gap-1) *
    cell_min`` — so each term's own tau never exceeds ``plan.qt_tau`` and
    the plan's dipole bound covers it.  A processed node failing the
    criterion is OPENED: its four children are processed at the next finer
    level.  Level-0 cells cannot be opened further and are force-closed on
    the gap test alone (``tau_eff`` was chosen at plan time to cover them).
    Empty nodes are neither opened nor emitted.  Induction over levels
    gives the partition the error budget needs: every far cell is counted
    by EXACTLY one closed node, every near cell by none.

    The walk is plain masked arithmetic over all ``(block, node)`` pairs —
    cheap bools, no weights.  Returns per level ``(closed (nb, n_nodes),
    n_closed, n_opened, n_processed)``; the far sweep weighs each block's
    closed nodes of the level and masks the rest to weight 0.
    """
    grid = plan.grid
    dtype = grid.pt_x.dtype
    radius = plan.farfield_radius
    tau = plan.qt_tau
    cell_min = jnp.minimum(grid.cell_size[0], grid.cell_size[1]).astype(dtype)
    nb = hxlo.shape[0]
    n_lv = len(plan.qt_levels)
    out = [None] * n_lv
    opened_up = None
    parent_nx = 0
    for lv in range(n_lv - 1, -1, -1):
        nx, ny, step, _n_pad, _tile = plan.qt_levels[lv]
        n_nodes = nx * ny
        jx = jnp.arange(nx, dtype=jnp.int32)
        jy = jnp.arange(ny, dtype=jnp.int32)
        nxlo = jx * step
        nxhi = jnp.minimum((jx + 1) * step, grid.gx) - 1
        nylo = jy * step
        nyhi = jnp.minimum((jy + 1) * step, grid.gy) - 1
        gapx = jnp.maximum(jnp.maximum(nxlo[None, :] - hxhi[:, None],
                                       hxlo[:, None] - nxhi[None, :]), 0)
        gapy = jnp.maximum(jnp.maximum(nylo[None, :] - hyhi[:, None],
                                       hylo[:, None] - nyhi[None, :]), 0)
        gap = jnp.maximum(gapy[:, :, None], gapx[:, None, :]).reshape(nb, n_nodes)
        cnt = plan.far[lv][2][:n_nodes]
        e = plan.far[lv][6][:n_nodes]
        if lv == n_lv - 1:
            proc = jnp.ones((nb, n_nodes), bool)
        else:
            pids = ((jy[:, None] // 2) * parent_nx + (jx[None, :] // 2)).reshape(-1)
            proc = opened_up[:, pids]
        parent_nx = nx
        nonempty = (cnt > 0)[None, :]
        far_enough = gap >= radius + 1
        if lv == 0:
            closed = proc & far_enough & nonempty
            n_opened = jnp.zeros((nb,), jnp.int32)
        else:
            tight = e[None, :] <= tau * (gap - 1).astype(dtype) * cell_min
            closed = proc & far_enough & tight & nonempty
            opened = proc & nonempty & ~(far_enough & tight)
            opened_up = opened
            n_opened = jnp.sum(opened.astype(jnp.int32), axis=1)
        n_proc = jnp.sum((proc & nonempty).astype(jnp.int32), axis=1)
        n_closed = jnp.sum(closed.astype(jnp.int32), axis=1)
        out[lv] = (closed, n_closed, n_opened, n_proc)
    return out


def _phase2_quadtree(plan: InterpolationPlan, qx_v, qy_v, alpha_v,
                     cx_v=None, cy_v=None, real_b=None):
    """Quadtree far-field Phase 2 over a blocked query view (DESIGN.md §8).

    The near field takes exact per-point weights over the home rectangle
    expanded by ``plan.farfield_radius``.  On the default ``pipeline=
    "prefetch"`` it reads the rectangle's CSR row runs in place
    (:func:`row_run_tiles` sized from ``p2_capacity``, walked by
    :func:`phase2_near_row_runs` in ``p2_block_d``-point tiles); the
    ``"dense"`` pipeline keeps the gathered near field (CSR gather at
    ``p2_capacity``, tile-table skip), its oracle.  The far field runs
    :func:`_quadtree_walk` and then one :func:`phase2_far_nodes` sweep per
    level over the level's nodes, each block's non-closed ones masked to
    the sentinel node, accumulating into the same
    ``(sum_w, sum_wz)`` the near sweep produced.  ``real_b (nb,)``, where
    given, flags the blocks holding a real query: the others (seam pad
    blocks) walk nothing.  Returns ``(z, need, overflow, closed_counts,
    opened_tot, proc_tot)`` — ``overflow (nb,)`` flags
    blocks whose near field was truncated (their queries must take the
    exact sweep; the bound assumes completeness), ``closed_counts`` the
    per-level ``(nb,)`` closed node counts for the stats dict.
    """
    grid = plan.grid
    if cx_v is None or cy_v is None:
        cx_v, cy_v = cell_of(grid, qx_v, qy_v)
    r_zero = jnp.zeros(cx_v.shape, jnp.int32)
    hxlo, hxhi, hylo, hyhi = block_rectangles(grid, cx_v, cy_v, r_zero,
                                              plan.block_q)
    r_near = jnp.full(cx_v.shape, plan.farfield_radius, jnp.int32)
    xlo, xhi, ylo, yhi = block_rectangles(grid, cx_v, cy_v, r_near, plan.block_q)
    ah = alpha_v * 0.5
    live = jnp.ones(xlo.shape, bool) if real_b is None else real_b
    with jax.named_scope("aidw.phase2.near"):
        need = rectangle_need(grid, xlo, xhi, ylo, yhi)
        overflow = need > plan.p2_capacity
        if plan.pipeline == "prefetch":
            tiles, run_tiles = row_run_tiles(
                grid, xlo, xhi, ylo, yhi, plan.p2_block_d,
                row_run_max_tiles(plan.p2_capacity, plan.p2_block_d, grid.gy))
            run_tiles = jnp.where(overflow | ~live, 0, run_tiles)
            sw, swz, md_n, hz_n = phase2_near_row_runs(
                qx_v, qy_v, ah, tiles, run_tiles,
                jnp.stack([xlo, xhi, ylo, yhi], axis=1),
                (grid.pt_x, grid.pt_y, grid.pt_z, plan.row_cells),
                tile=plan.p2_block_d, m_real=plan.m, block_q=plan.block_q,
                interpret=plan.interpret,
            )
        else:
            cand_x, cand_y, cand_z, _ = gather_candidates_csr(
                grid, xlo, xhi, ylo, yhi, plan.p2_capacity, with_z=True
            )
            num_tiles = _tile_table(need, plan.p2_capacity, plan.p2_block_d,
                                    plan.pipeline)
            sw, swz, md_n, hz_n = phase2_near_weights(
                qx_v, qy_v, ah, cand_x, cand_y, cand_z, num_tiles,
                block_q=plan.block_q, block_d=plan.p2_block_d,
                interpret=plan.interpret,
            )
    closed_counts = []
    opened_tot = jnp.zeros(need.shape, jnp.int32)
    proc_tot = jnp.zeros(need.shape, jnp.int32)
    with jax.named_scope("aidw.phase2.quadtree_walk"):
        levels = _quadtree_walk(plan, hxlo, hxhi, hylo, hyhi)
    big = coord_sentinel(qx_v.dtype)
    zero = jnp.zeros((), qx_v.dtype)
    with jax.named_scope("aidw.phase2.far_nodes"):
        for lv, (closed, n_closed, n_opened, n_proc) in enumerate(levels):
            _nx, _ny, _step, n_pad, tile = plan.qt_levels[lv]
            keep = jnp.pad(closed & live[:, None], ((0, 0), (0, n_pad - closed.shape[1])))
            # every block sweeps the whole level: a node it does not close
            # is the sentinel node (d2 -> inf, w -> 0, zero count, z-sum
            # and moment), so it adds exactly 0 to both sums
            fx, fy, fcnt, fzs, fmx, fmy = (
                jnp.where(keep, v[None, :], fill)
                for v, fill in zip(plan.far[lv][:6], (big, big, zero, zero, zero, zero)))
            nt = jnp.where(live & (n_closed > 0), n_pad // tile, 0)
            sw_f, swz_f = phase2_far_nodes(
                qx_v, qy_v, ah, fx, fy, fcnt, fzs, fmx, fmy, nt,
                block_q=plan.block_q, block_d=tile, interpret=plan.interpret,
            )
            sw = sw + sw_f
            swz = swz + swz_f
            closed_counts.append(n_closed)
            opened_tot = opened_tot + n_opened
            proc_tot = proc_tot + n_proc
    z = jnp.where(md_n <= plan.params.exact_hit_eps, hz_n, swz / sw)
    return z, need, overflow, closed_counts, opened_tot, proc_tot


def _real_blocks(plan: InterpolationPlan, nb: int, dest):
    """``(nb,)`` bool: the blocks of the seam-split view holding at least
    one real query (``dest`` maps the sorted queries to their slots; no
    split means every block is real)."""
    if dest is None:
        return jnp.ones((nb,), bool)
    real_slot = jnp.zeros((nb * plan.block_q,), bool).at[dest].set(True)
    return jnp.any(real_slot.reshape(nb, plan.block_q), axis=1)


def _phase2_exact_masked(plan: InterpolationPlan, qx_s, qy_s, alpha, over_q):
    """Per-block masked exact Phase 2 — the overflow arm of the blend.

    ``over_q (n_tot,)`` flags queries (sorted layout) whose approximated
    Phase 2 is unusable (near field truncated).  Instead of
    the old whole-batch ``lax.cond`` full sweep, each ``block_q`` run with
    at least one flagged query gets its OWN full-data sweep — a
    ``fori_loop`` whose per-block ``cond`` skips clean blocks, so one
    overflowing block costs O(block_q * m), not O(n * m) (the ``grid_knn
    (active=)`` discipline applied to Phase 2).  Per-block single calls of
    :func:`phase2_weights_full` are bit-identical to the corresponding
    blocks of a whole-batch call (the kernel is block-parallel), which the
    overflow bitwise tests pin.  Unswept blocks return 0 — callers blend
    through ``jnp.where(over_q, ...)``.
    """
    bq = plan.block_q
    n_tot = qx_s.shape[0]
    nb = n_tot // bq
    dtype = qx_s.dtype
    dxp, dyp, dzp = plan.data
    over_blk = jnp.any(over_q.reshape(nb, bq), axis=1)
    qx2 = qx_s.reshape(nb, bq)
    qy2 = qy_s.reshape(nb, bq)
    al2 = alpha.reshape(nb, bq)

    def sweep(b):
        qxb = jax.lax.dynamic_slice(qx2, (b, 0), (1, bq)).reshape(bq)
        qyb = jax.lax.dynamic_slice(qy2, (b, 0), (1, bq)).reshape(bq)
        alb = jax.lax.dynamic_slice(al2, (b, 0), (1, bq)).reshape(bq, 1)
        return phase2_weights_full(
            qxb, qyb, alb, dxp, dyp, dzp,
            eps=plan.params.exact_hit_eps, block_q=bq,
            block_d=plan.block_d, interpret=plan.interpret,
        )

    def body(b, z):
        zb = jax.lax.cond(over_blk[b], lambda: sweep(b),
                          lambda: jnp.zeros((bq, 1), dtype))
        return jax.lax.dynamic_update_slice(z, zb, (b * bq, 0))

    return jax.lax.fori_loop(0, nb, body, jnp.zeros((n_tot, 1), dtype))


def _execute_grid(plan: InterpolationPlan, qx, qy):
    grid = plan.grid
    params = plan.params
    n = qx.shape[0]
    dtype = qx.dtype

    # Morton-sort queries so each block's home cells form a compact patch,
    # pad the tail by repetition (adds no candidate cells)
    with jax.named_scope("aidw.sort"):
        cx, cy = cell_of(grid, qx, qy)
        order = jnp.argsort(morton_ids(cx, cy), stable=True)
        n_pad = (-n) % plan.block_q
        qx_s = pad_tail(qx[order], n_pad)
        qy_s = pad_tail(qy[order], n_pad)
        cx_s, cy_s = cell_of(grid, qx_s, qy_s)

        # Phase-1 view: seam-split blocks (rectangles can't straddle a Morton
        # seam, the measured overflow worst case); pad slots repeat a real query
        qx_v, qy_v, cx_v, cy_v, src, dest = _seam_split_layout(plan, qx_s, qy_s, cx_s, cy_s)

    # containment-safe radii: plan-time table + closed-form overhang term
    with jax.named_scope("aidw.gather"):
        r_need = plan.r_need[cy_v, cx_v]
        r_safe = safe_radius_from_need(grid, qx_v, qy_v, cx_v, cy_v, r_need)
        xlo, xhi, ylo, yhi = block_rectangles(grid, cx_v, cy_v, r_safe, plan.block_q)
        need = rectangle_need(grid, xlo, xhi, ylo, yhi)
        over_b = need > plan.cand_capacity
        # the row-run walk's tile list (an overflowing block walks nothing:
        # the ring-search arm answers it); the dense pipeline keeps only the
        # count, for phase1_tile_fill
        tiles, run_tiles = row_run_tiles(
            grid, xlo, xhi, ylo, yhi, plan.row_tile,
            row_run_max_tiles(plan.cand_capacity, plan.row_tile, grid.gy))
        run_tiles = jnp.where(over_b, 0, run_tiles)
        if plan.pipeline == "dense":
            cand_x, cand_y, _ = gather_candidates_csr(
                grid, xlo, xhi, ylo, yhi, plan.cand_capacity
            )
        n_tiles_static = plan.cand_capacity // plan.cand_block_d
        # the capacity tiles each block's candidates occupy, for the
        # skipped_tile_fraction diagnostic
        num_tiles = _tile_table(need, plan.cand_capacity, plan.cand_block_d,
                                "prefetch")

    # Phase 1, always on the kernel path; an overflowing block's alpha is
    # discarded by the blend below
    with jax.named_scope("aidw.phase1"):
        if plan.pipeline == "prefetch":
            alpha_fast = phase1_alpha_row_runs(
                qx_v, qy_v, tiles, run_tiles,
                jnp.stack([xlo, xhi, ylo, yhi], axis=1),
                (grid.pt_x, grid.pt_y, plan.row_cells),
                tile=plan.row_tile, params=params, area=plan.area, m_real=plan.m,
                block_q=plan.block_q, interpret=plan.interpret,
            )
        else:
            alpha_fast = phase1_alpha_from_candidates(
                qx_v, qy_v, cand_x, cand_y,
                params=params, area=plan.area, m_real=plan.m,
                block_q=plan.block_q, block_d=plan.cand_block_d,
                interpret=plan.interpret,
            )

    # Per-block overflow blend: back in the sorted layout, ring-search ONLY
    # queries whose block overflowed (masked — a clean batch adds zero loop
    # iterations) and keep the kernel alpha everywhere else.  Exactness is
    # per query: kernel where covered, ring search where not.
    with jax.named_scope("aidw.ring_search"):
        over_v = jnp.repeat(over_b, plan.block_q)
        if dest is not None:
            alpha_fast = alpha_fast[dest]
            over_q = over_v[dest]
        else:
            over_q = over_v
        r_obs = grid_r_obs(grid, qx_s, qy_s, params.k, active=over_q)
        alpha_exact = adaptive_alpha(r_obs, plan.m, plan.area, params).astype(dtype)[:, None]
        alpha = jnp.where(over_q[:, None], alpha_exact, alpha_fast)

    dxp, dyp, dzp = plan.data
    with jax.named_scope("aidw.phase2"):
        if plan.phase2 == "quadtree":
            # the quadtree Phase 2 runs in the seam-split view (its rectangles
            # must not straddle Morton seams either); alpha maps in through
            # src, the per-slot z maps back through dest.  Blocks whose near
            # field overflows p2_capacity would violate the error bound
            # (truncated sweep), so their queries take the per-block masked
            # exact sweep instead: one overflowing block costs
            # O(block_q * m), a clean batch costs nothing.
            alpha_v = alpha[src] if src is not None else alpha
            (z_v, need2, over2_b, closed_counts, opened_tot,
             proc_tot) = _phase2_quadtree(plan, qx_v, qy_v, alpha_v, cx_v, cy_v,
                                          _real_blocks(plan, need.shape[0], dest))
            over2_v = jnp.repeat(over2_b, plan.block_q)
            if dest is not None:
                z_near = z_v[dest]
                over2_s = over2_v[dest]
            else:
                z_near = z_v
                over2_s = over2_v
            with jax.named_scope("aidw.phase2.masked_exact"):
                z_full = _phase2_exact_masked(plan, qx_s, qy_s, alpha, over2_s)
            zhat = jnp.where(over2_s[:, None], z_full, z_near)
        else:
            zhat = phase2_weights_full(
                qx_s, qy_s, alpha, dxp, dyp, dzp,
                eps=params.exact_hit_eps, block_q=plan.block_q, block_d=plan.block_d,
                interpret=plan.interpret,
            )
    with jax.named_scope("aidw.sort"):
        inv = jnp.argsort(order)
    with jax.named_scope("aidw.stats"):
        # diagnostics count only blocks holding at least one real query — seam
        # pad blocks (all-duplicate, ~1 tile) would otherwise inflate the skip
        # fraction and the overflow-block count
        nb = need.shape[0]
        real_b = _real_blocks(plan, nb, dest)
        n_real_tiles = jnp.maximum(jnp.sum(real_b.astype(jnp.int32)) * n_tiles_static, 1)
        stats = {
            # every real query took the ring path — the batch got no kernel help
            "grid_fallback": jnp.all(over_q[:n]),
            "cand_need_max": jnp.max(need),
            "overflow_blocks": jnp.sum((over_b & real_b).astype(jnp.int32)),
            "overflow_queries": jnp.sum(over_q[:n].astype(jnp.int32)),
            "overflow_query_mask": over_q[:n][inv],
            "skipped_tile_fraction": 1.0
            - jnp.sum(jnp.where(real_b, num_tiles, 0)).astype(jnp.float32) / n_real_tiles,
            # rectangle points per lane the row-run walk reads
            "phase1_tile_fill": jnp.sum(jnp.where(real_b & ~over_b, need, 0).astype(jnp.float32))
            / jnp.maximum(jnp.sum(jnp.where(real_b, run_tiles, 0).astype(jnp.float32))
                          * plan.row_tile, 1.0),
        }
        if plan.phase2 == "quadtree":
            n_real_b = jnp.maximum(jnp.sum(real_b.astype(jnp.int32)), 1).astype(jnp.float32)
            # far work per block is the number of CLOSED nodes summed over
            # levels — the ~O(log m) quantity
            closed_stack = jnp.stack(closed_counts)           # (n_levels, nb)
            far_terms = jnp.sum(closed_stack, axis=0)
            far_mean = jnp.sum(
                jnp.where(real_b, far_terms, 0)).astype(jnp.float32) / n_real_b
            stats.update({
                "cells_per_level": jnp.sum(
                    jnp.where(real_b[None, :], closed_stack, 0), axis=1
                ).astype(jnp.float32) / n_real_b,
                "opened_fraction": jnp.sum(
                    jnp.where(real_b, opened_tot, 0)).astype(jnp.float32)
                / jnp.maximum(jnp.sum(jnp.where(real_b, proc_tot, 0)), 1
                              ).astype(jnp.float32),
                "quadtree_rtol_bound": plan.farfield_bound,
                "near_points_mean": jnp.sum(
                    jnp.where(real_b, need2, 0)).astype(jnp.float32) / n_real_b,
                "far_cells_mean": far_mean,
                "p2_overflow_queries": jnp.sum(over2_s[:n].astype(jnp.int32)),
                "p2_overflow_query_mask": over2_s[:n][inv],
                "p2_need_max": jnp.max(jnp.where(real_b, need2, 0)),
            })
    with jax.named_scope("aidw.sort"):
        return zhat[:n, 0][inv], alpha[:n, 0][inv], stats


def _execute_dense(plan: InterpolationPlan, qx, qy):
    params = plan.params
    n = qx.shape[0]
    dtype = qx.dtype
    zero = jnp.zeros((), dtype)
    qxp = pad_to(qx, plan.block_q, zero)
    qyp = pad_to(qy, plan.block_q, zero)
    kw = dict(params=params, area=plan.area, m_real=plan.m, interpret=plan.interpret)
    stats = {}

    if plan.layout == "aoas":
        (data,) = plan.data
        qx2, qy2 = qxp[None, :], qyp[None, :]
        if plan.impl == "naive":
            z, a = aidw_naive_aoas(data, qx2, qy2, block_q=plan.block_q, **kw)
        else:  # tiled (build_plan rejects the rest for aoas)
            z, a = aidw_tiled_aoas(
                data, qx2, qy2, block_q=plan.block_q, block_d=plan.block_d, **kw
            )
        return z[0, :n], a[0, :n], stats

    dx2, dy2, dz2 = plan.data
    qx2, qy2 = qxp[:, None], qyp[:, None]
    if plan.impl == "naive":
        z, a = aidw_naive_soa(dx2, dy2, dz2, qx2, qy2, block_q=plan.block_q, **kw)
    elif plan.impl == "tiled":
        z, a = aidw_tiled_soa(
            dx2, dy2, dz2, qx2, qy2, block_q=plan.block_q, block_d=plan.block_d, **kw
        )
    elif plan.impl == "binned":
        # nbins: power-of-two divisor of block_d near 6k (see DESIGN.md §3)
        nbins = 16
        while nbins * 2 <= min(6 * params.k, plan.block_d // 4):
            nbins *= 2
        z, a = aidw_tiled_soa(
            dx2, dy2, dz2, qx2, qy2, block_q=plan.block_q, block_d=plan.block_d,
            nbins=nbins, **kw,
        )
    elif plan.impl == "fused":
        z, a = aidw_fused_soa(
            dx2, dy2, dz2, qx2, qy2, block_q=plan.block_q, block_d=plan.block_d, **kw
        )
    else:  # tiled_v2: threshold-skip kNN pass + measured merge fraction
        z, a, merges = aidw_tiled_v2_soa(
            dx2, dy2, dz2, qx2, qy2, block_q=plan.block_q, block_d=plan.block_d, **kw
        )
        n_tiles = dx2.shape[1] // plan.block_d
        stats = {
            "merge_fraction": jnp.sum(merges).astype(jnp.float32)
            / (merges.shape[0] * n_tiles)
        }
    return z[:n, 0], a[:n, 0], stats


def _execute_idw(plan: InterpolationPlan, qx, qy):
    n = qx.shape[0]
    dtype = qx.dtype
    zero = jnp.zeros((), dtype)
    qx2 = pad_to(qx, plan.block_q, zero)[:, None]
    qy2 = pad_to(qy, plan.block_q, zero)[:, None]
    dx2, dy2, dz2 = plan.data
    with jax.named_scope("aidw.phase2"):
        z = idw_tiled_soa(
            dx2, dy2, dz2, qx2, qy2, alpha=plan.idw_alpha,
            block_q=plan.block_q, block_d=plan.block_d, interpret=plan.interpret,
        )
    alpha = jnp.full((n,), plan.idw_alpha, dtype)
    return z[:n, 0], alpha, {}


def _execute_chunked(plan: InterpolationPlan, qx, qy):
    dx, dy, dz = plan.data
    params = plan.params
    if plan.knn == "grid":
        r_obs = grid_r_obs(plan.grid, qx, qy, params.k)
    else:
        r_obs = brute_r_obs(
            dx, dy, qx, qy, params.k, q_chunk=plan.q_chunk, d_chunk=plan.d_chunk
        )
    alpha = adaptive_alpha(r_obs, plan.m, plan.area, params)
    zhat = _interpolate_pass2(
        dx, dy, dz, qx, qy, alpha, params,
        area=plan.area, q_chunk=plan.q_chunk, d_chunk=plan.d_chunk,
    )
    return zhat, alpha, {}


def _execute(plan: InterpolationPlan, qx, qy):
    # Input hardening: a NaN/Inf query coordinate would otherwise flow
    # through the kernel min-reductions into a silently wrong (finite) alpha
    # and z.  Replace non-finite queries with an in-bbox dummy for the
    # compute (so they cannot distort block rectangles or capacities
    # either) and NaN-mask their outputs — NaN in, NaN out.
    qx = jnp.asarray(qx)
    qy = jnp.asarray(qy)
    bad = ~(jnp.isfinite(qx) & jnp.isfinite(qy))
    zero = jnp.zeros((), qx.dtype)
    qx = jnp.where(bad, zero, qx)
    qy = jnp.where(bad, zero, qy)
    if plan.impl == "grid":
        z, a, stats = _execute_grid(plan, qx, qy)
    elif plan.impl == "idw":
        z, a, stats = _execute_idw(plan, qx, qy)
    elif plan.impl == "chunked":
        z, a, stats = _execute_chunked(plan, qx, qy)
    else:
        z, a, stats = _execute_dense(plan, qx, qy)
    nan = jnp.asarray(jnp.nan, z.dtype)
    return jnp.where(bad, nan, z), jnp.where(bad, nan, a), stats


@jax.jit
def execute(plan: InterpolationPlan, qx, qy):
    """Interpolate one query batch against a prebuilt plan.

    Pure and jit-compatible for every impl (the plan's statics live in the
    pytree aux data, so they are trace-time constants).  Returns
    ``(z_hat, alpha)``, shape ``(n,)`` each, in caller query order.

    Non-finite query coordinates are hardened: a query with a NaN/Inf in
    either coordinate yields NaN ``z_hat`` and NaN ``alpha`` (never a
    silently wrong finite value), and the finite queries in the same batch
    are computed exactly as if the bad slots held in-bbox dummies.
    """
    z, a, _ = _execute(plan, qx, qy)
    return z, a


@jax.jit
def _execute_with_stats_jit(plan: InterpolationPlan, qx, qy):
    return _execute(plan, qx, qy)


# ---- persistent-overflow tracking (ROADMAP capacity-model item) -------------
# The plan's static candidate capacity is sized from an *assumed* serving
# density (`query_occupancy`); a workload that is persistently sparser keeps
# paying the exact ring-search arm batch after batch.  execute_with_stats
# counts, per plan object, the consecutive diagnostic batches with
# overflow_queries > 0 and surfaces `persistent_overflow` (plus a one-shot
# RuntimeWarning) once the streak reaches the threshold — the hook a future
# per-batch capacity re-estimator will replace with an automatic re-plan.
PERSISTENT_OVERFLOW_BATCHES = 3
_overflow_streaks: dict[int, int] = {}
_overflow_lock = threading.Lock()


def _note_overflow(plan: InterpolationPlan, n_overflow: int) -> bool:
    key = id(plan)
    with _overflow_lock:
        if key not in _overflow_streaks:
            weakref.finalize(plan, _overflow_streaks.pop, key, None)
        streak = _overflow_streaks.get(key, 0) + 1 if n_overflow > 0 else 0
        _overflow_streaks[key] = streak
    if streak == PERSISTENT_OVERFLOW_BATCHES:
        warnings.warn(
            f"overflow_queries > 0 for {streak} consecutive batches against "
            "this plan: the static candidate capacity looks undersized for "
            "the serving workload (results stay exact via the ring-search "
            "blend, but at ring-search cost). Consider re-planning with a "
            "lower query_occupancy= or a coarser grid — or serve through "
            "repro.serving.CapacityReestimator, which re-plans and swaps "
            "automatically.",
            CapacityOverflowWarning,
            stacklevel=3,
        )
    return streak >= PERSISTENT_OVERFLOW_BATCHES


def execute_with_stats(plan: InterpolationPlan, qx, qy):
    """Like :func:`execute` but also returns the impl's diagnostics.

    ``grid``: ``overflow_blocks`` / ``overflow_queries`` (how much of the
    batch exceeded the plan's static candidate capacity and took the exact
    masked ring-search arm of the blend), ``overflow_query_mask`` (bool
    ``(n,)``, caller order — which queries those were),
    ``skipped_tile_fraction`` (share of the static capacity's Phase-1 tiles
    that hold no candidate of their block), ``phase1_tile_fill`` (share of
    the lanes the row-run Phase 1 walks that hold a rectangle point, over
    blocks with real queries — what aligned tiles waste), ``cand_need_max``,
    ``grid_fallback`` (bool — EVERY query overflowed, i.e. the batch got no
    kernel fast path at all; single blocks overflowing no longer drag the
    batch down), and ``persistent_overflow`` (host-side bool — overflow has
    now persisted for ``PERSISTENT_OVERFLOW_BATCHES`` consecutive diagnostic
    batches against this plan object; a RuntimeWarning suggesting a re-plan
    fires when the streak is first reached).  ``grid`` with
    ``phase2="quadtree"`` additionally reports ``near_points_mean`` (per
    real query block), ``p2_overflow_queries`` (queries routed to the
    exact Phase-2 sweep because their block's near field overflowed),
    ``p2_overflow_query_mask`` (bool ``(n,)``, caller order — which
    queries the masked exact sweep answered), ``p2_need_max`` (the largest
    near-field point count of a real block — what a re-plan sizes
    ``p2_capacity`` from), ``far_cells_mean`` (mean CLOSED nodes per real
    block, summed over levels — the ~O(log m) quantity),
    ``cells_per_level`` (its per-level split, shape ``(n_levels,)``),
    ``opened_fraction`` (opened / processed nonempty nodes — how much of
    the tree the walk descends) and the plan's proved
    ``quadtree_rtol_bound``; the dict structure is static per plan (the
    level count is a plan static).
    ``tiled_v2``: the measured ``merge_fraction``.
    The computation is jitted with a static dict structure per plan (no
    retrace across same-shape batches); only the streak bookkeeping runs on
    the host, which is why this entry — unlike :func:`execute` — syncs on
    ``overflow_queries``."""
    z, a, stats = _execute_with_stats_jit(plan, qx, qy)
    # Under an OUTER jit the call inlines and the stats are tracers: the
    # host-side streak bookkeeping cannot (and should not) run there — the
    # dict then simply lacks the persistent_overflow key, exactly the
    # pre-tracking behaviour, instead of raising on int(tracer).
    if plan.impl == "grid" and not isinstance(
        stats["overflow_queries"], jax.core.Tracer
    ):
        stats = dict(stats)
        stats["persistent_overflow"] = _note_overflow(
            plan, int(stats["overflow_queries"])
        )
    return z, a, stats


# the no-retrace contract is asserted against the underlying jit cache
execute_with_stats._cache_size = _execute_with_stats_jit._cache_size


def exact_arm_mask(stats):
    """``(n,)`` bool, caller order: the queries of one ``execute_with_stats``
    call that an exact fallback arm answered — the ring search (Phase-1
    overflow) or, on quadtree plans, the masked exact Phase-2 sweep."""
    mask = stats["overflow_query_mask"]
    if "p2_overflow_query_mask" in stats:
        mask = mask | stats["p2_overflow_query_mask"]
    return mask
