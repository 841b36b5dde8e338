"""Grid-accelerated AIDW — static-shape execute machinery over the plan's
CSR grid snapshot.

The PR-1 version of this module materialised per-block *ragged* candidate
rows eagerly in Python (their width was a measured ``max`` over blocks), so
``impl="grid"`` could not be traced, vmapped, or donated.  The plan/execute
engine (``repro.engine``, DESIGN.md §6) fixes the candidate capacity ONCE at
plan time from the occupancy histogram; everything here is a pure function
of ``(snapshot arrays, queries, static capacity)`` and runs under ``jax.jit``:

* :func:`block_rectangles` — per-block candidate rectangles (cell coords)
  for Morton-contiguous query blocks, from the per-query safe radii.
* :func:`gather_candidates_csr` — the traced gather: each rectangle row
  ``(y, xlo..xhi)`` is one contiguous run of the grid's CSR point arrays, so
  a block's candidates are ``ht`` contiguous runs decoded into a STATIC
  ``capacity``-wide row (sentinel-padded).  Returns the true per-block need
  so the engine can fall back to the exact ring search when the plan-time
  capacity is exceeded (far out-of-bbox queries, adversarial batches) —
  the static fast path never silently drops a neighbour.
* :func:`row_run_tiles` + :func:`phase1_alpha_row_runs` — Phase 1 (kNN →
  adaptive alpha) of the default ``pipeline="prefetch"``, reading the CSR
  row runs in place: a block's rectangle rows are contiguous runs of the
  CSR twin, so :func:`row_run_tiles` lists the aligned ``tile``-point
  slices of the CSR arrays that cover them (a ``(nb, max_tiles)`` int32
  table, bounded by :func:`row_run_max_tiles`), and the kernel streams
  those slices from HBM through a ``pltpu.PrefetchScalarGridSpec`` index
  map, masking every lane whose point lies outside the block's rectangle
  (or past the data's end, where blocks overrun it) — it merges exactly the
  point set :func:`gather_candidates_csr` would have materialised, without
  materialising it.
* :func:`phase1_alpha_from_candidates` — the ``pipeline="dense"`` Phase 1
  over materialised candidate rows: every block walks every tile of its
  row with the tiled version's kernel body (``_knn_kernel_soa``).  It is
  the oracle the row-run walk is tested against bit for bit.  Either way
  per-query work is O(|neighbourhood|) instead of O(m).
* :func:`phase2_weights_full` — exact Phase 2 (the default): AIDW weights
  ALL m data points, so the full-data sweep (``_weight_kernel_soa``) is
  reused verbatim.
* :func:`phase2_near_row_runs` — the quadtree arm's near field
  (``build_plan(phase2="quadtree")``, DESIGN.md §8) on the default
  ``pipeline="prefetch"``: exact per-point weights over the CSR row runs
  of each block's near rectangle, read in place by the Phase-1 row-run
  walk (the near rectangle is far too wide to gather), returned as the
  four partial accumulators ``(sum_w, sum_wz, min_d2, hit_z)`` instead of
  a finished z.  :func:`phase2_near_weights` is the ``pipeline="dense"``
  oracle: the same accumulators over the materialised CSR gather, with a
  scalar-prefetched per-block tile count (sparse blocks skip their
  all-sentinel tail tiles).
* :func:`phase2_far_nodes` — the multi-level quadtree far field: the
  sweep runs once per quadtree LEVEL over the level's nodes, those a
  block does not close (by the engine's Barnes–Hut walk) masked to the
  sentinel node, each closed node contributing its aggregate term plus a
  dipole z-moment correction — the piece that cancels the z budget's
  first-order error and makes the plan's bound second-order in the
  opening ratio.  The engine adds the levels to the near accumulators and
  applies the exact-hit guard; the worst-case relative error is bounded at
  plan time (``engine.plan._choose_quadtree_radius``).

Morton sorting, seam splitting, padding, the per-block overflow blend, the
quadtree level walk and the unsort live in ``repro.engine.execute``; this
module is only the kernel plumbing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.aidw import AIDWParams
from repro.core.grid import UniformGrid
from repro.core.knn import running_k_best
from repro.core.layouts import coord_sentinel
from repro.kernels._common import (
    alpha_from_best,
    pow_weight,
    sq_dist_tile,
    sweep_finish,
    sweep_init,
    sweep_scratch,
    sweep_step,
)
from repro.kernels.aidw_tiled import _SEMANTICS, _knn_kernel_soa, weight_sweep_soa


def block_rectangles(grid: UniformGrid, cx, cy, r_safe, block_q: int):
    """Candidate rectangles for Morton-contiguous query blocks.

    Args:
      cx, cy: (n_sorted,) clamped home cells, ``n_sorted % block_q == 0``.
      r_safe: (n_sorted,) per-query containment-safe ring radii.

    Returns ``(xlo, xhi, ylo, yhi)`` of shape ``(nb,)`` each — the inclusive
    cell bounds of every block's rectangle: the bounding box of the block's
    home cells expanded by the block-max safe radius, clipped to the grid.
    """
    nb = cx.shape[0] // block_q
    cxb = cx.reshape(nb, block_q)
    cyb = cy.reshape(nb, block_q)
    rb = r_safe.reshape(nb, block_q).max(axis=1)
    xlo = jnp.clip(cxb.min(axis=1) - rb, 0, grid.gx - 1)
    xhi = jnp.clip(cxb.max(axis=1) + rb, 0, grid.gx - 1)
    ylo = jnp.clip(cyb.min(axis=1) - rb, 0, grid.gy - 1)
    yhi = jnp.clip(cyb.max(axis=1) + rb, 0, grid.gy - 1)
    return xlo, xhi, ylo, yhi


def gather_candidates_csr(grid: UniformGrid, xlo, xhi, ylo, yhi, capacity: int,
                          with_z: bool = False):
    """Traced per-block candidate gather from the CSR snapshot, static width.

    Each rectangle row ``(y, xlo..xhi)`` maps to the contiguous CSR run
    ``pt_*[starts[y*gx + xlo] : starts[y*gx + xhi + 1]]``.  Slot ``s`` of a
    block's row indexes the concatenation of those runs: a batched
    ``searchsorted`` over the per-row prefix sums decodes ``s`` into
    ``(row, offset-within-row)``.  Slots past the block's true candidate
    count — and every slot past ``capacity`` when the block overflows — read
    the CSR sentinel (index ``m``), whose squared distance overflows to +inf.

    Returns ``(cand_x, cand_y, need)``: candidates ``(nb, capacity)`` and the
    true per-block candidate count ``need (nb,)``.  ``need > capacity`` means
    this gather is incomplete and the caller must use the exact fallback.
    ``with_z=True`` additionally gathers the attribute rows (sentinel slot
    z = 0, i.e. weightless) and returns ``(cand_x, cand_y, cand_z, need)`` —
    the quadtree's dense near field needs the z values of its points.
    """
    nb = xlo.shape[0]
    gx, gy = grid.gx, grid.gy
    rows = jnp.arange(gy, dtype=jnp.int32)[None, :]                 # (1, gy)
    ht = yhi - ylo + 1
    y = ylo[:, None] + rows                                          # (nb, gy)
    row_ok = rows < ht[:, None]
    ysafe = jnp.minimum(y, gy - 1)
    c = grid.cum
    x0 = xlo[:, None]
    x1 = xhi[:, None] + 1
    cnt = c[ysafe + 1, x1] - c[ysafe + 1, x0] - c[ysafe, x1] + c[ysafe, x0]
    cnt = jnp.where(row_ok, cnt, 0)
    offs = jnp.concatenate([jnp.zeros((nb, 1), jnp.int32), jnp.cumsum(cnt, axis=1)], axis=1)
    need = offs[:, -1]

    s = jnp.broadcast_to(jnp.arange(capacity, dtype=jnp.int32)[None, :], (nb, capacity))
    row = jax.vmap(functools.partial(jnp.searchsorted, side="right"))(offs, s) - 1
    row = jnp.clip(row, 0, gy - 1)
    within = s - jnp.take_along_axis(offs, row, axis=1)
    base_cid = (ylo[:, None] + row) * gx + x0
    idx = grid.starts[jnp.clip(base_cid, 0, grid.n_cells)] + within
    m = grid.n_points
    valid = s < jnp.minimum(need, capacity)[:, None]
    idx = jnp.where(valid, jnp.clip(idx, 0, m - 1), m)               # m = sentinel slot
    if with_z:
        return grid.pt_x[idx], grid.pt_y[idx], grid.pt_z[idx], need
    return grid.pt_x[idx], grid.pt_y[idx], need


def rectangle_need(grid: UniformGrid, xlo, xhi, ylo, yhi):
    """Points inside each block's rectangle, ``(nb,)`` — O(1) per block from
    the integral image (the ``need`` :func:`gather_candidates_csr` returns)."""
    c = grid.cum
    return (c[yhi + 1, xhi + 1] - c[ylo, xhi + 1]
            - c[yhi + 1, xlo] + c[ylo, xlo])


def row_run_max_tiles(capacity: int, tile: int, gy: int) -> int:
    """Static bound on the tiles :func:`row_run_tiles` lists for a block
    whose rectangle holds ``need <= capacity`` points.

    Proof.  Tile ``t`` is the CSR slice ``[t*tile, (t+1)*tile)``.  A
    non-empty row run ``[a, b)`` of ``L = b - a`` points meets the tiles
    ``floor(a/tile) .. floor((b-1)/tile)``, and for integers ``p >= q``,
    ``floor(p/T) - floor(q/T) <= (p - q + T - 1)/T``, so the run meets at
    most ``(L - 1 + T - 1)/T + 1 < L/T + 2`` tiles.  Summed over the
    rectangle's at most ``gy`` non-empty rows, the listed tiles number
    fewer than ``need/T + 2*gy <= capacity/T + 2*gy``, hence at most
    ``ceil(capacity/T) + 2*gy``.  Every listed tile also holds at least one
    rectangle point, so there are at most ``need <= capacity`` of them.  A
    block with ``need > capacity`` is answered by the ring-search arm, and
    its walk is never launched.
    """
    return min(-(-capacity // tile) + 2 * gy, capacity)


def row_run_tiles(grid: UniformGrid, xlo, xhi, ylo, yhi, tile: int, max_tiles: int):
    """Aligned CSR tiles covering each block's rectangle rows, in place.

    Row ``y`` of block ``i``'s rectangle is the CSR run ``[starts[y*gx +
    xlo], starts[y*gx + xhi + 1])``.  The runs of successive rows follow one
    another in CSR order (cell ids are row-major), so the tiles meeting them
    are listed in increasing order, and a tile can repeat only where a row's
    first tile is the previous non-empty row's last: it is listed once.

    Returns ``(tiles (nb, max_tiles) int32, n_tiles (nb,) int32)``: block
    ``i``'s first ``n_tiles[i]`` entries are its tiles, strictly increasing,
    and the rest repeat its last tile (0 where it has none).  ``n_tiles`` is
    the true count: past ``max_tiles`` the list is truncated, which
    :func:`row_run_max_tiles` proves cannot happen to a block within the
    plan's capacity.
    """
    gx, gy = grid.gx, grid.gy
    rows = jnp.arange(gy, dtype=jnp.int32)[None, :]                 # (1, gy)
    y = jnp.minimum(ylo[:, None] + rows, gy - 1)
    a = grid.starts[y * gx + xlo[:, None]]
    b = grid.starts[y * gx + xhi[:, None] + 1]
    live = (rows <= (yhi - ylo)[:, None]) & (b > a)
    first = a // tile
    last = (b - 1) // tile
    # last tile of the previous non-empty row: at most this row's first
    seen = jax.lax.cummax(jnp.where(live, last, -1), axis=1)
    prev = jnp.concatenate([jnp.full_like(seen[:, :1], -1), seen[:, :-1]], axis=1)
    start = jnp.where(first > prev, first, first + 1)
    cnt = jnp.where(live, last - start + 1, 0)
    ends = jnp.cumsum(cnt, axis=1)
    n_tiles = ends[:, -1]

    s = jnp.arange(max_tiles, dtype=jnp.int32)[None, :]
    s = jnp.minimum(s, jnp.maximum(n_tiles - 1, 0)[:, None])          # pad: last tile
    row = jax.vmap(functools.partial(jnp.searchsorted, side="right"))(ends, s)
    row = jnp.minimum(row, gy - 1)
    at = functools.partial(jnp.take_along_axis, indices=row, axis=1)
    tiles = at(start) + s - (at(ends) - at(cnt))
    tiles = jnp.where(n_tiles[:, None] > 0, tiles, 0)
    return tiles.astype(jnp.int32), n_tiles.astype(jnp.int32)


# Index maps shared by the scalar-prefetch Phase-2 kernels (near, far cell,
# far node); the first argument after (i, j) is the prefetched scalar ref,
# unused by the query/output maps.
def _pf_query_map(i, j, _scalar):
    return (i, 0)


def _pf_clamped_tile_map(i, j, nt):
    # clamp past-need steps to the block's last real tile: Pallas skips the
    # DMA for a revisited block index, the kernel skips the merge
    return (i, 0, jnp.maximum(jnp.minimum(j, nt[i] - 1), 0))


def _row_tiles(rows):
    """Per-block rows ``(nb, c)`` as ``(nb, 1, c)`` for ``_row_spec`` tiles.

    Mosaic requires a block's last two dims to be multiples of (8, 128) or
    the array's own, so a ``(1, block_d)`` tile cannot index an ``(nb, c)``
    array by row; with a unit middle axis the tile's ``(1, block_d)`` matches
    it.  The kernel still sees a ``(1, block_d)`` ref (the block axis is
    squeezed)."""
    nb, c = rows.shape
    return rows.reshape(nb, 1, c)


def _row_spec(block_d: int, index_map):
    return pl.BlockSpec((None, 1, block_d), index_map)


def phase1_alpha_from_candidates(
    qx_s, qy_s, cand_x, cand_y, *,
    params: AIDWParams, area: float, m_real: int,
    block_q: int, block_d: int, interpret: bool,
):
    """Phase 1 over materialised per-block candidate rows (``pipeline="dense"``).

    qx_s/qy_s: (n_tot,) Morton-sorted padded queries, ``n_tot % block_q == 0``;
    cand_x/cand_y: (nb, c_tot) with ``c_tot % block_d == 0``.  Every block
    streams all ``c_tot // block_d`` tiles of its row.
    Returns alpha, shape ``(n_tot, 1)``.
    """
    n_tot = qx_s.shape[0]
    nb, c_tot = cand_x.shape
    dtype = qx_s.dtype
    q_spec = pl.BlockSpec((block_q, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_knn_kernel_soa, m_real=m_real, area=area, params=params),
        grid=(nb, c_tot // block_d),
        in_specs=[q_spec, q_spec,
                  _row_spec(block_d, lambda i, j: (i, 0, j)),
                  _row_spec(block_d, lambda i, j: (i, 0, j))],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((n_tot, 1), dtype),
        scratch_shapes=[pltpu.VMEM((block_q, params.k), dtype)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="_knn_kernel_soa",
    )(qx_s[:, None], qy_s[:, None], _row_tiles(cand_x), _row_tiles(cand_y))


# Scalar-prefetch operands live in SMEM (1 MiB on a TPU v5e); one launch's
# tile table, counts and rectangles are held to this many words, and a
# larger batch launches over static chunks of blocks.
_SMEM_TABLE_WORDS = 1 << 17

# The row-run walk reads the CSR arrays in place, 1-D, in blocks of this many
# points: a TPU lays a 1-D f32 array out in 1024-element tiles, and Mosaic
# takes no smaller block of it.  Each step rotates its tile's lanes to the
# front of the block it sits in.
_ROW_BLOCK = 1024


def _row_run_step(tiles_ref, rect_ref, pc_ref, i, j, *, tile, max_tiles, m_real):
    """Step ``j`` of block ``i``'s row-run walk: ``(lanes, inside)``.

    The point refs hold the ``_ROW_BLOCK``-point block the step's tile
    ``tiles_ref[i * max_tiles + j]`` lies in; ``lanes(ref)`` rotates the
    tile's lanes to the front and returns them ``(1, tile)``.  ``inside``
    marks the lanes that hold a point (index below ``m_real``: the blocks
    run past the arrays' ends) whose cell (packed ``cy << 16 | cx``, from
    ``pc_ref``) lies in the block's rectangle ``rect_ref[4i : 4i+4] =
    (xlo, xhi, ylo, yhi)``.
    """
    t = tiles_ref[i * max_tiles + j]
    shift = (_ROW_BLOCK - t % (_ROW_BLOCK // tile) * tile) % _ROW_BLOCK

    def lanes(ref):
        block = ref[...].reshape(1, _ROW_BLOCK)
        return pltpu.roll(block, shift, 1)[:, :tile]

    cell = lanes(pc_ref)
    cx = jnp.bitwise_and(cell, 0xFFFF)
    cy = jnp.right_shift(cell, 16)
    index = t * tile + jax.lax.broadcasted_iota(jnp.int32, cell.shape, 1)
    inside = ((index < m_real)
              & (cx >= rect_ref[4 * i]) & (cx <= rect_ref[4 * i + 1])
              & (cy >= rect_ref[4 * i + 2]) & (cy <= rect_ref[4 * i + 3]))
    return lanes, inside


def _row_run_chunks(tiles, n_tiles, tile: int):
    """The launches of a row-run walk, ``(b0, b1, steps, q_map, p_map)``
    each: a static chunk ``b0:b1`` of the blocks, its traced step count
    (its longest block walk, so the static ``max_tiles`` sizes only the
    SMEM table) and the index maps of its query columns and of its points,
    read in place in ``_ROW_BLOCK``-point blocks at each step's tile (a
    step past a block's count revisits its last tile).  SMEM holds one
    launch's tile table, counts and rectangles, so a batch launches over
    chunks of at most ``_SMEM_TABLE_WORDS`` words."""
    nb, max_tiles = tiles.shape
    n_chunks = -(-nb * (max_tiles + 5) // _SMEM_TABLE_WORDS)
    chunk = -(-nb // n_chunks)
    for b0 in range(0, nb, chunk):
        b1 = min(b0 + chunk, nb)

        def q_map(i, j, *_refs, b0=b0):
            return (i + b0, 0)

        def p_map(i, j, tiles_ref, nt_ref, _rect):
            t = tiles_ref[i * max_tiles + jnp.maximum(jnp.minimum(j, nt_ref[i] - 1), 0)]
            return (t * tile // _ROW_BLOCK,)

        yield b0, b1, jnp.maximum(jnp.max(n_tiles[b0:b1]), 1), q_map, p_map


def _knn_kernel_skip(tiles_ref, nt_ref, rect_ref, qx_ref, qy_ref, px_ref, py_ref,
                     pc_ref, _alpha_in, alpha_ref, best, *, tile, max_tiles,
                     m_real, area, params):
    """Phase-1 kNN over a block's CSR row runs, one aligned tile a step.

    ``tiles_ref`` (flat ``(nb * max_tiles,)``) lists each block's tiles and
    ``nt_ref`` counts them: steps past the count are clamped revisits of the
    block's last tile (no DMA) with the merge predicated off.  A lane
    counts only if :func:`_row_run_step` finds it inside the block's
    rectangle; every other lane reads ``d2 = +inf``, as a sentinel slot of
    a materialised candidate row does.  Init/finish fire on the first/last
    grid step, so the output block is written exactly once per query block.
    """
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best[...] = jnp.full(best.shape, jnp.inf, best.dtype)

    @pl.when(j < nt_ref[i])
    def _merge():
        lanes, inside = _row_run_step(tiles_ref, rect_ref, pc_ref, i, j, tile=tile,
                                      max_tiles=max_tiles, m_real=m_real)
        d2 = sq_dist_tile(qx_ref[...], qy_ref[...], lanes(px_ref), lanes(py_ref))
        d2 = jnp.where(inside, d2, jnp.asarray(jnp.inf, d2.dtype))
        best[...] = running_k_best(best[...], d2, axis=1)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        alpha_ref[...] = alpha_from_best(best[...], m_real, area, params, data_axis=1)


def phase1_alpha_row_runs(
    qx_s, qy_s, tiles, n_tiles, rects, points, *,
    tile: int, params: AIDWParams, area: float, m_real: int,
    block_q: int, interpret: bool,
):
    """Phase 1 over each block's CSR row runs, read in place (``pipeline="prefetch"``).

    qx_s/qy_s: (n_tot,) Morton-sorted padded queries, ``n_tot % block_q == 0``;
    tiles: (nb, max_tiles) int32 from :func:`row_run_tiles`; n_tiles: (nb,)
    int32 tiles to walk per block (0 skips the block: its alpha is then
    meaningless and must be discarded); rects: (nb, 4) int32 ``(xlo, xhi,
    ylo, yhi)``; points: the grid's ``(pt_x, pt_y, point_cells)``, read in
    place in ``_ROW_BLOCK``-point blocks (lanes past ``m_real`` are masked).
    Launches: :func:`_row_run_chunks`; ``_alpha_in`` is the output buffer
    itself (aliased, never read), so the chunks write one buffer.  Returns
    alpha, shape ``(n_tot, 1)``.
    """
    dtype = qx_s.dtype
    nb, max_tiles = tiles.shape
    qx2, qy2 = qx_s[:, None], qy_s[:, None]
    alpha = jnp.zeros((nb * block_q, 1), dtype)
    for b0, b1, steps, q_map, p_map in _row_run_chunks(tiles, n_tiles, tile):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b1 - b0, steps),
            in_specs=[pl.BlockSpec((block_q, 1), q_map)] * 2
            + [pl.BlockSpec((_ROW_BLOCK,), p_map)] * 3 + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block_q, 1), q_map),
            scratch_shapes=[pltpu.VMEM((block_q, params.k), dtype)],
        )
        alpha = pl.pallas_call(
            functools.partial(_knn_kernel_skip, tile=tile, max_tiles=max_tiles,
                              m_real=m_real, area=area, params=params),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(alpha.shape, dtype),
            input_output_aliases={8: 0},
            compiler_params=_SEMANTICS,
            interpret=interpret,
            name="_knn_kernel_skip",
        )(tiles[b0:b1].reshape(-1), n_tiles[b0:b1], rects[b0:b1].reshape(-1), qx2, qy2,
          *points, alpha)
    return alpha


def _near_weight_kernel(nt_ref, qx_ref, qy_ref, ah_ref, dx_ref, dy_ref, dz_ref,
                        sw_ref, swz_ref, md_ref, hz_ref, *acc):
    """Near-field half of the quadtree Phase 2 on ``pipeline="dense"``:
    ``_weight_kernel_soa``'s sweep step over per-block candidate rows, with
    the Phase-1 tile-table skip (steps past ``nt_ref[i]`` are clamped
    revisits, the accumulation is predicated off) — and the four reduced
    accumulators written out instead of a finished z, so the engine can
    fold in the far-node terms before dividing."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        sweep_init(acc)

    @pl.when(j < nt_ref[i])
    def _accumulate():
        sweep_step(qx_ref, qy_ref, ah_ref, dx_ref, dy_ref, dz_ref, acc, j)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        for ref, v in zip((sw_ref, swz_ref, md_ref, hz_ref), sweep_finish(acc)):
            ref[...] = v


def phase2_near_weights(
    qx_s, qy_s, alpha_half, cand_x, cand_y, cand_z, num_tiles, *,
    block_q: int, block_d: int, interpret: bool,
):
    """Exact near-field weight sweep over per-block candidate rows.

    qx_s/qy_s/alpha_half: (n_tot,) / (n_tot, 1), ``n_tot % block_q == 0``;
    cand_*: (nb, c_tot) near-rectangle candidates, ``c_tot % block_d == 0``;
    num_tiles: (nb,) int32 per-block real-tile count (the scalar-prefetch
    tile table; pass the full tile count for a dense walk — bit-identical,
    the skipped tiles are all-sentinel).

    Returns ``(sum_w, sum_wz, min_d2, hit_z)``, each ``(n_tot, 1)``.
    """
    n_tot = qx_s.shape[0]
    nb, c_tot = cand_x.shape
    dtype = qx_s.dtype
    qx2, qy2 = qx_s[:, None], qy_s[:, None]
    q_spec = pl.BlockSpec((block_q, 1), _pf_query_map)
    c_spec = _row_spec(block_d, _pf_clamped_tile_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, c_tot // block_d),
        in_specs=[q_spec, q_spec, q_spec, c_spec, c_spec, c_spec],
        out_specs=[q_spec] * 4,
        scratch_shapes=sweep_scratch(block_q, block_d, dtype),
    )
    return pl.pallas_call(
        _near_weight_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_tot, 1), dtype)] * 4,
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="_near_weight_kernel",
    )(num_tiles.astype(jnp.int32), qx2, qy2, alpha_half,
      *map(_row_tiles, (cand_x, cand_y, cand_z)))


def _near_weight_kernel_rows(tiles_ref, nt_ref, rect_ref, qx_ref, qy_ref, ah_ref,
                             px_ref, py_ref, pz_ref, pc_ref, _sw_in, _swz_in, _md_in,
                             _hz_in, sw_ref, swz_ref, md_ref, hz_ref, x_row, y_row, z_row,
                             *acc, tile, max_tiles, m_real):
    """Near-field weight sweep over a block's CSR row runs, read in place.

    The walk is :func:`_knn_kernel_skip`'s.  Each step writes its tile's
    points to the ``(1, tile)`` rows ``x_row/y_row/z_row``, a lane that
    :func:`_row_run_step` finds outside the block's near rectangle as the
    coordinate sentinel (``d2 = +inf``, weight 0, never a hit) with
    ``z = 0`` (the blocks run past the arrays' ends, where z is not data),
    and folds them with :func:`sweep_step` into the accumulators of
    :func:`_near_weight_kernel`.
    """
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        sweep_init(acc)

    @pl.when(j < nt_ref[i])
    def _accumulate():
        lanes, inside = _row_run_step(tiles_ref, rect_ref, pc_ref, i, j, tile=tile,
                                      max_tiles=max_tiles, m_real=m_real)
        big = coord_sentinel(x_row.dtype)
        x_row[...] = jnp.where(inside, lanes(px_ref), big)
        y_row[...] = jnp.where(inside, lanes(py_ref), big)
        z_row[...] = jnp.where(inside, lanes(pz_ref), jnp.zeros((), z_row.dtype))
        sweep_step(qx_ref, qy_ref, ah_ref, x_row, y_row, z_row, acc, j)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        for ref, v in zip((sw_ref, swz_ref, md_ref, hz_ref), sweep_finish(acc)):
            ref[...] = v


def phase2_near_row_runs(
    qx_s, qy_s, alpha_half, tiles, n_tiles, rects, points, *,
    tile: int, m_real: int, block_q: int, interpret: bool,
):
    """Exact near-field weight sweep over each block's CSR row runs, in place.

    qx_s/qy_s/alpha_half: (n_tot,) / (n_tot, 1), ``n_tot % block_q == 0``;
    tiles/n_tiles/rects: the near rectangles' :func:`row_run_tiles` table,
    counts (0 skips a block: its accumulators are then meaningless) and
    ``(nb, 4)`` int32 bounds; points: the grid's ``(pt_x, pt_y, pt_z,
    point_cells)``.  Launches: :func:`_row_run_chunks`; the ``_*_in``
    refs of the kernel are the output buffers themselves (aliased, never
    read), so the chunks write one set of buffers.

    Returns ``(sum_w, sum_wz, min_d2, hit_z)``, each ``(n_tot, 1)``: the
    accumulators :func:`phase2_near_weights` returns for the same point
    set, summed in row-run order.
    """
    dtype = qx_s.dtype
    nb, max_tiles = tiles.shape
    qx2, qy2 = qx_s[:, None], qy_s[:, None]
    outs = [jnp.zeros((nb * block_q, 1), dtype) for _ in range(4)]
    for b0, b1, steps, q_map, p_map in _row_run_chunks(tiles, n_tiles, tile):
        q_spec = pl.BlockSpec((block_q, 1), q_map)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b1 - b0, steps),
            in_specs=[q_spec] * 3 + [pl.BlockSpec((_ROW_BLOCK,), p_map)] * 4
            + [pl.BlockSpec(memory_space=pl.ANY)] * 4,
            out_specs=[q_spec] * 4,
            scratch_shapes=[pltpu.VMEM((1, tile), dtype) for _ in range(3)]
            + sweep_scratch(block_q, tile, dtype),
        )
        outs = pl.pallas_call(
            functools.partial(_near_weight_kernel_rows, tile=tile, max_tiles=max_tiles,
                              m_real=m_real),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(o.shape, dtype) for o in outs],
            input_output_aliases={10: 0, 11: 1, 12: 2, 13: 3},
            compiler_params=_SEMANTICS,
            interpret=interpret,
            name="_near_weight_kernel_rows",
        )(tiles[b0:b1].reshape(-1), n_tiles[b0:b1], rects[b0:b1].reshape(-1), qx2, qy2,
          alpha_half, *points, *outs)
    return tuple(outs)


def _far_node_kernel(nt_ref, qx_ref, qy_ref, ah_ref, fx_ref, fy_ref,
                     fcnt_ref, fzs_ref, fmx_ref, fmy_ref,
                     sw_ref, swz_ref, acc_w, acc_wz):
    """Quadtree far-field level sweep: one aggregate + DIPOLE term per
    closed node of the block's row of the level (DESIGN.md §8).

    The monopole terms are ``count * w`` / ``z_sum * w`` at the centroid
    distance; the dipole adds ``grad w(cent) . M`` with
    ``M = (mx, my)`` the node's stored first z-moment about its centroid:
    for ``w(p) = |q - p|^-a``, ``grad_p w = a |q - p|^(-a-2) (q - p)``, so
    the term is ``a * w / d2 * ((qx-cx) mx + (qy-cy) my)`` — it cancels the
    z budget's first-order error, which is what makes the plan's quadtree
    bound second-order.  A node the block does not close, and a pad slot,
    is the sentinel node: centroid at the coordinate sentinel (``d2``
    overflows to +inf, ``w = 0``, ``w / d2 = 0``) and zero count/z-sum/
    moment, so it adds exactly 0 to both accumulators.  Steps past ``nt_ref[i]`` are
    clamped revisits with the accumulation predicated off, same tile-table
    discipline as the near kernel.
    """
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_w[...] = jnp.zeros(acc_w.shape, acc_w.dtype)
        acc_wz[...] = jnp.zeros(acc_wz.shape, acc_wz.dtype)

    @pl.when(j < nt_ref[i])
    def _accumulate():
        dqx = qx_ref[...] - fx_ref[...]
        dqy = qy_ref[...] - fy_ref[...]
        d2 = dqx * dqx + dqy * dqy
        ah = ah_ref[...]
        w = pow_weight(d2, ah)
        tiny = jnp.asarray(1e-30 if d2.dtype == jnp.float32 else 1e-290, d2.dtype)
        grad = (2.0 * ah) * w / jnp.maximum(d2, tiny)
        dip = grad * (dqx * fmx_ref[...] + dqy * fmy_ref[...])
        acc_w[...] += jnp.sum(w * fcnt_ref[...], axis=1, keepdims=True)
        acc_wz[...] += jnp.sum(w * fzs_ref[...] + dip, axis=1, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        sw_ref[...] = acc_w[...]
        swz_ref[...] = acc_wz[...]


def phase2_far_nodes(
    qx_s, qy_s, alpha_half, node_x, node_y, node_cnt, node_zs, node_mx,
    node_my, num_tiles, *, block_q: int, block_d: int, interpret: bool,
):
    """One quadtree level's far sweep over per-block gathered node tables.

    qx_s/qy_s/alpha_half: (n_tot,) / (n_tot, 1), ``n_tot % block_q == 0``;
    node_*: (nb, k_pad) per-block node aggregates — the engine passes the
    level's nodes with those a block does not close set to the sentinel
    node — ``k_pad % block_d == 0``; num_tiles: (nb,) int32 tiles each
    block walks (0 skips it).

    Returns ``(sum_w_far, sum_wz_far)``, each ``(n_tot, 1)`` — the engine
    accumulates them across levels before the near/far combine.
    """
    n_tot = qx_s.shape[0]
    nb, k_pad = node_x.shape
    dtype = qx_s.dtype
    qx2, qy2 = qx_s[:, None], qy_s[:, None]
    q_spec = pl.BlockSpec((block_q, 1), _pf_query_map)
    c_spec = _row_spec(block_d, _pf_clamped_tile_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, k_pad // block_d),
        in_specs=[q_spec, q_spec, q_spec] + [c_spec] * 6,
        out_specs=[q_spec] * 2,
        scratch_shapes=[pltpu.VMEM((block_q, 1), dtype) for _ in range(2)],
    )
    return pl.pallas_call(
        _far_node_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_tot, 1), dtype)] * 2,
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="_far_node_kernel",
    )(num_tiles.astype(jnp.int32), qx2, qy2, alpha_half,
      *map(_row_tiles, (node_x, node_y, node_cnt, node_zs, node_mx, node_my)))


def phase2_weights_full(
    qx_s, qy_s, alpha, dxp, dyp, dzp, *,
    eps: float, block_q: int, block_d: int, interpret: bool,
):
    """Phase 2: full-data weighted sweep (AIDW weights ALL m points).

    dxp/dyp/dzp: (1, mp) sentinel-padded data, ``mp % block_d == 0``.
    Returns z_hat, shape ``(n_tot, 1)``.
    """
    return weight_sweep_soa(qx_s[:, None], qy_s[:, None], alpha * 0.5, dxp, dyp, dzp,
                            eps=eps, block_q=block_q, block_d=block_d, interpret=interpret)
