"""Uniform-grid spatial partition for fast kNN — the Phase-1 accelerator.

The paper's AIDW Phase 1 computes ``r_obs`` (mean distance to the k nearest
data points) by brute-force scanning all m data points per query.  The
follow-up work (arXiv:1601.05904, "Improving GPU-accelerated Adaptive IDW
Interpolation Algorithm Using Fast kNN Search") replaces that scan with a
uniform grid: bucket the data points into ``gx x gy`` cells, then search
outward from the query's home cell in expanding Chebyshev rings until the
running kth-best distance proves no unvisited cell can hold a closer point.

Layout (DESIGN.md §4): points are sorted by cell id and scattered into a
*padded* ``(n_cells + 1, cap)`` array (``cap`` = max cell occupancy).  Empty
slots hold a large sentinel coordinate whose squared distance overflows to
+inf, so they can never enter a k-best set; row ``n_cells`` is an
all-sentinel row used as the gather target for out-of-grid / masked cell
ids — every gather is in-bounds and branch-free.  A ``(gy+1, gx+1)``
integral image of the occupancy counts answers "how many points in the
(2r+1)^2 block around cell C" in O(1), which powers both the empty-ring
skip of :func:`grid_knn` and the occupancy-only :func:`safe_radius` bound
used by the Pallas grid kernel.

Ring-search invariant (the correctness contract, exercised by the property
tests): for a query whose *clamped* home cell is C, every point in a cell
at Chebyshev distance ``c`` from C lies at Euclidean distance
``>= (c - 1) * min(cell_w, cell_h)`` from the query.  Hence once rings
``0..r`` are merged, the search may stop as soon as
``kth_best^2 <= (r * min(cell_w, cell_h))^2`` — all unvisited cells are at
Chebyshev ``>= r + 1``.  The bound survives queries *outside* the grid:
clamping the home cell only ever moves it toward the query along each axis,
so per-axis gaps to other cells only grow.

Everything below is pure jnp + lax (no Pallas) so it lowers identically
under jit, eagerly, and in interpret-mode comparisons.  ``build_grid`` is
the one eager-only entry point: the padded capacity is data-dependent.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.knn import running_k_best
from repro.core.layouts import coord_sentinel  # re-export: the one sentinel definition

# Default mean points-per-cell the auto-resolution aims for.  ~16 keeps the
# home 3x3 block at ~144 expected points — comfortably above the paper's
# k=10 — while cells stay small enough that the stop bound fires on ring 1.
DEFAULT_OCCUPANCY = 16.0

# Cells per axis are clamped here: beyond this the integral image and the
# per-ring bookkeeping start to dominate the win over brute force.
MAX_CELLS_PER_AXIS = 512


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class UniformGrid:
    """Padded uniform-grid bucketing of an attributed 2-D point set.

    Attributes:
      gx, gy: cells per axis (static).
      cap: padded per-cell capacity = max occupancy (static).
      origin: (2,) lower-left corner ``(x0, y0)``.
      cell_size: (2,) ``(cell_w, cell_h)``.
      cell_x, cell_y, cell_z: ``(gx*gy + 1, cap)`` padded per-cell point
        data; coordinate pad slots hold the +inf-overflow sentinel, ``z``
        pad slots hold 0.  The final row is all-sentinel (masked gathers).
      counts: ``(gy, gx)`` int32 occupancy.
      cum: ``(gy+1, gx+1)`` int32 integral image of ``counts``.
      pt_x, pt_y, pt_z: ``(m + 1,)`` CSR twin of the padded layout — the
        points sorted by cell id, with one trailing sentinel slot (index
        ``m``) so masked gathers stay in-bounds.  Cell ``c`` owns the
        contiguous run ``pt_*[starts[c]:starts[c+1]]``; a *row* of cells
        ``(y, xlo..xhi)`` is likewise one contiguous run — the property the
        static-shape candidate gather of ``repro.engine`` exploits.
      starts: ``(gx*gy + 1,)`` int32 CSR row pointers into ``pt_*``.
    """

    gx: int
    gy: int
    cap: int
    origin: jnp.ndarray
    cell_size: jnp.ndarray
    cell_x: jnp.ndarray
    cell_y: jnp.ndarray
    cell_z: jnp.ndarray
    counts: jnp.ndarray
    cum: jnp.ndarray
    pt_x: jnp.ndarray
    pt_y: jnp.ndarray
    pt_z: jnp.ndarray
    starts: jnp.ndarray

    @property
    def n_cells(self) -> int:
        return self.gx * self.gy

    @property
    def n_points(self) -> int:
        return self.pt_x.shape[0] - 1

    @functools.cached_property
    def point_cells(self) -> jnp.ndarray:
        """``(n_points,)`` int32 cell of each CSR point, packed ``cy << 16 |
        cx`` — what the row-run Phase 1 masks its lanes by.  Computed on
        first use and kept with the grid, so every plan of one grid shares
        it."""
        if self.gx > 0xFFFF or self.gy > 0x7FFF:
            raise ValueError(f"grid {self.gx}x{self.gy} is too large to pack a cell "
                             "into one int32 (gx <= 65535, gy <= 32767)")
        return _packed_point_cells(self.starts, self.gx, self.n_points)

    def tree_flatten(self):
        children = (self.origin, self.cell_size, self.cell_x, self.cell_y,
                    self.cell_z, self.counts, self.cum, self.pt_x, self.pt_y,
                    self.pt_z, self.starts)
        return children, (self.gx, self.gy, self.cap)

    @classmethod
    def tree_unflatten(cls, aux, children):
        gx, gy, cap = aux
        return cls(gx, gy, cap, *children)




@functools.partial(jax.jit, static_argnums=(1, 2))
def _packed_point_cells(starts, gx: int, m: int):
    cid = jnp.searchsorted(starts, jnp.arange(m, dtype=jnp.int32), side="right") - 1
    return jnp.left_shift(cid // gx, 16) | (cid % gx)


def build_grid(
    dx, dy, dz=None, *,
    gx: int | None = None,
    gy: int | None = None,
    target_occupancy: float = DEFAULT_OCCUPANCY,
    bounds: tuple[float, float, float, float] | None = None,
) -> UniformGrid:
    """Bucket points into a uniform grid with a ragged-to-padded cell layout.

    Eager-only (the padded capacity is ``max(counts)``, a concrete value);
    call it once per dataset outside jit and pass the resulting pytree into
    jitted consumers.

    Args:
      dx, dy: (m,) point coordinates.  dz: optional (m,) attribute.
      gx, gy: cells per axis; default ``ceil(sqrt(m / target_occupancy))``
        per axis, clamped to [1, 512].
      bounds: ``(x0, x1, y0, y1)`` grid extent; defaults to the data bbox.
    """
    m = int(dx.shape[0])
    dtype = jnp.asarray(dx).dtype
    if dz is None:
        dz = jnp.zeros((m,), dtype)
    if bounds is None:
        x0, x1 = float(jnp.min(dx)), float(jnp.max(dx))
        y0, y1 = float(jnp.min(dy)), float(jnp.max(dy))
    else:
        x0, x1, y0, y1 = map(float, bounds)
    if gx is None or gy is None:
        g = max(1, min(MAX_CELLS_PER_AXIS, math.ceil(math.sqrt(m / max(target_occupancy, 1e-9)))))
        gx = gx or g
        gy = gy or g
    # degenerate spans (all points on a line/point) still need a positive cell
    span_x = max(x1 - x0, 1e-12)
    span_y = max(y1 - y0, 1e-12)
    origin = jnp.asarray([x0, y0], jnp.float32)
    cell_size = jnp.asarray([span_x / gx, span_y / gy], jnp.float32)

    n_cells = gx * gy
    cx = jnp.clip(jnp.floor((jnp.asarray(dx) - x0) / cell_size[0]).astype(jnp.int32), 0, gx - 1)
    cy = jnp.clip(jnp.floor((jnp.asarray(dy) - y0) / cell_size[1]).astype(jnp.int32), 0, gy - 1)
    cid = cy * gx + cx

    counts_flat = jnp.zeros((n_cells,), jnp.int32).at[cid].add(1)
    cap = max(int(jnp.max(counts_flat)), 1)

    order = jnp.argsort(cid, stable=True)
    cid_s = cid[order]
    starts = jnp.searchsorted(cid_s, jnp.arange(n_cells + 1, dtype=cid_s.dtype)).astype(jnp.int32)
    rank = jnp.arange(m, dtype=jnp.int32) - starts[cid_s]

    big = coord_sentinel(dtype)
    dx_s, dy_s, dz_s = jnp.asarray(dx)[order], jnp.asarray(dy)[order], jnp.asarray(dz)[order]
    cell_x = jnp.full((n_cells + 1, cap), big, dtype).at[cid_s, rank].set(dx_s)
    cell_y = jnp.full((n_cells + 1, cap), big, dtype).at[cid_s, rank].set(dy_s)
    cell_z = jnp.zeros((n_cells + 1, cap), dtype).at[cid_s, rank].set(dz_s)
    # CSR twin: sorted points + row pointers, one trailing sentinel slot
    pt_x = jnp.concatenate([dx_s, jnp.full((1,), big, dtype)])
    pt_y = jnp.concatenate([dy_s, jnp.full((1,), big, dtype)])
    pt_z = jnp.concatenate([dz_s, jnp.zeros((1,), dtype)])

    counts = counts_flat.reshape(gy, gx)
    cum = jnp.zeros((gy + 1, gx + 1), jnp.int32)
    cum = cum.at[1:, 1:].set(jnp.cumsum(jnp.cumsum(counts, axis=0), axis=1))
    return UniformGrid(gx, gy, cap, origin, cell_size, cell_x, cell_y, cell_z,
                       counts, cum, pt_x, pt_y, pt_z, starts)


def cell_of(grid: UniformGrid, x, y):
    """Clamped home-cell indices ``(cx, cy)`` for query coordinates."""
    cx = jnp.clip(jnp.floor((x - grid.origin[0]) / grid.cell_size[0]).astype(jnp.int32), 0, grid.gx - 1)
    cy = jnp.clip(jnp.floor((y - grid.origin[1]) / grid.cell_size[1]).astype(jnp.int32), 0, grid.gy - 1)
    return cx, cy


def block_count(grid: UniformGrid, cx, cy, r):
    """Points inside the (2r+1)^2 cell block centred at ``(cx, cy)``, O(1)
    via the integral image.  All args broadcastable int32."""
    xlo = jnp.clip(cx - r, 0, grid.gx)
    xhi = jnp.clip(cx + r + 1, 0, grid.gx)
    ylo = jnp.clip(cy - r, 0, grid.gy)
    yhi = jnp.clip(cy + r + 1, 0, grid.gy)
    c = grid.cum
    return c[yhi, xhi] - c[ylo, xhi] - c[yhi, xlo] + c[ylo, xlo]


def cover_radius(grid: UniformGrid, cx, cy):
    """Ring radius at which the block around ``(cx, cy)`` covers the grid."""
    return jnp.maximum(
        jnp.maximum(cx, grid.gx - 1 - cx), jnp.maximum(cy, grid.gy - 1 - cy)
    )


def _ring_cell_offset(r, i):
    """Decode perimeter index ``i in [0, 8r)`` of Chebyshev ring ``r`` into a
    cell offset ``(ox, oy)``; ring 0 is the single home cell."""
    rr = jnp.maximum(r, 1)
    side = i // (2 * rr)
    t = i % (2 * rr)
    ox = jnp.where(side == 0, -rr + t, jnp.where(side == 1, rr, jnp.where(side == 2, rr - t, -rr)))
    oy = jnp.where(side == 0, -rr, jnp.where(side == 1, -rr + t, jnp.where(side == 2, rr, rr - t)))
    ox = jnp.where(r == 0, 0, ox)
    oy = jnp.where(r == 0, 0, oy)
    return ox, oy


@functools.partial(jax.jit, static_argnames=("k",))
def grid_knn(grid: UniformGrid, qx, qy, k: int, active=None):
    """Exact k nearest neighbours via expanding ring search.

    Returns ``(n, k)`` squared distances, ascending.  If the grid holds
    fewer than ``k`` points the tail is +inf (callers validate ``m >= k``).

    Batched: one global ``while_loop``; each iteration folds ONE cell of the
    current ring into every live query's k-best set (a ``(k, k+cap)``
    branch-free merge), with two shortcuts driven by the integral image:
    entirely-empty rings complete in a single iteration, and a query stops
    as soon as the ring bound proves its k-best is final (see module
    docstring for the invariant).

    ``active`` (optional bool ``(n,)``) masks the search to a subset of
    queries: inactive queries start ``done`` (their rows stay +inf) and add
    no loop iterations, so the cost is bounded by the *active* queries'
    ring work — an all-inactive batch exits in zero iterations.  This is
    what the engine's per-block overflow blend uses to ring-search only the
    queries whose block exceeded the plan's static candidate capacity.
    """
    n = qx.shape[0]
    dtype = qx.dtype
    gx, gy = grid.gx, grid.gy
    cx, cy = cell_of(grid, qx, qy)
    cell_min = jnp.minimum(grid.cell_size[0], grid.cell_size[1]).astype(dtype)
    r_cover = cover_radius(grid, cx, cy)
    qxc, qyc = qx[:, None], qy[:, None]

    def cond(state):
        return ~jnp.all(state[3])

    def body(state):
        best, r, i, done = state
        ring_n = jnp.where(r == 0, 1, 8 * r)
        inner = jnp.where(r > 0, block_count(grid, cx, cy, r - 1), 0)
        ring_cnt = block_count(grid, cx, cy, r) - inner
        at_end = i >= ring_n
        skip = (ring_cnt == 0) & (i == 0)  # whole ring empty: complete in one step
        scan_now = (~done) & (~at_end) & (~skip)

        ox, oy = _ring_cell_offset(r, i)
        ccx, ccy = cx + ox, cy + oy
        valid = scan_now & (ccx >= 0) & (ccx < gx) & (ccy >= 0) & (ccy < gy)
        cid = jnp.where(valid, ccy * gx + ccx, grid.n_cells)  # sentinel row
        px = grid.cell_x[cid]
        py = grid.cell_y[cid]
        d2 = (qxc - px) ** 2 + (qyc - py) ** 2  # pad slots overflow to +inf
        best = jnp.where(scan_now[:, None], running_k_best(best, d2), best)

        completing = (~done) & (at_end | skip)
        kth = best[:, k - 1]
        bound = r.astype(dtype) * cell_min
        stop = completing & ((kth <= bound * bound) | (r >= r_cover))
        done = done | stop
        adv = completing & (~stop)
        r = jnp.where(adv, r + 1, r)
        i = jnp.where(adv, 0, jnp.where(scan_now, i + 1, i))
        return best, r, i, done

    done0 = jnp.zeros((n,), bool) if active is None else ~active
    state = (
        jnp.full((n, k), jnp.inf, dtype),
        jnp.zeros((n,), jnp.int32),
        jnp.zeros((n,), jnp.int32),
        done0,
    )
    best, _, _, _ = jax.lax.while_loop(cond, body, state)
    return best


@functools.partial(jax.jit, static_argnames=("k",))
def grid_r_obs(grid: UniformGrid, qx, qy, k: int, active=None):
    """Phase-1 statistic: mean distance to the k nearest data points.
    Inactive queries (see :func:`grid_knn`) return +inf."""
    return jnp.mean(jnp.sqrt(grid_knn(grid, qx, qy, k, active)), axis=1)


def required_radius(grid: UniformGrid, cx, cy, k: int):
    """Smallest ring radius whose (2r+1)^2 block holds >= k points (or the
    whole grid).  Occupancy-only — O(max radius) integral-image lookups."""
    n = cx.shape[0]
    want = jnp.minimum(k, grid.cum[-1, -1])
    r_cover = cover_radius(grid, cx, cy)

    def cond(state):
        return ~jnp.all(state[1])

    def body(state):
        r, found = state
        ok = (block_count(grid, cx, cy, r) >= want) | (r >= r_cover)
        return jnp.where(found | ok, r, r + 1), found | ok

    r, _ = jax.lax.while_loop(
        cond, body, (jnp.zeros((n,), jnp.int32), jnp.zeros((n,), bool))
    )
    return r


def safe_radius(grid: UniformGrid, qx, qy, k: int):
    """Ring radius guaranteed (from occupancy alone, no distances) to contain
    the true k nearest neighbours of the query at ``(qx, qy)``.

    With ``r_need`` from :func:`required_radius`, every point of that block
    is within ``Dx = ex + (r_need + 1) * cell_w`` / ``Dy = ...`` of the
    query per axis, where ``(ex, ey)`` is the query's overhang beyond its
    clamped home cell (0 inside the grid) — so the kth-NN distance is
    ``<= D = sqrt(Dx^2 + Dy^2)``.  Conversely every cell at Chebyshev ``c``
    is at distance ``>= sqrt(ex^2 + ey^2 + ((c-1) * cell_min)^2)`` (the
    overhang adds to the axis gap for every in-grid cell), so only cells
    with ``(c - 1) * cell_min < sqrt(D^2 - e^2)`` can matter.  For in-grid
    queries this reduces to the plain ``(r_need + 1) * diag`` bound; for
    out-of-grid queries the overhang correction is what keeps the guarantee
    sound (the naive bound misses neighbours once the query is more than
    about a cell outside the bbox).  Used by the Pallas grid kernel, whose
    candidate neighbourhoods must be fixed before any distance is computed.

    Returns ``(cx, cy, r_safe)`` (the clamped home cells are needed by every
    caller anyway).
    """
    cx, cy = cell_of(grid, qx, qy)
    r_need = required_radius(grid, cx, cy, k)
    return cx, cy, safe_radius_from_need(grid, qx, qy, cx, cy, r_need)


def safe_radius_from_need(grid: UniformGrid, qx, qy, cx, cy, r_need):
    """The closed-form half of :func:`safe_radius`: given each query's
    clamped home cell and its occupancy-only ``required_radius``, return the
    containment-safe ring radius.  Split out so jitted consumers (the
    plan/execute engine) can replace the ``required_radius`` while-loop with
    a plan-time per-cell table lookup and keep the overhang correction
    exact for out-of-grid queries."""
    cw, ch = grid.cell_size[0], grid.cell_size[1]
    cmin = jnp.minimum(cw, ch)
    # per-axis overhang beyond the clamped home cell's span (0 inside)
    x_lo = grid.origin[0] + cx.astype(cw.dtype) * cw
    y_lo = grid.origin[1] + cy.astype(ch.dtype) * ch
    ex = jnp.maximum(jnp.maximum(x_lo - qx, qx - (x_lo + cw)), 0.0).astype(jnp.float32)
    ey = jnp.maximum(jnp.maximum(y_lo - qy, qy - (y_lo + ch)), 0.0).astype(jnp.float32)
    dx_bound = ex + (r_need.astype(jnp.float32) + 1.0) * cw
    dy_bound = ey + (r_need.astype(jnp.float32) + 1.0) * ch
    slack = jnp.sqrt(jnp.maximum(dx_bound * dx_bound + dy_bound * dy_bound
                                 - ex * ex - ey * ey, 0.0))
    r_safe = jnp.floor(slack / cmin).astype(jnp.int32) + 1
    return jnp.clip(jnp.maximum(r_safe, r_need), 0, cover_radius(grid, cx, cy))


def required_radius_table(grid: UniformGrid, k: int):
    """``(gy, gx)`` int32 table of :func:`required_radius` for every cell.

    Occupancy-only, so it depends on the data alone — computed once at plan
    time (eagerly) and looked up per query inside the traced execute step,
    replacing the data-dependent while-loop on the hot path."""
    ys, xs = jnp.meshgrid(
        jnp.arange(grid.gy, dtype=jnp.int32),
        jnp.arange(grid.gx, dtype=jnp.int32),
        indexing="ij",
    )
    return required_radius(grid, xs.reshape(-1), ys.reshape(-1), k).reshape(grid.gy, grid.gx)


def static_cell_radius(grid: UniformGrid, r_need_table):
    """Per-cell safe ring radius for a query anywhere *inside* the cell
    (overhang 0) — the worst case the plan's static candidate capacity must
    cover for in-bbox queries.  Vectorised twin of the in-grid branch of
    :func:`safe_radius_from_need`."""
    cw, ch = grid.cell_size[0], grid.cell_size[1]
    cmin = jnp.minimum(cw, ch)
    rf = r_need_table.astype(jnp.float32) + 1.0
    slack = jnp.sqrt((rf * cw) ** 2 + (rf * ch) ** 2)
    r_safe = jnp.floor(slack / cmin).astype(jnp.int32) + 1
    ys, xs = jnp.meshgrid(
        jnp.arange(grid.gy, dtype=jnp.int32),
        jnp.arange(grid.gx, dtype=jnp.int32),
        indexing="ij",
    )
    return jnp.clip(jnp.maximum(r_safe, r_need_table), 0, cover_radius(grid, xs, ys))


def seam_segment_ids(grid: UniformGrid, cx, cy, level: int):
    """Morton quadrant id (``0 .. 4**level - 1``) of each home cell.

    ``level`` recursive quadrant splits of the (power-of-two ceiling of the)
    grid: the id is the Morton interleave of the top ``level`` bits of each
    cell axis, i.e. exactly ``morton_ids(cx, cy) >> 2*(nbits - level)``.
    Because those are the *most significant* bits of the full Morton id, the
    segment id is nondecreasing along any Morton-sorted cell order — a
    Morton-sorted query batch is already segment-contiguous, which is what
    :func:`seam_layout` relies on to split query blocks at seams.
    """
    if level <= 0:
        return jnp.zeros(jnp.shape(cx), jnp.int32)
    nbits = max((max(grid.gx, grid.gy) - 1).bit_length(), level)
    shift = nbits - level
    return morton_ids(cx >> shift, cy >> shift)


def seam_layout(seg_sorted, n_segments: int, block_q: int, n_slots: int):
    """Block layout that never straddles a Morton seam — gather/scatter maps.

    A Morton-contiguous block of ``block_q`` queries that straddles a
    top-level Z-order quadrant boundary has home cells on *both* sides of
    the grid's centre cross, so its candidate rectangle approaches full grid
    width and blows past any sane static capacity (the measured m=100K
    overflow in ROADMAP.md).  The fix: pad each seam segment up to a
    multiple of ``block_q`` so block boundaries coincide with segment
    boundaries.

    Args:
      seg_sorted: ``(n_tot,)`` int32 nondecreasing segment id per
        Morton-sorted query (from :func:`seam_segment_ids`).
      n_segments: static segment-id bound (``4**level``).
      n_slots: static output length; any value ``>= n_tot +
        n_segments * block_q`` (the worst-case padding) works.

    Returns ``(src, dest)``: ``src (n_slots,)`` gathers the sorted arrays
    into the split layout — slots past a segment's true count repeat the
    segment's *last* query (the ``pad_tail`` trick, kept local to the
    segment so pad blocks have one-cell rectangles), and slots past the last
    segment repeat the final query.  ``dest (n_tot,)`` is each sorted
    query's slot (``src[dest[i]] == i``), for mapping per-slot results back.
    """
    n_tot = seg_sorted.shape[0]
    zero = jnp.zeros((1,), jnp.int32)
    counts = jnp.zeros((n_segments,), jnp.int32).at[seg_sorted].add(1)
    starts = jnp.concatenate([zero, jnp.cumsum(counts)])
    padded = jnp.concatenate([zero, jnp.cumsum(-(-counts // block_q) * block_q)])
    d = jnp.arange(n_slots, dtype=jnp.int32)
    seg_of = jnp.clip(jnp.searchsorted(padded, d, side="right").astype(jnp.int32) - 1,
                      0, n_segments - 1)
    within = d - padded[seg_of]
    src = starts[seg_of] + jnp.minimum(within, jnp.maximum(counts[seg_of] - 1, 0))
    src = jnp.minimum(src, n_tot - 1)  # trailing slots (and empty tail segments)
    dest = padded[seg_sorted] + jnp.arange(n_tot, dtype=jnp.int32) - starts[seg_sorted]
    return src, dest


class CellAggregates(NamedTuple):
    """Per-cell aggregates over a grid's point set (plan-time): level 0 of
    the quadtree (:func:`quadtree_aggregates`).

    One entry per real cell (``n_cells``): the point count, the z-sum and
    the centroid of the cell's points.  Empty cells get their *geometric*
    centre as centroid (count and z-sum are 0, so the value never matters —
    but a finite coordinate keeps the far kernel's weight finite instead of
    manufacturing inf·0).
    """

    cent_x: jnp.ndarray  # (n_cells,) centroid x (cell centre when empty)
    cent_y: jnp.ndarray  # (n_cells,)
    count: jnp.ndarray   # (n_cells,) point count, data dtype (kernel operand)
    z_sum: jnp.ndarray   # (n_cells,) sum of z over the cell's points


def cell_aggregates(grid: UniformGrid) -> CellAggregates:
    """Compute :class:`CellAggregates` from the padded cell layout."""
    nc = grid.n_cells
    dtype = grid.pt_x.dtype
    big = coord_sentinel(dtype)
    cx_cells = grid.cell_x[:nc]  # (nc, cap), pad slots hold the sentinel
    cy_cells = grid.cell_y[:nc]
    mask = cx_cells < big / 2
    cnt = grid.counts.reshape(-1).astype(dtype)
    denom = jnp.maximum(cnt, 1.0)
    sum_x = jnp.sum(jnp.where(mask, cx_cells, 0.0), axis=1)
    sum_y = jnp.sum(jnp.where(mask, cy_cells, 0.0), axis=1)
    ix = (jnp.arange(nc, dtype=jnp.int32) % grid.gx).astype(jnp.int32)
    iy = (jnp.arange(nc, dtype=jnp.int32) // grid.gx).astype(jnp.int32)
    centre_x = (grid.origin[0] + (ix.astype(dtype) + 0.5) * grid.cell_size[0]).astype(dtype)
    centre_y = (grid.origin[1] + (iy.astype(dtype) + 0.5) * grid.cell_size[1]).astype(dtype)
    cent_x = jnp.where(cnt > 0, sum_x / denom, centre_x)
    cent_y = jnp.where(cnt > 0, sum_y / denom, centre_y)
    z_sum = jnp.sum(grid.cell_z[:nc], axis=1)  # pad slots hold 0
    return CellAggregates(cent_x, cent_y, cnt, z_sum)


class QuadtreeLevel(NamedTuple):
    """One level of the far-field quadtree (plan-time, DESIGN.md §8).

    Level 0 is the grid's cells themselves; level ``l`` nodes cover
    ``2**l x 2**l`` cells (edge nodes cover the clipped remainder).  Each
    level is one flat padded array set of ``nx * ny`` nodes in row-major
    node order — no pointers, so the whole pyramid is a static-shape
    pytree the plan can carry.

    Per node: point ``count``, ``z_sum``, points centroid (``cent_x/y``,
    geometric node centre when empty), the FIRST z-moment about the
    centroid ``(mx, my) = sum_j z_j * (p_j - cent)`` (the dipole term that
    cancels the z budget's first-order error — DESIGN.md §8), ``e`` (an
    upper bound on the max point-to-centroid distance: exact at level 0,
    combined upward as ``max_children(|cent_child - cent| + e_child)``)
    and ``zd`` (same upward bound for the max |z_j - node z-mean|).

    ``e_max`` / ``zd_max`` are the level maxima as concrete floats — the
    plan's level-selection table is built from them.
    """

    nx: int              # nodes along x (= ceil(gx / 2**level))
    ny: int              # nodes along y
    step: int            # cells per node side (= 2**level)
    cent_x: jnp.ndarray  # (nx*ny,) points centroid (node centre when empty)
    cent_y: jnp.ndarray
    count: jnp.ndarray   # (nx*ny,) point count, data dtype (kernel operand)
    z_sum: jnp.ndarray   # (nx*ny,)
    mx: jnp.ndarray      # (nx*ny,) first z-moment about the centroid, x
    my: jnp.ndarray      # (nx*ny,) ... y
    e: jnp.ndarray       # (nx*ny,) per-node dispersion radius (upper bound)
    zd: jnp.ndarray      # (nx*ny,) per-node z-spread (upper bound)
    e_max: float         # max of e over the level's nonempty nodes
    zd_max: float        # max of zd over the level's nonempty nodes


def quadtree_level_count(gx: int, gy: int) -> int:
    """Static level count for :func:`quadtree_aggregates` — derived from the
    grid resolution alone: coarsen by 2x per level until at most 2 nodes
    remain per axis (a coarser root is never closeable: its opening gap
    would exceed the grid)."""
    levels = 1
    g = max(gx, gy)
    while (g + 1) // 2 > 2 and (1 << (levels - 1)) < g:
        g = (g + 1) // 2
        levels += 1
    return levels


def _node_centres(grid: UniformGrid, nx: int, ny: int, step: int, dtype):
    """Geometric centres of level nodes (used for empty nodes only)."""
    jx = jnp.arange(nx, dtype=jnp.int32)
    jy = jnp.arange(ny, dtype=jnp.int32)
    x_mid = 0.5 * (jx * step + jnp.minimum((jx + 1) * step, grid.gx)).astype(dtype)
    y_mid = 0.5 * (jy * step + jnp.minimum((jy + 1) * step, grid.gy)).astype(dtype)
    cx = (grid.origin[0] + x_mid * grid.cell_size[0]).astype(dtype)
    cy = (grid.origin[1] + y_mid * grid.cell_size[1]).astype(dtype)
    return (jnp.broadcast_to(cx[None, :], (ny, nx)),
            jnp.broadcast_to(cy[:, None], (ny, nx)))


def _pad_even(a, ny, nx, fill=0.0):
    """Pad a (ny, nx) level image to even dims with ``fill`` (empty nodes)."""
    return jnp.pad(a, ((0, ny % 2), (0, nx % 2)), constant_values=fill)


def quadtree_aggregates(grid: UniformGrid) -> tuple[QuadtreeLevel, ...]:
    """Bottom-up quadtree of far-field aggregates over the grid's points.

    Eager-only by convention (plan time, like :func:`build_grid`):
    the per-level ``e_max`` / ``zd_max`` are concrete floats for the plan's
    level-selection table.  Level 0 is computed exactly from the padded
    cell layout; each coarser level combines 2x2 children with the exact
    reductions for count / z-sum / centroid / z-moment (the property the
    hypothesis re-aggregation test pins: a NumPy reduction of level ``l``
    reproduces level ``l+1`` bit for bit) and conservative upward bounds
    for the dispersion and z-spread radii:

        e_parent  = max over nonempty children of |cent_c - cent| + e_c
        zd_parent = max over nonempty children of |zbar_c - zbar| + zd_c

    The z-moment combination is exact because ``sum_{j in c} z_j (p_j -
    cent) = m_c + s_c (cent_c - cent)`` for each child c (``m_c`` its own
    moment, ``s_c`` its z-sum).
    """
    nc = grid.n_cells
    dtype = grid.pt_x.dtype
    big = coord_sentinel(dtype)
    agg = cell_aggregates(grid)
    cx_cells = grid.cell_x[:nc]
    cy_cells = grid.cell_y[:nc]
    mask = cx_cells < big / 2
    dev_x = jnp.where(mask, cx_cells - agg.cent_x[:, None], 0.0)
    dev_y = jnp.where(mask, cy_cells - agg.cent_y[:, None], 0.0)
    e0 = jnp.sqrt(jnp.max(dev_x * dev_x + dev_y * dev_y, axis=1))
    z_cells = grid.cell_z[:nc]
    mx0 = jnp.sum(jnp.where(mask, z_cells, 0.0) * dev_x, axis=1)
    my0 = jnp.sum(jnp.where(mask, z_cells, 0.0) * dev_y, axis=1)
    denom = jnp.maximum(agg.count, 1.0)
    zbar0 = agg.z_sum / denom
    zd0 = jnp.max(jnp.where(mask, jnp.abs(z_cells - zbar0[:, None]), 0.0), axis=1)

    n_levels = quadtree_level_count(grid.gx, grid.gy)
    levels = []
    nx, ny, step = grid.gx, grid.gy, 1
    cnt = agg.count.reshape(ny, nx)
    zs = agg.z_sum.reshape(ny, nx)
    ctx = agg.cent_x.reshape(ny, nx)
    cty = agg.cent_y.reshape(ny, nx)
    mx = mx0.reshape(ny, nx)
    my = my0.reshape(ny, nx)
    e = e0.reshape(ny, nx)
    zd = zd0.reshape(ny, nx)
    for level in range(n_levels):
        nonempty = cnt > 0
        e_max = float(jnp.max(jnp.where(nonempty, e, 0.0))) if nc else 0.0
        zd_max = float(jnp.max(jnp.where(nonempty, zd, 0.0))) if nc else 0.0
        levels.append(QuadtreeLevel(
            nx=nx, ny=ny, step=step,
            cent_x=ctx.reshape(-1), cent_y=cty.reshape(-1),
            count=cnt.reshape(-1), z_sum=zs.reshape(-1),
            mx=mx.reshape(-1), my=my.reshape(-1),
            e=e.reshape(-1), zd=zd.reshape(-1),
            e_max=e_max, zd_max=zd_max,
        ))
        if level == n_levels - 1:
            break
        children = [
            [_pad_even(a, ny, nx)[dy_::2, dx_::2] for a in
             (cnt, zs, ctx, cty, mx, my, e, zd)]
            for dy_, dx_ in ((0, 0), (0, 1), (1, 0), (1, 1))
        ]
        nx, ny, step = (nx + 1) // 2, (ny + 1) // 2, step * 2
        # exact reductions, fixed association order (the bitwise contract
        # of the re-aggregation test): c00 + c01 + c10 + c11
        cnt = ((children[0][0] + children[1][0]) + children[2][0]) + children[3][0]
        zs = ((children[0][1] + children[1][1]) + children[2][1]) + children[3][1]
        denom = jnp.maximum(cnt, 1.0)
        wsum_x = ((children[0][0] * children[0][2] + children[1][0] * children[1][2])
                  + children[2][0] * children[2][2]) + children[3][0] * children[3][2]
        wsum_y = ((children[0][0] * children[0][3] + children[1][0] * children[1][3])
                  + children[2][0] * children[2][3]) + children[3][0] * children[3][3]
        gx_mid, gy_mid = _node_centres(grid, nx, ny, step, dtype)
        ctx = jnp.where(cnt > 0, wsum_x / denom, gx_mid)
        cty = jnp.where(cnt > 0, wsum_y / denom, gy_mid)
        mx = sum(c[4] + c[1] * (c[2] - ctx) for c in children)
        my = sum(c[5] + c[1] * (c[3] - cty) for c in children)
        zbar = zs / denom
        e_terms = []
        zd_terms = []
        for c in children:
            dist = jnp.sqrt((c[2] - ctx) ** 2 + (c[3] - cty) ** 2)
            e_terms.append(jnp.where(c[0] > 0, dist + c[6], 0.0))
            czbar = c[1] / jnp.maximum(c[0], 1.0)
            zd_terms.append(jnp.where(c[0] > 0, jnp.abs(czbar - zbar) + c[7], 0.0))
        e = jnp.maximum(jnp.maximum(e_terms[0], e_terms[1]),
                        jnp.maximum(e_terms[2], e_terms[3]))
        zd = jnp.maximum(jnp.maximum(zd_terms[0], zd_terms[1]),
                         jnp.maximum(zd_terms[2], zd_terms[3]))
    return tuple(levels)


def morton_ids(cx, cy):
    """Morton (Z-order) interleave of cell indices — sorting queries by this
    keeps consecutive queries in spatially adjacent cells, so per-block
    candidate rectangles in the grid kernel stay compact (no row-major
    wrap-around blowup)."""

    def part1by1(v):
        v = v.astype(jnp.uint32)
        v = (v | (v << 8)) & jnp.uint32(0x00FF00FF)
        v = (v | (v << 4)) & jnp.uint32(0x0F0F0F0F)
        v = (v | (v << 2)) & jnp.uint32(0x33333333)
        v = (v | (v << 1)) & jnp.uint32(0x55555555)
        return v

    return (part1by1(cx) | (part1by1(cy) << 1)).astype(jnp.int32)
