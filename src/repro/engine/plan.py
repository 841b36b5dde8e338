"""Plan construction — the eager half of the plan/execute engine.

A plan captures, once per dataset, every decision that would otherwise leak
data-dependent *shapes* into the hot path:

* padded data layouts (sentinel coordinates, block-multiple widths, the
  SoA/AoaS transform) for the dense kernel family;
* the grid impl's **static-shape snapshot**: the :class:`UniformGrid` (with
  its CSR point arrays), the per-cell ``required_radius`` table, and a fixed
  candidate capacity chosen from the occupancy histogram — including the
  per-workload ``block_d`` autotune and the pathological-resolution
  warn-or-rebuild loop (ROADMAP item);
* chunk sizes / constant powers for the pure-jnp and IDW paths.

Everything a plan stores is either a static (hashable aux data of the
pytree, a trace-time constant) or an array child, so ``execute(plan, ...)``
jits with the plan as an ordinary argument and two same-shape query batches
against one plan hit the same executable.  Plan construction is eager by
design for ``impl="grid"`` (capacities are concrete ints); the ``chunked``
brute path builds traceable plans so the distributed sharded path can plan
inside ``shard_map``.
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Literal

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core.aidw import AIDWParams
from repro.core.grid import (
    DEFAULT_OCCUPANCY,
    UniformGrid,
    build_grid,
    quadtree_aggregates,
    required_radius_table,
    static_cell_radius,
)
from repro.core.layouts import coord_sentinel, pad_to, soa_to_aoas
from repro.errors import PathologicalGridWarning, UnprovableRtolWarning

Impl = Literal["naive", "tiled", "binned", "fused", "grid", "tiled_v2", "idw", "chunked"]
Layout = Literal["soa", "aoas"]

_DENSE_IMPLS = ("naive", "tiled", "binned", "fused", "tiled_v2")
_SOA_ONLY = ("binned", "fused", "grid", "tiled_v2", "idw", "chunked")

# Rebuild threshold: a resolution is "pathological" when some cell needs a
# safe ring radius beyond this — the signature of a grid too fine for its
# data (clustered points leave most cells empty, so ``required_radius``
# explodes in the voids and candidate rectangles approach a full sweep).
# A well-sized grid sits at r_safe ~ 2-3 (see ``static_cell_radius``).
_MAX_SAFE_RADIUS = 6
_MAX_REBUILDS = 3

# Far-field fallback: when the requested rtol is unprovable at any
# profitable radius, take the cheapest radius proving at least this bound
# (worst-case relative error above ~half the data scale promises nothing).
_FALLBACK_BOUND_CEIL = 0.5

# The exact Phase-2 sweep's data tile (grid plans): its lane-dense
# accumulators do not grow with the tile, and a wider tile spreads a grid
# step's fixed cost over more pairs (on a TPU v5e the kernel runs 2.89e11
# pairs/s at 4,096 points, 2.50-2.74e11 at 2,048, 1.18e11 at 512; PERF.md §6).
_SWEEP_TILE = 4096

# Per-tile element budget for the quadtree's gathered near field and level
# sweeps: block_q * tile_d capped so the in-kernel (block_q, tile_d) f32
# distance tile stays ~1 MiB.
_P2_TILE_ELEMS = 64 * 4096

# Row-run Phase 1 (DESIGN.md §6): the CSR tile widths it may walk, and a
# grid step's fixed cost in lanes of k-best merge work, fitted on a TPU v5e
# to the kernel's times at all three widths on 1M uniform points.  A row run
# of L points walked in T-point tiles costs about
# (L + T - 1) * (_STEP_LANES / T + 1) lanes, least near sqrt(_STEP_LANES * L).
_ROW_TILES = (128, 256, 512)
_STEP_LANES = 300


def _auto_interpret(interpret: bool | None) -> bool:
    """Pallas interpret mode only where it is the one way to run: on the
    CPU backend.  On a TPU the kernels are compiled by Mosaic; any other
    backend has no lowering for them, and interpreting there would hide a
    device that failed to come up behind a silently slow path."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"no Pallas lowering for backend {backend!r}: the kernels compile for "
        "TPU and interpret on CPU; pass interpret= explicitly to override"
    )


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class InterpolationPlan:
    """Everything needed to interpolate any number of query batches.

    Static fields (pytree aux — trace-time constants, part of the jit cache
    key) vs array children (``data``, ``grid``, ``r_need``) are split so the
    whole plan passes through ``jax.jit`` as one argument.
    """

    # --- static ---
    impl: str
    layout: str
    params: AIDWParams
    area: float
    m: int                    # real (unpadded) data-point count
    block_q: int
    block_d: int              # data-axis tile: dense sweep / grid exact Phase 2
    interpret: bool
    knn: str                  # chunked: "brute" | "grid"
    q_chunk: int
    d_chunk: int
    idw_alpha: float
    cand_capacity: int        # grid: static candidate-row width (points)
    cand_block_d: int         # grid: Phase-1 candidate tile (autotuned)
    grid_rebuilds: int        # grid: coarsening rebuilds during planning
    seam_level: int           # grid: Morton quadrant split depth (0 = off)
    pipeline: str             # grid Phase 1: "prefetch" (row runs) | "dense"
    phase2: str               # grid Phase 2: "exact" (full sweep) | "quadtree"
    farfield_rtol: float      # quadtree: user-requested relative error target
    farfield_radius: int      # quadtree: near-field radius (cells)
    farfield_bound: float     # quadtree: proved worst-case rel error
    p2_capacity: int          # quadtree: static near-field point capacity
    p2_block_d: int           # quadtree: near-field sweep tile (the row-run
                              # tile on "prefetch")
    qt_tau: float             # quadtree: effective opening ratio tau_eff
    qt_levels: tuple          # quadtree: per-level (nx, ny, step, n_pad, tile)
    row_tile: int             # grid Phase 1: CSR tile of the row-run walk
    # --- children ---
    data: tuple               # impl-specific padded arrays
    grid: UniformGrid | None
    r_need: jnp.ndarray | None  # (gy, gx) int32 per-cell required_radius
    far: tuple                # quadtree: per-level node-aggregate tuples
    row_cells: jnp.ndarray | None  # grid "prefetch": packed cell per CSR point

    def tree_flatten(self):
        aux = (self.impl, self.layout, self.params, self.area, self.m,
               self.block_q, self.block_d, self.interpret, self.knn,
               self.q_chunk, self.d_chunk, self.idw_alpha,
               self.cand_capacity, self.cand_block_d, self.grid_rebuilds,
               self.seam_level, self.pipeline, self.phase2,
               self.farfield_rtol, self.farfield_radius, self.farfield_bound,
               self.p2_capacity, self.p2_block_d,
               self.qt_tau, self.qt_levels, self.row_tile)
        return (self.data, self.grid, self.r_need, self.far, self.row_cells), aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, grid, r_need, far, row_cells = children
        return cls(*aux, data=data, grid=grid, r_need=r_need, far=far,
                   row_cells=row_cells)


def _choose_candidate_capacity(grid: UniformGrid, r_need, block_q: int, m: int,
                               query_occupancy: float | None):
    """Static candidate capacity (points) from the occupancy histogram.

    A block of ``block_q`` Morton-contiguous queries at ~``query_occupancy``
    queries per cell spans a home-cell bbox of side about
    ``2*ceil(sqrt(block_q / query_occupancy))`` (a contiguous Morton run of
    L cells fits a box of side <= 2*ceil(sqrt(L))); expanding by the
    grid-max in-cell safe radius bounds the rectangle side ``W``.  The
    capacity is the densest WxW occupancy window (one integral-image sweep).
    Query density is unknowable at plan time, so the default assumes serving
    batches ~4x sparser than the data; blocks that exceed the capacity at
    execute time (sparser/far-out-of-bbox batches) take the exact
    ring-search fallback instead of losing neighbours.

    Returns ``(capacity, r_static, window, side)`` — all concrete ints
    (``side`` is reused to size the quadtree near-field capacity with the
    same block-bbox model).
    """
    r_cell = static_cell_radius(grid, r_need)
    r_static = int(jnp.max(r_cell))
    occ_mean = max(m / max(grid.n_cells, 1), 1.0)
    if query_occupancy is None:
        query_occupancy = occ_mean / 4.0
    query_occupancy = max(query_occupancy, 0.5)
    side = 2 * math.ceil(math.sqrt(block_q / query_occupancy))
    window = min(side + 2 * r_static + 1, max(grid.gx, grid.gy))
    capacity = _densest_window_count(grid, window)
    return capacity, r_static, window, side


def _choose_row_tile(grid: UniformGrid, m: int, window: int) -> int:
    """Tile width of the row-run Phase 1 from the plan's mean row run: the
    mean cell occupancy times the capacity model's rectangle width."""
    run = max(m / max(grid.n_cells, 1), 1.0) * window
    best = math.sqrt(_STEP_LANES * run)
    return min(_ROW_TILES, key=lambda t: abs(math.log2(t / best)))


def _densest_window_count(grid: UniformGrid, window: int) -> int:
    """Max point count of any ``window x window`` cell block — one
    integral-image sweep, concrete int."""
    c = grid.cum
    ys = jnp.minimum(jnp.arange(grid.gy, dtype=jnp.int32) + window, grid.gy)
    xs = jnp.minimum(jnp.arange(grid.gx, dtype=jnp.int32) + window, grid.gx)
    y0 = jnp.arange(grid.gy, dtype=jnp.int32)
    x0 = jnp.arange(grid.gx, dtype=jnp.int32)
    sums = (c[ys[:, None], xs[None, :]] - c[y0[:, None], xs[None, :]]
            - c[ys[:, None], x0[None, :]] + c[y0[:, None], x0[None, :]])
    return max(int(jnp.max(sums)), 1)


def _bound_from_tau(tau: float, a_max: float):
    """Worst-case relative error of the quadtree far field at opening ratio
    ``tau`` (DESIGN.md §8), for ``A = a_max = max(alpha_levels)``.

    A closed node of ``n`` points within ``e`` of their centroid, at
    distance ``d >= e / tau`` from the query, stands in for their weights
    with ``n * w(d)``.  The centroid zeroes the first position moment, so
    Taylor with the Lagrange Hessian of ``w(p) = |q - p|^-A`` (largest
    eigenvalue ``A*(A+1)*d^-A-2``, evaluated no closer than ``d - e``)
    leaves a second-order error, ``eps2 = 0.5 * A * (A+1) * tau^2 *
    (1 - tau)^-(A+2)`` of ``n * w(d)``.  The kernel adds the stored first
    z-moment term ``grad w(cent) . M``, which cancels the z-sum term's
    first-order piece in the same way, so both sums are second-order:

        |N_hat - N| <= eps2 * n * w(d) * z_abs_max,
        |D_hat - D| <= eps2 * n * w(d),
        bound = 2 * eps2 * (1+tau)^A / (1 - eps2 * (1+tau)^A),

    with ``sum_node w_j >= n w(d) (1+tau)^-A`` and the exact interpolant a
    convex combination of data z.

    Monotone non-increasing as tau shrinks (the property the hypothesis
    test pins); ``inf`` when no guarantee exists at this tau.
    """
    if tau >= 1.0:
        return math.inf
    grow = (1.0 + tau) ** a_max
    eps2 = 0.5 * a_max * (a_max + 1.0) * tau * tau * (1.0 - tau) ** (-a_max - 2.0)
    eps2h = eps2 * grow
    if eps2h >= 1.0:
        return math.inf
    return 2.0 * eps2 * grow / (1.0 - eps2h)


def _quadtree_tau_required(a_max: float, rtol: float) -> float:
    """Largest opening ratio tau whose dipole bound still proves ``rtol`` —
    bisection on the monotone :func:`_bound_from_tau` (60 steps ~ 1 ulp).
    To leading order ``tau_req ~ sqrt(rtol / (2 * a * (a+1)))``; at a = 4,
    rtol = 1e-3 that is ~7e-3 — an opening angle sub-cell-clustered data
    can actually meet."""
    hi = 0.5
    if _bound_from_tau(hi, a_max) <= rtol:
        return hi
    lo = 0.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if _bound_from_tau(mid, a_max) <= rtol:
            lo = mid
        else:
            hi = mid
    return lo


def _choose_quadtree_radius(grid: UniformGrid, params: AIDWParams,
                            farfield_rtol: float, e0_max: float, *,
                            side: int, m: int, radius: int | None = None):
    """Near-field radius + effective opening ratio for the quadtree arm.

    Returns ``(radius, tau_eff, bound)``; a given ``radius`` (the user's
    ``farfield_radius=``, clamped to ``[1, max(gx, gy)]``) is kept as it is
    and only its ``tau_eff`` and bound are computed.

    The walk closes a node only when its own dispersion ``e`` fits
    ``tau_eff * (gap-1) * cell_min`` — EXCEPT level-0 cells, which cannot
    be opened further and are force-closed
    whenever their gap clears ``radius + 1``.  ``tau_eff`` therefore must
    also cover the worst level-0 cell at the near boundary:

        tau_eff = max(tau_req, e0_max / (radius * cell_min)).

    The smallest radius under the Phase-2 profitability cap (the modeled
    near-field work, window occupancy, stays under ``m / 4``, else the split
    would not beat the exact m-point sweep it replaces) whose
    ``_bound_from_tau(tau_eff)`` meets ``farfield_rtol`` wins.  When even
    the cap radius cannot prove the target (cell dispersion too coarse —
    e.g. uniform data, where e0 ~ 0.7 * cell) the plan takes the cheapest
    radius whose bound is at most ``_FALLBACK_BOUND_CEIL`` (the cap radius
    if none is) and warns with that honest bound.  A radius ``>= max(gx,
    gy)`` makes every near rectangle span the whole grid: the far set is
    empty and the bound 0.
    """
    a_max = float(max(params.alpha_levels))
    cell_min = float(jnp.minimum(grid.cell_size[0], grid.cell_size[1]))
    cover = max(grid.gx, grid.gy)
    occ_mean = max(m / max(grid.n_cells, 1), 1.0)
    tau_req = _quadtree_tau_required(a_max, farfield_rtol)

    def at_radius(radius):
        if radius >= cover:
            return tau_req, 0.0
        if cell_min <= 0:
            return math.inf, math.inf
        tau_eff = max(tau_req, e0_max / (radius * cell_min))
        return tau_eff, _bound_from_tau(tau_eff, a_max)

    if radius is not None:
        radius = max(1, min(int(radius), cover))
        return (radius, *at_radius(radius))

    def modeled_cost(radius):
        window = min(side + 2 * radius + 1, cover)
        return window * window * occ_mean

    r_cap = 1
    while r_cap + 1 < cover and modeled_cost(r_cap + 1) <= m / 4:
        r_cap += 1
    for radius in range(1, r_cap + 1):
        tau_eff, bound = at_radius(radius)
        if bound <= farfield_rtol:
            return radius, tau_eff, bound
    radius = r_cap
    for r in range(1, r_cap + 1):
        if at_radius(r)[1] <= _FALLBACK_BOUND_CEIL:
            radius = r
            break
    tau_eff, bound = at_radius(radius)
    warnings.warn(
        f"farfield_rtol={farfield_rtol:g} is not provable by the quadtree "
        f"model within the profitable near-field budget (radius <= {r_cap} "
        f"of a {grid.gx}x{grid.gy} grid): the worst cell's dispersion gives "
        f"opening ratio {tau_eff:.3g} > required {tau_req:.3g}. Using radius "
        f"{radius} with worst-case bound {bound:.3g}; measured error is "
        "typically far below it — check farfield_error_report, or use a "
        "coarser grid / sub-cell-clustered data for a provable target.",
        UnprovableRtolWarning,
        stacklevel=4,
    )
    return radius, tau_eff, bound


def _quadtree_level_statics(qt, tile_cap: int):
    """Static per-level ``(nx, ny, step, n_pad, tile)`` table: the level's
    node grid, its cells per node side, and the sweep tile and the node
    count padded to it.  Every block sweeps the whole level, its
    non-closed nodes masked to weight 0 (DESIGN.md §8), so a level's
    sweep width is its node count."""
    out = []
    for level in qt:
        n_nodes = level.nx * level.ny
        tile = min(tile_cap, max(128, _round_up(n_nodes, 128)))
        out.append((level.nx, level.ny, level.step, _round_up(n_nodes, tile), tile))
    return tuple(out)


def _choose_seam_level(grid: UniformGrid, window: int) -> int:
    """Morton seam-split depth from the occupancy histogram's window.

    Splitting at depth L bounds every query block's home-cell bbox to one
    ``4**L``-quadrant, so the seam-straddling rectangle blowup (a block with
    home cells on both sides of the grid's centre cross has a bbox near full
    grid width) cannot happen at any split boundary.  Deeper splits mean
    smaller worst-case rectangles but more block padding, so go only as deep
    as quadrants stay comfortably larger than the expected candidate window
    ``window`` (the same densest-window statistic that sizes the capacity):
    then a non-straddling block's rectangle was going to fit anyway and the
    split costs at most one padded block per occupied quadrant.
    """
    level = 0
    nbits = max(1, (max(grid.gx, grid.gy) - 1).bit_length())
    while (level < min(nbits, 4)
           and (min(grid.gx, grid.gy) >> (level + 1)) >= max(window, 4)):
        level += 1
    return level


def _plan_grid(dx, dy, dz, *, params, block_q, block_d, grid, target_occupancy,
               query_occupancy, seam_level, pipeline, phase2, farfield_rtol,
               farfield_radius, min_cand_capacity=None, min_p2_capacity=None):
    """Grid-impl plan: snapshot + static capacity + block_d autotune.

    ``min_cand_capacity`` / ``min_p2_capacity`` floor the occupancy-model
    capacities (still clamped to ``m`` — a candidate row can never need
    more than every data point).  This is the capacity re-estimator's
    entry: a re-plan raises the floor past the observed ``cand_need_max``
    instead of re-deriving the same undersized model answer.
    """
    m = int(dx.shape[0])
    dtype = jnp.asarray(dx).dtype
    user_grid = grid is not None
    occupancy = target_occupancy or DEFAULT_OCCUPANCY
    if grid is None:
        grid = build_grid(dx, dy, dz, target_occupancy=occupancy)

    rebuilds = 0
    while True:
        r_need = required_radius_table(grid, params.k)
        capacity, r_static, window, side = _choose_candidate_capacity(
            grid, r_need, block_q, m, query_occupancy
        )
        pathological = grid.n_cells > 1 and r_static > _MAX_SAFE_RADIUS
        # A quadtree plan keeps the occupancy grid: its near radius is a
        # number of cells set by rtol and the cells' dispersion ratio
        # (DESIGN.md §8), so coarser cells only widen its near field (and
        # on a grid narrower than that radius nothing is provable), while
        # Phase 1 pays for sparse regions (voids) only in their own blocks
        if not pathological or phase2 == "quadtree":
            break
        if user_grid or rebuilds >= _MAX_REBUILDS:
            warnings.warn(
                f"grid resolution {grid.gx}x{grid.gy} is pathological for this "
                f"data (grid-max safe radius {r_static}, static candidate "
                f"window {window} cells); candidate rows approach a full "
                "sweep. Pass a coarser grid or higher target_occupancy.",
                PathologicalGridWarning,
                stacklevel=3,
            )
            break
        # coarsen: 4x the target occupancy halves the cells per axis,
        # raising occupancy in sparse regions and shrinking required_radius
        occupancy *= 4.0
        grid = build_grid(dx, dy, dz, target_occupancy=occupancy)
        rebuilds += 1

    # block_d autotune from the occupancy histogram: a candidate tile no
    # wider than the (128-aligned) capacity — narrow neighbourhoods get a
    # single tile instead of streaming block_d of sentinel padding
    capacity = min(capacity, m)
    if min_cand_capacity is not None:
        capacity = min(max(capacity, int(min_cand_capacity)), m)
    cand_block_d = min(block_d, max(128, _round_up(capacity, 128)))
    cand_capacity = _round_up(capacity, cand_block_d)

    if seam_level is None:
        seam_level = _choose_seam_level(grid, window)

    # Phase-2 full-data sweep: sentinel-pad to its own tile multiple (kept on
    # quadtree plans too — it is the exact arm of the overflow fallback)
    bd2 = min(_SWEEP_TILE, max(128, _round_up(m, 128)))
    big = coord_sentinel(dtype)
    data = (
        pad_to(jnp.asarray(dx), bd2, big)[None, :],
        pad_to(jnp.asarray(dy), bd2, big)[None, :],
        pad_to(jnp.asarray(dz), bd2, jnp.zeros((), dtype))[None, :],
    )

    ff = dict(farfield_radius=0, farfield_bound=0.0, p2_capacity=0,
              p2_block_d=0, qt_tau=0.0, qt_levels=(), far=())
    if phase2 == "quadtree":
        qt = quadtree_aggregates(grid)
        radius, tau_eff, bound = _choose_quadtree_radius(
            grid, params, farfield_rtol, qt[0].e_max, side=side, m=m,
            radius=farfield_radius,
        )
        # near-field capacity: Phase 1's densest-window model, with the
        # block's home bbox expanded by the near radius instead of r_safe.
        # The row-run near field walks CSR tiles of the row-run rule's
        # width for its own (near-rectangle-wide) runs; the dense pipeline
        # gathers the near field and sweeps it in the widest tile that keeps
        # the (block_q x tile) distance tile within _P2_TILE_ELEMS
        window2 = min(side + 2 * radius + 1, max(grid.gx, grid.gy))
        cap2 = min(_densest_window_count(grid, window2), m)
        if min_p2_capacity is not None:
            cap2 = min(max(cap2, int(min_p2_capacity)), m)
        tile_cap = max(512, _round_up(_P2_TILE_ELEMS // block_q, 128))
        if pipeline == "prefetch":
            p2_block_d = _choose_row_tile(grid, m, window2)
        else:
            p2_block_d = min(tile_cap, max(128, _round_up(cap2, 128)))
        p2_capacity = _round_up(cap2, p2_block_d)
        qt_levels = _quadtree_level_statics(qt, tile_cap)
        # per level: node aggregates padded to the sweep tile with sentinel
        # nodes — sentinel centroid (d2 -> inf, w -> 0) and zero
        # count/z-sum/moment, so they contribute exactly 0 to both sums
        zero = jnp.zeros((), dtype)
        far = tuple(
            (
                pad_to(level.cent_x.astype(dtype), tile, big),
                pad_to(level.cent_y.astype(dtype), tile, big),
                pad_to(level.count.astype(dtype), tile, zero),
                pad_to(level.z_sum.astype(dtype), tile, zero),
                pad_to(level.mx.astype(dtype), tile, zero),
                pad_to(level.my.astype(dtype), tile, zero),
                pad_to(level.e.astype(dtype), tile, zero),
            )
            for level, (_nx, _ny, _step, _n_pad, tile) in zip(qt, qt_levels)
        )
        ff = dict(farfield_radius=radius, farfield_bound=float(bound),
                  p2_capacity=p2_capacity, p2_block_d=p2_block_d,
                  qt_tau=float(tau_eff),
                  qt_levels=qt_levels, far=far)

    return dict(block_d=bd2, cand_capacity=cand_capacity, cand_block_d=cand_block_d,
                grid_rebuilds=rebuilds, seam_level=int(seam_level),
                row_tile=_choose_row_tile(grid, m, window),
                row_cells=grid.point_cells if pipeline == "prefetch" else None,
                data=data, grid=grid, r_need=r_need, **ff)


@telemetry.spanned("plan.build")
def build_plan(
    dx, dy, dz, *,
    params: AIDWParams = AIDWParams(),
    area: float | None = None,
    impl: Impl = "tiled",
    layout: Layout = "soa",
    block_q: int = 256,
    block_d: int = 512,
    interpret: bool | None = None,
    grid: UniformGrid | None = None,
    knn: str = "brute",
    q_chunk: int = 1024,
    d_chunk: int = 4096,
    idw_alpha: float = 2.0,
    target_occupancy: float | None = None,
    query_occupancy: float | None = None,
    seam_level: int | None = None,
    pipeline: str = "prefetch",
    phase2: str = "exact",
    farfield_rtol: float = 1e-3,
    farfield_radius: int | None = None,
    min_cand_capacity: int | None = None,
    min_p2_capacity: int | None = None,
) -> InterpolationPlan:
    """Build an :class:`InterpolationPlan` from a dataset + configuration.

    The one place padding/sentinel/layout decisions are made for every impl
    (the kernels' public wrappers in ``kernels.ops``, the pure-jnp
    ``aidw_interpolate`` and the distributed sharded path all plan here).

    ``impl``: the dense kernel family ("naive", "tiled", "binned", "fused",
    "tiled_v2"), the static-shape grid path ("grid"), the pure-jnp chunked
    path ("chunked", with ``knn`` = "brute" | "grid"), or constant-power
    "idw".  ``grid=`` supplies a prebuilt :class:`UniformGrid` (reused, never
    rebuilt); ``target_occupancy`` seeds the auto-resolution otherwise.
    ``query_occupancy`` (grid impl) sizes the static candidate capacity: the
    expected queries per cell of a serving batch (default: data occupancy /
    4).  Lower values buy headroom for sparse batches at the cost of wider
    candidate rows; queries in blocks beyond the capacity stay exact via the
    per-block ring-search blend.
    ``seam_level`` (grid impl) is the Morton quadrant depth at which query
    blocks are split during the execute-side sort so no block straddles a
    top-level Z-order seam (the rectangle-blowup worst case); ``None``
    auto-chooses from the occupancy histogram, ``0`` disables.
    ``pipeline`` (grid impl) selects the Phase-1 kernel: "prefetch" (default;
    each block's CSR row runs are read in place, in aligned tiles listed
    per block and scalar-prefetched) or "dense" (candidate rows are
    gathered to the static capacity and every block walks all of it; the
    oracle the default is tested against, bit-identical results).
    ``phase2`` (grid impl) selects the Phase-2 sweep: "exact" (default; the
    full m-point weighted sweep, bit-identical to every prior release) or
    "quadtree" (DESIGN.md §8, the approximating arm: exact per-point
    weights inside a plan-chosen near-field radius, and beyond it a
    Barnes–Hut walk over a quadtree of cell aggregates, coarse levels
    closed wherever the per-node opening criterion holds and a dipole
    z-moment term added per closed node, making BOTH error terms
    second-order in the opening ratio — per-query far work is ~O(log m)
    and rtol=1e-3 is provable wherever data clusters below the cell
    scale).  Its worst-case relative error, proved by the model in
    :func:`_choose_quadtree_radius`, is reported as
    ``plan.farfield_bound``.
    ``farfield_rtol`` is the requested relative-error ceiling, measured
    against ``max|z_data|`` (see ``core.accuracy.farfield_error_report``);
    when it is not provable at a profitable radius the plan WARNS and
    ``farfield_bound`` is the honest, larger worst case — always check it
    rather than assuming the request was met.  ``farfield_radius``
    overrides the model's radius choice directly (the bound is still
    computed and reported for the chosen radius — possibly ``inf`` for
    radii too small to prove anything).
    ``min_cand_capacity`` / ``min_p2_capacity`` (grid impl) floor the
    occupancy-model capacities, clamped to ``m`` — the capacity
    re-estimator's re-plan knob (see :func:`replan_with_capacity`).

    Data must be finite: non-finite coordinates or z values raise
    ``ValueError`` (a NaN data point would silently poison every distance
    reduction it streams through).  Non-finite *queries* are handled at
    execute time instead — they yield NaN results.
    """
    valid_impls = _DENSE_IMPLS + ("grid", "idw", "chunked")
    if impl not in valid_impls:
        raise ValueError(f"impl must be one of {valid_impls}, got {impl!r}")
    if layout not in ("soa", "aoas"):
        raise ValueError(layout)
    if layout == "aoas" and impl in _SOA_ONLY:
        raise ValueError(f"impl={impl!r} is SoA-only (not available for layout=aoas)")
    uses_grid = impl == "grid" or (impl == "chunked" and knn == "grid")
    if grid is not None and not uses_grid:
        raise ValueError("grid= is only meaningful with impl='grid' or knn='grid'")
    if impl == "chunked" and knn not in ("brute", "grid"):
        raise ValueError(f"knn must be 'brute' or 'grid', got {knn!r}")
    if pipeline not in ("prefetch", "dense"):
        raise ValueError(f"pipeline must be 'prefetch' or 'dense', got {pipeline!r}")
    if seam_level is not None and not (0 <= int(seam_level) <= 8):
        raise ValueError(f"seam_level must be in [0, 8], got {seam_level!r}")
    if phase2 not in ("exact", "quadtree"):
        raise ValueError(f"phase2 must be 'exact' or 'quadtree', got {phase2!r}")
    if phase2 == "quadtree" and impl != "grid":
        raise ValueError(f"phase2={phase2!r} requires impl='grid' (the cell "
                         "aggregates live on the grid snapshot)")
    if not float(farfield_rtol) > 0.0:
        raise ValueError(f"farfield_rtol must be > 0, got {farfield_rtol!r}")
    if farfield_radius is not None and int(farfield_radius) < 1:
        raise ValueError(f"farfield_radius must be >= 1, got {farfield_radius!r}")
    for name, floor in (("min_cand_capacity", min_cand_capacity),
                        ("min_p2_capacity", min_p2_capacity)):
        if floor is not None and int(floor) < 1:
            raise ValueError(f"{name} must be >= 1, got {floor!r}")

    # Reject non-finite data eagerly (tracers — the sharded chunked path
    # plans inside shard_map — can't be checked and are trusted instead).
    for name, arr in (("dx", dx), ("dy", dy), ("dz", dz)):
        if isinstance(arr, jax.core.Tracer):
            continue
        vals = jnp.asarray(arr)
        if jnp.issubdtype(vals.dtype, jnp.floating) and not bool(
            jnp.all(jnp.isfinite(vals))
        ):
            raise ValueError(
                f"non-finite values in {name}: data points and z must be "
                "finite (NaN/Inf would silently poison the kernel distance "
                "reductions). Filter the dataset before planning."
            )

    m = int(dx.shape[0])
    if impl != "idw" and m < params.k:
        raise ValueError(f"need at least k={params.k} data points, got {m}")
    if area is None:
        area = params.area
    if area is None:
        if impl != "idw":  # constant-power IDW has no Eq. (2), no area
            raise ValueError("plans require a static area; pass area= or set params.area")
        area = 0.0
    area = float(area)
    params = dataclasses.replace(params, alpha_levels=tuple(params.alpha_levels))
    interp = _auto_interpret(interpret)
    dtype = jnp.asarray(dx).dtype

    fields = dict(
        impl=impl, layout=layout, params=params, area=area, m=m,
        block_q=block_q, block_d=block_d, interpret=interp,
        knn=knn, q_chunk=q_chunk, d_chunk=d_chunk, idw_alpha=float(idw_alpha),
        cand_capacity=0, cand_block_d=0, grid_rebuilds=0,
        seam_level=0, pipeline=pipeline,
        phase2=phase2, farfield_rtol=float(farfield_rtol),
        farfield_radius=0, farfield_bound=0.0,
        p2_capacity=0, p2_block_d=0,
        qt_tau=0.0, qt_levels=(), row_tile=0,
        data=(), grid=None, r_need=None, far=(), row_cells=None,
    )

    if impl == "grid":
        fields.update(_plan_grid(
            dx, dy, dz, params=params, block_q=block_q, block_d=block_d,
            grid=grid, target_occupancy=target_occupancy,
            query_occupancy=query_occupancy, seam_level=seam_level,
            pipeline=pipeline, phase2=phase2, farfield_rtol=float(farfield_rtol),
            farfield_radius=farfield_radius,
            min_cand_capacity=min_cand_capacity,
            min_p2_capacity=min_p2_capacity,
        ))
    elif impl == "chunked":
        if knn == "grid" and grid is None:
            grid = build_grid(dx, dy, dz, target_occupancy=target_occupancy or DEFAULT_OCCUPANCY)
        fields.update(data=(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz)), grid=grid)
    else:
        # dense kernel family + idw: sentinel-pad the streamed data axis
        if impl == "naive":
            fields["block_q"] = min(block_q, 64)
        big = coord_sentinel(dtype)
        dxp = pad_to(jnp.asarray(dx), block_d, big)
        dyp = pad_to(jnp.asarray(dy), block_d, big)
        dzp = pad_to(jnp.asarray(dz), block_d, jnp.zeros((), dtype))
        if layout == "aoas":
            fields.update(data=(soa_to_aoas(dxp, dyp, dzp),))
        else:
            fields.update(data=(dxp[None, :], dyp[None, :], dzp[None, :]))

    return InterpolationPlan(**fields)


@telemetry.spanned("plan.replan")
def replan_with_capacity(
    plan: InterpolationPlan, *,
    min_cand_capacity: int | None = None,
    min_p2_capacity: int | None = None,
) -> InterpolationPlan:
    """Rebuild a grid plan with floored capacities — the re-plan entry the
    serving-layer capacity re-estimator calls from its background thread.

    Everything else is carried over from ``plan``: the original (unpadded)
    data arrays are recovered from the plan's padded copies, the grid
    snapshot is REUSED (no rebuild — the data didn't change, the capacity
    model did), and the statics (params/area/blocks/seam/pipeline/phase2
    and the quadtree's ``farfield_*`` knobs, including an explicit-radius
    carry-over so the radius cannot drift between old and new plan) are
    passed through.  The
    result serves the same queries with the same exactness contract; only
    the static candidate widths (and their derived tile sizes) grow.
    """
    if plan.impl != "grid":
        raise ValueError(
            f"replan_with_capacity requires impl='grid', got {plan.impl!r}"
        )
    dxp, dyp, dzp = plan.data
    dx, dy, dz = dxp[0, :plan.m], dyp[0, :plan.m], dzp[0, :plan.m]
    return build_plan(
        dx, dy, dz,
        params=plan.params, area=plan.area, impl="grid",
        block_q=plan.block_q, block_d=plan.block_d,
        interpret=plan.interpret, grid=plan.grid,
        seam_level=plan.seam_level, pipeline=plan.pipeline,
        phase2=plan.phase2, farfield_rtol=plan.farfield_rtol,
        farfield_radius=plan.farfield_radius or None,
        min_cand_capacity=min_cand_capacity,
        min_p2_capacity=min_p2_capacity,
    )
