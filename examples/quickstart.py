"""Quickstart: AIDW interpolation with the Pallas kernels (60 seconds).

Builds a clustered synthetic elevation field, interpolates a query set with
the paper's tiled kernel (interpret mode on CPU, same call compiles for TPU),
and compares AIDW vs standard IDW accuracy on the known ground truth.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core.aidw import AIDWParams
from repro.data.spatial import clustered_points, uniform_points
from repro.engine import build_plan, execute, execute_with_stats
from repro.kernels import aidw, idw


def main():
    rng = np.random.default_rng(0)
    truth = lambda x, y: np.sin(4 * x) * np.cos(3 * y) + 0.5 * x

    # clustered samples of a smooth field (the regime AIDW was designed for)
    dx, dy, _ = clustered_points(4096, seed=1, n_clusters=24, spread=0.04)
    dz = truth(dx, dy).astype(np.float32)
    qx, qy, _ = uniform_points(2048, seed=2)
    q_truth = truth(qx, qy)

    params = AIDWParams(k=10, area=1.0)
    z_aidw, alpha = aidw(dx, dy, dz, qx, qy, params=params, area=1.0, impl="tiled", layout="soa")
    # impl="grid" buckets the data points into a uniform grid so Phase 1
    # (the kNN -> adaptive-alpha pass) only visits candidate neighbourhoods
    # instead of all m points — same answer, near-O(k) per query (DESIGN.md §4)
    z_grid, alpha_grid = aidw(dx, dy, dz, qx, qy, params=params, area=1.0, impl="grid")
    z_idw = idw(dx, dy, dz, qx, qy, alpha=2.0)

    # Serving more than one query batch?  Build the plan ONCE and reuse it:
    # everything shape- and occupancy-dependent (the grid snapshot, padded
    # layouts, candidate capacity) is captured at plan time, so execute() is
    # a pure jitted function — the second same-shape batch below reuses both
    # the snapshot and the compiled executable (DESIGN.md §6).
    plan = build_plan(dx, dy, dz, params=params, area=1.0, impl="grid")
    z_batch1, _ = execute(plan, qx, qy)                     # compiles once
    qx2, qy2, _ = uniform_points(2048, seed=3)
    z_batch2, _ = execute(plan, qx2, qy2)                   # jit cache hit

    # When does the fast path degrade?  execute_with_stats says.  Demo: a
    # uniform dataset with a serving-tuned (tight) candidate capacity, and a
    # batch that is mostly tile-local plus a full-bbox diagonal — the
    # diagonal's Morton block straddles the grid's Z-order seams, its
    # candidate rectangle overflows the capacity, and ONLY its queries are
    # ring-searched exactly (never wrong, just slower); the rest keep the
    # kernel fast path, and sparse blocks skip their all-sentinel candidate
    # tiles entirely (DESIGN.md §6).
    udx, udy, _ = uniform_points(4096, seed=4)
    udz = truth(udx, udy).astype(np.float32)
    tight = build_plan(udx, udy, udz, params=params, area=1.0, impl="grid",
                       query_occupancy=64.0, seam_level=0)
    local = (0.05 + 0.03 * rng.random((256, 2))).astype(np.float32)
    diag = np.linspace(0.02, 0.98, 256).astype(np.float32)
    sqx = np.concatenate([local[:, 0], diag])
    sqy = np.concatenate([local[:, 1], diag])
    _, _, stats = execute_with_stats(tight, sqx, sqy)
    print("seam-straddling batch diagnostics (execute_with_stats):")
    print(f"  overflow_blocks={int(stats['overflow_blocks'])} "
          f"overflow_queries={int(stats['overflow_queries'])} of {sqx.shape[0]} "
          f"(ring-searched exactly; the rest stay on the kernel fast path)")
    print(f"  skipped_tile_fraction={float(stats['skipped_tile_fraction']):.2f} "
          f"whole_batch_fallback={bool(stats['grid_fallback'])}")

    # What if EVERY batch overflows — the capacity model's occupancy
    # assumption was just wrong for this workload?  Serve through the
    # self-healing layer instead: a persistent-overflow streak triggers a
    # background re-plan at a bumped capacity and an atomic hot-swap; the
    # storm batches keep being served exactly (blend arms) on the old plan
    # while the build runs, and the swapped plan stops the overflow
    # (DESIGN.md §9; bitwise recovery proof in tests/serving).
    import warnings
    from repro.serving import CapacityReestimator, PlanRegistry

    healer = CapacityReestimator(PlanRegistry(), "quickstart", tight)
    storm_x = (rng.random(64) * 6 - 3).astype(np.float32)  # out-of-bbox
    storm_y = (rng.random(64) * 6 - 3).astype(np.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # the overflow-streak warning
        ovf = []
        while healer.state == "healthy" and len(ovf) < 10:
            _, _, s = healer.execute(storm_x, storm_y)
            ovf.append(int(s["overflow_queries"]))
        healer.join()                      # let the background re-plan land
        _, _, s = healer.execute(storm_x, storm_y)
        ovf.append(int(s["overflow_queries"]))
    print("self-healing serving (overflow storm -> re-plan -> hot-swap):")
    print(f"  overflow_queries per batch: {ovf} "
          f"(cand_capacity {tight.cand_capacity} -> {healer.plan.cand_capacity})")
    print(f"  state={healer.state} swaps={healer.stats()['swaps']}")

    # Phase 2 is a full m-point sweep in every exact impl.  phase2="quadtree"
    # sweeps exact weights only inside a plan-chosen near radius and walks a
    # quadtree of cell aggregates beyond it, one aggregate + dipole term per
    # closed node — an approximating path, so it ships with an error budget:
    # the plan proves a worst-case bound, and farfield_error_report measures
    # the real error against the Kahan oracle (DESIGN.md §8; the budget is
    # enforced by tests/engine/test_quadtree.py).  The bound is meaningful
    # when cells are compact relative to the near distance — demo data: tight
    # per-cell sensor clusters on a coarse grid (on generic data the plan
    # warns and reports an honest, weak bound).
    from repro.core.accuracy import farfield_error_report
    from repro.core.grid import build_grid
    import jax.numpy as jnp

    g = 12
    centers = (np.stack(np.meshgrid(np.arange(g), np.arange(g)), -1)
               .reshape(-1, 2) + 0.5) / g
    spts = centers[rng.integers(0, g * g, 4096)] + rng.normal(0, 0.003, (4096, 2))
    spts = np.clip(spts, 0.0, 1.0).astype(np.float32)
    sdz = truth(spts[:, 0], spts[:, 1]).astype(np.float32)
    sgrid = build_grid(jnp.asarray(spts[:, 0]), jnp.asarray(spts[:, 1]),
                       jnp.asarray(sdz), gx=g, gy=g)
    qt = build_plan(spts[:, 0], spts[:, 1], sdz, params=params, area=1.0,
                    impl="grid", grid=sgrid, phase2="quadtree",
                    farfield_radius=3, block_q=64)
    fq = rng.random((512, 2)).astype(np.float32)
    report = farfield_error_report(qt, fq[:, 0], fq[:, 1])
    _, _, qt_stats = execute_with_stats(qt, fq[:, 0], fq[:, 1])
    print("quadtree Phase 2 (near radius "
          f"{qt.farfield_radius} cells, proved bound {qt.farfield_bound:.3g}):")
    print(f"  near_points_mean={float(qt_stats['near_points_mean']):.0f} of m={qt.m}, "
          f"far_cells_mean={float(qt_stats['far_cells_mean']):.0f}")
    print(f"  measured max rel err {report['max_rel_err']:.2e} "
          f"(within_bound={report['within_bound']})")

    rmse = lambda z: float(np.sqrt(np.mean((np.asarray(z) - q_truth) ** 2)))
    print(f"data points: {dx.shape[0]}, queries: {qx.shape[0]}")
    print(f"adaptive alpha range: [{float(np.min(alpha)):.2f}, {float(np.max(alpha)):.2f}]")
    print(f"RMSE  AIDW (tiled kernel): {rmse(z_aidw):.4f}")
    print(f"RMSE  AIDW (grid kNN):     {rmse(z_grid):.4f}")
    print(f"RMSE  IDW  (alpha=2):      {rmse(z_idw):.4f}")
    print(f"grid vs tiled max |dz|:    {float(np.max(np.abs(np.asarray(z_grid) - np.asarray(z_aidw)))):.2e}")
    print(f"plan reuse max |dz|:       {float(np.max(np.abs(np.asarray(z_batch1) - np.asarray(z_grid)))):.2e}")
    print("AIDW adapts the decay power to local density; IDW uses one global power.")


if __name__ == "__main__":
    main()
