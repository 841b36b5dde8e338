"""The quadtree arm's near field read in place (DESIGN.md §8), and the
serving loop healing Phase-2 overflow (DESIGN.md §9).

* The row-run near field (:func:`phase2_near_row_runs`, the default
  ``pipeline="prefetch"``) sweeps exactly the point set of the gathered
  near field (:func:`phase2_near_weights`, kept as the ``"dense"``
  oracle): ``min_d2`` and ``hit_z`` agree bitwise, and ``sum_w`` /
  ``sum_wz`` to a few ulps, since only the order of the sum changes.
* The served quadtree path stays within the plan's proved bound against
  the Kahan oracle.
* ``p2_overflow_query_mask`` marks exactly the queries the masked exact
  sweep answered.
* A Phase-2 overflow streak re-plans with a larger ``p2_capacity``, keeps
  the arm and its rtol, and then serves a fresh plan's answers.
"""

import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.accuracy import aidw_interpolate_kahan
from repro.core.aidw import AIDWParams
from repro.core.grid import build_grid, cell_of, morton_ids
from repro.core.layouts import pad_tail
from repro.engine import (build_plan, exact_arm_mask, execute, execute_with_stats,
                          replan_with_capacity)
from repro.engine.execute import PERSISTENT_OVERFLOW_BATCHES
from repro.errors import CapacityOverflowWarning
from repro.kernels.aidw_grid import (
    block_rectangles,
    gather_candidates_csr,
    phase2_near_row_runs,
    phase2_near_weights,
    rectangle_need,
    row_run_max_tiles,
    row_run_tiles,
)
from repro.serving import CapacityReestimator, PlanRegistry

P = AIDWParams(k=10, area=1.0)
DISTRIBUTIONS = ("uniform", "clustered", "seam", "out_of_bbox")
# Both sweeps add the same f32 terms; the row-run walk adds them in CSR
# tiles of its own width, with masked lanes adding exact zeros, so only
# the association of the sum changes.  Each tile's partial sum and the
# running total can then differ by a few roundings of the total: 16 ulps
# of float32 is far above what reordering a few thousand positive terms
# moves (measured: 4.2 ulps on the sums, 5.7 on z) and far below any real difference
# (one point more or less moves the sums by its weight, >> 1e-4 here).
ULPS = 16
RTOL = ULPS * float(np.finfo(np.float32).eps)


def _field(x, y):
    return (np.sin(6 * x) * np.cos(6 * y) + 2.0).astype(x.dtype)


def _data(seed, m=4096):
    rng = np.random.default_rng(seed)
    dx = rng.random(m).astype(np.float32)
    dy = rng.random(m).astype(np.float32)
    return dx, dy, _field(dx, dy)


def _tight_data(seed, gx=12, m=4000, sigma=1e-4):
    """Per-cell clusters far below the cell scale: the quadtree proves
    rtol=1e-3 here (the data of ``test_quadtree.py``)."""
    rng = np.random.default_rng(seed)
    centers = (np.stack(np.meshgrid(np.arange(gx), np.arange(gx)), -1)
               .reshape(-1, 2) + 0.5) / gx
    pts = centers[rng.integers(0, gx * gx, m)] + rng.normal(0, sigma, (m, 2))
    pts = np.clip(pts, 0.0, 1.0).astype(np.float32)
    return pts[:, 0], pts[:, 1], _field(pts[:, 0], pts[:, 1])


def _queries(dist, nq, seed):
    rng = np.random.default_rng(seed)
    if dist == "uniform":
        q = rng.random((nq, 2))
    elif dist == "clustered":
        q = 0.35 + 0.12 * rng.random((nq, 2))
    elif dist == "seam":  # blocks straddling the grid's centre cross
        t = np.linspace(0.02, 0.98, nq)
        q = np.stack([t, t[::-1]], 1) + rng.normal(0, 0.01, (nq, 2))
    elif dist == "out_of_bbox":
        q = rng.random((nq, 2)) * 6.0 - 3.0
    else:  # pragma: no cover
        raise ValueError(dist)
    q = q.astype(np.float32)
    return jnp.asarray(q[:, 0]), jnp.asarray(q[:, 1])


@pytest.mark.parametrize("tile", [128, 256])
@pytest.mark.parametrize("dist", DISTRIBUTIONS)
def test_row_run_near_field_matches_gathered(dist, tile):
    dx, dy, dz = _data(seed=1)
    grid = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz), gx=16, gy=16)
    bq, radius = 32, 3
    qx, qy = _queries(dist, 200, seed=2)
    cx, cy = cell_of(grid, qx, qy)
    order = jnp.argsort(morton_ids(cx, cy), stable=True)
    pad = (-qx.shape[0]) % bq
    qx_s, qy_s = pad_tail(qx[order], pad), pad_tail(qy[order], pad)
    cx_s, cy_s = cell_of(grid, qx_s, qy_s)
    r = jnp.full(cx_s.shape, radius, jnp.int32)
    xlo, xhi, ylo, yhi = block_rectangles(grid, cx_s, cy_s, r, bq)
    need = rectangle_need(grid, xlo, xhi, ylo, yhi)
    cap = int(dx.shape[0])  # no block overflows: every point fits
    rng = np.random.default_rng(3)
    ah = jnp.asarray(rng.uniform(0.25, 2.0, (qx_s.shape[0], 1)).astype(np.float32))

    cand_x, cand_y, cand_z, need_g = gather_candidates_csr(grid, xlo, xhi, ylo, yhi, cap,
                                                           with_z=True)
    np.testing.assert_array_equal(np.asarray(need_g), np.asarray(need))
    gathered = phase2_near_weights(qx_s, qy_s, ah, cand_x, cand_y, cand_z,
                                   (need + 127) // 128, block_q=bq, block_d=128,
                                   interpret=True)
    tiles, n_tiles = row_run_tiles(grid, xlo, xhi, ylo, yhi, tile,
                                   row_run_max_tiles(cap, tile, grid.gy))
    rows = phase2_near_row_runs(qx_s, qy_s, ah, tiles, n_tiles,
                                jnp.stack([xlo, xhi, ylo, yhi], axis=1),
                                (grid.pt_x, grid.pt_y, grid.pt_z, grid.point_cells),
                                tile=tile, m_real=grid.n_points, block_q=bq,
                                interpret=True)
    sw_g, swz_g, md_g, hz_g = (np.asarray(v) for v in gathered)
    sw_r, swz_r, md_r, hz_r = (np.asarray(v) for v in rows)
    np.testing.assert_array_equal(md_r, md_g)
    np.testing.assert_array_equal(hz_r, hz_g)
    np.testing.assert_allclose(sw_r, sw_g, rtol=RTOL, atol=0)
    np.testing.assert_allclose(swz_r, swz_g, rtol=RTOL, atol=0)
    assert np.all(sw_g > 0)


@pytest.mark.parametrize("dist", DISTRIBUTIONS)
def test_prefetch_quadtree_matches_dense_pipeline(dist):
    """Whole calls: the row-run near field against the gathered one,
    through the engine (same radius, same far tables, same overflow)."""
    dx, dy, dz = _tight_data(seed=4)
    grid = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz), gx=12, gy=12)
    kw = dict(params=P, area=1.0, impl="grid", grid=grid, phase2="quadtree", block_q=64)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # this configuration proves its rtol
        rows = build_plan(dx, dy, dz, **kw)
        dense = build_plan(dx, dy, dz, pipeline="dense", **kw)
    assert rows.farfield_radius == dense.farfield_radius and rows.farfield_bound <= 1e-3
    qx, qy = _queries(dist, 300, seed=5)
    z_r, a_r, s_r = execute_with_stats(rows, qx, qy)
    z_d, a_d, s_d = execute_with_stats(dense, qx, qy)
    np.testing.assert_array_equal(np.asarray(a_r), np.asarray(a_d))
    np.testing.assert_array_equal(np.asarray(s_r["p2_overflow_query_mask"]),
                                  np.asarray(s_d["p2_overflow_query_mask"]))
    # z = (near + far sums) / (near + far weights): a few ulps of each sum
    np.testing.assert_allclose(np.asarray(z_r), np.asarray(z_d), rtol=4 * RTOL, atol=0)


@pytest.mark.parametrize("dist", DISTRIBUTIONS)
def test_served_quadtree_within_proved_bound(dist):
    """The served path (registry + re-estimator) against the Kahan oracle,
    on the scale the bound is stated on, ``max|z_data|``."""
    dx, dy, dz = _tight_data(seed=6)
    grid = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz), gx=12, gy=12)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        plan = build_plan(dx, dy, dz, params=P, area=1.0, impl="grid", grid=grid,
                          phase2="quadtree", farfield_rtol=1e-3, block_q=64)
    assert plan.farfield_bound <= 1e-3
    re_ = CapacityReestimator(PlanRegistry(), "qt", plan, backoff=0.0)
    qx, qy = _queries(dist, 256, seed=7)
    z, _, stats = re_.execute(qx, qy)
    assert int(stats["p2_overflow_queries"]) == int(np.sum(stats["p2_overflow_query_mask"]))
    z_exact, _ = aidw_interpolate_kahan(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz),
                                        qx, qy, P, area=1.0)
    scale = float(np.max(np.abs(dz)))
    err = np.max(np.abs(np.asarray(z, np.float64) - np.asarray(z_exact, np.float64))) / scale
    fp_slack = 64 * float(np.finfo(np.float32).eps) * np.sqrt(plan.m)
    assert err <= plan.farfield_bound + fp_slack, (err, plan.farfield_bound)
    assert re_.stats()["p2_overflow_queries"] == int(stats["p2_overflow_queries"])


def _small_near_plan(data, **kw):
    # dense query occupancy: the near capacity is sized for compact blocks,
    # so out-of-bbox blocks (whose home box spans the grid) overflow it;
    # Phase 1 gets every point, so only Phase 2 can overflow
    return build_plan(*data, params=AIDWParams(k=10, area=1.0, r_max=64.0), area=1.0,
                      impl="grid", phase2="quadtree", farfield_radius=1,
                      query_occupancy=64.0, min_cand_capacity=int(data[0].shape[0]), block_q=64,
                      **kw)


def test_p2_overflow_mask_marks_the_masked_exact_answers():
    data = _data(seed=8)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # radius 1 proves nothing here
        plan = _small_near_plan(data)
        roomy = _small_near_plan(data, min_p2_capacity=int(data[0].shape[0]))
    exact = build_plan(*data, params=plan.params, area=1.0, impl="grid",
                       query_occupancy=64.0, min_cand_capacity=plan.m, block_q=64)
    assert plan.p2_capacity < plan.m == roomy.p2_capacity
    q_in = _queries("clustered", 192, seed=9)
    q_out = _queries("out_of_bbox", 64, seed=10)
    qx = jnp.concatenate([q_in[0], q_out[0]])
    qy = jnp.concatenate([q_in[1], q_out[1]])
    z, _, stats = execute_with_stats(plan, qx, qy)
    mask = np.asarray(stats["p2_overflow_query_mask"])
    assert mask.shape == (qx.shape[0],) and 0 < mask.sum() < mask.size
    assert int(stats["p2_overflow_queries"]) == int(mask.sum())
    assert int(stats["overflow_queries"]) == 0
    np.testing.assert_array_equal(np.asarray(exact_arm_mask(stats)), mask)
    assert int(stats["p2_need_max"]) > plan.p2_capacity
    # marked: the exact sweep's answer, bitwise; unmarked: the quadtree's,
    # bitwise that of a plan whose near field never overflows
    z_exact, _ = execute(exact, qx, qy)
    z_roomy, _ = execute(roomy, qx, qy)
    z = np.asarray(z)
    np.testing.assert_array_equal(z[mask], np.asarray(z_exact)[mask])
    np.testing.assert_array_equal(z[~mask], np.asarray(z_roomy)[~mask])


def test_p2_overflow_streak_replans_the_near_capacity():
    data = _data(seed=11)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        plan = _small_near_plan(data)
        ref_old = _small_near_plan(data)
    reg = PlanRegistry()
    re_ = CapacityReestimator(reg, "qt", plan, backoff=0.0)
    qx, qy = _queries("out_of_bbox", 64, seed=12)
    z_old, a_old = execute(ref_old, qx, qy)
    need = 0
    with pytest.warns(CapacityOverflowWarning), warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        for _ in range(PERSISTENT_OVERFLOW_BATCHES):
            z, a, st = re_.execute(qx, qy)
            assert int(st["p2_overflow_queries"]) > 0 and int(st["overflow_queries"]) == 0
            need = max(need, int(st["p2_need_max"]))
            np.testing.assert_array_equal(np.asarray(z), np.asarray(z_old))
        assert st["persistent_overflow"] is True
        assert re_.join() == "healthy"
    new = re_.plan
    target = min(max(int(plan.p2_capacity * 2.0), need), plan.m)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        ref_new = replan_with_capacity(ref_old, min_cand_capacity=ref_old.cand_capacity,
                                       min_p2_capacity=target)
    assert new.p2_capacity == ref_new.p2_capacity > plan.p2_capacity
    assert new.cand_capacity == plan.cand_capacity
    assert (new.phase2, new.farfield_rtol, new.farfield_radius) == (
        "quadtree", plan.farfield_rtol, plan.farfield_radius)
    z2, a2, st2 = re_.execute(qx, qy)
    assert int(st2["p2_overflow_queries"]) == 0
    z_new, a_new = execute(ref_new, qx, qy)
    np.testing.assert_array_equal(np.asarray(z2), np.asarray(z_new))
    np.testing.assert_array_equal(np.asarray(a2), np.asarray(a_new))
    s = re_.stats()
    assert (s["triggers"], s["swaps"]) == (1, 1)
    assert s["p2_overflow_queries"] >= PERSISTENT_OVERFLOW_BATCHES and s["overflow_queries"] == 0
