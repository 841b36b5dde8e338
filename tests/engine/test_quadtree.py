"""Quadtree far-field Phase 2 (build_plan(phase2="quadtree"), DESIGN.md §8).

What this file enforces, beyond the single-level contract of
test_farfield.py:

* the measured error stays within the plan's proved dipole bound on
  uniform / clustered / seam / out-of-bbox query distributions — and the
  bound itself is <= 1e-3 at the plan-chosen sub-cell-clustered
  configuration (the "finally proves rtol=1e-3" acceptance);
* there exist configurations (z varying INSIDE tight spatial clusters)
  where the single-level model cannot prove 1e-3 at the same radius but
  the dipole model does — the reason the quadtree arm exists;
* every quadtree level re-aggregates EXACTLY (bitwise) to a NumPy
  reduction of the level below, and the per-node dispersion/z-spread
  fields really are upper bounds over the raw points (hypothesis + grid
  sweep);
* the proved bound is monotone non-increasing as the opening ratio
  shrinks;
* near-capacity or level-table overflow routes those queries to the exact
  sweep — bitwise — never to a truncated approximation;
* the stats dict has static structure (no retrace across same-shape
  batches) and carries {cells_per_level, opened_fraction,
  quadtree_rtol_bound}.
"""

import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.accuracy import farfield_error_report
from repro.core.aidw import AIDWParams
from repro.core.grid import build_grid, quadtree_aggregates, quadtree_level_count
from repro.engine import build_plan, execute, execute_with_stats
from repro.engine.plan import _bound_from_tau, _quadtree_tau_required
from repro.errors import UnprovableRtolWarning

P = AIDWParams(k=10, area=1.0)
DISTRIBUTIONS = ("uniform", "clustered", "seam", "out_of_bbox")


def _field(x, y):
    return (np.sin(6 * x) * np.cos(6 * y) + 2.0).astype(x.dtype)


def _tight_data(seed, dtype=np.float32, gx=12, m=4000, sigma=1e-4,
                z_noise=0.0):
    """Per-cell clusters far below the cell scale: the opening ratio of
    every level-0 cell fits tau_req, so the dipole bound PROVES rtol=1e-3.
    ``z_noise`` adds z variation INSIDE each cluster — harmless to the
    dipole model (its z budget is second-order with an |z|-scale
    coefficient) but first-order poison for the single-level model."""
    rng = np.random.default_rng(seed)
    centers = (np.stack(np.meshgrid(np.arange(gx), np.arange(gx)), -1)
               .reshape(-1, 2) + 0.5) / gx
    pts = centers[rng.integers(0, gx * gx, m)] + rng.normal(0, sigma, (m, 2))
    pts = np.clip(pts, 0.0, 1.0).astype(dtype)
    dx, dy = pts[:, 0], pts[:, 1]
    dz = _field(dx, dy) + (z_noise * rng.standard_normal(m)).astype(dtype)
    return dx, dy, dz.astype(dtype)


def _queries(dist, nq, seed, dtype=np.float32):
    rng = np.random.default_rng(seed)
    if dist == "uniform":
        q = rng.random((nq, 2))
    elif dist == "clustered":
        q = 0.35 + 0.12 * rng.random((nq, 2))
    elif dist == "seam":
        t = np.linspace(0.02, 0.98, nq)
        q = np.stack([t, t], 1) + rng.normal(0, 0.01, (nq, 2))
    elif dist == "out_of_bbox":
        q = rng.random((nq, 2)) * 6.0 - 3.0
    else:  # pragma: no cover
        raise ValueError(dist)
    return q.astype(dtype)[:, 0], q.astype(dtype)[:, 1]


def _quadtree_plan(dx, dy, dz, *, gx=12, block_q=64, **kw):
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz),
                   gx=gx, gy=gx)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return build_plan(dx, dy, dz, params=P, area=1.0, impl="grid",
                          grid=g, phase2="quadtree", block_q=block_q, **kw)


# ------------------------------------------------ error budget (tentpole)
@pytest.mark.parametrize("dist", DISTRIBUTIONS)
def test_measured_error_within_proved_bound(dist):
    """Acceptance: measured max relative error <= the proved dipole bound
    on all four query distributions — AND the bound itself proves the
    default rtol=1e-3 at this plan-chosen configuration (the single-level
    arm's provable floor at profitable radii is ~0.25, see DESIGN.md §7)."""
    dx, dy, dz = _tight_data(seed=10)
    qx, qy = _queries(dist, 220, seed=11)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a provable config must not warn
        plan = _quadtree_plan(dx, dy, dz)
    assert plan.farfield_bound <= 1e-3, "the dipole bound must prove rtol=1e-3"
    assert len(plan.qt_levels) == quadtree_level_count(12, 12)
    rep = farfield_error_report(plan, jnp.asarray(qx), jnp.asarray(qy))
    assert rep["phase2"] == "quadtree"
    assert rep["bound"] == plan.farfield_bound
    assert rep["within_bound"], rep


def test_quadtree_proves_where_single_level_cannot():
    """The reason the dipole term exists: z varying inside tight spatial
    clusters costs the single-level model a first-order term (eta * g) that
    blocks rtol=1e-3, while the dipole model stays second-order and proves
    it at the same radius."""
    dx, dy, dz = _tight_data(seed=20, z_noise=0.5)
    plan_q = _quadtree_plan(dx, dy, dz)
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz),
                   gx=12, gy=12)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        plan_f = build_plan(dx, dy, dz, params=P, area=1.0, impl="grid",
                            grid=g, phase2="farfield", block_q=64,
                            farfield_radius=plan_q.farfield_radius)
    assert plan_q.farfield_bound <= 1e-3
    assert plan_f.farfield_bound > 1e-3, (
        "single-level bound unexpectedly proves 1e-3 here — the first-order "
        "z term should block it"
    )
    qx, qy = _queries("uniform", 200, seed=21)
    rep = farfield_error_report(plan_q, jnp.asarray(qx), jnp.asarray(qy))
    assert rep["within_bound"], rep


def test_measured_error_within_bound_f64():
    import jax

    with jax.enable_x64():
        dx, dy, dz = _tight_data(seed=12, dtype=np.float64)
        qx, qy = _queries("out_of_bbox", 150, seed=13, dtype=np.float64)
        plan = _quadtree_plan(dx, dy, dz)
        assert plan.farfield_bound <= 1e-3
        rep = farfield_error_report(plan, jnp.asarray(qx), jnp.asarray(qy))
        assert rep["within_bound"], rep
        assert rep["max_rel_err"] <= plan.farfield_bound + 1e-12


def test_unprovable_config_warns_and_stays_within_honest_bound():
    """Coarse data (dispersion ~ the cell size) cannot meet tau_req: the
    plan warns, reports the honest (larger) bound, and the measured error
    still honours it."""
    rng = np.random.default_rng(30)
    dx = rng.random(3000).astype(np.float32)
    dy = rng.random(3000).astype(np.float32)
    dz = _field(dx, dy)
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz),
                   gx=12, gy=12)
    with pytest.warns(UnprovableRtolWarning):
        plan = build_plan(dx, dy, dz, params=P, area=1.0, impl="grid",
                          grid=g, phase2="quadtree", block_q=64)
    assert plan.farfield_bound > 1e-3
    qx, qy = _queries("uniform", 200, seed=31)
    rep = farfield_error_report(plan, jnp.asarray(qx), jnp.asarray(qy))
    assert rep["within_bound"], rep


# ------------------------------------------- level re-aggregation (bitwise)
def _assert_levels_consistent(g):
    """Bitwise: combining level l's 2x2 children with the documented exact
    reductions reproduces level l+1's count/z-sum/centroid/moment arrays;
    conservative: per-node e/zd really bound the raw points."""
    qt = quadtree_aggregates(g)
    assert len(qt) == quadtree_level_count(g.gx, g.gy)
    for a, b in zip(qt, qt[1:]):
        def img(arr, lv=a):
            return np.asarray(arr).reshape(lv.ny, lv.nx)

        def pad(x, fill=0.0):
            return np.pad(x, ((0, a.ny % 2), (0, a.nx % 2)),
                          constant_values=fill)

        ch = [(pad(img(a.count))[dy::2, dx::2], pad(img(a.z_sum))[dy::2, dx::2],
               pad(img(a.cent_x))[dy::2, dx::2], pad(img(a.cent_y))[dy::2, dx::2],
               pad(img(a.mx))[dy::2, dx::2], pad(img(a.my))[dy::2, dx::2])
              for dy, dx in ((0, 0), (0, 1), (1, 0), (1, 1))]
        cnt = ((ch[0][0] + ch[1][0]) + ch[2][0]) + ch[3][0]
        zs = ((ch[0][1] + ch[1][1]) + ch[2][1]) + ch[3][1]
        np.testing.assert_array_equal(np.asarray(b.count).reshape(b.ny, b.nx), cnt)
        np.testing.assert_array_equal(np.asarray(b.z_sum).reshape(b.ny, b.nx), zs)
        denom = np.maximum(cnt, np.asarray(1.0, cnt.dtype))
        wx = ((ch[0][0] * ch[0][2] + ch[1][0] * ch[1][2])
              + ch[2][0] * ch[2][2]) + ch[3][0] * ch[3][2]
        wy = ((ch[0][0] * ch[0][3] + ch[1][0] * ch[1][3])
              + ch[2][0] * ch[2][3]) + ch[3][0] * ch[3][3]
        bx = np.asarray(b.cent_x).reshape(b.ny, b.nx)
        by = np.asarray(b.cent_y).reshape(b.ny, b.nx)
        nonempty = cnt > 0
        np.testing.assert_array_equal(np.where(nonempty, wx / denom, bx), bx)
        np.testing.assert_array_equal(np.where(nonempty, wy / denom, by), by)
        mx = sum(c[4] + c[1] * (c[2] - bx) for c in ch)
        my = sum(c[5] + c[1] * (c[3] - by) for c in ch)
        np.testing.assert_array_equal(np.asarray(b.mx).reshape(b.ny, b.nx), mx)
        np.testing.assert_array_equal(np.asarray(b.my).reshape(b.ny, b.nx), my)

    # conservative invariants against the raw CSR layout, every level
    counts = np.asarray(g.counts).reshape(-1)
    cell_x, cell_y, cell_z = (np.asarray(g.cell_x), np.asarray(g.cell_y),
                              np.asarray(g.cell_z))
    for level in qt:
        for c in range(g.n_cells):
            k = int(counts[c])
            if k == 0:
                continue
            iy, ix = divmod(c, g.gx)
            nid = (iy // level.step) * level.nx + (ix // level.step)
            d = np.sqrt(
                (cell_x[c, :k].astype(np.float64) - float(level.cent_x[nid])) ** 2
                + (cell_y[c, :k].astype(np.float64) - float(level.cent_y[nid])) ** 2
            )
            assert (d <= float(level.e[nid]) + 1e-5).all()
            zbar = float(level.z_sum[nid]) / float(level.count[nid])
            zdev = np.abs(cell_z[c, :k].astype(np.float64) - zbar)
            assert (zdev <= float(level.zd[nid]) + 1e-4).all()


@pytest.mark.parametrize("gx", [3, 5, 12])
def test_level_reaggregation_bitwise(gx):
    dx, dy, dz = _tight_data(seed=40 + gx, gx=max(gx, 2), m=500, sigma=0.01)
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz),
                   gx=gx, gy=gx)
    _assert_levels_consistent(g)


def test_level_reaggregation_property():
    """Arbitrary point sets x tiny/odd grid resolutions.  Hypothesis is a CI
    dependency; without it this falls back to a fixed adversarial battery
    (identical points, two-corner, collinear, random) rather than skipping,
    so the tier-1 skip count stays flat and the CI skip-count guard keeps
    the real sweep honest."""
    def check(pts, gres):
        pts = np.asarray(pts, np.float32)
        g = build_grid(jnp.asarray(pts[:, 0]), jnp.asarray(pts[:, 1]),
                       jnp.asarray(pts[:, 2]), gx=gres, gy=gres)
        _assert_levels_consistent(g)

    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        rng = np.random.default_rng(0)
        cases = [
            np.full((12, 3), 0.5, np.float32),
            np.array([[0.0, 0.0, -3.0]] * 6 + [[1.0, 1.0, 3.0]] * 6,
                     dtype=np.float32),
            np.column_stack([np.linspace(0, 1, 20), np.zeros(20),
                             np.linspace(-3, 3, 20)]).astype(np.float32),
            rng.random((60, 3)).astype(np.float32),
        ]
        for gres in (2, 3, 6, 9):
            for pts in cases:
                check(pts, gres)
        return

    coord = st.floats(0.0, 1.0, allow_nan=False, width=32)
    zval = st.floats(-3.0, 3.0, allow_nan=False, width=32)

    @settings(deadline=None, max_examples=15)
    @given(
        pts=st.lists(st.tuples(coord, coord, zval), min_size=12, max_size=60),
        gres=st.sampled_from([2, 3, 6, 9]),
    )
    def run(pts, gres):
        check(pts, gres)

    run()


# ----------------------------------------------------------- bound model
def test_dipole_bound_monotone_in_tau():
    """The proved bound is monotone non-increasing as the opening ratio
    shrinks (the property the plan's level-selection relies on), sits
    strictly below the single-level bound wherever z varies in-cell, and
    the tau_req solver inverts it."""
    taus = np.linspace(0.3, 1e-4, 60)
    bounds = [_bound_from_tau(float(t), 4.0, dipole=True) for t in taus]
    assert all(b1 >= b2 for b1, b2 in zip(bounds, bounds[1:]))
    assert _bound_from_tau(0.0, 4.0, dipole=True) == 0.0
    assert _bound_from_tau(1.0, 4.0, dipole=True) == np.inf
    # second-order vs first-order: strictly better when g > 0
    for t in (0.01, 0.05, 0.1):
        assert (_bound_from_tau(t, 4.0, dipole=True)
                < _bound_from_tau(t, 4.0, g=0.5))
    for rtol in (1e-2, 1e-3, 1e-4):
        tau = _quadtree_tau_required(4.0, rtol)
        assert _bound_from_tau(tau, 4.0, dipole=True) <= rtol
        assert _bound_from_tau(tau * 1.1, 4.0, dipole=True) > rtol


def test_dipole_bound_monotone_property():
    """Same local-fallback policy as test_level_reaggregation_property."""
    def check(tau, shrink, a):
        assert (_bound_from_tau(tau * shrink, a, dipole=True)
                <= _bound_from_tau(tau, a, dipole=True))

    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        rng = np.random.default_rng(1)
        for _ in range(500):
            check(10.0 ** rng.uniform(-6, np.log10(0.9)),
                  rng.uniform(0.1, 1.0),
                  float(rng.choice([2.0, 3.0, 4.0, 5.0])))
        return

    @settings(deadline=None, max_examples=50)
    @given(
        tau=st.floats(1e-6, 0.9, allow_nan=False),
        shrink=st.floats(0.1, 1.0, allow_nan=False),
        a=st.sampled_from([2.0, 3.0, 4.0, 5.0]),
    )
    def run(tau, shrink, a):
        check(tau, shrink, a)

    run()


# ------------------------------------------------------- overflow fallback
def test_overflow_falls_back_to_exact_bitwise():
    """Out-of-bbox batches overflowing the near capacity take the per-block
    masked exact sweep: bitwise the exact plan's answer, and the overflow
    is reported per query."""
    rng = np.random.default_rng(14)
    dx = rng.random(4096).astype(np.float32)
    dy = rng.random(4096).astype(np.float32)
    dz = _field(dx, dy)
    p = AIDWParams(k=10, area=1.0, r_max=64.0)
    qx = jnp.asarray((rng.random(96) * 6 - 3).astype(np.float32))
    qy = jnp.asarray((rng.random(96) * 6 - 3).astype(np.float32))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        plan_qt = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                             phase2="quadtree", farfield_radius=1,
                             query_occupancy=64.0)
        plan_ex = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                             query_occupancy=64.0)
    assert plan_qt.p2_capacity < plan_qt.m
    z_qt, a_qt, stats = execute_with_stats(plan_qt, qx, qy)
    z_ex, a_ex = execute(plan_ex, qx, qy)
    assert int(stats["p2_overflow_queries"]) == 96
    np.testing.assert_array_equal(np.asarray(z_qt), np.asarray(z_ex))
    np.testing.assert_array_equal(np.asarray(a_qt), np.asarray(a_ex))


# -------------------------------------------------- stats / no-retrace
def test_quadtree_stats_static_and_no_retrace():
    dx, dy, dz = _tight_data(seed=15)
    plan = _quadtree_plan(dx, dy, dz)
    rng = np.random.default_rng(16)
    qs = [(jnp.asarray(rng.random(200).astype(np.float32)),
           jnp.asarray(rng.random(200).astype(np.float32))) for _ in range(2)]
    n0 = execute_with_stats._cache_size()
    _, _, s1 = execute_with_stats(plan, *qs[0])
    n1 = execute_with_stats._cache_size()
    _, _, s2 = execute_with_stats(plan, *qs[1])
    n2 = execute_with_stats._cache_size()
    assert n1 == n0 + 1 and n2 == n1, "quadtree stats must not retrace"
    assert set(s1) == set(s2)
    assert {"cells_per_level", "opened_fraction", "quadtree_rtol_bound",
            "far_cells_mean", "near_points_mean",
            "p2_overflow_queries"} < set(s1)
    assert s1["cells_per_level"].shape == (len(plan.qt_levels),)
    assert float(s1["far_cells_mean"]) > 0
    assert np.allclose(float(jnp.sum(s1["cells_per_level"])),
                       float(s1["far_cells_mean"]), rtol=1e-5)
    assert 0.0 <= float(s1["opened_fraction"]) <= 1.0
    assert float(s1["quadtree_rtol_bound"]) == np.float32(plan.farfield_bound)


# -------------------------------------------------------------- validations
def test_quadtree_validations():
    dx, dy, dz = _tight_data(seed=7, m=256)
    with pytest.raises(ValueError, match="phase2"):
        build_plan(dx, dy, dz, params=P, area=1.0, impl="grid", phase2="bh")
    with pytest.raises(ValueError, match="quadtree"):
        build_plan(dx, dy, dz, params=P, area=1.0, impl="tiled",
                   phase2="quadtree")
    # exact/farfield plans carry empty quadtree statics
    plan = build_plan(dx, dy, dz, params=P, area=1.0, impl="grid")
    assert plan.qt_levels == () and plan.qt_tau == 0.0
