"""Quadtree far-field Phase 2 (build_plan(phase2="quadtree"), DESIGN.md §8):
the error budget is ENFORCED, not just reported.

What this file enforces:

* the measured error (Kahan-oracle comparison,
  core.accuracy.farfield_error_report) stays within the plan's proved
  dipole bound on uniform / clustered / seam / out-of-bbox query
  distributions, at the plan-chosen radius and at user radii, in f32 and
  f64 — and the bound itself is <= 1e-3 at the plan-chosen
  sub-cell-clustered configuration (the "proves rtol=1e-3" acceptance);
* the same under a hypothesis sweep (and a deterministic battery) of
  arbitrary point sets, z fields, query positions, radii and grids;
* z varying INSIDE tight spatial clusters does not block the proof: the
  dipole budget does not depend on the in-cell z spread;
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
  quadtree_rtol_bound}; the eager and jitted programs agree;
* far work per query grows sub-linearly in the cell count;
* the default phase2="exact" path stays bitwise identical, and Phase 1 is
  shared bitwise with quadtree plans.
"""

import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.accuracy import farfield_error_report
from repro.core.aidw import AIDWParams
from repro.core.grid import build_grid, quadtree_aggregates, quadtree_level_count
from repro.engine import build_plan, execute, execute_with_stats
from repro.engine.execute import _execute
from repro.engine.plan import _bound_from_tau, _quadtree_tau_required
from repro.errors import UnprovableRtolWarning
from conftest import require_hypothesis

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
    coefficient; a monopole-only budget pays for it to first order)."""
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
@pytest.mark.parametrize("radius", [None, 2, 3])
def test_measured_error_within_proved_bound(radius, dist):
    """Acceptance: measured max relative error <= the proved dipole bound
    on all four query distributions, at the plan-chosen radius (None) and
    at user ``farfield_radius=`` overrides — AND the bound is finite at
    every radius and proves the default rtol=1e-3 at this configuration."""
    dx, dy, dz = _tight_data(seed=10)
    qx, qy = _queries(dist, 220, seed=11)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a provable config must not warn
        plan = _quadtree_plan(dx, dy, dz, farfield_radius=radius)
    assert np.isfinite(plan.farfield_bound), "this configuration must be provable"
    assert plan.farfield_bound <= 1e-3, "the dipole bound must prove rtol=1e-3"
    if radius is not None:
        assert plan.farfield_radius == radius
    assert len(plan.qt_levels) == quadtree_level_count(12, 12)
    rep = farfield_error_report(plan, jnp.asarray(qx), jnp.asarray(qy))
    assert rep["phase2"] == "quadtree"
    assert rep["bound"] == plan.farfield_bound
    assert rep["within_bound"], rep


def test_quadtree_proves_where_single_level_cannot():
    """The reason the dipole term exists: z varying inside tight spatial
    clusters costs a monopole-only budget a first-order term that blocks
    rtol=1e-3 (the retired single-level arm could not prove it here), while
    the dipole budget stays second-order: the same clusters with and
    without in-cluster z noise get the same radius, opening ratio and
    bound, and the noisy one's measured error honours it."""
    dx, dy, dz = _tight_data(seed=20, z_noise=0.5)
    plan_q = _quadtree_plan(dx, dy, dz)
    plan_flat = _quadtree_plan(dx, dy, _field(dx, dy))
    assert plan_q.farfield_bound <= 1e-3
    assert ((plan_q.farfield_radius, plan_q.qt_tau, plan_q.farfield_bound)
            == (plan_flat.farfield_radius, plan_flat.qt_tau,
                plan_flat.farfield_bound)), "in-cell z spread entered the budget"
    qx, qy = _queries("uniform", 200, seed=21)
    rep = farfield_error_report(plan_q, jnp.asarray(qx), jnp.asarray(qy))
    assert rep["within_bound"], rep


@pytest.mark.parametrize("dist", DISTRIBUTIONS)
def test_measured_error_within_bound_f64(dist):
    """Same enforcement in f64 (no native f64 on the TPU target, but the
    interpret-mode path must honour the budget at both widths)."""
    with jax.enable_x64():
        dx, dy, dz = _tight_data(seed=12, dtype=np.float64)
        qx, qy = _queries(dist, 150, seed=13, dtype=np.float64)
        plan = _quadtree_plan(dx, dy, dz)
        assert plan.farfield_bound <= 1e-3
        rep = farfield_error_report(plan, jnp.asarray(qx), jnp.asarray(qy))
        assert rep["within_bound"], rep
        # f64 fp slack is ~1e-14: the measured error must be genuinely tiny
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


def test_error_budget_property():
    """Hypothesis sweep: arbitrary point sets, z values, query positions
    (inside and far outside the bbox), radii and grid resolutions — the
    measured error NEVER exceeds the proved bound."""
    require_hypothesis()
    from hypothesis import given, settings, strategies as st

    coord = st.floats(0.0, 1.0, allow_nan=False, width=32)
    zval = st.floats(-3.0, 3.0, allow_nan=False, width=32)
    qcoord = st.floats(-2.0, 3.0, allow_nan=False, width=32)

    @settings(deadline=None, max_examples=15)
    @given(
        pts=st.lists(st.tuples(coord, coord, zval), min_size=12, max_size=80),
        qs=st.lists(st.tuples(qcoord, qcoord), min_size=1, max_size=20),
        radius=st.sampled_from([1, 2, 3, 4]),
        gres=st.sampled_from([2, 4, 8]),
    )
    def run(pts, qs, radius, gres):
        _check_bound(np.asarray(pts, np.float32), np.asarray(qs, np.float32),
                     radius, gres)

    run()


def _check_bound(pts, qs, radius, gres):
    """Shared body of the property test — also driven deterministically
    below, so the check itself runs even where hypothesis is absent."""
    k = min(10, pts.shape[0])
    p = AIDWParams(k=k, area=1.0)
    g = build_grid(jnp.asarray(pts[:, 0]), jnp.asarray(pts[:, 1]),
                   jnp.asarray(pts[:, 2]), gx=gres, gy=gres)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        plan = build_plan(pts[:, 0], pts[:, 1], pts[:, 2], params=p, area=1.0,
                          impl="grid", grid=g, phase2="quadtree",
                          farfield_radius=radius, block_q=64)
    rep = farfield_error_report(plan, jnp.asarray(qs[:, 0]), jnp.asarray(qs[:, 1]))
    assert rep["within_bound"], (rep, radius, gres, pts.shape)


def test_error_budget_deterministic_draws():
    """Deterministic instances of the property body: degenerate point sets
    (identical points, collinear, one point per cell), mixed-sign z, and
    queries far outside the bbox."""
    rng = np.random.default_rng(3)
    cases = [
        np.column_stack([np.full(16, 0.5), np.full(16, 0.5), np.full(16, 2.0)]),
        np.column_stack([np.linspace(0, 1, 24), np.linspace(0, 1, 24),
                         np.sin(np.arange(24.0))]),
        np.column_stack([rng.random(40), rng.random(40), rng.random(40) * 4 - 2]),
    ]
    qs = np.asarray([[0.5, 0.5], [-1.5, 2.5], [0.0, 1.0], [2.9, -1.9]])
    for pts in cases:
        for radius, gres in ((1, 2), (2, 4), (3, 8)):
            _check_bound(pts.astype(np.float32), qs.astype(np.float32),
                         radius, gres)


# ------------------------------------------- level re-aggregation (bitwise)
def _ftz(x):
    """Flush subnormals to zero, as XLA's CPU backend does to the operands
    and results of its arithmetic: the levels are computed there, so the
    NumPy side reads its inputs and states its results the same way."""
    x = np.asarray(x)
    return np.where(np.abs(x) < np.finfo(x.dtype).tiny, np.zeros((), x.dtype), x)


def _assert_levels_consistent(g):
    """Bitwise (up to the CPU backend's flush of subnormals, :func:`_ftz`):
    combining level l's 2x2 children with the documented exact reductions
    reproduces level l+1's count/z-sum/centroid/moment arrays;
    conservative: per-node e/zd really bound the raw points."""
    qt = quadtree_aggregates(g)
    assert len(qt) == quadtree_level_count(g.gx, g.gy)
    for a, b in zip(qt, qt[1:]):
        def img(arr, lv=a):
            return _ftz(arr).reshape(lv.ny, lv.nx)

        def pad(x, fill=0.0):
            return np.pad(x, ((0, a.ny % 2), (0, a.nx % 2)),
                          constant_values=fill)

        ch = [(pad(img(a.count))[dy::2, dx::2], pad(img(a.z_sum))[dy::2, dx::2],
               pad(img(a.cent_x))[dy::2, dx::2], pad(img(a.cent_y))[dy::2, dx::2],
               pad(img(a.mx))[dy::2, dx::2], pad(img(a.my))[dy::2, dx::2])
              for dy, dx in ((0, 0), (0, 1), (1, 0), (1, 1))]
        # every intermediate is flushed as XLA flushes it
        cnt = _ftz(_ftz(_ftz(ch[0][0] + ch[1][0]) + ch[2][0]) + ch[3][0])
        zs = _ftz(_ftz(_ftz(ch[0][1] + ch[1][1]) + ch[2][1]) + ch[3][1])
        np.testing.assert_array_equal(_ftz(b.count).reshape(b.ny, b.nx), cnt)
        np.testing.assert_array_equal(_ftz(b.z_sum).reshape(b.ny, b.nx), zs)
        denom = np.maximum(cnt, np.asarray(1.0, cnt.dtype))

        def wsum(i):
            t = [_ftz(c[0] * c[i]) for c in ch]
            return _ftz(_ftz(_ftz(t[0] + t[1]) + t[2]) + t[3])

        bx = _ftz(b.cent_x).reshape(b.ny, b.nx)
        by = _ftz(b.cent_y).reshape(b.ny, b.nx)
        nonempty = cnt > 0
        np.testing.assert_array_equal(np.where(nonempty, _ftz(wsum(2) / denom), bx), bx)
        np.testing.assert_array_equal(np.where(nonempty, _ftz(wsum(3) / denom), by), by)

        def moment(i, j, cent):
            total = None
            for c in ch:
                term = _ftz(c[i] + _ftz(c[1] * _ftz(c[j] - cent)))
                total = term if total is None else _ftz(total + term)
            return total

        np.testing.assert_array_equal(_ftz(b.mx).reshape(b.ny, b.nx), moment(4, 2, bx))
        np.testing.assert_array_equal(_ftz(b.my).reshape(b.ny, b.nx), moment(5, 3, by))

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
    shrinks (the property the plan's level-selection relies on) and the
    tau_req solver inverts it."""
    taus = np.linspace(0.3, 1e-4, 60)
    bounds = [_bound_from_tau(float(t), 4.0) for t in taus]
    assert all(b1 >= b2 for b1, b2 in zip(bounds, bounds[1:]))
    assert _bound_from_tau(0.0, 4.0) == 0.0
    assert _bound_from_tau(1.0, 4.0) == np.inf
    for rtol in (1e-2, 1e-3, 1e-4):
        tau = _quadtree_tau_required(4.0, rtol)
        assert _bound_from_tau(tau, 4.0) <= rtol
        assert _bound_from_tau(tau * 1.1, 4.0) > rtol


def test_dipole_bound_monotone_property():
    """Same local-fallback policy as test_level_reaggregation_property."""
    def check(tau, shrink, a):
        assert (_bound_from_tau(tau * shrink, a)
                <= _bound_from_tau(tau, a))

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


def test_quadtree_eager_matches_jit():
    """The traced program and the same code run op by op agree, stats
    included: nothing in the quadtree path depends on being under jit."""
    dx, dy, dz = _tight_data(seed=18)
    plan = _quadtree_plan(dx, dy, dz)
    qx, qy = map(jnp.asarray, _queries("clustered", 200, seed=19))
    z_jit, a_jit = execute(plan, qx, qy)
    with jax.disable_jit():
        z_eag, a_eag, stats = _execute(plan, qx, qy)
    np.testing.assert_allclose(np.asarray(z_eag), np.asarray(z_jit), rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(a_eag), np.asarray(a_jit), rtol=0, atol=1e-5)
    _, _, stats_jit = execute_with_stats(plan, qx, qy)
    for key in ("far_cells_mean", "near_points_mean", "p2_overflow_queries"):
        assert float(stats[key]) == float(stats_jit[key]), key


def test_quadtree_far_work_grows_sublinearly():
    """Far terms per query block grow ~O(log m), not with the cell count:
    uniform data at two sizes 16x apart, the near radius pinned (the test
    measures level scaling, not the radius policy)."""
    rng = np.random.default_rng(22)
    far, cells = [], []
    for m in (4096, 65536):
        dx = rng.random(m).astype(np.float32)
        dy = rng.random(m).astype(np.float32)
        dz = _field(dx, dy)
        q = (rng.random(2) * 0.85 + 0.12 * rng.random((64, 2))).astype(np.float32)
        g = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # uniform data: honest bound
            plan = build_plan(dx, dy, dz, params=P, area=1.0, impl="grid", grid=g,
                              phase2="quadtree", farfield_radius=2, block_q=64,
                              query_occupancy=max(64 / (0.12 * g.gx) ** 2, 0.5))
        _, _, stats = execute_with_stats(plan, jnp.asarray(q[:, 0]), jnp.asarray(q[:, 1]))
        assert int(stats["p2_overflow_queries"]) == 0, "the far field must run"
        far.append(float(stats["far_cells_mean"]))
        cells.append(g.n_cells)
    cells_growth = cells[1] / cells[0]
    work_growth = far[1] / max(far[0], 1.0)
    assert cells_growth >= 8, cells
    assert work_growth <= max(np.sqrt(cells_growth), 2.0), (far, cells)


# -------------------------------------------------------------- validations
def test_farfield_validations():
    dx, dy, dz = _tight_data(seed=7, m=256)
    with pytest.raises(ValueError, match="phase2"):
        build_plan(dx, dy, dz, params=P, area=1.0, impl="grid", phase2="fmm")
    # the retired single-level arm is refused by name
    with pytest.raises(ValueError, match="'exact' or 'quadtree'"):
        build_plan(dx, dy, dz, params=P, area=1.0, impl="grid", phase2="farfield")
    with pytest.raises(ValueError, match="quadtree"):
        build_plan(dx, dy, dz, params=P, area=1.0, impl="tiled",
                   phase2="quadtree")
    with pytest.raises(ValueError, match="farfield_rtol"):
        build_plan(dx, dy, dz, params=P, area=1.0, impl="grid",
                   phase2="quadtree", farfield_rtol=0.0)
    with pytest.raises(ValueError, match="farfield_radius"):
        build_plan(dx, dy, dz, params=P, area=1.0, impl="grid",
                   phase2="quadtree", farfield_radius=0)


def test_quadtree_validations():
    dx, dy, dz = _tight_data(seed=7, m=256)
    with pytest.raises(ValueError, match="phase2"):
        build_plan(dx, dy, dz, params=P, area=1.0, impl="grid", phase2="bh")
    with pytest.raises(ValueError, match="quadtree"):
        build_plan(dx, dy, dz, params=P, area=1.0, impl="tiled",
                   phase2="quadtree")
    # exact plans carry empty quadtree statics
    plan = build_plan(dx, dy, dz, params=P, area=1.0, impl="grid")
    assert plan.qt_levels == () and plan.qt_tau == 0.0


# ------------------------------------------------------- exact path untouched
def test_default_phase2_exact_is_bitwise_identical():
    """phase2 defaults to "exact" and produces bitwise-identical z AND alpha
    to an explicitly-exact plan; quadtree plans share Phase 1 bitwise (alpha
    equal), only z may differ — and only within the bound."""
    dx, dy, dz = _tight_data(seed=8)
    qx, qy = map(jnp.asarray, _queries("uniform", 300, seed=9))
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz), gx=12, gy=12)
    plan_default = build_plan(dx, dy, dz, params=P, area=1.0, impl="grid", grid=g)
    plan_exact = build_plan(dx, dy, dz, params=P, area=1.0, impl="grid", grid=g,
                            phase2="exact")
    assert plan_default.phase2 == "exact"
    z0, a0 = execute(plan_default, qx, qy)
    z1, a1 = execute(plan_exact, qx, qy)
    np.testing.assert_array_equal(np.asarray(z0), np.asarray(z1))
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a1))

    plan_qt = _quadtree_plan(dx, dy, dz, block_q=256)
    assert plan_qt.farfield_bound <= 1e-3
    z2, a2 = execute(plan_qt, qx, qy)
    np.testing.assert_array_equal(np.asarray(a2), np.asarray(a0))
    scale = float(np.max(np.abs(dz)))
    assert float(jnp.max(jnp.abs(z2 - z0))) / scale <= plan_qt.farfield_bound + 1e-5


def test_cell_aggregates_consistency():
    """Quadtree level 0 matches a numpy recomputation: counts, z-sums,
    centroids, and the dispersion and z-spread maxima."""
    dx, dy, dz = _tight_data(seed=17, m=600, gx=6, sigma=0.003)
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz), gx=6, gy=6)
    lv0 = quadtree_aggregates(g)[0]
    assert (lv0.nx, lv0.ny, lv0.step) == (6, 6, 1)
    cx = np.clip((dx * 6).astype(int), 0, 5)
    cy = np.clip((dy * 6).astype(int), 0, 5)
    cid = cy * 6 + cx
    assert np.sum(np.asarray(lv0.count)) == 600
    e_ref, zdev_ref = 0.0, 0.0
    for c in range(36):
        sel = cid == c
        if not sel.any():
            assert float(lv0.count[c]) == 0.0
            continue
        np.testing.assert_allclose(float(lv0.count[c]), sel.sum())
        np.testing.assert_allclose(float(lv0.z_sum[c]), dz[sel].sum(), rtol=1e-5)
        np.testing.assert_allclose(float(lv0.cent_x[c]), dx[sel].mean(), atol=1e-5)
        np.testing.assert_allclose(float(lv0.cent_y[c]), dy[sel].mean(), atol=1e-5)
        e_ref = max(e_ref, np.sqrt((dx[sel] - dx[sel].mean()) ** 2
                                   + (dy[sel] - dy[sel].mean()) ** 2).max())
        zdev_ref = max(zdev_ref, np.abs(dz[sel] - dz[sel].mean()).max())
    np.testing.assert_allclose(lv0.e_max, e_ref, rtol=1e-4)
    np.testing.assert_allclose(lv0.zd_max, zdev_ref, rtol=1e-3, atol=1e-6)
