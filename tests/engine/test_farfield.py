"""Far-field Phase 2 (build_plan(phase2="farfield"), DESIGN.md §7): the
error budget is ENFORCED, not just reported.

* the measured relative error (Kahan-oracle comparison,
  core.accuracy.farfield_error_report) must stay within the plan's proved
  worst-case bound on uniform / clustered / seam-straddling / out-of-bbox
  query distributions, in f32 and f64, deterministically AND under a
  hypothesis sweep of arbitrary point sets, z fields, radii and grids;
* the default phase2="exact" path must remain bitwise identical to a plan
  that never heard of far fields (Phase 1 shares one code path, so alpha is
  bitwise equal even on farfield plans);
* near-field overflow (batches sparser than the capacity model assumed)
  must route those queries to the exact sweep — bitwise — never to an
  unproved truncated near field;
* the model itself is sanity-pinned: zero dispersion => zero bound,
  monotone improvement with radius, inf when nothing is provable.
"""

import warnings

import numpy as np
import jax.numpy as jnp
import pytest

import jax

from repro.core.accuracy import farfield_error_report
from repro.core.aidw import AIDWParams
from repro.core.grid import build_grid, cell_aggregates
from repro.engine import build_plan, execute, execute_with_stats
from repro.engine.plan import _farfield_bound_model
from repro.errors import UnprovableRtolWarning
from conftest import require_hypothesis

P = AIDWParams(k=10, area=1.0)
DISTRIBUTIONS = ("uniform", "clustered", "seam", "out_of_bbox")


def _field(x, y):
    return (np.sin(6 * x) * np.cos(6 * y) + 2.0).astype(x.dtype)


def _cluster_data(seed, dtype=np.float32, gx=12, m=4000, sigma=0.003):
    """Tight per-cell clusters on a coarse user grid: small dispersion
    relative to the cell size, so the worst-case model proves a FINITE
    bound at small radii — the configuration where the budget test bites."""
    rng = np.random.default_rng(seed)
    centers = (np.stack(np.meshgrid(np.arange(gx), np.arange(gx)), -1)
               .reshape(-1, 2) + 0.5) / gx
    pts = centers[rng.integers(0, gx * gx, m)] + rng.normal(0, sigma, (m, 2))
    pts = np.clip(pts, 0.0, 1.0).astype(dtype)
    dx, dy = pts[:, 0], pts[:, 1]
    return dx, dy, _field(dx, dy)


def _queries(dist, nq, seed, dtype=np.float32):
    rng = np.random.default_rng(seed)
    if dist == "uniform":
        q = rng.random((nq, 2))
    elif dist == "clustered":  # tile-local serving batch
        q = 0.35 + 0.12 * rng.random((nq, 2))
    elif dist == "seam":  # full diagonal: straddles every Morton seam level
        t = np.linspace(0.02, 0.98, nq)
        q = np.stack([t, t], 1) + rng.normal(0, 0.01, (nq, 2))
    elif dist == "out_of_bbox":
        q = rng.random((nq, 2)) * 6.0 - 3.0
    else:  # pragma: no cover
        raise ValueError(dist)
    q = q.astype(dtype)
    return q[:, 0], q[:, 1]


def _farfield_plan(dx, dy, dz, *, radius, gx=12, block_q=64):
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz),
                   gx=gx, gy=gx)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # pathological-resolution warnings
        return build_plan(dx, dy, dz, params=P, area=1.0, impl="grid",
                          grid=g, phase2="farfield", farfield_radius=radius,
                          block_q=block_q)


# ----------------------------------------------------- error budget (tentpole)
@pytest.mark.parametrize("dist", DISTRIBUTIONS)
@pytest.mark.parametrize("radius", [2, 3])
def test_measured_error_within_proved_bound(dist, radius):
    """The acceptance property: measured max relative error <= the plan's
    farfield_rtol_bound, on all four query distributions, with a FINITE
    bound (the tight-cluster data keeps the model's tau small)."""
    dx, dy, dz = _cluster_data(seed=10)
    qx, qy = _queries(dist, 220, seed=11)
    plan = _farfield_plan(dx, dy, dz, radius=radius)
    assert np.isfinite(plan.farfield_bound), "this configuration must be provable"
    rep = farfield_error_report(plan, jnp.asarray(qx), jnp.asarray(qy))
    assert rep["bound"] == plan.farfield_bound
    assert rep["within_bound"], rep


@pytest.mark.parametrize("dist", ["uniform", "out_of_bbox"])
def test_measured_error_within_bound_f64(dist):
    """Same enforcement in f64 (no native f64 on the TPU target, but the
    interpret-mode path must honour the budget at both widths)."""
    with jax.enable_x64():
        dx, dy, dz = _cluster_data(seed=12, dtype=np.float64)
        qx, qy = _queries(dist, 150, seed=13, dtype=np.float64)
        plan = _farfield_plan(dx, dy, dz, radius=2)
        assert np.isfinite(plan.farfield_bound)
        rep = farfield_error_report(plan, jnp.asarray(qx), jnp.asarray(qy))
        assert rep["within_bound"], rep
        # f64 fp slack is ~1e-14: the measured error must be genuinely tiny
        assert rep["max_rel_err"] <= plan.farfield_bound + 1e-12


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
                          impl="grid", grid=g, phase2="farfield",
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


# -------------------------------------------------- model sanity / plan choice
def test_bound_model_shape():
    """Zero dispersion proves zero error; the bound improves monotonically
    with the radius; radii too small for any guarantee report inf."""
    assert _farfield_bound_model(3, 0.1, 4.0, 0.0, 0.5, 1.0) == 0.0
    bounds = [_farfield_bound_model(r, 0.1, 4.0, 0.005, 0.1, 1.0)
              for r in (1, 2, 4, 8, 16)]
    assert all(np.isfinite(bounds))
    assert all(b1 >= b2 for b1, b2 in zip(bounds, bounds[1:]))
    assert _farfield_bound_model(1, 0.1, 4.0, 0.2, 0.1, 1.0) == np.inf
    # z varying inside cells costs a first-order term: strictly worse than
    # the same geometry with cell-constant z
    assert (_farfield_bound_model(4, 0.1, 4.0, 0.005, 0.5, 1.0)
            > _farfield_bound_model(4, 0.1, 4.0, 0.005, 0.0, 1.0))


def test_plan_reports_bound_and_warns_when_unprovable():
    """farfield_rtol far below what a single-level aggregate can prove at a
    profitable radius: the plan warns, reports the honest bound, and the
    stats carry it; a huge rtol is chosen without warning."""
    rng = np.random.default_rng(5)
    dx, dy = rng.random(4096).astype(np.float32), rng.random(4096).astype(np.float32)
    dz = _field(dx, dy)
    with pytest.warns(UnprovableRtolWarning):
        plan = build_plan(dx, dy, dz, params=P, area=1.0, impl="grid",
                          phase2="farfield", farfield_rtol=1e-6)
    assert plan.farfield_radius >= 1
    qx = jnp.asarray(rng.random(300).astype(np.float32))
    qy = jnp.asarray(rng.random(300).astype(np.float32))
    _, _, stats = execute_with_stats(plan, qx, qy)
    assert float(stats["farfield_rtol_bound"]) == np.float32(plan.farfield_bound)
    assert {"near_points_mean", "far_cells_mean", "p2_overflow_queries"} < set(stats)
    # an easily-provable target (far set empty at worst) never warns
    dxc, dyc, dzc = _cluster_data(seed=6)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        plan2 = _farfield_plan(dxc, dyc, dzc, radius=3)
    assert np.isfinite(plan2.farfield_bound)


def test_farfield_validations():
    dx, dy, dz = _cluster_data(seed=7, m=256)
    with pytest.raises(ValueError, match="phase2"):
        build_plan(dx, dy, dz, params=P, area=1.0, impl="grid", phase2="fmm")
    with pytest.raises(ValueError, match="farfield"):
        build_plan(dx, dy, dz, params=P, area=1.0, impl="tiled",
                   phase2="farfield")
    with pytest.raises(ValueError, match="farfield_rtol"):
        build_plan(dx, dy, dz, params=P, area=1.0, impl="grid",
                   phase2="farfield", farfield_rtol=0.0)
    with pytest.raises(ValueError, match="farfield_radius"):
        build_plan(dx, dy, dz, params=P, area=1.0, impl="grid",
                   phase2="farfield", farfield_radius=0)


# ------------------------------------------------------- exact path untouched
def test_default_phase2_exact_is_bitwise_identical():
    """phase2 defaults to "exact" and produces bitwise-identical z AND alpha
    to an explicitly-exact plan; farfield plans share Phase 1 bitwise (alpha
    equal), only z may differ — and only within the bound."""
    dx, dy, dz = _cluster_data(seed=8)
    qx, qy = map(jnp.asarray, _queries("uniform", 300, seed=9))
    plan_default = build_plan(dx, dy, dz, params=P, area=1.0, impl="grid")
    plan_exact = build_plan(dx, dy, dz, params=P, area=1.0, impl="grid",
                            phase2="exact")
    assert plan_default.phase2 == "exact"
    z0, a0 = execute(plan_default, qx, qy)
    z1, a1 = execute(plan_exact, qx, qy)
    np.testing.assert_array_equal(np.asarray(z0), np.asarray(z1))
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a1))

    plan_ff = _farfield_plan(dx, dy, dz, radius=2, block_q=256)
    z2, a2 = execute(plan_ff, qx, qy)
    scale = float(np.max(np.abs(dz)))
    assert float(jnp.max(jnp.abs(z2 - z0))) / scale <= plan_ff.farfield_bound + 1e-5


def test_near_overflow_falls_back_to_exact_bitwise():
    """A batch sparser/wider than the near-capacity model assumed must NOT
    run on a truncated near field: every overflowed query's z is bitwise the
    exact full-sweep answer (same padded data, same alpha)."""
    rng = np.random.default_rng(14)
    dx, dy = rng.random(4096).astype(np.float32), rng.random(4096).astype(np.float32)
    dz = _field(dx, dy)
    p = AIDWParams(k=10, area=1.0, r_max=64.0)
    qx = jnp.asarray((rng.random(96) * 6 - 3).astype(np.float32))
    qy = jnp.asarray((rng.random(96) * 6 - 3).astype(np.float32))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        plan_ff = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                             phase2="farfield", farfield_radius=1,
                             query_occupancy=64.0)
        plan_ex = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                             query_occupancy=64.0)
    assert plan_ff.p2_capacity < plan_ff.m
    z_ff, a_ff, stats = execute_with_stats(plan_ff, qx, qy)
    z_ex, a_ex = execute(plan_ex, qx, qy)
    assert int(stats["p2_overflow_queries"]) == 96, "batch should overflow the near capacity"
    np.testing.assert_array_equal(np.asarray(z_ff), np.asarray(z_ex))
    np.testing.assert_array_equal(np.asarray(a_ff), np.asarray(a_ex))


# ----------------------------------------------------------- stats / no-retrace
def test_farfield_stats_static_and_no_retrace():
    dx, dy, dz = _cluster_data(seed=15)
    plan = _farfield_plan(dx, dy, dz, radius=2)
    rng = np.random.default_rng(16)
    qs = [(jnp.asarray(rng.random(200).astype(np.float32)),
           jnp.asarray(rng.random(200).astype(np.float32))) for _ in range(2)]
    n0 = execute_with_stats._cache_size()
    _, _, s1 = execute_with_stats(plan, *qs[0])
    n1 = execute_with_stats._cache_size()
    _, _, s2 = execute_with_stats(plan, *qs[1])
    n2 = execute_with_stats._cache_size()
    assert n1 == n0 + 1 and n2 == n1, "farfield stats must not retrace"
    assert set(s1) == set(s2)
    assert float(s1["far_cells_mean"]) > 0, "far path should engage in-bbox"
    assert float(s1["near_points_mean"]) > 0
    # the jitted stats carry the bound at the compute dtype
    assert float(s1["farfield_rtol_bound"]) == np.float32(plan.farfield_bound)


def test_cell_aggregates_consistency():
    """Aggregates match a numpy recomputation: counts, z-sums, centroids,
    dispersion and z-deviation maxima."""
    dx, dy, dz = _cluster_data(seed=17, m=600, gx=6)
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz), gx=6, gy=6)
    agg = cell_aggregates(g)
    cx = np.clip((dx * 6).astype(int), 0, 5)
    cy = np.clip((dy * 6).astype(int), 0, 5)
    cid = cy * 6 + cx
    assert np.sum(np.asarray(agg.count)) == 600
    e_ref, zdev_ref = 0.0, 0.0
    for c in range(36):
        sel = cid == c
        if not sel.any():
            assert float(agg.count[c]) == 0.0
            continue
        np.testing.assert_allclose(float(agg.count[c]), sel.sum())
        np.testing.assert_allclose(float(agg.z_sum[c]), dz[sel].sum(), rtol=1e-5)
        np.testing.assert_allclose(float(agg.cent_x[c]), dx[sel].mean(), atol=1e-5)
        np.testing.assert_allclose(float(agg.cent_y[c]), dy[sel].mean(), atol=1e-5)
        e_ref = max(e_ref, np.sqrt((dx[sel] - dx[sel].mean()) ** 2
                                   + (dy[sel] - dy[sel].mean()) ** 2).max())
        zdev_ref = max(zdev_ref, np.abs(dz[sel] - dz[sel].mean()).max())
    np.testing.assert_allclose(agg.e_max, e_ref, rtol=1e-4)
    np.testing.assert_allclose(agg.z_dev_max, zdev_ref, rtol=1e-3, atol=1e-6)
    np.testing.assert_allclose(agg.z_abs_max, np.abs(dz).max(), rtol=1e-6)
