"""Plan/execute engine (repro.engine): parity of the jit-compatible grid
execute against the oracle on uniform + clustered data, jit compilation with
no retrace across same-shape query batches, bitwise plan reuse, the
static-capacity overflow fallback, and the unified dispatch for every impl
(dense family, tiled_v2 diagnostics, idw, chunked)."""

import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.aidw import AIDWParams, aidw_interpolate, aidw_reference
from repro.core.grid import build_grid
from repro.core.idw import idw_reference
from repro.engine import build_plan, execute, execute_with_stats
from repro.engine.execute import _execute
from repro.errors import PathologicalGridWarning
from repro.kernels import aidw, idw
from conftest import make_points

RTOL, ATOL = 2e-4, 2e-5


def _as_jnp(*arrays):
    return tuple(jnp.asarray(a) for a in arrays)


# ------------------------------------------------------------ grid execute
@pytest.mark.parametrize("clustered", [False, True])
def test_grid_execute_matches_reference(clustered):
    """execute(plan, q) must match the oracle on uniform AND clustered data
    (the acceptance parity: same r_obs -> alpha and z_hat as the eager
    brute-force reference, to kernel tolerance)."""
    dx, dy, dz, qx, qy = make_points(900, 400, seed=21, clustered=clustered)
    p = AIDWParams(k=10, area=1.0)
    z_ref, a_ref = aidw_reference(dx, dy, dz, qx, qy, p, area=1.0)
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid")
    z, a = execute(plan, *_as_jnp(qx, qy))
    np.testing.assert_allclose(np.asarray(a), np.asarray(a_ref), rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), rtol=RTOL, atol=ATOL)


def test_grid_execute_matches_wrapper():
    """kernels.ops.aidw(impl='grid') routes through the same plan path —
    results must be bitwise identical to a hand-built plan."""
    dx, dy, dz, qx, qy = make_points(700, 300, seed=22, clustered=True)
    p = AIDWParams(k=10, area=1.0)
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid")
    z1, a1 = execute(plan, *_as_jnp(qx, qy))
    z2, a2 = aidw(dx, dy, dz, qx, qy, params=p, area=1.0, impl="grid")
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


def test_grid_execute_jit_no_retrace():
    """The acceptance contract: the grid execute step compiles under jax.jit
    (plan built eagerly, execute traced) and does NOT retrace across query
    batches of the same shape."""
    dx, dy, dz, qx1, qy1 = make_points(600, 173, seed=23)
    _, _, _, qx2, qy2 = make_points(600, 173, seed=24)
    p = AIDWParams(k=10, area=1.0)
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid")
    n0 = execute._cache_size()
    z1, a1 = execute(plan, *_as_jnp(qx1, qy1))
    n1 = execute._cache_size()
    z2, a2 = execute(plan, *_as_jnp(qx2, qy2))
    n2 = execute._cache_size()
    assert n1 == n0 + 1, "first same-shape batch should add exactly one executable"
    assert n2 == n1, "second same-shape batch must hit the jit cache (no retrace)"
    # and the traced results are the real thing: parity vs the eager trace
    z_eager, a_eager, _ = _execute(plan, *_as_jnp(qx2, qy2))
    np.testing.assert_allclose(np.asarray(z2), np.asarray(z_eager), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(a2), np.asarray(a_eager), rtol=1e-6)


def test_plan_reuse_bitwise_identical():
    """One plan, two query sets: results must be bitwise identical to
    building a fresh plan per batch (nothing about a plan is batch-coupled)."""
    dx, dy, dz, qx1, qy1 = make_points(800, 256, seed=25, clustered=True)
    _, _, _, qx2, qy2 = make_points(800, 256, seed=26, clustered=True)
    p = AIDWParams(k=10, area=1.0)
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid")
    for qx, qy in ((qx1, qy1), (qx2, qy2)):
        z_reused, a_reused = execute(plan, *_as_jnp(qx, qy))
        fresh = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid")
        z_fresh, a_fresh = execute(fresh, *_as_jnp(qx, qy))
        np.testing.assert_array_equal(np.asarray(z_reused), np.asarray(z_fresh))
        np.testing.assert_array_equal(np.asarray(a_reused), np.asarray(a_fresh))


def test_grid_fallback_stays_exact_out_of_bbox():
    """Query batches beyond the plan's static candidate capacity (far
    out-of-bbox) must flip the fallback flag and STILL match the oracle —
    the static fast path never silently drops a neighbour."""
    dx, dy, dz, qx, qy = make_points(4096, 80, seed=27, clustered=False)
    qx = (qx * 6.0 - 3.0).astype(np.float32)
    qy = (qy * 6.0 - 3.0).astype(np.float32)
    p = AIDWParams(k=10, area=1.0, r_max=64.0)
    # a dense-batch capacity hint keeps the static rows tight, so the far
    # batch genuinely overflows them
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                      query_occupancy=64.0)
    assert plan.cand_capacity < plan.m
    z_ref, a_ref = aidw_reference(dx, dy, dz, qx, qy, p, area=1.0)
    z, a, stats = execute_with_stats(plan, *_as_jnp(qx, qy))
    assert bool(stats["grid_fallback"]), "far queries should exceed the static capacity"
    np.testing.assert_allclose(np.asarray(a), np.asarray(a_ref), rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), rtol=RTOL, atol=ATOL)


def test_grid_fast_path_used_for_dense_batches():
    """In-bbox query batches as dense as the data must fit the plan's static
    capacity (no fallback) — the capacity heuristic is doing its job."""
    dx, dy, dz, qx, qy = make_points(2048, 2048, seed=28, clustered=False)
    p = AIDWParams(k=10, area=1.0)
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid")
    _, _, stats = execute_with_stats(plan, *_as_jnp(qx, qy))
    assert not bool(stats["grid_fallback"])
    assert int(stats["cand_need_max"]) <= plan.cand_capacity


def test_grid_plan_autotunes_block_d():
    """Narrow candidate neighbourhoods must shrink the Phase-1 tile below
    the requested block_d (the ROADMAP autotune), and the padded capacity
    must stay a multiple of it."""
    dx, dy, dz, _, _ = make_points(4096, 1, seed=29, clustered=False)
    p = AIDWParams(k=10, area=1.0)
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid", block_d=4096,
                      query_occupancy=64.0)
    assert plan.cand_block_d < 4096
    assert plan.cand_block_d % 128 == 0
    assert plan.cand_capacity % plan.cand_block_d == 0


def test_grid_plan_rebuilds_pathological_resolution():
    """Strongly clustered data on the default (too fine) resolution must
    trigger the plan-time coarsening rebuild; a user-supplied grid must be
    kept and warned about instead."""
    rng = np.random.default_rng(31)
    a = 0.01 * rng.random((400, 2)).astype(np.float32)
    b = 0.99 + 0.01 * rng.random((400, 2)).astype(np.float32)
    pts = np.concatenate([a, b])
    dz = rng.random(800).astype(np.float32)
    p = AIDWParams(k=10, area=1.0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # may still warn after max rebuilds
        plan = build_plan(pts[:, 0], pts[:, 1], dz, params=p, area=1.0, impl="grid",
                          target_occupancy=0.25)
    assert plan.grid_rebuilds > 0
    g = build_grid(jnp.asarray(pts[:, 0]), jnp.asarray(pts[:, 1]), jnp.asarray(dz),
                   gx=64, gy=64)
    with pytest.warns(PathologicalGridWarning):
        user_plan = build_plan(pts[:, 0], pts[:, 1], dz, params=p, area=1.0,
                               impl="grid", grid=g)
    assert user_plan.grid is g
    assert user_plan.grid_rebuilds == 0


# ------------------------------------------------------- unified dispatch
@pytest.mark.parametrize("impl", ["naive", "tiled", "fused", "tiled_v2"])
def test_dense_plans_match_reference(impl):
    dx, dy, dz, qx, qy = make_points(512, 200, seed=32, clustered=True)
    p = AIDWParams(k=10, area=1.0)
    z_ref, a_ref = aidw_reference(dx, dy, dz, qx, qy, p, area=1.0)
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl=impl,
                      block_q=64, block_d=128)
    z, a = execute(plan, *_as_jnp(qx, qy))
    np.testing.assert_allclose(np.asarray(a), np.asarray(a_ref), rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), rtol=RTOL, atol=ATOL)


def test_tiled_v2_dispatch_and_diagnostic():
    """impl='tiled_v2' flows through aidw() and keeps its merge-fraction
    diagnostic via execute_with_stats; the standalone aidw_v2 is deprecated
    but still functional."""
    from repro.kernels.ops import aidw_v2

    dx, dy, dz, qx, qy = make_points(1000, 256, seed=33, clustered=True)
    p = AIDWParams(k=10, area=1.0)
    z1, a1 = aidw(dx, dy, dz, qx, qy, params=p, area=1.0, impl="tiled_v2",
                  block_q=64, block_d=128)
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="tiled_v2",
                      block_q=64, block_d=128)
    z2, a2, stats = execute_with_stats(plan, *_as_jnp(qx, qy))
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))
    assert 0.0 < float(stats["merge_fraction"]) <= 1.0
    with pytest.warns(DeprecationWarning):
        z3, a3, frac = aidw_v2(dx, dy, dz, qx, qy, params=p, area=1.0,
                               block_q=64, block_d=128)
    np.testing.assert_array_equal(np.asarray(z3), np.asarray(z2))
    np.testing.assert_array_equal(np.asarray(frac), np.asarray(stats["merge_fraction"]))


def test_idw_plan_matches_reference():
    dx, dy, dz, qx, qy = make_points(400, 150, seed=34)
    plan = build_plan(dx, dy, dz, impl="idw", idw_alpha=2.0, area=1.0,
                      block_q=64, block_d=128)
    z, alpha = execute(plan, *_as_jnp(qx, qy))
    z_ref = idw_reference(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz),
                          jnp.asarray(qx), jnp.asarray(qy), alpha=2.0)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), rtol=RTOL, atol=ATOL)
    np.testing.assert_array_equal(np.asarray(alpha), np.full(150, 2.0, np.float32))
    z_wrapper = idw(dx, dy, dz, qx, qy, alpha=2.0, block_q=64, block_d=128)
    np.testing.assert_array_equal(np.asarray(z), np.asarray(z_wrapper))


@pytest.mark.parametrize("knn", ["brute", "grid"])
def test_chunked_plan_matches_interpolate(knn):
    """aidw_interpolate is a thin wrapper over impl='chunked' plans — a
    hand-built plan must reproduce it bitwise, for both knn modes."""
    dx, dy, dz, qx, qy = make_points(700, 300, seed=35, clustered=True)
    p = AIDWParams(k=10, area=1.0)
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="chunked", knn=knn,
                      q_chunk=128, d_chunk=256)
    z1, a1 = execute(plan, *_as_jnp(qx, qy))
    z2, a2 = aidw_interpolate(dx, dy, dz, qx, qy, p, area=1.0, q_chunk=128,
                              d_chunk=256, knn=knn)
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


def test_chunked_grid_execute_is_jit_compatible():
    """Since the refactor the chunked knn='grid' path also executes under an
    outer jit (the grid is a plan child, the ring search is traced)."""
    dx, dy, dz, qx, qy = make_points(600, 200, seed=36)
    p = AIDWParams(k=10, area=1.0)
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="chunked", knn="grid")
    z, a = jax.jit(lambda pl_, x, y: execute(pl_, x, y))(plan, *_as_jnp(qx, qy))
    z_ref, a_ref = aidw_reference(dx, dy, dz, qx, qy, p, area=1.0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(a_ref), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------- validation
def test_build_plan_validations():
    dx, dy, dz, qx, qy = make_points(128, 32, seed=37)
    p = AIDWParams(k=10, area=1.0)
    with pytest.raises(ValueError):
        build_plan(dx, dy, dz, params=p, area=1.0, impl="octree")
    with pytest.raises(ValueError):
        build_plan(dx, dy, dz, params=p, area=1.0, impl="grid", layout="aoas")
    with pytest.raises(ValueError):
        g = build_grid(jnp.asarray(dx), jnp.asarray(dy))
        build_plan(dx, dy, dz, params=p, area=1.0, impl="tiled", grid=g)
    with pytest.raises(ValueError):
        build_plan(dx, dy, dz, params=p, area=1.0, impl="chunked", knn="octree")
    with pytest.raises(ValueError):
        build_plan(dx[:5], dy[:5], dz[:5], params=p, area=1.0, impl="tiled")
    with pytest.raises(ValueError):
        build_plan(dx, dy, dz, params=AIDWParams(k=10), impl="tiled")
    # the engine plans "idw"/"chunked" but aidw() must keep rejecting them
    # (they have their own entry points with different semantics)
    for impl in ("idw", "chunked"):
        with pytest.raises(ValueError):
            aidw(dx, dy, dz, qx, qy, params=p, area=1.0, impl=impl)


@pytest.mark.parametrize("backend, expected", [("cpu", True), ("tpu", False), ("gpu", None)])
def test_auto_interpret_only_on_cpu(monkeypatch, backend, expected):
    """Interpret mode only on the CPU; a backend with no Pallas lowering
    raises rather than hiding behind a slow interpreter."""
    from repro.engine import plan as plan_mod

    monkeypatch.setattr(plan_mod.jax, "default_backend", lambda: backend)
    if expected is None:
        with pytest.raises(RuntimeError, match="no Pallas lowering"):
            plan_mod._auto_interpret(None)
    else:
        assert plan_mod._auto_interpret(None) is expected
    assert plan_mod._auto_interpret(False) is False
