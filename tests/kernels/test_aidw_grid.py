"""Grid-kNN AIDW paths vs the oracle: the Pallas grid kernel (impl="grid",
interpret mode) and the pure-jnp grid-accelerated interpolate (knn="grid")
must match aidw_reference on uniform AND clustered data — including ragged
shapes, grid reuse, exact hits, and out-of-grid queries."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.aidw import AIDWParams, aidw_interpolate, aidw_reference
from repro.core.grid import build_grid
from repro.kernels import aidw
from conftest import make_points

RTOL, ATOL = 2e-4, 2e-5


def _check_grid_kernel(m, n, k=10, block_q=64, block_d=128, seed=0, clustered=True):
    dx, dy, dz, qx, qy = make_points(m, n, seed=seed, clustered=clustered)
    p = AIDWParams(k=k, area=1.0)
    z_ref, a_ref = aidw_reference(dx, dy, dz, qx, qy, p, area=1.0)
    z, a = aidw(
        dx, dy, dz, qx, qy,
        params=p, area=1.0, impl="grid", block_q=block_q, block_d=block_d,
    )
    np.testing.assert_allclose(np.asarray(a), np.asarray(a_ref), rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("clustered", [False, True])
@pytest.mark.parametrize("m,n", [(512, 256), (500, 203), (130, 77), (1024, 64)])
def test_grid_kernel_shape_sweep(m, n, clustered):
    _check_grid_kernel(m, n, seed=m + n, clustered=clustered)


@pytest.mark.parametrize("k", [1, 4, 10, 16])
def test_grid_kernel_k_sweep(k):
    _check_grid_kernel(300, 100, k=k, seed=k)


@pytest.mark.parametrize("block_q,block_d", [(32, 64), (64, 256), (128, 128)])
def test_grid_kernel_block_sweep(block_q, block_d):
    _check_grid_kernel(700, 300, block_q=block_q, block_d=block_d, seed=block_q)


def test_grid_kernel_exact_hits():
    dx, dy, dz, _, _ = make_points(256, 1, seed=9)
    z, _ = aidw(
        dx, dy, dz, dx[:64], dy[:64],
        params=AIDWParams(k=8, area=1.0), area=1.0, impl="grid",
        block_q=32, block_d=64,
    )
    np.testing.assert_allclose(np.asarray(z), dz[:64], atol=1e-6)


@pytest.mark.parametrize("stretch", [2.0, 6.0])
def test_grid_kernel_queries_outside_data_bbox(stretch):
    """Far out-of-bbox queries (up to [-3, 3]^2 around unit-square data) need
    the overhang-corrected safe_radius — the naive (r+1)*diag bound provably
    drops true neighbours there.  Parity is checked on r_obs (via a fine
    custom grid + non-saturating r_max) so a containment miss is visible in
    alpha, not masked by the fuzzy-membership clamp."""
    dx, dy, dz, qx, qy = make_points(400, 60, seed=12, clustered=True)
    qx = (qx * stretch - stretch / 4).astype(np.float32)
    qy = (qy * stretch - stretch / 4).astype(np.float32)
    p = AIDWParams(k=10, area=1.0, r_max=64.0)
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy), gx=40, gy=40)
    z_ref, a_ref = aidw_reference(dx, dy, dz, qx, qy, p, area=1.0)
    z, a = aidw(dx, dy, dz, qx, qy, params=p, area=1.0, impl="grid", grid=g,
                block_q=32, block_d=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(a_ref), rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), rtol=RTOL, atol=ATOL)


def test_grid_kernel_prebuilt_grid_reuse():
    """A prebuilt grid must give identical results across query batches."""
    dx, dy, dz, qx, qy = make_points(600, 200, seed=13, clustered=True)
    p = AIDWParams(k=10, area=1.0)
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz))
    z1, a1 = aidw(dx, dy, dz, qx, qy, params=p, area=1.0, impl="grid", grid=g)
    z2, a2 = aidw(dx, dy, dz, qx, qy, params=p, area=1.0, impl="grid")
    np.testing.assert_allclose(np.asarray(z1), np.asarray(z2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), rtol=1e-6)


def test_grid_kernel_rejects_aoas_layout():
    dx, dy, dz, qx, qy = make_points(128, 32, seed=14)
    with pytest.raises(ValueError):
        aidw(dx, dy, dz, qx, qy, params=AIDWParams(k=10, area=1.0), area=1.0,
             impl="grid", layout="aoas")


def test_grid_kwarg_rejected_for_dense_impls():
    dx, dy, dz, qx, qy = make_points(128, 32, seed=14)
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy))
    with pytest.raises(ValueError):
        aidw(dx, dy, dz, qx, qy, params=AIDWParams(k=10, area=1.0), area=1.0,
             impl="tiled", grid=g)


@pytest.mark.parametrize("clustered", [False, True])
def test_interpolate_knn_grid_matches_brute(clustered):
    """aidw_interpolate(knn='grid') == aidw_interpolate(knn='brute'), both
    chunkings, plus grid reuse."""
    dx, dy, dz, qx, qy = make_points(900, 400, seed=15, clustered=clustered)
    p = AIDWParams(k=10, area=1.0)
    zb, ab = aidw_interpolate(dx, dy, dz, qx, qy, p, area=1.0, q_chunk=128, d_chunk=256)
    zg, ag = aidw_interpolate(dx, dy, dz, qx, qy, p, area=1.0, q_chunk=128, d_chunk=256,
                              knn="grid")
    np.testing.assert_allclose(np.asarray(ag), np.asarray(ab), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(zg), np.asarray(zb), rtol=1e-6, atol=1e-7)
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy))
    zg2, _ = aidw_interpolate(dx, dy, dz, qx, qy, p, area=1.0, q_chunk=128, d_chunk=256,
                              knn="grid", grid=g)
    np.testing.assert_allclose(np.asarray(zg2), np.asarray(zg), rtol=1e-6)


def test_interpolate_rejects_unknown_knn():
    dx, dy, dz, qx, qy = make_points(64, 16, seed=16)
    with pytest.raises(ValueError):
        aidw_interpolate(dx, dy, dz, qx, qy, AIDWParams(k=5, area=1.0), area=1.0,
                         knn="octree")


@pytest.mark.parametrize("tile", [4, 16, 128])
def test_row_run_tiles_cover_each_rectangle_once(tile):
    """The row-run tile list of random rectangles on a small grid with empty
    cells and an empty row: every rectangle point's CSR index lies in
    exactly one listed tile, tiles increase strictly within a block, the
    count stays within the proved bound at any capacity the block fits,
    and the lanes the kernel's mask keeps are exactly the points the
    materialised gather yields."""
    from repro.kernels.aidw_grid import (
        gather_candidates_csr,
        rectangle_need,
        row_run_max_tiles,
        row_run_tiles,
    )

    rng = np.random.default_rng(tile)
    x = rng.random(3000).astype(np.float32)
    y = rng.random(3000).astype(np.float32)
    keep = ~(((y > 0.5) & (y < 0.6)) | ((x < 0.3) & (y < 0.3)))
    gx, gy = 13, 10
    g = build_grid(jnp.asarray(x[keep]), jnp.asarray(y[keep]), gx=gx, gy=gy,
                   bounds=(0.0, 1.0, 0.0, 1.0))
    assert (np.asarray(g.counts).sum(axis=1) == 0).any(), "want an empty grid row"
    nb = 96
    xs = np.sort(rng.integers(0, gx, (nb, 2)), axis=1)
    ys = np.sort(rng.integers(0, gy, (nb, 2)), axis=1)
    ys[:8] = [0, gy - 1]                                   # full-height rectangles
    xs[:4] = [0, gx - 1]                                   # and full-width ones
    rect = [jnp.asarray(v, jnp.int32) for v in (xs[:, 0], xs[:, 1], ys[:, 0], ys[:, 1])]

    need = np.asarray(rectangle_need(g, *rect))
    cap = int(need.max())
    max_tiles = row_run_max_tiles(cap, tile, gy)
    tiles, n_tiles = (np.asarray(v) for v in row_run_tiles(g, *rect, tile, max_tiles))
    cand_x, cand_y, need_g = (np.asarray(v) for v in gather_candidates_csr(g, *rect, cap))
    np.testing.assert_array_equal(need, need_g)
    px, py, pc = (np.asarray(v) for v in (g.pt_x, g.pt_y, g.point_cells))
    starts = np.asarray(g.starts)
    for i in range(nb):
        (xlo, xhi), (ylo, yhi) = xs[i], ys[i]
        n = int(n_tiles[i])
        assert n <= row_run_max_tiles(int(need[i]), tile, gy), "over the proved bound"
        listed = tiles[i, :n]
        assert (np.diff(listed) > 0).all(), "tiles must increase strictly"
        assert (tiles[i, n:] == (listed[-1] if n else 0)).all()
        idx = np.concatenate([np.arange(starts[yy * gx + xlo], starts[yy * gx + xhi + 1])
                              for yy in range(ylo, yhi + 1)])
        hits = (idx[:, None] // tile == listed[None, :]).sum(axis=1)
        assert (hits == 1).all(), "every rectangle point lies in one listed tile"
        lanes = (listed[:, None] * tile + np.arange(tile)[None, :]).ravel()
        lanes = lanes[lanes < g.n_points]                  # the kernel masks the rest
        cx, cy = pc[lanes] & 0xFFFF, pc[lanes] >> 16
        kept = lanes[(cx >= xlo) & (cx <= xhi) & (cy >= ylo) & (cy <= yhi)]
        np.testing.assert_array_equal(np.sort(kept), np.sort(idx))
        got = sorted(zip(px[kept], py[kept]))
        want = sorted(zip(cand_x[i, :need[i]], cand_y[i, :need[i]]))
        assert got == want, f"block {i}: mask keeps a different point set"


def test_row_run_kernel_ignores_lanes_past_the_data():
    """The row-run kernel reads whole 1024-point blocks, which run past the
    CSR arrays' ends (on the chip those lanes hold whatever the copy left):
    a lane at or past ``m_real`` must never count, even where its stored
    cell lies inside the rectangle and its point is the nearest."""
    from repro.core.aidw import AIDWParams
    from repro.kernels.aidw_grid import phase1_alpha_row_runs

    rng = np.random.default_rng(3)
    m, m_real, tile = 1000, 900, 128
    px = jnp.asarray(rng.random(m + 1).astype(np.float32))
    py = jnp.asarray(rng.random(m + 1).astype(np.float32))
    cells = jnp.zeros((m,), jnp.int32)                      # every point in cell (0, 0)
    qx = jnp.asarray(rng.random(64).astype(np.float32))
    qy = jnp.asarray(rng.random(64).astype(np.float32))
    tiles = jnp.arange(8, dtype=jnp.int32)[None, :]         # tiles 0..7 cover 0..1023
    rects = jnp.zeros((1, 4), jnp.int32)
    kw = dict(tile=tile, params=AIDWParams(k=10, area=1.0), area=1.0, m_real=m_real,
              block_q=64, interpret=True)
    near = (px.at[m_real:].set(qx[0]), py.at[m_real:].set(qy[0]))  # exact hits past the end
    a_far = phase1_alpha_row_runs(qx, qy, tiles, jnp.asarray([8]), rects, (px, py, cells), **kw)
    a_near = phase1_alpha_row_runs(qx, qy, tiles, jnp.asarray([8]), rects, (*near, cells), **kw)
    a_cut = phase1_alpha_row_runs(qx, qy, tiles, jnp.asarray([8]), rects,
                                  (px[:m_real + 1], py[:m_real + 1], cells[:m_real]), **kw)
    np.testing.assert_array_equal(np.asarray(a_far), np.asarray(a_near))
    np.testing.assert_array_equal(np.asarray(a_far), np.asarray(a_cut))
