"""Mosaic compile checks for the main-path kernels at real widths.

Every other kernel test runs in Pallas interpret mode, which accepts block
shapes and primitives the TPU compiler refuses.  These tests compile each
kernel with ``interpret=False`` for a described (not attached) TPU v5e chip
at n = m = 2^20 queries/points, ``block_q=256``, ``block_d=512``, grid
candidate capacity 4096 and k = 10 — and Phase 1, the exact sweep and the
row-run near field of the grid path also at the served benchmark cells'
shapes: nothing runs, but a kernel Mosaic would refuse on the chip (a tile
it cannot lay out, an SMEM table that does not fit) fails here.

The topology is described inside a module-scoped fixture (never at import):
only one process may hold the TPU compiler library, so describing it while
pytest workers import this module would break the others.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.aidw import AIDWParams
from repro.engine.plan import _ROW_TILES, _SWEEP_TILE
from repro.kernels.aidw_grid import (
    phase1_alpha_from_candidates,
    phase1_alpha_row_runs,
    phase2_far_nodes,
    phase2_near_row_runs,
    phase2_near_weights,
    phase2_weights_full,
    row_run_max_tiles,
)
from repro.kernels.aidw_naive import aidw_naive_soa
from repro.kernels.aidw_tiled import aidw_tiled_aoas, aidw_tiled_soa

N = M = 1 << 20
BLOCK_Q, BLOCK_D = 256, 512
CAPACITY = 4096
NB = N // BLOCK_Q
PARAMS = AIDWParams(k=10, area=1.0)
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for_chip(one_chip):
    """``compile_for_chip(fn, *shapes)`` lowers and compiles ``fn`` for the
    described chip, with the persistent compile cache off (an entry written
    for a described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_tiled_soa_both_phases(compile_for_chip):
    fn = functools.partial(aidw_tiled_soa, params=PARAMS, area=1.0, m_real=M,
                           block_q=BLOCK_Q, block_d=BLOCK_D, interpret=False)
    col, row = ((N, 1), F32), ((1, M), F32)
    compile_for_chip(lambda dx, dy, dz, qx, qy: fn(dx, dy, dz, qx, qy),
                     row, row, row, col, col)


def test_tiled_aoas_both_phases(compile_for_chip):
    fn = functools.partial(aidw_tiled_aoas, params=PARAMS, area=1.0, m_real=M,
                           block_q=BLOCK_Q, block_d=BLOCK_D, interpret=False)
    row = ((1, N), F32)
    compile_for_chip(lambda data, qx, qy: fn(data, qx, qy), ((M, 4), F32), row, row)


def test_naive_soa_at_10k(compile_for_chip):
    m = 10 * 1024
    fn = functools.partial(aidw_naive_soa, params=PARAMS, area=1.0, m_real=m,
                           block_q=64, interpret=False)
    col, row = ((m, 1), F32), ((1, m), F32)
    compile_for_chip(lambda dx, dy, dz, qx, qy: fn(dx, dy, dz, qx, qy),
                     row, row, row, col, col)


# Phase-1 launches: (queries incl. seam padding, blocks, capacity, gy, m).
# "2^20" is the paper-size launch above; the raster and scatter shapes are
# the two served cells' (a 91x91 grid at seam level 2; the healed capacity
# on a 253x253 grid at seam level 3), where the row-run walk's SMEM tile
# table is largest.
PHASE1_SHAPES = {
    "2^20": (N, NB, CAPACITY, 256, M),
    "raster": (80 * BLOCK_Q, 80, 114_688, 91, 2_097_152),
    "scatter": (320 * BLOCK_Q, 320, 21_504, 253, 1_024_000),
}
PHASE1_CASES = [
    pytest.param("prefetch", "2^20", 128, id="prefetch"),
    pytest.param("dense", "2^20", 0, id="dense"),
    *(pytest.param("prefetch", cell, tile, id=f"prefetch-{cell}-{tile}")
      for cell in ("raster", "scatter") for tile in _ROW_TILES),
    *(pytest.param("dense", cell, 0, id=f"dense-{cell}") for cell in ("raster", "scatter")),
]


@pytest.mark.parametrize("pipeline,shape,tile", PHASE1_CASES)
def test_grid_phase1(compile_for_chip, pipeline, shape, tile):
    n, nb, capacity, gy, m = PHASE1_SHAPES[shape]
    q = ((n,), F32)
    kw = dict(params=PARAMS, area=1.0, m_real=m, block_q=BLOCK_Q, interpret=False)
    if pipeline == "dense":
        fn = functools.partial(phase1_alpha_from_candidates, block_d=BLOCK_D, **kw)
        compile_for_chip(fn, q, q, ((nb, capacity), F32), ((nb, capacity), F32))
        return

    def fn(qx, qy, tiles, nt, rects, px, py, pc):
        return phase1_alpha_row_runs(qx, qy, tiles, nt, rects, (px, py, pc), tile=tile, **kw)

    max_tiles = row_run_max_tiles(capacity, tile, gy)
    compile_for_chip(fn, q, q, ((nb, max_tiles), jnp.int32), ((nb,), jnp.int32),
                     ((nb, 4), jnp.int32), ((m + 1,), F32), ((m + 1,), F32),
                     ((m,), jnp.int32))


def test_near_weight_kernel(compile_for_chip):
    fn = functools.partial(phase2_near_weights, block_q=BLOCK_Q, block_d=BLOCK_D,
                           interpret=False)
    q, cand = ((N,), F32), ((NB, CAPACITY), F32)
    compile_for_chip(fn, q, q, ((N, 1), F32), cand, cand, cand, ((NB,), jnp.int32))


# The row-run near field: (queries incl. seam padding, blocks, near
# capacity, gy, m).  The quadtree cell's (16,384 raster queries split at
# seam level 3, a 512x512 grid over m = 8,388,608 points, near capacity
# about 2.1M points: the SMEM tile table takes three launches), and the
# exact cells' batches and data (65,536 and 16,384 queries; m = 1,024,000
# on a 253x253 grid and 2,097,152 on a 256x256 one) at a quarter-grid
# near field.
NEAR_SHAPES = {
    "quadtree": (128 * BLOCK_Q, 128, 2_097_152, 512, 8_388_608),
    "scatter": (320 * BLOCK_Q, 320, 262_144, 253, 1_024_000),
    "lidar-raster": (80 * BLOCK_Q, 80, 524_288, 256, 2_097_152),
}


@pytest.mark.parametrize("shape", sorted(NEAR_SHAPES))
@pytest.mark.parametrize("tile", _ROW_TILES)
def test_near_weight_kernel_rows(compile_for_chip, tile, shape):
    n, nb, capacity, gy, m = NEAR_SHAPES[shape]

    def fn(qx, qy, ah, tiles, nt, rects, px, py, pz, pc):
        return phase2_near_row_runs(qx, qy, ah, tiles, nt, rects, (px, py, pz, pc),
                                    tile=tile, m_real=m, block_q=BLOCK_Q, interpret=False)

    q, pts = ((n,), F32), ((m + 1,), F32)
    compile_for_chip(fn, q, q, ((n, 1), F32),
                     ((nb, row_run_max_tiles(capacity, tile, gy)), jnp.int32),
                     ((nb,), jnp.int32), ((nb, 4), jnp.int32), pts, pts, pts,
                     ((m,), jnp.int32))


def test_far_node_kernel(compile_for_chip):
    fn = functools.partial(phase2_far_nodes, block_q=BLOCK_Q, block_d=BLOCK_D,
                           interpret=False)
    q, nodes = ((N,), F32), ((NB, CAPACITY), F32)
    compile_for_chip(fn, q, q, ((N, 1), F32), *[nodes] * 6, ((NB,), jnp.int32))


# The exact sweep's launches: (queries incl. seam padding, m).  "2^20" at
# the paper-size tile above; the served cells' at the tile the plan picks
# (scatter: 65,536 queries over the paper's 1000K; the two rasters: 16,384
# cell centres over 1000K uniform points and over the 2,097,152 QL2 returns).
SWEEP_SHAPES = {
    "scatter": (320 * BLOCK_Q, 1_024_000),
    "uniform-raster": (80 * BLOCK_Q, 1_024_000),
    "lidar-raster": (80 * BLOCK_Q, 2_097_152),
}
SWEEP_CASES = [
    pytest.param(N, M, BLOCK_D, id="2^20"),
    *(pytest.param(n, m, _SWEEP_TILE, id=cell) for cell, (n, m) in SWEEP_SHAPES.items()),
]


@pytest.mark.parametrize("n,m,block_d", SWEEP_CASES)
def test_phase2_weights_full(compile_for_chip, n, m, block_d):
    fn = functools.partial(phase2_weights_full, eps=PARAMS.exact_hit_eps,
                           block_q=BLOCK_Q, block_d=block_d, interpret=False)
    q, row = ((n,), F32), ((1, -(-m // block_d) * block_d), F32)
    compile_for_chip(fn, q, q, ((n, 1), F32), row, row, row)
