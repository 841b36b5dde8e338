"""The benchmark's data and query generators, on the CPU at small sizes."""

import numpy as np
import pytest

from bench_cells import FIRST_CELL_OF_CONFIG, small_cell
from bench import gen, manifest

SEED = 2 ** 31 + 12345  # seeds run past 32 signed bits


@pytest.mark.parametrize("name", FIRST_CELL_OF_CONFIG)
def test_same_seed_same_data(name):
    cell = small_cell(name)
    a = gen.make_data(cell["config"], SEED)
    b = gen.make_data(cell["config"], SEED)
    c = gen.make_data(cell["config"], SEED + 1)
    for u, v in zip(a[:3], b[:3]):
        np.testing.assert_array_equal(u, v)
    assert not all(np.array_equal(u, v) for u, v in zip(a[:3], c[:3]))


@pytest.mark.parametrize("name", FIRST_CELL_OF_CONFIG)
def test_count_extent_and_dtype(name):
    cell = small_cell(name)
    cfg = cell["config"]
    x, y, z, _ = gen.make_data(cfg, SEED)
    x0, x1, y0, y1 = cfg["extent"]
    assert x.shape == y.shape == z.shape == (cfg["m"],)
    assert x.dtype == y.dtype == z.dtype == np.float32
    assert x.min() >= x0 and x.max() < x1 and y.min() >= y0 and y.max() < y1
    assert np.all(np.isfinite(z))


@pytest.mark.parametrize("name", FIRST_CELL_OF_CONFIG)
def test_every_seed_holds_the_same_positions_up_to_symmetry(name):
    """The seed moves the points by a symmetry of the square and reorders
    them: the occupancy of any grid aligned to the extent is the same
    multiset, so the plan's static sizes do not depend on the seed."""
    cfg = small_cell(name)["config"]
    x0, x1, y0, y1 = cfg["extent"]

    def occupancy(seed):
        x, y, _, _ = gen.make_data(cfg, seed)
        h, _, _ = np.histogram2d(x, y, bins=16, range=[[x0, x1], [y0, y1]])
        return np.sort(h.ravel())

    base = occupancy(0)
    for seed in (1, 2, 3, SEED):
        np.testing.assert_array_equal(occupancy(seed), base)


def test_full_size_counts():
    """The configurations as committed: m points exactly, from the
    generators' own arithmetic (the lidar pattern leaves room to drop)."""
    paper = manifest.config("paper-uniform-1000k")
    lidar = manifest.config("lidar-ql2-dem1m")
    assert paper["m"] == 1_024_000
    assert lidar["m"] == 2_097_152 == 2 * int(lidar["tile_m"]) ** 2


def test_lidar_density_is_ql2():
    """Aggregate density 2 pts/m2 (QL2: at least 2, spacing at most
    0.71 m), with the sidelap strips about twice as dense as single
    coverage."""
    cfg = small_cell("lidar-ql2-dem1m.raster")["config"]
    cfg.update(m=2 * 64 * 64)
    x, y, _, info = gen.make_data(cfg, 0)
    assert x.shape[0] / cfg["tile_m"] ** 2 == 2.0 and 1.0 / np.sqrt(2.0) <= 0.71
    assert info["returns_before_dropout"] >= cfg["m"]
    which = info["symmetry"]
    across = y if which & 4 else x  # the flight lines' cross-track axis
    if which & 1:
        across = cfg["tile_m"] - across
    sidelap = np.mean((across >= 16) & (across < 24)) / 8.0
    single = np.mean((across >= 26) & (across < 44)) / 18.0
    assert 1.7 < sidelap / single < 2.3


def test_uniform_pool_batches():
    cell = small_cell("paper-uniform-1000k.scatter")
    batches = gen.make_batches(cell["traffic"], cell["config"], SEED)
    q = cell["traffic"]["queries"]
    assert len(batches) == q["pool"] // q["batch"]
    assert all(b[0].shape == (q["batch"],) for b in batches)
    again = gen.make_batches(cell["traffic"], cell["config"], SEED)
    np.testing.assert_array_equal(batches[3][0], again[3][0])


def test_raster_tiles_cover_the_dem_once_per_cycle():
    cell = small_cell("lidar-ql2-dem1m.raster")
    q = cell["traffic"]["queries"]
    x0, x1, y0, y1 = cell["config"]["extent"]
    batches = gen.make_batches(cell["traffic"], cell["config"], SEED)
    assert len(batches) == (q["cells"] // q["tile"]) ** 2
    qx = np.concatenate([b[0] for b in batches])
    qy = np.concatenate([b[1] for b in batches])
    cw = (x1 - x0) / q["cells"]
    col = np.floor((qx - x0) / cw).astype(int)
    row = np.floor((qy - y0) / cw).astype(int)
    counts = np.zeros((q["cells"], q["cells"]), int)
    np.add.at(counts, (row, col), 1)
    assert np.all(counts == 1)
    np.testing.assert_allclose(qx, x0 + (col + 0.5) * cw)
    # each call is one whole block of cells
    for bx, by in zip(*(np.asarray([np.floor((b[i] - x0) / (cw * q["tile"])) for b in batches])
                        for i in (0, 1))):
        assert len(set(bx)) == len(set(by)) == 1


@pytest.mark.parametrize("blocks,step,shift", [(8, 3, 3), (8, 5, 0), (2, 3, 3), (6, 5, 1)])
def test_raster_block_order_is_a_latin_square(blocks, step, shift):
    """Each round of ``blocks`` calls holds every block row and column once,
    the first round's rows are spread, and a cycle covers every block."""
    from bench import manifest

    order = manifest.query_kind("raster_tiles").block_order(blocks, step, shift)
    assert sorted(order) == [(c, r) for c in range(blocks) for r in range(blocks)]
    for k in range(blocks):
        rnd = order[k * blocks:(k + 1) * blocks]
        assert sorted(c for c, _ in rnd) == sorted(r for _, r in rnd) == list(range(blocks))
    if blocks == 8:
        assert [r for _, r in order[:4]] == [0, 4, 2, 6]


def test_raster_window_spans_the_extent():
    """The committed raster traffic: the first six calls, what a 10 s window
    holds, take six block rows and six block columns, with both sidelap
    strips and two of the three voids."""
    from bench import manifest

    spec = manifest.traffic("raster")["queries"]
    blocks = spec["cells"] // spec["tile"]
    first = manifest.query_kind("raster_tiles").block_order(blocks, spec["order_step"],
                                                             spec["order_shift"])[:6]
    assert len({c for c, _ in first}) == len({r for _, r in first}) == 6
    size = manifest.config("lidar-ql2-dem1m")["tile_m"] / blocks
    cfg = manifest.config("lidar-ql2-dem1m")
    voids = {(int(cx // size), int(cy // size)) for cx, cy, _, _ in cfg["voids_m"]}
    assert len(voids & set(first)) == 2
    half = cfg["swath_width_m"] / 2
    lines = cfg["line_centres_m"]
    for a, b in zip(lines, lines[1:]):  # each sidelap strip [b - half, a + half]
        assert any(c * size < a + half and (c + 1) * size > b - half for c, _ in first)


def test_dihedral_is_exact_and_a_group():
    rng = np.random.default_rng(0)
    ix, iy = rng.integers(0, 1 << 24, 1000), rng.integers(0, 1 << 24, 1000)
    for which in range(8):
        a, b = gen.dihedral(ix, iy, 1 << 24, which)
        assert a.min() >= 0 and a.max() < 1 << 24
        assert sorted(zip(*gen.dihedral(a, b, 1 << 24, 0))) == sorted(zip(a, b))
    x = (gen.dihedral(ix, iy, 1 << 24, 1)[0] / (1 << 24)).astype(np.float32)
    np.testing.assert_array_equal(x.astype(np.float64) * (1 << 24), (1 << 24) - 1 - ix)
