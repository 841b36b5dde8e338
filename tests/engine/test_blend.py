"""Sparsity-skipping Phase 1 + per-block overflow blend (PR 4).

Covers the three layers of the grid-path worst-case fix:
* the per-block blend — queries in blocks that overflow the plan's static
  candidate capacity get their alpha from the exact masked ring search,
  everyone else keeps the kernel result (regression for the ROADMAP m=100K
  seam-overflow batch, scaled down; full-size variant marked slow);
* the row-run Phase-1 pipeline vs its dense twin over the materialised
  gather (bit-identical results on tile-local, full-width, edge-clipped,
  voided, seam-split and overflowing batches, at every row tile);
* Morton seam splitting of query blocks (layout invariants + a
  deterministic straddle whose overflow the split eliminates);
plus the extended execute_with_stats diagnostics (static dict structure,
no retrace) and the convenience-API plan memoization in kernels.ops.
"""

import dataclasses
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.aidw import AIDWParams, adaptive_alpha, aidw_reference
from repro.core.grid import build_grid, cell_of, grid_r_obs, seam_layout, seam_segment_ids
from repro.engine import build_plan, execute, execute_with_stats
from repro.errors import CapacityOverflowWarning
from repro.kernels import aidw, aidw_grid, ops
from repro.serving import default_registry

RTOL, ATOL = 2e-4, 2e-5

STATS_KEYS = {
    "grid_fallback", "cand_need_max", "overflow_blocks", "overflow_queries",
    "overflow_query_mask", "skipped_tile_fraction", "phase1_tile_fill",
    "persistent_overflow",
}


def _uniform(m, seed):
    rng = np.random.default_rng(seed)
    return (rng.random(m).astype(np.float32), rng.random(m).astype(np.float32),
            rng.random(m).astype(np.float32))


# --------------------------------------------------- overflow blend (tentpole)
def test_seam_overflow_blend_regression():
    """Scaled-down deterministic repro of the ROADMAP m=100K seam-overflow
    batch: one Morton block straddles the grid's centre seams (a full-bbox
    diagonal), its rectangle blows past the static capacity — the blend must
    ring-search exactly those queries (bitwise-equal alpha to the full ring
    search) while the rest of the batch keeps the kernel fast path
    (overflow_blocks > 0 but grid_fallback=False: no whole-batch fallback)."""
    m = 4096
    dx, dy, dz = _uniform(m, 42)
    p = AIDWParams(k=10, area=1.0)
    rng = np.random.default_rng(42)
    qa = (0.05 + 0.03 * rng.random((256, 2))).astype(np.float32)  # tile-local
    t = np.linspace(0.02, 0.98, 256).astype(np.float32)           # seam diagonal
    qx = jnp.asarray(np.concatenate([qa[:, 0], t]))
    qy = jnp.asarray(np.concatenate([qa[:, 1], t]))

    # seam_level=0 keeps the straddling block intact so the blend (not the
    # splitter) is what's under test; the tight capacity makes it overflow
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                      query_occupancy=64.0, seam_level=0)
    z, a, stats = execute_with_stats(plan, qx, qy)

    assert int(stats["overflow_blocks"]) > 0
    assert int(stats["overflow_queries"]) > 0
    assert not bool(stats["grid_fallback"]), "blend must not drop the whole batch"
    mask = np.asarray(stats["overflow_query_mask"])
    assert mask.sum() == int(stats["overflow_queries"])

    # blend exactness invariant: ring-search alpha where overflowed (bitwise
    # — it IS the masked ring search), kernel alpha (same candidates, oracle
    # tolerance) everywhere else
    a_ring = adaptive_alpha(grid_r_obs(plan.grid, qx, qy, p.k), m, 1.0, p)
    np.testing.assert_array_equal(np.asarray(a)[mask], np.asarray(a_ring)[mask])
    z_ref, a_ref = aidw_reference(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz),
                                  qx, qy, p, area=1.0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(a_ref), rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), rtol=RTOL, atol=ATOL)


@pytest.mark.slow
def test_seam_overflow_blend_full_size():
    """The actual ROADMAP scenario: m=100K uniform, one full-bbox batch of
    8192 queries.  Unsplit (seam_level=0) it overflows; the blend keeps it
    exact without a whole-batch fallback, and the auto seam split reduces
    the overflow."""
    m = 100_000
    dx, dy, dz = _uniform(m, 0)
    p = AIDWParams(k=10, area=1.0)
    rng = np.random.default_rng(1)
    qx = jnp.asarray(rng.random(8192).astype(np.float32))
    qy = jnp.asarray(rng.random(8192).astype(np.float32))

    plan0 = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid", seam_level=0)
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid")
    assert plan.seam_level > 0, "auto seam split should engage at this scale"
    _, a0, stats0 = execute_with_stats(plan0, qx, qy)
    _, a1, stats1 = execute_with_stats(plan, qx, qy)
    assert int(stats0["overflow_queries"]) > 0, "the ROADMAP cliff should reproduce"
    assert not bool(stats0["grid_fallback"])
    assert int(stats1["overflow_queries"]) < int(stats0["overflow_queries"])
    a_ring = adaptive_alpha(grid_r_obs(plan.grid, qx, qy, p.k), m, 1.0, p)
    for a in (a0, a1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(a_ring), rtol=RTOL, atol=ATOL)


def test_out_of_bbox_batch_all_overflow_is_fallback():
    """When EVERY query lands in an overflowing block the batch degrades to
    ring-search speed — grid_fallback reports it, and it is still exact."""
    dx, dy, dz = _uniform(4096, 7)
    p = AIDWParams(k=10, area=1.0, r_max=64.0)
    rng = np.random.default_rng(8)
    qx = jnp.asarray((rng.random(80) * 6 - 3).astype(np.float32))
    qy = jnp.asarray((rng.random(80) * 6 - 3).astype(np.float32))
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                      query_occupancy=64.0)
    z, a, stats = execute_with_stats(plan, qx, qy)
    assert bool(stats["grid_fallback"])
    assert int(stats["overflow_queries"]) == 80
    z_ref, a_ref = aidw_reference(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz),
                                  qx, qy, p, area=1.0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(a_ref), rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), rtol=RTOL, atol=ATOL)


# ------------------------------------------------ row-run Phase-1 pipeline
def _voided(m, seed):
    """Uniform points with an empty band of grid rows and an empty disk."""
    rng = np.random.default_rng(seed)
    x = rng.random(2 * m).astype(np.float32)
    y = rng.random(2 * m).astype(np.float32)
    keep = ~(((y > 0.40) & (y < 0.52)) | ((x - 0.7) ** 2 + (y - 0.25) ** 2 < 0.015))
    x, y = x[keep][:m], y[keep][:m]
    return x, y, np.sin(6 * x) * np.cos(6 * y)


def _pipeline_case(name):
    """``(data, queries, build_plan kwargs, row tile or None)`` of one case."""
    rng = np.random.default_rng(4)
    kw = {}
    tile = None
    if name == "tile-local":
        data = _uniform(20000, 3)
        q = 0.05 + 0.1 * rng.random((256, 2))
    elif name == "full-bbox":
        # one block spans the grid: full-width rows whose runs touch, so the
        # boundary tile of each row pair must be listed once; the last tile
        # runs past the data, whose lanes must not count
        data = _uniform(4000, 6)
        q = rng.random((256, 2))
        kw = dict(seam_level=0, min_cand_capacity=4000)
    elif name == "edges":
        # one block at each grid corner and edge midpoint, its rectangle
        # clipped there; some queries lie just outside the data
        data = _uniform(8192, 7)
        centres = [(u, v) for u in (0.0, 0.5, 1.0) for v in (0.0, 0.5, 1.0) if (u, v) != (0.5, 0.5)]
        q = np.concatenate([np.asarray(c) + 0.06 * (rng.random((32, 2)) - 0.5) for c in centres])
        kw = dict(block_q=32, seam_level=0)
    elif name == "voids":
        # an empty band of grid rows and an empty disk inside the rectangles
        data = _voided(8192, 8)
        q = rng.random((512, 2))
        kw = dict(block_q=64)
    elif name.startswith("tile-"):
        # every candidate tile width: row runs start and end mid-tile
        data = _uniform(8192, 9)
        q = 0.3 + 0.4 * rng.random((512, 2))
        tile = int(name.split("-")[1])
    elif name == "chunked":
        # a tile table too large for one launch's SMEM: one launch per block,
        # each writing its blocks of one shared output
        data = _uniform(6000, 11)
        q = rng.random((640, 2))
        kw = dict(block_q=64)
    elif name == "seam-split":
        data = _uniform(16384, 10)
        q = rng.random((1024, 2))
        kw = dict(block_q=64, seam_level=2)
    else:  # one-overflow: a seam-straddling diagonal block beside a local one
        data = _uniform(4096, 42)
        t = np.linspace(0.02, 0.98, 256)
        q = np.concatenate([0.05 + 0.03 * rng.random((256, 2)), np.stack([t, t], 1)])
        kw = dict(query_occupancy=64.0, seam_level=0)
    q = q.astype(np.float32)
    return data, (jnp.asarray(q[:, 0]), jnp.asarray(q[:, 1])), kw, tile


@pytest.mark.parametrize("case", [
    "tile-local", "full-bbox", "edges", "voids", "tile-128", "tile-256",
    "tile-512", "chunked", "seam-split", "one-overflow",
])
def test_prefetch_and_dense_pipelines_bitwise_equal(case, monkeypatch):
    """The row-run walk merges exactly the points the materialised gather
    puts in a block's candidate row (every other lane reads +inf, as a
    sentinel slot does), and the k-best merge returns the k smallest in
    ascending order whatever the order it sees them in: z and alpha must be
    bitwise equal to the dense pipeline's, and the capacity diagnostics
    equal."""
    (dx, dy, dz), (qx, qy), kw, tile = _pipeline_case(case)
    if case == "chunked":
        monkeypatch.setattr(aidw_grid, "_SMEM_TABLE_WORDS", 1)
    p = AIDWParams(k=10, area=1.0)
    plans = {pipe: build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                              pipeline=pipe, **kw)
             for pipe in ("prefetch", "dense")}
    if tile is not None:
        plans["prefetch"] = dataclasses.replace(plans["prefetch"], row_tile=tile)
    z_p, a_p, stats = execute_with_stats(plans["prefetch"], qx, qy)
    z_d, a_d, stats_d = execute_with_stats(plans["dense"], qx, qy)
    np.testing.assert_array_equal(np.asarray(z_p), np.asarray(z_d))
    np.testing.assert_array_equal(np.asarray(a_p), np.asarray(a_d))
    for key in ("skipped_tile_fraction", "cand_need_max", "overflow_queries"):
        assert float(stats[key]) == float(stats_d[key]), key
    np.testing.assert_array_equal(np.asarray(stats["overflow_query_mask"]),
                                  np.asarray(stats_d["overflow_query_mask"]))
    assert 0.0 < float(stats["phase1_tile_fill"]) <= 1.0
    overflowed = int(stats["overflow_blocks"])
    assert overflowed == (1 if case == "one-overflow" else 0)
    if case == "tile-local":
        assert float(stats["skipped_tile_fraction"]) > 0.5, "tile-local batch should skip most tiles"
    if case == "full-bbox":
        assert int(stats["cand_need_max"]) == dx.shape[0], "the block should span the grid"


def test_build_plan_rejects_bad_pipeline_and_seam_level():
    dx, dy, dz = _uniform(256, 9)
    p = AIDWParams(k=10, area=1.0)
    with pytest.raises(ValueError):
        build_plan(dx, dy, dz, params=p, area=1.0, impl="grid", pipeline="magic")
    with pytest.raises(ValueError):
        build_plan(dx, dy, dz, params=p, area=1.0, impl="grid", seam_level=-1)


# ---------------------------------------------------------- Morton seam split
def test_seam_layout_invariants():
    """src/dest maps: every sorted query owns exactly one slot
    (src[dest[i]] == i), blocks never straddle segment boundaries, and pad
    slots repeat a query of their own segment."""
    block_q = 4
    seg = jnp.asarray([0, 0, 0, 0, 0, 2, 2, 3, 3, 3, 3, 3], jnp.int32)  # nondecreasing
    n_tot = seg.shape[0]
    n_segments = 4
    n_slots = n_tot + n_segments * block_q
    src, dest = seam_layout(seg, n_segments, block_q, n_slots)
    src, dest = np.asarray(src), np.asarray(dest)
    np.testing.assert_array_equal(src[dest], np.arange(n_tot))
    seg_np = np.asarray(seg)
    slot_seg = seg_np[src]  # segment of the query each slot holds
    for b in range(n_slots // block_q):
        blk = slot_seg[b * block_q:(b + 1) * block_q]
        assert len(set(blk.tolist())) == 1, f"block {b} straddles segments: {blk}"


def test_seam_segment_ids_monotone_along_morton():
    """Segment ids are the top Morton bits: nondecreasing along any
    Morton-sorted cell order, constant at level 0."""
    from repro.core.grid import morton_ids

    dx, dy, dz = _uniform(2048, 11)
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz))
    rng = np.random.default_rng(12)
    qx = jnp.asarray(rng.random(500).astype(np.float32))
    qy = jnp.asarray(rng.random(500).astype(np.float32))
    cx, cy = cell_of(g, qx, qy)
    order = np.asarray(jnp.argsort(morton_ids(cx, cy)))
    assert int(jnp.max(seam_segment_ids(g, cx, cy, 0))) == 0
    for level in (1, 2):
        seg = np.asarray(seam_segment_ids(g, cx, cy, level))[order]
        assert (np.diff(seg) >= 0).all()
        assert seg.max() < 4 ** level


def test_seam_split_eliminates_straddle_overflow():
    """A deterministic Morton-boundary straddle (queries at the END of
    quadrant 0's Z-curve next to queries at the START of quadrant 1's): one
    block with a half-grid rectangle that overflows the capacity.  Splitting
    at the seam must eliminate the overflow entirely, with identical
    results."""
    m = 16384
    dx, dy, dz = _uniform(m, 5)
    p = AIDWParams(k=10, area=1.0)
    rng = np.random.default_rng(5)
    g = 32  # default resolution for m=16384 at ~16/cell
    fill = (0.2 + 0.1 * rng.random((192, 2))).astype(np.float32)
    qa = ((np.array([g / 2 - 0.5, g / 2 - 0.5]) + 0.02 * rng.random((32, 2))) / g).astype(np.float32)
    qb = ((np.array([g / 2 + 0.5, 0.5]) + 0.02 * rng.random((32, 2))) / g).astype(np.float32)
    q = np.concatenate([fill, qa, qb])
    qx, qy = jnp.asarray(q[:, 0]), jnp.asarray(q[:, 1])

    outs = {}
    for sl in (0, 1):
        plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                          block_q=64, query_occupancy=1024.0, seam_level=sl)
        assert plan.grid.gx == g
        outs[sl] = execute_with_stats(plan, qx, qy)
    assert int(outs[0][2]["overflow_blocks"]) > 0, "the straddle should overflow unsplit"
    assert int(outs[1][2]["overflow_queries"]) == 0, "the seam split should eliminate it"
    np.testing.assert_allclose(np.asarray(outs[0][0]), np.asarray(outs[1][0]),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(outs[0][1]), np.asarray(outs[1][1]),
                               rtol=RTOL, atol=ATOL)


# ------------------------------------------------------- stats + jit identity
def test_stats_structure_static_per_plan():
    """The extended grid diagnostics keep a static dict structure: two
    same-shape batches against one plan hit the same executable (no
    retrace), and the keys are exactly the documented set."""
    dx, dy, dz = _uniform(2048, 13)
    p = AIDWParams(k=10, area=1.0)
    rng = np.random.default_rng(14)
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid")
    qs = [(jnp.asarray(rng.random(300).astype(np.float32)),
           jnp.asarray(rng.random(300).astype(np.float32))) for _ in range(2)]
    n0 = execute_with_stats._cache_size()
    _, _, stats1 = execute_with_stats(plan, *qs[0])
    n1 = execute_with_stats._cache_size()
    _, _, stats2 = execute_with_stats(plan, *qs[1])
    n2 = execute_with_stats._cache_size()
    assert n1 == n0 + 1 and n2 == n1, "stats dict must not retrace across batches"
    assert set(stats1) == set(stats2) == STATS_KEYS
    assert stats1["overflow_query_mask"].shape == (300,)
    assert 0.0 <= float(stats1["skipped_tile_fraction"]) <= 1.0
    assert 0.0 < float(stats1["phase1_tile_fill"]) <= 1.0


def test_persistent_overflow_counter_and_warning():
    """ROADMAP capacity-model regression: a deterministic sparse batch whose
    overflow_queries persists across repeated execute_with_stats calls must
    raise the persistent_overflow flag (and a one-shot RuntimeWarning
    suggesting a re-plan) once the streak reaches the threshold — the hook
    the future per-batch capacity re-estimator builds on.  A clean batch
    resets the streak; a fresh plan starts from zero."""
    from repro.engine.execute import PERSISTENT_OVERFLOW_BATCHES

    dx, dy, dz = _uniform(4096, 19)
    p = AIDWParams(k=10, area=1.0, r_max=64.0)
    rng = np.random.default_rng(20)
    # deterministic sparse out-of-bbox batch: overflows the tight capacity
    qx = jnp.asarray((rng.random(64) * 6 - 3).astype(np.float32))
    qy = jnp.asarray((rng.random(64) * 6 - 3).astype(np.float32))
    # clean batch: tile-local (compact block rectangle fits the capacity)
    qcx = jnp.asarray((0.4 + 0.05 * rng.random(64)).astype(np.float32))
    qcy = jnp.asarray((0.4 + 0.05 * rng.random(64)).astype(np.float32))
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                      query_occupancy=64.0)

    assert PERSISTENT_OVERFLOW_BATCHES == 3
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # the first two batches must NOT warn
        for _ in range(PERSISTENT_OVERFLOW_BATCHES - 1):
            _, _, stats = execute_with_stats(plan, qx, qy)
            assert int(stats["overflow_queries"]) > 0
            assert stats["persistent_overflow"] is False
    with pytest.warns(CapacityOverflowWarning):
        _, _, stats = execute_with_stats(plan, qx, qy)
    assert stats["persistent_overflow"] is True
    # further overflowing batches keep the flag without re-warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        _, _, stats = execute_with_stats(plan, qx, qy)
    assert stats["persistent_overflow"] is True
    # one clean batch resets the streak
    _, _, stats = execute_with_stats(plan, qcx, qcy)
    assert int(stats["overflow_queries"]) == 0
    assert stats["persistent_overflow"] is False
    # plan identity scopes the streak: a fresh plan starts clean
    plan2 = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                       query_occupancy=64.0)
    _, _, stats = execute_with_stats(plan2, qx, qy)
    assert stats["persistent_overflow"] is False


def test_execute_with_stats_composes_under_outer_jit():
    """Wrapping execute_with_stats in an outer jax.jit must keep working
    (pre-tracking behaviour): the host-side streak bookkeeping is skipped
    under a trace — the stats are tracers there — instead of raising."""
    import jax

    dx, dy, dz = _uniform(1024, 21)
    p = AIDWParams(k=10, area=1.0)
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid")
    rng = np.random.default_rng(22)
    qx = jnp.asarray(rng.random(100).astype(np.float32))
    qy = jnp.asarray(rng.random(100).astype(np.float32))
    z_j, a_j, stats_j = jax.jit(
        lambda x, y: execute_with_stats(plan, x, y))(qx, qy)
    assert "persistent_overflow" not in stats_j
    z_e, a_e, stats_e = execute_with_stats(plan, qx, qy)
    assert "persistent_overflow" in stats_e
    np.testing.assert_array_equal(np.asarray(z_j), np.asarray(z_e))
    np.testing.assert_array_equal(np.asarray(a_j), np.asarray(a_e))


# --------------------------------------------------- convenience plan memoization
def _hits_misses(registry) -> dict:
    stats = registry.stats()
    return {"hits": stats["hits"], "misses": stats["misses"]}


def test_ops_plan_cache_reuses_plan():
    """Two aidw() calls on the same data arrays must build ONE plan (weak-ref
    cache keyed on array ids + statics); new arrays — even equal ones — miss."""
    dx, dy, dz = _uniform(600, 15)
    rng = np.random.default_rng(16)
    qx, qy = rng.random(100).astype(np.float32), rng.random(100).astype(np.float32)
    qx2, qy2 = rng.random(100).astype(np.float32), rng.random(100).astype(np.float32)
    p = AIDWParams(k=10, area=1.0)
    registry = default_registry()
    ops.plan_cache_clear()
    z1, a1 = aidw(dx, dy, dz, qx, qy, params=p, area=1.0, impl="grid")
    assert _hits_misses(registry) == {"hits": 0, "misses": 1}
    (plan_first,) = registry.plans()
    z2, a2 = aidw(dx, dy, dz, qx2, qy2, params=p, area=1.0, impl="grid")
    assert _hits_misses(registry) == {"hits": 1, "misses": 1}
    (plan_second,) = registry.plans()
    assert plan_second is plan_first, "second call must reuse the same plan object"
    # a same-shape second batch through the cached plan matches a fresh plan
    fresh = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid")
    z_ref, a_ref = execute(fresh, jnp.asarray(qx2), jnp.asarray(qy2))
    np.testing.assert_array_equal(np.asarray(z2), np.asarray(z_ref))
    np.testing.assert_array_equal(np.asarray(a2), np.asarray(a_ref))
    # different array objects (equal contents) are a different dataset identity
    z3, _ = aidw(dx.copy(), dy.copy(), dz.copy(), qx, qy, params=p, area=1.0, impl="grid")
    assert registry.stats()["misses"] == 2
    np.testing.assert_array_equal(np.asarray(z3), np.asarray(z1))
    # dropping the data arrays evicts their entry (no pinned dataset copies)
    n_before = len(registry)
    del dx, dy, dz, plan_first, plan_second
    import gc

    gc.collect()
    assert len(registry) < n_before
    ops.plan_cache_clear()


def test_ops_plan_cache_distinguishes_config():
    dx, dy, dz = _uniform(600, 17)
    rng = np.random.default_rng(18)
    qx, qy = rng.random(64).astype(np.float32), rng.random(64).astype(np.float32)
    p = AIDWParams(k=10, area=1.0)
    ops.plan_cache_clear()
    aidw(dx, dy, dz, qx, qy, params=p, area=1.0, impl="grid")
    aidw(dx, dy, dz, qx, qy, params=p, area=1.0, impl="tiled", block_q=64, block_d=128)
    assert _hits_misses(default_registry()) == {"hits": 0, "misses": 2}
    assert len(default_registry()) == 2
    ops.plan_cache_clear()
