"""The quadtree cell's own parts: its metric readers and kernel groups on
known traces, the work counts of ``bench/roofline_quadtree.py``, its
traffic's first calls, and its path's refusal of a plan that does not
prove the configuration's bound."""

import time
import types

import pytest

from bench_cells import small_cell
from bench import manifest, roofline, roofline_quadtree, run, tracing

CELL = "lidar-ql2-2048m-rtol1e-3.raster-quadtree"
MS = 1_000_000  # ns


def _pallas(name, operands="f32[8,1]{1,0} %a"):
    return (f"%{name} = f32[8,1]{{1,0}} custom-call({operands}), "
            'custom_call_target="tpu_custom_call"')


NEAR = _pallas("_near_weight_kernel_rows.3", "s32[64]{0} %t, f32[512,1]{1,0} %q")
NEAR_DENSE = _pallas("_near_weight_kernel.1")
FAR = _pallas("_far_node_kernel.7")
SWEEP = _pallas("_weight_kernel_soa.2")
KNN = _pallas("_knn_kernel_skip.4")


@pytest.mark.parametrize("name,group", [
    (NEAR, "phase2_near"), (NEAR_DENSE, "phase2_near"), (FAR, "phase2_far_nodes"),
    (SWEEP, "phase2_sweep"), (KNN, "phase1_knn"),
])
def test_kernel_groups_match_by_name(name, group):
    assert tracing.group_of(name, tracing.kernel_groups()) == group


def _reduced():
    dev = "/device:TPU:0"
    ops = [(dev, KNN, 0, 2 * MS), (dev, NEAR, 2 * MS, 42 * MS), (dev, FAR, 42 * MS, 52 * MS),
           (dev, FAR, 52 * MS, 54 * MS), (dev, SWEEP, 54 * MS, 60 * MS)]
    spans = [("bench.window", 0, 100 * MS), ("bench.call", 0, 100 * MS)]
    return tracing.reduce(ops, spans, tracing.kernel_groups())


def test_readers_on_a_known_trace():
    t = _reduced()
    assert t["kernel_s"]["phase2_near"] == pytest.approx(0.040)
    assert t["kernel_s"]["phase2_far_nodes"] == pytest.approx(0.012)
    ctx = {"trace": t, "peaks": None,
           "counters": {"queries": 16_384, "marked": 164, "sizes": [16_384], "m": 8_388_608}}
    read = {m: manifest.metric_reader(m).read(ctx) for m in (
        "qt.kernel.phase2_near.ms_per_kquery", "qt.kernel.phase2_far_nodes.ms_per_kquery",
        "qt.engine.exact_arm_share")}
    assert read["qt.kernel.phase2_near.ms_per_kquery"] == pytest.approx(40.0 / 16.384)
    assert read["qt.kernel.phase2_far_nodes.ms_per_kquery"] == pytest.approx(12.0 / 16.384)
    assert read["qt.engine.exact_arm_share"] == pytest.approx(100 * 164 / 16_384)


def test_readers_find_nothing_where_nothing_ran():
    """A window without the quadtree kernels (a parent's, or an exact
    path's) reads nothing, and nothing raises."""
    dev = "/device:TPU:0"
    t = tracing.reduce([(dev, SWEEP, 0, MS)], [("bench.window", 0, 2 * MS)],
                       tracing.kernel_groups())
    ctx = {"trace": t, "peaks": None, "counters": {"queries": 10, "marked": None}}
    for m in ("qt.kernel.phase2_near.ms_per_kquery", "qt.kernel.phase2_far_nodes.ms_per_kquery",
              "qt.engine.exact_arm_share"):
        assert manifest.metric_reader(m).read(ctx) is None


def test_quadtree_work_counts_are_pinned():
    """One 16,384-query call: 1.9M near points and 2.0e5 closed nodes a
    query; 11 operations a near pair, 20 a far term."""
    assert roofline_quadtree.NEAR_OPS_PER_PAIR == 11 and roofline_quadtree.FAR_OPS_PER_TERM == 20
    ops, nbytes = roofline_quadtree.near_work(16_384, 1.9e6, 8_388_608)
    assert ops == 11 * 16_384 * 1.9e6
    assert nbytes == 4 * (3 * 8_388_608 + 7 * 16_384)
    ops_f, bytes_f = roofline_quadtree.far_node_work(16_384, 2.0e5, 262_144)
    assert ops_f == 20 * 16_384 * 2.0e5
    assert bytes_f == 4 * (6 * 262_144 + 5 * 16_384)
    peak = roofline.peaks("TPU v5 lite")
    assert roofline_quadtree.share(ops, nbytes, 0.5, peak) == pytest.approx(
        100 * ops / 197e12 / 0.5)


def test_traffic_first_calls_hold_the_strips_and_voids():
    """The committed quadtree traffic: the first four calls take one block
    in each sidelap strip, the first eight two of the three voids."""
    spec = manifest.traffic("raster-quadtree")["queries"]
    blocks = spec["cells"] // spec["tile"]
    order = manifest.query_kind("raster_tiles").block_order(blocks, spec["order_step"],
                                                            spec["order_shift"])
    cfg = manifest.config("lidar-ql2-2048m-rtol1e-3")
    size = cfg["tile_m"] / blocks
    half = cfg["swath_width_m"] / 2
    lines = cfg["line_centres_m"]
    strips = [(b - half, a + half) for a, b in zip(lines, lines[1:])]
    first4 = order[:4]
    for lo, hi in strips:
        assert any(c * size < hi and (c + 1) * size > lo for c, _ in first4), (lo, hi)
    first8 = order[:8]

    def meets(void, block):  # the block meets the void's bounding box
        (cx, cy, ax, ay), (c, r) = void, block
        return (c * size < cx + ax and (c + 1) * size > cx - ax
                and r * size < cy + ay and (r + 1) * size > cy - ay)

    assert sum(any(meets(v, b) for b in first8) for v in cfg["voids_m"]) == 2


def test_full_size_is_ql2_at_2048_m():
    cfg = manifest.config("lidar-ql2-2048m-rtol1e-3")
    assert cfg["m"] == 8_388_608 == 2 * int(cfg["tile_m"]) ** 2
    assert cfg["aidw"]["area"] == cfg["tile_m"] ** 2
    assert cfg["farfield_rtol"] == 1e-3 and cfg["farfield_radius"] is None


def test_path_refuses_a_plan_that_does_not_prove_the_bound():
    """At the small size no near radius under the plan's budget proves
    1e-3 on lidar geometry: left to choose, the plan warns, and the path
    turns the warning into an error before any call."""
    from repro.errors import UnprovableRtolWarning

    cell = small_cell(CELL)
    cell["config"]["farfield_radius"] = None
    with pytest.raises(UnprovableRtolWarning):
        run.run_cell(cell, 2 ** 31 + 7, 0.0, False, t_start=time.perf_counter(), min_calls=1)


def test_path_holds_every_plan_to_the_rtol():
    held = manifest.path_module("served-grid-quadtree")._held_to
    ok = types.SimpleNamespace(farfield_bound=9.5e-4)
    assert held(ok, 1e-3) is ok
    with pytest.raises(RuntimeError, match="above the configuration's farfield_rtol"):
        held(types.SimpleNamespace(farfield_bound=2e-3), 1e-3)
