"""Peaks table and the Phase-2 work count."""

import pytest

import bench_cells  # noqa: F401  (the repository root on the path)
from bench import manifest, roofline


def test_peaks_for_v5e_and_error_for_unknown_kinds():
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_phase2_work_is_pinned():
    """65,536 queries against the paper's 1,024,000 points: 11 operations
    per pair; the data read once, queries and alpha in, z out."""
    ops, nbytes = roofline.phase2_work(65_536, 1_024_000)
    assert ops == 11 * 65_536 * 1_024_000 == 738_197_504_000
    assert nbytes == 4 * (3 * 1_024_000 + 4 * 65_536) == 13_336_576
    t, bound = roofline.least_time(ops, nbytes, roofline.peaks("TPU v5 lite"))
    assert bound == "compute" and t == pytest.approx(ops / 197e12)


@pytest.mark.parametrize("impl,layout,block_q,block_d", [
    ("tiled", "soa", 256, 512), ("tiled", "soa", 128, 1024), ("tiled", "aoas", 256, 512),
    ("grid", "soa", 256, 512), ("grid", "soa", 128, 256),
])
def test_reader_does_not_depend_on_the_implementation(impl, layout, block_q, block_d):
    """The roofline share counts the formula's pairs: plans of any impl,
    layout or block size, with their own padded widths, give the same
    share for the same kernel time."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.aidw import AIDWParams
    from repro.engine import build_plan

    rng = np.random.default_rng(0)
    m = 3000  # a width no block size divides
    dx, dy, dz = (jnp.asarray(rng.random(m, dtype=np.float32)) for _ in range(3))
    plan = build_plan(dx, dy, dz, params=AIDWParams(k=10, area=1.0), area=1.0, impl=impl,
                      layout=layout, block_q=block_q, block_d=block_d)
    reader = manifest.metric_reader("kernel.phase2_sweep_roofline")
    ctx = {"trace": {"kernel_s": {"phase2_sweep": 2e-3}}, "peaks": roofline.peaks("TPU v5 lite"),
           "counters": {"sizes": [500, 500], "m": plan.m}}
    share = reader.read(ctx)
    ops = 2 * roofline.phase2_work(500, m)[0]
    assert share == pytest.approx(100 * ops / 197e12 / 2e-3)
