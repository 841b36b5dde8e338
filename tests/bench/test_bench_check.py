"""The comparison that decides ``correct``: the plain reference, its
control (the reference in bfloat16, the next precision down) failing the
cell limits, and the sample drawn from the seed."""

import jax.numpy as jnp
import numpy as np
import pytest

from bench_cells import CELLS, small_cell
from bench import check, gen, reference


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [11, 2 ** 31 + 7, 10 ** 10])
def test_control_fails_the_cell_limits(name, seed):
    cell = small_cell(name)
    cfg = cell["config"]
    x, y, z, _ = gen.make_data(cfg, seed)
    qx, qy = (np.concatenate(v) for v in zip(*gen.make_batches(cell["traffic"], cfg, seed)))
    idx = check.draw_sample(qx.shape[0], seed, 256)
    z_ref, a_ref = reference.aidw(x, y, z, qx[idx], qy[idx], cfg["aidw"])
    z_c, a_c = reference.aidw(x, y, z, qx[idx], qy[idx], cfg["aidw"], dtype=jnp.bfloat16)
    correct, checks = check.verdict(check.gaps(z_c, a_c, z_ref, a_ref), cell["limits"]["limits"])
    assert not correct, checks
    ok, _ = check.verdict(check.gaps(z_ref, a_ref, z_ref, a_ref), cell["limits"]["limits"])
    assert ok


def test_reference_matches_a_direct_computation():
    """Eq. (1)-(6) written out in float64 numpy for a handful of queries."""
    rng = np.random.default_rng(3)
    x, y = rng.random(700).astype(np.float32), rng.random(700).astype(np.float32)
    x[:300] *= 0.1  # a cluster, so alpha takes values below a5
    z = (1.0 + x * y).astype(np.float32)
    qx, qy = rng.random(40).astype(np.float32), rng.random(40).astype(np.float32)
    cfg = {"k": 10, "alpha_levels": [0.5, 1, 2, 3, 4], "r_min": 0.0, "r_max": 2.0,
           "area": 1.0, "exact_hit_eps": 1e-18}
    z_ref, a_ref = reference.aidw(x, y, z, qx, qy, cfg, q_block=8, chunk=128)
    d = np.hypot(qx[:, None].astype(np.float64) - x, qy[:, None].astype(np.float64) - y)
    r_obs = np.sort(d, axis=1)[:, :10].mean(axis=1)
    big_r = r_obs / (1.0 / (2.0 * np.sqrt(700 / 1.0)))
    mu = np.where(big_r >= 2.0, 1.0, 0.5 - 0.5 * np.cos(np.pi / 2.0 * big_r))
    alpha = np.interp(mu, [0.1, 0.3, 0.5, 0.7, 0.9], [0.5, 1, 2, 3, 4])
    w = d ** -alpha[:, None]
    np.testing.assert_allclose(a_ref, alpha, rtol=0, atol=1e-4)
    np.testing.assert_allclose(z_ref, (w * z).sum(1) / w.sum(1), rtol=1e-5)
    assert len(np.unique(np.round(alpha, 3))) > 3


def test_exact_hit_takes_the_data_value():
    x = np.array([0.1, 0.5, 0.9] * 5, np.float32) + np.repeat(np.arange(5), 3).astype(np.float32) * 0.01
    y = np.linspace(0, 1, 15).astype(np.float32)
    z = np.arange(15, dtype=np.float32) + 1
    cfg = {"k": 3, "alpha_levels": [0.5, 1, 2, 3, 4], "r_min": 0.0, "r_max": 2.0,
           "area": 1.0, "exact_hit_eps": 1e-18}
    z_ref, _ = reference.aidw(x, y, z, x[[4, 7]], y[[4, 7]], cfg, q_block=8, chunk=128)
    np.testing.assert_array_equal(z_ref, z[[4, 7]])


def test_sample_is_drawn_from_the_seed_and_takes_marked_answers():
    marked = np.zeros(10_000, bool)
    marked[::50] = True
    low = np.zeros(10_000, bool)
    low[7::400] = True
    strata = [(marked, 100), (low, 25), (None, 10)]
    a = check.draw_sample(10_000, 5, 300, strata)
    assert np.array_equal(a, check.draw_sample(10_000, 5, 300, strata))
    assert not np.array_equal(a, check.draw_sample(10_000, 5, 300, strata[:1]))
    assert not np.array_equal(a, check.draw_sample(10_000, 6, 300, strata))
    assert marked[a].sum() >= 100 and low[a].sum() == 25 and len(np.unique(a)) == len(a)


def test_non_finite_answers_fail():
    ok, checks = check.verdict(check.gaps(np.array([np.nan]), np.array([1.0]), np.array([1.0]),
                                          np.array([1.0])), {"z_rel_gap": 1.0, "alpha_abs_gap": 1.0})
    assert not ok and checks["z_rel_gap"]["value"] == float("inf")
