"""The lane-dense weight sweep step (``kernels/_common.sweep_step``, DESIGN.md
§2) against ``core/aidw``'s reference, in interpret mode.

* Ragged ``m`` (a multiple of neither 128 nor the tile): the sentinel pad
  points weigh 0 and are never a hit, even with a large pad z.
* Block shapes from the tests' 32x64 up to the served cells' tiles.
* Duplicate coincident points with different z, placed so that lane order
  and sweep order disagree: the first point in sweep order wins, as in the
  reference's ``argmin``.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.aidw import AIDWParams, aidw_reference
from repro.core.layouts import coord_sentinel, pad_to
from repro.engine.plan import _SWEEP_TILE
from repro.kernels.aidw_grid import phase2_near_weights, phase2_weights_full
from conftest import make_points

RTOL, ATOL = 2e-4, 2e-5
P = AIDWParams(k=10, area=1.0)
PAD_Z = 1e6  # a pad point that weighed anything would move z by far more than ATOL


def _padded(v, block_d, value):
    return pad_to(jnp.asarray(v), block_d, jnp.asarray(value, jnp.float32))[None, :]


def _sweep(dx, dy, dz, qx, qy, alpha, block_q, block_d):
    n = qx.shape[0]
    pad = (-n) % block_q
    q = [jnp.pad(jnp.asarray(v), (0, pad)) for v in (qx, qy)]
    al = jnp.pad(jnp.asarray(alpha), (0, pad), constant_values=1.0)[:, None]
    big = coord_sentinel(jnp.float32)
    z = phase2_weights_full(*q, al, _padded(dx, block_d, big), _padded(dy, block_d, big),
                            _padded(dz, block_d, PAD_Z), eps=P.exact_hit_eps,
                            block_q=block_q, block_d=block_d, interpret=True)
    return np.asarray(z[:n, 0])


BLOCKS = [(32, 64), (64, 128), (256, 512), (256, _SWEEP_TILE)]


@pytest.mark.parametrize("m", [1000, 2900])
@pytest.mark.parametrize("block_q,block_d", BLOCKS)
def test_sweep_matches_reference_on_ragged_data(block_q, block_d, m):
    dx, dy, dz, qx, qy = make_points(m, 300, seed=m + block_d)
    qx[:7], qy[:7] = dx[::97][:7], dy[::97][:7]               # exact hits
    assert m % 128 and m % block_d
    z_ref, a_ref = aidw_reference(dx, dy, dz, qx, qy, P, area=1.0)
    z = _sweep(dx, dy, dz, qx, qy, a_ref, block_q, block_d)
    np.testing.assert_allclose(z, np.asarray(z_ref), rtol=RTOL, atol=ATOL)
    np.testing.assert_array_equal(z[:7], dz[::97][:7])


# Positions, in sweep order, of coincident points with different z, in a
# 256-point tile of 128-lane chunks: the earlier point in a higher lane of
# an earlier chunk, in the same grid step and across two; and three in one
# lane, where the lane itself must keep the first.
DUPLICATES = {
    "same-step": (100, 128 + 5),
    "across-steps": (250, 256 + 3),
    "three-way": (255, 256 + 200, 512 + 1),
    "same-lane": (100, 128 + 100, 256 + 100),
}


def _with_duplicates(positions, m=700, seed=5):
    rng = np.random.default_rng(seed)
    dx = rng.random(m).astype(np.float32)
    dy = rng.random(m).astype(np.float32)
    dz = (1.0 + rng.random(m)).astype(np.float32)
    dx[list(positions)], dy[list(positions)] = np.float32(0.5), np.float32(0.5)
    for k, p in enumerate(positions):
        dz[p] = np.float32(10.0 + k)
    qx = np.concatenate([[0.5], rng.random(40)]).astype(np.float32)
    qy = np.concatenate([[0.5], rng.random(40)]).astype(np.float32)
    return dx, dy, dz, qx, qy


@pytest.mark.parametrize("case", sorted(DUPLICATES))
def test_first_point_in_sweep_order_wins(case):
    positions = DUPLICATES[case]
    dx, dy, dz, qx, qy = _with_duplicates(positions)
    z_ref, a_ref = aidw_reference(dx, dy, dz, qx, qy, P, area=1.0)
    assert float(z_ref[0]) == dz[positions[0]]
    z = _sweep(dx, dy, dz, qx, qy, a_ref, 32, 256)
    assert z[0] == dz[positions[0]]
    np.testing.assert_allclose(z, np.asarray(z_ref), rtol=RTOL, atol=ATOL)


def _near_field(dx, dy, dz, qx, qy, alpha, bq, bd):
    n = qx.shape[0]
    q = [jnp.pad(jnp.asarray(v), (0, bq - n)) for v in (qx, qy)]
    ah = jnp.pad(jnp.asarray(alpha) * 0.5, (0, bq - n), constant_values=0.5)[:, None]
    big = coord_sentinel(jnp.float32)
    cand = [_padded(v, bd, fill) for v, fill in ((dx, big), (dy, big), (dz, PAD_Z))]
    tiles = jnp.asarray([cand[0].shape[1] // bd], jnp.int32)
    return tuple(np.asarray(v[:n, 0]) for v in phase2_near_weights(
        *q, ah, *cand, tiles, block_q=bq, block_d=bd, interpret=True))


@pytest.mark.parametrize("case", sorted(DUPLICATES))
def test_near_field_hit_is_first_in_sweep_order(case):
    """The near field's finishing reduction gives the least ``d2`` and the
    z of its first point, whatever the tile, and sums that match the
    reference's."""
    positions = DUPLICATES[case]
    dx, dy, dz, qx, qy = _with_duplicates(positions)
    _, a_ref = aidw_reference(dx, dy, dz, qx, qy, P, area=1.0)
    sw, swz, md, hz = _near_field(dx, dy, dz, qx, qy, a_ref, 64, 256)
    _, _, md_128, hz_128 = _near_field(dx, dy, dz, qx, qy, a_ref, 64, 128)
    np.testing.assert_array_equal(md, md_128)
    np.testing.assert_array_equal(hz, hz_128)
    assert md[0] == 0.0 and hz[0] == dz[positions[0]]
    d2 = (qx[:, None] - dx[None, :]) ** 2 + (qy[:, None] - dy[None, :]) ** 2
    np.testing.assert_allclose(md[1:], d2[1:].min(axis=1), rtol=4 * np.finfo(np.float32).eps)
    np.testing.assert_array_equal(hz[1:], dz[d2[1:].argmin(axis=1)])
    w = np.exp(-np.asarray(a_ref)[1:, None] * 0.5 * np.log(d2[1:].astype(np.float64)))
    np.testing.assert_allclose(sw[1:], w.sum(axis=1), rtol=RTOL)
    np.testing.assert_allclose(swz[1:], (w * dz[None, :]).sum(axis=1), rtol=RTOL)
