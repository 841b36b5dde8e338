"""Golden-oracle cross-impl drift gate.

Every EXACT implementation must reproduce the committed Kahan-reference
fixture (``tests/fixtures/aidw_golden.npz``, seeded uniform + clustered
batches) within dtype-appropriate tolerance.  Pairwise parity tests compare
impls to a freshly-computed oracle, so a change that shifts the oracle and
an impl together passes them silently; this gate pins everyone to one
absolute committed reference.  The approximating ``binned`` prefilter is
deliberately excluded — its contract is error-bounded, not golden-equal.
The approximating quadtree Phase 2 gets two pins on its tight-cluster
batch: ``qtpin_*`` commits the served quadtree plan's OUTPUT (semantic-drift
gate, near-bitwise tolerance) and ``qtree_*`` commits a Kahan reference
plus the proved dipole bound the plan must reproduce and stay within (see
tests/engine/test_quadtree.py for the live-oracle version of this
contract).

Regenerate (only for an intentional semantic change, noted in the PR):
``PYTHONPATH=src python tests/fixtures/make_golden.py``.
"""

import os

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.aidw import AIDWParams, aidw_interpolate
from repro.engine import build_plan, execute

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "aidw_golden.npz")
# Kahan reference vs plain-f32 kernel accumulation over ~1K points: the
# committed values are ~f64-accurate, the impls accumulate in f32, so the
# gate is a few f32 ulps of headroom above the observed drift.
RTOL, ATOL = 5e-4, 5e-5
EXACT_IMPLS = ("naive", "tiled", "tiled_v2", "grid", "chunked")


@pytest.fixture(scope="module")
def golden():
    with np.load(FIXTURE) as blob:
        return {k: blob[k] for k in blob.files}


@pytest.mark.parametrize("batch", ["uniform", "clustered"])
@pytest.mark.parametrize("impl", EXACT_IMPLS)
def test_exact_impl_reproduces_golden(golden, impl, batch):
    p = AIDWParams(k=int(golden["k"]), area=float(golden["area"]))
    dx, dy, dz, qx, qy = (golden[f"{batch}_{n}"] for n in ("dx", "dy", "dz", "qx", "qy"))
    if impl == "chunked":
        z, a = aidw_interpolate(dx, dy, dz, qx, qy, p, area=float(golden["area"]),
                                q_chunk=64, d_chunk=128)
    else:
        plan = build_plan(dx, dy, dz, params=p, area=float(golden["area"]),
                          impl=impl, block_q=64, block_d=128)
        z, a = execute(plan, jnp.asarray(qx), jnp.asarray(qy))
    np.testing.assert_allclose(np.asarray(a), golden[f"{batch}_alpha"],
                               rtol=RTOL, atol=ATOL, err_msg=f"{impl} alpha drift")
    np.testing.assert_allclose(np.asarray(z), golden[f"{batch}_z"],
                               rtol=RTOL, atol=ATOL, err_msg=f"{impl} z drift")


def _quadtree_plan(golden):
    """The served quadtree plan (default pipeline and rtol) on the fixture's
    provable tight-cluster batch, and that batch's queries."""
    from repro.core.grid import build_grid

    p = AIDWParams(k=int(golden["k"]), area=float(golden["area"]))
    dx, dy, dz, qx, qy = (golden[f"qtree_{n}"]
                          for n in ("dx", "dy", "dz", "qx", "qy"))
    gx = int(golden["qtree_gx"])
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz),
                   gx=gx, gy=gx)
    plan = build_plan(dx, dy, dz, params=p, area=float(golden["area"]),
                      impl="grid", grid=g, phase2="quadtree", block_q=64)
    return plan, jnp.asarray(qx), jnp.asarray(qy)


def test_quadtree_output_pinned(golden):
    """The served ``phase2="quadtree"`` plan's output is pinned to the
    committed fixture: a change that leaves the arm's semantics alone must
    leave its z and alpha here.  Tolerance covers cross-backend codegen
    jitter only — a semantic change moves values far beyond it and must
    come with a deliberate regeneration noted in the PR."""
    plan, qx, qy = _quadtree_plan(golden)
    z, a = execute(plan, qx, qy)
    np.testing.assert_allclose(np.asarray(a), golden["qtpin_alpha"],
                               rtol=0, atol=1e-6, err_msg="quadtree alpha drift")
    np.testing.assert_allclose(np.asarray(z), golden["qtpin_z"],
                               rtol=2e-6, atol=2e-6, err_msg="quadtree z drift")


def test_quadtree_pinned_within_proved_bound(golden):
    """``phase2="quadtree"`` against the committed Kahan reference on the
    provable tight-cluster batch: the live plan must reproduce the committed
    proved bound (<= 1e-3) and its output must stay within that bound of
    the committed reference."""
    from repro.core.accuracy import FP_SLACK_ULPS

    plan, qx, qy = _quadtree_plan(golden)
    bound = float(golden["qtree_bound"])
    assert bound <= 1e-3
    np.testing.assert_allclose(plan.farfield_bound, bound, rtol=1e-9,
                               err_msg="dipole bound model drift")
    z, a = execute(plan, qx, qy)
    scale = float(np.max(np.abs(golden["qtree_dz"])))
    fp_slack = (FP_SLACK_ULPS * float(np.finfo(np.float32).eps)
                * float(np.sqrt(golden["qtree_dx"].shape[0])))
    rel = float(np.max(np.abs(np.asarray(z, np.float64)
                              - golden["qtree_z"].astype(np.float64))) / scale)
    assert rel <= bound + fp_slack, (rel, bound, fp_slack)
    np.testing.assert_allclose(np.asarray(a), golden["qtree_alpha"],
                               rtol=RTOL, atol=ATOL, err_msg="quadtree alpha drift")


def test_fixture_is_self_consistent(golden):
    """The committed fixture itself: sane shapes and finite values (guards
    against a truncated or mis-regenerated npz slipping into the repo)."""
    for batch in ("uniform", "clustered", "qtree"):
        for name in ("dx", "dy", "dz", "qx", "qy", "z", "alpha"):
            arr = golden[f"{batch}_{name}"]
            assert arr.dtype == np.float32
            assert np.isfinite(arr).all(), f"{batch}_{name} has non-finite values"
        assert golden[f"{batch}_dx"].shape == golden[f"{batch}_dz"].shape
        assert golden[f"{batch}_z"].shape == golden[f"{batch}_qx"].shape
        a = golden[f"{batch}_alpha"]
        levels = AIDWParams().alpha_levels
        assert (a >= min(levels) - 1e-6).all() and (a <= max(levels) + 1e-6).all()
    # the quadtree output pin: the served plan's answers on the qtree batch
    for name in ("z", "alpha"):
        pin = golden[f"qtpin_{name}"]
        assert pin.dtype == np.float32 and np.isfinite(pin).all()
        assert pin.shape == golden["qtree_qx"].shape
    assert not any(k.startswith("ffpin_") for k in golden)
