"""Regenerate the committed golden-oracle fixture ``aidw_golden.npz``.

Two seeded batches (uniform + clustered data, uniform queries) with
Kahan-compensated reference interpolants and alphas
(``core.accuracy.aidw_interpolate_kahan`` — ~f64-quality accumulation at
f32 cost).  ``tests/test_golden.py`` asserts every EXACT impl reproduces
these values within dtype-appropriate tolerance, pinning the whole impl
family to one absolute reference across PRs (pairwise parity tests cannot
see a drift that moves two impls together).

Beyond the exact-impl batches, the fixture pins the approximating
quadtree Phase 2 on a tight-cluster batch where its dipole bound PROVES
rtol=1e-3:

* ``qtree_*`` — the batch, its Kahan reference and the proved bound
  recorded at generation time; ``test_golden.py`` asserts the live plan
  reproduces the bound and stays within it against the committed
  reference;
* ``qtpin_*`` — the served quadtree plan's committed OUTPUT (z and alpha)
  on that batch: a semantic-regression gate that the arm is unchanged
  across PRs.

Run from the repo root (only when the reference semantics intentionally
change — note it in the PR):

    PYTHONPATH=src python tests/fixtures/make_golden.py
"""

import os
import sys

import numpy as np
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # conftest
from conftest import make_points  # noqa: E402

from repro.core.accuracy import aidw_interpolate_kahan  # noqa: E402
from repro.core.aidw import AIDWParams  # noqa: E402
from repro.core.grid import build_grid  # noqa: E402
from repro.engine import build_plan, execute  # noqa: E402

M, N, K = 900, 320, 10
QT_GX, QT_M = 12, 4000
OUT = os.path.join(os.path.dirname(__file__), "aidw_golden.npz")


def _quadtree_batch(seed=303):
    """Per-cell clusters far below the cell scale (sub-cell dispersion) with
    z noise INSIDE each cluster — the configuration where the quadtree
    dipole bound proves rtol=1e-3 (a monopole-only budget would pay the
    in-cluster z noise to first order)."""
    rng = np.random.default_rng(seed)
    centers = (np.stack(np.meshgrid(np.arange(QT_GX), np.arange(QT_GX)), -1)
               .reshape(-1, 2) + 0.5) / QT_GX
    pts = (centers[rng.integers(0, QT_GX * QT_GX, QT_M)]
           + rng.normal(0, 1e-4, (QT_M, 2)))
    pts = np.clip(pts, 0.0, 1.0).astype(np.float32)
    dx, dy = pts[:, 0], pts[:, 1]
    dz = (np.sin(6 * dx) * np.cos(6 * dy) + 2.0
          + 0.3 * rng.standard_normal(QT_M)).astype(np.float32)
    q = rng.random((N, 2)).astype(np.float32)
    return dx, dy, dz, q[:, 0], q[:, 1]


def main():
    params = AIDWParams(k=K, area=1.0)
    blobs = {"k": np.int32(K), "area": np.float32(1.0)}
    for name, clustered, seed in (("uniform", False, 101), ("clustered", True, 202)):
        dx, dy, dz, qx, qy = make_points(M, N, seed=seed, clustered=clustered)
        z_ref, a_ref = aidw_interpolate_kahan(
            dx, dy, dz, qx, qy, params, area=1.0, q_chunk=64, d_chunk=128
        )
        blobs.update({
            f"{name}_dx": dx, f"{name}_dy": dy, f"{name}_dz": dz,
            f"{name}_qx": qx, f"{name}_qy": qy,
            f"{name}_z": np.asarray(z_ref), f"{name}_alpha": np.asarray(a_ref),
        })

    # quadtree pins: Kahan reference + the proved dipole bound on the
    # provable batch, and the served plan's output on it — any semantic
    # drift across PRs trips the golden gate.
    dx, dy, dz, qx, qy = _quadtree_batch()
    z_ref, a_ref = aidw_interpolate_kahan(dx, dy, dz, qx, qy, params,
                                          area=1.0, q_chunk=64, d_chunk=128)
    g = build_grid(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz),
                   gx=QT_GX, gy=QT_GX)
    plan = build_plan(dx, dy, dz, params=params, area=1.0, impl="grid",
                      grid=g, phase2="quadtree", block_q=64)
    assert plan.farfield_bound <= 1e-3, "qtree batch must be provable"
    z, a = execute(plan, jnp.asarray(qx), jnp.asarray(qy))
    blobs.update({
        "qtree_dx": dx, "qtree_dy": dy, "qtree_dz": dz,
        "qtree_qx": qx, "qtree_qy": qy,
        "qtree_z": np.asarray(z_ref), "qtree_alpha": np.asarray(a_ref),
        "qtree_bound": np.float64(plan.farfield_bound),
        "qtree_gx": np.int32(QT_GX),
        "qtpin_z": np.asarray(z), "qtpin_alpha": np.asarray(a),
    })
    np.savez_compressed(OUT, **blobs)
    print(f"wrote {OUT} ({os.path.getsize(OUT)} bytes)")


if __name__ == "__main__":
    main()
