"""Public entry points for the AIDW/IDW Pallas kernels.

Since the plan/execute refactor (DESIGN.md §6) these are thin conveniences
over ``repro.engine``: each call builds an :class:`InterpolationPlan`
(padding, sentinel data points, SoA/AoaS layout, interpret-mode
autodetection, the grid snapshot — all captured once, in one place) and
runs the jitted ``execute`` step.  Repeated convenience calls against the
*same* data arrays reuse one memoized plan — since PR 9 the memo is the
process-default :class:`repro.serving.PlanRegistry` (bounded LRU, identity
guards, counters: ``default_registry().stats()``), so they stop paying the
plan rebuild; callers that interpolate many query batches should still
hold the plan themselves — it is explicit about lifetime and survives
array identity changes:

    from repro.engine import build_plan, execute
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid")
    z, a = execute(plan, qx, qy)          # compile once
    z2, a2 = execute(plan, qx2, qy2)      # cache hit
"""

from __future__ import annotations

import warnings
from typing import Literal

from repro.core.aidw import AIDWParams
from repro.serving.registry import default_registry, plan_key

Impl = Literal["naive", "tiled", "fused", "binned", "grid", "tiled_v2"]
Layout = Literal["soa", "aoas"]


def plan_cache_clear():
    """Drop all memoized convenience-API plans (test / memory-pressure hook).

    Since PR 9 this clears the process-default ``repro.serving``
    :class:`~repro.serving.PlanRegistry` (entries and counters), which is
    where the convenience memo lives.
    """
    default_registry().clear()


def _cached_build_plan(dx, dy, dz, **config):
    """Plan memoization for the one-shot conveniences, backed by the
    process-default serving registry: repeated aidw()/idw() calls against
    the same data arrays reuse one InterpolationPlan instead of paying the
    eager plan build (grid snapshot, required_radius table, capacity sweep)
    per call.  Keyed on the data arrays' ids + the static config; the
    registry's identity guards re-check the ids on every hit and evict the
    entry when a data array is collected (see ``serving/registry.py``).
    CAVEAT (documented on aidw/idw): identity-based memoization cannot see
    in-place mutation of a cached array's contents — mutate-and-
    reinterpolate callers must pass fresh arrays or call
    plan_cache_clear()."""
    from repro.engine import build_plan  # lazy: kernels <-> engine

    key = plan_key(dx, dy, dz, config)
    if key is None:  # unhashable config (e.g. a prebuilt grid=): no caching
        return build_plan(dx, dy, dz, **config)
    return default_registry().get_or_build(
        key, lambda: build_plan(dx, dy, dz, **config), guards=(dx, dy, dz)
    )


def aidw(
    dx, dy, dz, qx, qy,
    *,
    params: AIDWParams = AIDWParams(),
    area: float,
    impl: Impl = "tiled",
    layout: Layout = "soa",
    block_q: int = 256,
    block_d: int = 512,
    interpret: bool | None = None,
    grid=None,
    phase2: str = "exact",
    farfield_rtol: float = 1e-3,
    farfield_radius: int | None = None,
):
    """AIDW via the Pallas kernels.  Returns ``(z_hat, alpha)``, shape (n,).

    ``impl``: "naive" (paper, no VMEM tiling), "tiled" (paper, shared-memory
    analogue), "binned" (approximate prefilter), "fused" (beyond-paper
    single-launch two-phase; SoA only), "grid" (static-shape spatial-partition
    Phase 1 — jit-compatible since the plan/execute refactor; ``grid=``
    accepts a prebuilt ``repro.core.grid.UniformGrid``), "tiled_v2"
    (threshold-skip kNN pass; use ``repro.engine.execute_with_stats`` for its
    merge-fraction diagnostic).
    ``layout``: "soa" | "aoas" — layout of the streamed data-point array.
    ``phase2``/``farfield_rtol``/``farfield_radius`` (impl="grid" only)
    select the quadtree Phase 2 with its plan-time error budget — see
    :func:`repro.engine.build_plan`.

    Repeat calls with the *same* ``dx/dy/dz`` array objects reuse a memoized
    plan (keyed on array identity, not contents): don't mutate data arrays
    in place between calls — pass fresh arrays, or call
    :func:`plan_cache_clear`.
    """
    from repro.engine import execute  # lazy: kernels <-> engine

    if impl not in ("naive", "tiled", "fused", "binned", "grid", "tiled_v2"):
        # the engine also plans "idw"/"chunked"; those have their own entry
        # points (idw(), aidw_interpolate()) with different semantics
        raise ValueError(impl)
    plan = _cached_build_plan(
        dx, dy, dz,
        params=params, area=area, impl=impl, layout=layout,
        block_q=block_q, block_d=block_d, interpret=interpret, grid=grid,
        phase2=phase2, farfield_rtol=farfield_rtol,
        farfield_radius=farfield_radius,
    )
    return execute(plan, qx, qy)


def aidw_v2(
    dx, dy, dz, qx, qy,
    *,
    params: AIDWParams = AIDWParams(),
    area: float,
    block_q: int = 256,
    block_d: int = 512,
    interpret: bool | None = None,
):
    """Deprecated standalone entry for the threshold-skip kernel; use
    ``aidw(..., impl="tiled_v2")`` (or the engine directly, which exposes the
    merge-fraction diagnostic via ``execute_with_stats``).

    Returns ``(z_hat, alpha, merge_fraction)`` — merge_fraction is the
    measured share of (query-block x data-tile) steps that actually ran the
    k-best merge.
    """
    warnings.warn(
        "aidw_v2 is deprecated; use aidw(..., impl='tiled_v2') or "
        "repro.engine.execute_with_stats for the merge-fraction diagnostic",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.engine import execute_with_stats  # lazy: kernels <-> engine

    plan = _cached_build_plan(
        dx, dy, dz,
        params=params, area=area, impl="tiled_v2",
        block_q=block_q, block_d=block_d, interpret=interpret,
    )
    z, a, stats = execute_with_stats(plan, qx, qy)
    return z, a, stats["merge_fraction"]


def idw(
    dx, dy, dz, qx, qy,
    *,
    alpha: float = 2.0,
    block_q: int = 256,
    block_d: int = 512,
    interpret: bool | None = None,
):
    """Standard IDW via the tiled Pallas kernel (SoA). Returns z_hat (n,).

    Plans are memoized on data-array identity (see :func:`aidw`): don't
    mutate ``dx/dy/dz`` in place between calls."""
    from repro.engine import execute  # lazy: kernels <-> engine

    plan = _cached_build_plan(
        dx, dy, dz,
        impl="idw", idw_alpha=alpha,
        block_q=block_q, block_d=block_d, interpret=interpret,
    )
    z, _ = execute(plan, qx, qy)
    return z
