"""Typed warnings and errors for the repro package.

One class per failure mode, so callers and tests select on *type* instead of
substring-matching message text (the pre-PR-9 pattern: ``pytest.warns(...,
match="not provable")`` breaks on any rewording).  Every warning keeps the
stdlib category it historically used as a second base (``UserWarning`` for
plan-time honesty warnings, ``RuntimeWarning`` for serving-time degradation),
so existing ``warnings.simplefilter`` configurations and ``pytest.warns``
assertions against the stdlib categories keep working.

Hierarchy::

    ReproWarning
    ├── UnprovableRtolWarning      (UserWarning)     plan: a quadtree plan's
    │                                                farfield_rtol not provable at
    │                                                a profitable radius; honest
    │                                                bound reported
    ├── PathologicalGridWarning    (UserWarning)     plan: grid resolution leaves
    │                                                candidate rows near a full sweep
    ├── CapacityOverflowWarning    (RuntimeWarning)  execute: overflow_queries > 0
    │                                                persisted for the streak
    │                                                threshold — capacity undersized
    └── PlanDegradedWarning        (RuntimeWarning)  serving: the capacity
                                                     re-estimator gave up (build
                                                     failures / capacity cap);
                                                     results stay exact via the
                                                     ring-search / masked-exact
                                                     blend arms, at blend-arm cost
"""

from __future__ import annotations


class ReproWarning(Warning):
    """Base class for every warning the repro package emits on purpose."""


class UnprovableRtolWarning(ReproWarning, UserWarning):
    """The requested ``farfield_rtol`` of a ``phase2="quadtree"`` plan is not
    provable at a profitable near-field radius; the plan ships the honest
    (larger) worst-case bound."""


class PathologicalGridWarning(ReproWarning, UserWarning):
    """The grid resolution is pathological for the data: some cell's safe
    ring radius is so large that candidate rows approach a full sweep."""


class CapacityOverflowWarning(ReproWarning, RuntimeWarning):
    """``overflow_queries > 0`` persisted for the streak threshold against
    one plan: the static candidate capacity looks undersized for the serving
    workload (results stay exact via the blend, at ring-search cost)."""


class PlanDegradedWarning(ReproWarning, RuntimeWarning):
    """The capacity re-estimator exhausted its retries or its capacity cap
    and stopped re-planning; serving continues on the installed plan, exact
    through the ring-search / masked-exact blend arms."""


class PlanBuildError(RuntimeError):
    """A background re-plan failed terminally (carried as the cause on the
    re-estimator's degrade event; never raised into the serving thread)."""
