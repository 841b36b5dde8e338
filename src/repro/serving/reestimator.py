"""Capacity re-estimator — the self-healing loop over the overflow streak.

A grid plan's static candidate capacity is sized from an *assumed* serving
density (``query_occupancy``).  A workload that is persistently sparser or
clustered differently keeps paying the exact ring-search blend arm batch
after batch — correct, but at ring-search cost.  PR 5 shipped the trigger
(``engine/execute.py: _note_overflow``, the ``persistent_overflow`` streak);
this module ships the response:

``healthy``
    Every batch is served through the registry's current plan; the observed
    ``cand_need_max`` high-water mark is tracked, and on far-field and
    quadtree plans the near field's ``p2_need_max`` too.
``replanning``
    The streak — consecutive batches in which Phase 1 overflowed (ring
    search) or, on quadtree plans, Phase 2 did (masked exact
    sweep) — reached ``PERSISTENT_OVERFLOW_BATCHES``: a background thread
    rebuilds the plan via ``engine.plan.replan_with_capacity`` with a
    geometrically bumped floor on the capacity of each phase that
    overflowed — at least ``growth ×`` the current capacity AND at least
    the phase's observed need, hard-capped at ``min(m, capacity_cap)``
    (capacity ``m`` provably cannot overflow: a candidate row never needs
    more than every data point).  The other phase's capacity, ``phase2``,
    the radius and ``farfield_rtol`` carry over.  Build
    failures retry with exponential backoff, at most ``max_retries``
    attempts.  Serving continues on the OLD plan throughout — exact via
    the blend — and the new plan is published by the registry's atomic
    :meth:`~repro.serving.registry.PlanRegistry.swap` (optionally warmed
    first, so the first post-swap batch doesn't pay the compile).
``degraded``
    The capacity cap left no room to grow, or every build attempt failed:
    re-planning stops, serving continues on the installed plan (results
    stay exact through the ring-search / masked-exact arms, at blend-arm
    cost), and ONE :class:`~repro.errors.PlanDegradedWarning` is emitted —
    on the serving thread, at the next :meth:`~CapacityReestimator.execute`
    (warnings raised on a background thread are invisible to standard
    warning filters and to ``pytest.warns``).  :meth:`reset` re-arms.

Fault-injection points (``serving.faults``): ``reestimator.stats`` (per
batch, the diagnostics dict — fabricate synthetic overflow streaks),
``reestimator.build`` (top of every build attempt — inject failures/slow
builds), ``reestimator.capacity`` (the proposed capacity — force cap
exhaustion).  See DESIGN.md §9 for the full state machine.
"""

from __future__ import annotations

import threading
import time
import warnings

from repro import telemetry
from repro.errors import PlanBuildError, PlanDegradedWarning
from repro.serving import faults

HEALTHY = "healthy"
REPLANNING = "replanning"
DEGRADED = "degraded"


class CapacityReestimator:
    """Serve batches through a registry entry; re-plan + hot-swap on
    persistent overflow; degrade gracefully when re-planning cannot help.

    ``registry``/``key``: where the served plan lives (``plan`` is
    registered under ``key`` if absent).  ``growth``: geometric capacity
    bump per re-plan (> 1).  ``capacity_cap``: hard ceiling on the bumped
    candidate capacity (default: ``plan.m``, itself always an implicit
    cap).  ``max_retries`` / ``backoff``: bounded build retries with
    exponential backoff (``backoff * 2**attempt`` seconds between tries).
    ``warmup``: optional ``(qx, qy)`` batch compiled against every new plan
    before its swap becomes visible — keeps the swap stall off the serving
    path.
    """

    def __init__(self, registry, key, plan, *, growth: float = 2.0,
                 capacity_cap: int | None = None, max_retries: int = 3,
                 backoff: float = 0.05, warmup=None):
        if plan.impl != "grid":
            raise ValueError(
                f"CapacityReestimator requires a grid plan, got impl={plan.impl!r}"
            )
        if not growth > 1.0:
            raise ValueError(f"growth must be > 1, got {growth!r}")
        if max_retries < 1:
            raise ValueError(f"max_retries must be >= 1, got {max_retries!r}")
        if backoff < 0.0:
            raise ValueError(f"backoff must be >= 0, got {backoff!r}")
        self.registry = registry
        self.key = key
        self.growth = float(growth)
        self.capacity_cap = None if capacity_cap is None else int(capacity_cap)
        self.max_retries = int(max_retries)
        self.backoff = float(backoff)
        self._warmup = warmup
        self._lock = threading.Lock()
        self._state = HEALTHY
        self._thread: threading.Thread | None = None
        self._pending_warning: str | None = None
        self._need_max = 0
        self.last_error: PlanBuildError | None = None
        self.counters = {"batches": 0, "triggers": 0, "replans": 0,
                         "build_failures": 0, "swaps": 0, "degraded": 0,
                         "overflow_queries": 0, "p2_overflow_queries": 0,
                         "replan_s": 0.0}
        self._p2_need_max = 0
        self._overflowed = set()  # phases that overflowed since the last swap
        if key not in registry:
            registry.register(key, plan)

    # ------------------------------------------------------------- serving
    @property
    def plan(self):
        """The currently installed plan (whatever the last swap published)."""
        plan = self.registry.get(self.key)
        if plan is None:
            raise KeyError(
                f"plan under key {self.key!r} is gone from the registry "
                "(evicted?); the re-estimator cannot serve without it"
            )
        return plan

    def execute(self, qx, qy):
        """Serve one batch; returns ``(z, alpha, stats)`` like
        ``engine.execute_with_stats``.

        The overflow streak is advanced with the REAL ``_note_overflow``
        machinery (after the ``reestimator.stats`` injection point, so
        fault-injected synthetic streaks take the production path), and a
        streak trigger launches the background re-plan.  Results are
        whatever the installed plan computes — exact for every arm — so a
        batch served during a re-plan equals the same batch on the old
        plan, and a batch after the swap equals a fresh-plan reference.
        """
        import jax

        from repro.engine.execute import _execute_with_stats_jit, _note_overflow

        plan = self.plan
        with telemetry.span("serving.execute", call=self.counters["batches"]):
            with telemetry.span("serving.dispatch"):
                z, a, stats = _execute_with_stats_jit(plan, qx, qy)
            if not isinstance(stats["overflow_queries"], jax.core.Tracer):
                stats = dict(faults.fire("reestimator.stats", dict(stats)))
                with telemetry.span("serving.sync"):
                    n_overflow = int(stats["overflow_queries"])
                    need = int(stats["cand_need_max"])
                    n_p2 = int(stats.get("p2_overflow_queries", 0))
                    need_p2 = int(stats.get("p2_need_max", 0))
                with telemetry.span("serving.observe"):
                    with self._lock:
                        self.counters["batches"] += 1
                        self.counters["overflow_queries"] += n_overflow
                        self.counters["p2_overflow_queries"] += n_p2
                        self._need_max = max(self._need_max, need)
                        self._p2_need_max = max(self._p2_need_max, need_p2)
                        if n_overflow:
                            self._overflowed.add(1)
                        if n_p2:
                            self._overflowed.add(2)
                    persistent = _note_overflow(plan, n_overflow + n_p2)
                    stats["persistent_overflow"] = persistent
                    if persistent:
                        self._maybe_replan(plan)
            self._deliver_pending()
        return z, a, stats

    # ------------------------------------------------------ replan machinery
    def _maybe_replan(self, plan):
        # stale evidence guard: a batch in flight while a swap lands carries
        # the OLD plan's streak — re-triggering on it would rebuild a plan
        # that was already replaced (the free-running bench exposed this as
        # a doubled trigger/replan/swap count)
        if self.registry.get(self.key) is not plan:
            return
        with self._lock:
            if self._state != HEALTHY:
                return
            self._state = REPLANNING
            self.counters["triggers"] += 1
            needs = (self._need_max, self._p2_need_max, frozenset(self._overflowed))
            t = threading.Thread(
                target=self._replan, args=(plan, needs),
                name="repro-capacity-replan", daemon=True,
            )
            self._thread = t
        t.start()

    def _propose_capacity(self, capacity: int, need: int, m: int) -> int:
        cap = m
        if self.capacity_cap is not None:
            cap = min(cap, self.capacity_cap)
        return min(max(int(capacity * self.growth), need), cap)

    def _replan(self, plan, needs):
        t0 = time.perf_counter()
        try:
            with telemetry.span("serving.replan"):
                self._replan_and_swap(plan, *needs)
        finally:
            with self._lock:
                self.counters["replan_s"] += time.perf_counter() - t0

    def _replan_and_swap(self, plan, need: int, need_p2: int, overflowed):
        from repro.engine.plan import replan_with_capacity

        try:
            # a streak of Phase-2 overflow alone keeps Phase 1's capacity
            target = plan.cand_capacity
            if 1 in overflowed or not overflowed:
                target = int(faults.fire(
                    "reestimator.capacity",
                    self._propose_capacity(plan.cand_capacity, need, plan.m)))
            target_p2 = None
            if plan.phase2 != "exact":
                target_p2 = plan.p2_capacity
                if 2 in overflowed:
                    target_p2 = self._propose_capacity(plan.p2_capacity, need_p2, plan.m)
            grows = target > plan.cand_capacity or (
                target_p2 is not None and target_p2 > plan.p2_capacity)
            if not grows:
                self._degrade(
                    f"capacity cap exhausted: current cand_capacity="
                    f"{plan.cand_capacity} already meets the bumped target "
                    f"{target}"
                    + ("" if target_p2 is None else
                       f" and p2_capacity={plan.p2_capacity} the target {target_p2}")
                    + f" (cap {self.capacity_cap or plan.m}, m={plan.m})",
                    None,
                )
                return
            last_exc = None
            new_plan = None
            for attempt in range(self.max_retries):
                if attempt and self.backoff > 0.0:
                    time.sleep(self.backoff * (2 ** (attempt - 1)))
                try:
                    faults.fire("reestimator.build")
                    with self._lock:
                        self.counters["replans"] += 1
                    new_plan = replan_with_capacity(
                        plan, min_cand_capacity=target, min_p2_capacity=target_p2
                    )
                    break
                except Exception as exc:  # noqa: BLE001 — any build failure retries
                    last_exc = exc
                    with self._lock:
                        self.counters["build_failures"] += 1
            if new_plan is None:
                self._degrade(
                    f"re-plan to cand_capacity>={target} failed after "
                    f"{self.max_retries} attempts "
                    f"({type(last_exc).__name__}: {last_exc})",
                    last_exc,
                )
                return
            self.registry.swap(self.key, new_plan, warmup=self._warmup)
            with self._lock:
                self.counters["swaps"] += 1
                self._state = HEALTHY
                self._need_max = 0
                self._p2_need_max = 0
                self._overflowed = set()
        except Exception as exc:  # noqa: BLE001 — swap/injection failures degrade too
            self._degrade(f"background re-plan crashed "
                          f"({type(exc).__name__}: {exc})", exc)

    def _degrade(self, reason: str, cause):
        err = PlanBuildError(reason)
        if cause is not None:
            err.__cause__ = cause
        with self._lock:
            self._state = DEGRADED
            self.counters["degraded"] += 1
            self.last_error = err
            self._pending_warning = (
                f"capacity re-estimator degraded: {reason}. Serving continues "
                "on the installed plan — results stay exact through the "
                "ring-search / masked-exact blend arms, at blend-arm cost. "
                "Call reset() to re-arm after addressing the cause."
            )

    def _deliver_pending(self):
        with self._lock:
            msg, self._pending_warning = self._pending_warning, None
        if msg is not None:
            warnings.warn(msg, PlanDegradedWarning, stacklevel=3)

    # ------------------------------------------------------------ lifecycle
    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def join(self, timeout: float | None = 10.0) -> str:
        """Wait for any in-flight background re-plan; returns the state."""
        with self._lock:
            t = self._thread
        if t is not None:
            t.join(timeout)
        return self.state

    def reset(self):
        """Re-arm a degraded (or mid-streak) re-estimator: back to healthy,
        high-water mark and pending warning cleared.  The installed plan and
        the registry entry are untouched."""
        self.join()
        with self._lock:
            self._state = HEALTHY
            self._need_max = 0
            self._p2_need_max = 0
            self._overflowed = set()
            self._pending_warning = None
            self.last_error = None

    def stats(self) -> dict:
        """Snapshot: counters + state + the installed plan's capacity.

        Besides the event counts, ``overflow_queries`` sums every served
        batch's Phase-1 overflowed queries (ring search),
        ``p2_overflow_queries`` its Phase-2 ones (masked exact sweep; 0 on
        exact plans), and ``replan_s`` the seconds background re-plans
        took, build to swap."""
        with self._lock:
            out = dict(self.counters, state=self._state,
                       need_max=self._need_max)
        out["cand_capacity"] = self.plan.cand_capacity
        return out
