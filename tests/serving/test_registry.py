"""PlanRegistry contract (serving/registry.py): bounded LRU + eviction,
hit/miss/eviction/swap counters, identity-guard lifetime (GC eviction, id
mismatch = miss), warmup-before-publish, and the atomic hot-swap — readers
concurrent with a (fault-widened) swap only ever see a complete plan."""

import gc
import threading

import numpy as np
import pytest

from repro.core.aidw import AIDWParams
from repro.engine import build_plan
from repro.serving import PlanRegistry, default_registry, faults, plan_key

P = AIDWParams(k=5, area=1.0)


def _data(seed, m=64):
    rng = np.random.default_rng(seed)
    dx = rng.random(m).astype(np.float32)
    dy = rng.random(m).astype(np.float32)
    dz = (dx + dy).astype(np.float32)
    return dx, dy, dz


def _plan(seed=0):
    # chunked: the cheapest real plan (no kernels, no grid snapshot)
    return build_plan(*_data(seed), params=P, area=1.0, impl="chunked")


def test_register_get_hit_miss_counters():
    reg = PlanRegistry(max_plans=4)
    assert reg.get("absent") is None
    plan = _plan(0)
    assert reg.register("a", plan) is plan
    assert reg.get("a") is plan
    assert "a" in reg and "b" not in reg
    s = reg.stats()
    assert (s["hits"], s["misses"], s["size"]) == (1, 1, 1)


def test_plans_snapshot_touches_no_counter():
    reg = PlanRegistry()
    plans = [_plan(10), _plan(11)]
    reg.register("a", plans[0])
    reg.register("b", plans[1])
    assert sorted(map(id, reg.plans())) == sorted(map(id, plans))
    reg.swap("a", plans[1])
    assert [id(p) for p in reg.plans()] == [id(plans[1])] * 2
    s = reg.stats()
    assert (s["hits"], s["misses"]) == (0, 0)


def test_lru_bound_evicts_oldest_and_get_refreshes_recency():
    reg = PlanRegistry(max_plans=2)
    plans = {k: _plan(i) for i, k in enumerate("abc")}
    reg.register("a", plans["a"])
    reg.register("b", plans["b"])
    assert reg.get("a") is plans["a"]  # refresh: "b" is now the LRU entry
    reg.register("c", plans["c"])
    assert len(reg) == 2
    assert reg.get("b") is None
    assert reg.get("a") is plans["a"] and reg.get("c") is plans["c"]
    assert reg.stats()["evictions"] == 1


def test_guards_gc_evicts_entry():
    reg = PlanRegistry()
    dx, dy, dz = _data(1)
    plan = build_plan(dx, dy, dz, params=P, area=1.0, impl="chunked")
    reg.register("g", plan, guards=(dx, dy, dz))
    assert reg.get("g", live=(dx, dy, dz)) is plan
    del dx, dy, dz
    gc.collect()
    assert len(reg) == 0
    assert reg.stats()["evictions"] >= 1


def test_guard_identity_mismatch_is_miss():
    reg = PlanRegistry()
    dx, dy, dz = _data(2)
    plan = build_plan(dx, dy, dz, params=P, area=1.0, impl="chunked")
    reg.register("g", plan, guards=(dx, dy, dz))
    other = dx.copy()
    assert reg.get("g", live=(other, dy, dz)) is None
    assert len(reg) == 0  # the stale entry was dropped, not served


def test_get_or_build_builds_once():
    reg = PlanRegistry()
    calls = []

    def build():
        calls.append(1)
        return _plan(3)

    p1 = reg.get_or_build("k", build)
    p2 = reg.get_or_build("k", build)
    assert p1 is p2 and len(calls) == 1


def test_swap_replaces_atomically_and_counts():
    reg = PlanRegistry()
    old, new = _plan(4), _plan(5)
    reg.register("k", old)
    assert reg.swap("k", new) is old
    assert reg.get("k") is new
    assert reg.stats()["swaps"] == 1
    with pytest.raises(KeyError):
        reg.swap("absent", new)


def test_swap_with_failing_warmup_keeps_old_plan():
    reg = PlanRegistry()
    old, new = _plan(6), _plan(7)
    reg.register("k", old)
    with pytest.raises(Exception):
        # a warmup batch execute() cannot consume fails BEFORE publication
        reg.swap("k", new, warmup=("not-an-array", None))
    assert reg.get("k") is old
    assert reg.stats()["swaps"] == 0


def test_warmup_runs_execute_before_publish():
    reg = PlanRegistry()
    plan = _plan(8)
    qx = np.linspace(0.1, 0.9, 16).astype(np.float32)
    reg.register("k", plan, warmup=(qx, qx))
    assert reg.get("k") is plan


def test_concurrent_readers_never_see_torn_state_during_swap():
    reg = PlanRegistry()
    old, new = _plan(9), _plan(10)
    reg.register("k", old)
    seen, stop = [], threading.Event()

    def reader():
        while not stop.is_set():
            seen.append(reg.get("k"))

    threads = [threading.Thread(target=reader) for _ in range(4)]
    for t in threads:
        t.start()
    # widen the swap critical section so readers overlap it
    with faults.inject("registry.swap", delay=0.05):
        reg.swap("k", new)
    stop.set()
    for t in threads:
        t.join()
    assert seen and all(p is old or p is new for p in seen)
    assert reg.get("k") is new


def test_clear_resets_entries_and_counters():
    reg = PlanRegistry()
    reg.register("a", _plan(11))
    reg.get("a")
    reg.clear()
    s = reg.stats()
    assert (len(reg), s["hits"], s["misses"], s["evictions"], s["swaps"]) \
        == (0, 0, 0, 0, 0)


def test_max_plans_validation():
    with pytest.raises(ValueError, match="max_plans"):
        PlanRegistry(max_plans=0)


def test_plan_key_hashable_and_unhashable_config():
    dx, dy, dz = _data(12)
    k1 = plan_key(dx, dy, dz, {"impl": "grid", "block_q": 64})
    k2 = plan_key(dx, dy, dz, {"impl": "grid", "block_q": 64})
    assert k1 == k2 and hash(k1) == hash(k2)
    assert plan_key(dx, dy, dz, {"grid": [1, 2]}) is None  # unhashable value


def test_default_registry_is_a_singleton_plan_registry():
    reg = default_registry()
    assert reg is default_registry()
    assert isinstance(reg, PlanRegistry)
