"""Telemetry (``repro.telemetry``): host spans on the profiler's clock,
device scopes in the compiled program, compile counters, the serving
layer's counters and the names of the Pallas kernels."""

import ast
import glob
import os
import re
import subprocess
import sys
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core.aidw import AIDWParams
from repro.engine import build_plan
from repro.engine.execute import PERSISTENT_OVERFLOW_BATCHES, _execute_with_stats_jit
from repro.serving import CapacityReestimator, PlanRegistry
from repro.serving.reestimator import HEALTHY

P = AIDWParams(k=10, area=1.0, r_max=64.0)
M = 4096
KERNELS = Path(__file__).resolve().parent.parent / "src" / "repro" / "kernels"
GRID_SCOPES = ("aidw.sort", "aidw.gather", "aidw.phase1", "aidw.ring_search",
               "aidw.phase2", "aidw.stats")


def _dataset(m=M, seed=19):
    rng = np.random.default_rng(seed)
    dx = rng.random(m).astype(np.float32)
    dy = rng.random(m).astype(np.float32)
    dz = (np.sin(3 * dx) * np.cos(2 * dy)).astype(np.float32)
    return dx, dy, dz


def _storm():
    """An undersized plan and a batch far outside its box: every call
    overflows, so the re-estimator re-plans after the streak."""
    plan = build_plan(*_dataset(), params=P, area=1.0, impl="grid", query_occupancy=64.0)
    rng = np.random.default_rng(20)
    qx, qy = ((rng.random((2, 64)) * 6 - 3).astype(np.float32))
    return plan, jnp.asarray(qx), jnp.asarray(qy)


def _host_spans(trace_dir) -> list:
    """``(name, start_ns, end_ns, stats)`` of every ``aidw.*`` host event."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                        for e in line.events if e.name.startswith(telemetry.PREFIX)]
    return sorted(out, key=lambda s: s[1])


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_serving_spans_nest_with_call_id(tmp_path):
    plan, qx, qy = _storm()
    re_ = CapacityReestimator(PlanRegistry(), "serve", plan, backoff=0.0)
    jax.block_until_ready(_execute_with_stats_jit(plan, qx, qy))  # compile untraced
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(PERSISTENT_OVERFLOW_BATCHES):
            re_.execute(qx, qy)
        assert re_.join(timeout=300.0) == HEALTHY
    spans = _host_spans(tmp_path)
    calls = [s for s in spans if s[0] == "aidw.serving.execute"]
    assert [int(s[3]["call"]) for s in calls] == list(range(PERSISTENT_OVERFLOW_BATCHES))
    for child in ("aidw.serving.dispatch", "aidw.serving.sync", "aidw.serving.observe"):
        kids = [s for s in spans if s[0] == child]
        assert len(kids) == len(calls)
        assert all(_inside(k, c) for k, c in zip(kids, calls))
    # the background re-plan, build to swap, on its own thread
    (replan,) = [s for s in spans if s[0] == "aidw.serving.replan"]
    for name in ("aidw.plan.replan", "aidw.plan.build", "aidw.registry.swap"):
        (inner,) = [s for s in spans if s[0] == name]
        assert _inside(inner, replan)


def test_replan_counters_grow_on_forced_replan():
    plan, qx, qy = _storm()
    re_ = CapacityReestimator(PlanRegistry(), "serve", plan, backoff=0.0)
    assert re_.stats()["replan_s"] == 0.0
    overflowed = 0
    for _ in range(PERSISTENT_OVERFLOW_BATCHES):
        overflowed += int(re_.execute(qx, qy)[2]["overflow_queries"])
    assert re_.join(timeout=300.0) == HEALTHY
    s = re_.stats()
    assert s["swaps"] == 1
    assert s["replan_s"] > 0.0
    assert overflowed > 0 and s["overflow_queries"] == overflowed


def test_new_jit_counts_one_compile_and_a_cached_call_none():
    x = jnp.arange(8.0)
    f = jax.jit(lambda v: v * 2.0 + 1.0)
    before = telemetry.snapshot()
    jax.block_until_ready(f(x))
    mid = telemetry.snapshot()
    jax.block_until_ready(f(x))
    after = telemetry.snapshot()
    assert mid["compiles"] - before["compiles"] == 1
    assert mid["traces"] > before["traces"] and mid["lowerings"] > before["lowerings"]
    assert mid["compile_s"] > before["compile_s"]
    assert after["compiles"] == mid["compiles"] and after["traces"] == mid["traces"]


def test_import_installs_no_listener():
    code = ("from jax._src import monitoring as m\n"
            "n = len(m.get_event_duration_listeners()), len(m.get_event_listeners())\n"
            "import repro.engine, repro.serving, repro.telemetry\n"
            "assert (len(m.get_event_duration_listeners()), len(m.get_event_listeners())) == n\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(KERNELS.parent.parent))
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120, env=env)


def test_span_is_a_profiler_annotation():
    s = telemetry.span("serving.execute", call=3)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:  # no profiler session: records nothing, raises nothing
        pass


def _compiled_text(fn, plan, n=256):
    rng = np.random.default_rng(5)
    qx, qy = (jnp.asarray(v) for v in rng.random((2, n)).astype(np.float32))
    return fn.lower(plan, qx, qy).compile().as_text()


@pytest.mark.parametrize("phase2, extra", [
    ("exact", ()),
    ("quadtree", ("aidw.phase2.quadtree_walk", "aidw.phase2.masked_exact")),
])
def test_grid_program_names_every_stage(phase2, extra):
    kw = {} if phase2 == "exact" else {"farfield_radius": 2}
    with warnings.catch_warnings():  # a small far-field plan proves no tight bound
        warnings.simplefilter("ignore")
        plan = build_plan(*_dataset(2048), params=P, area=1.0, impl="grid", phase2=phase2, **kw)
    text = _compiled_text(_execute_with_stats_jit, plan)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in GRID_SCOPES + extra:
        assert any(f"/{scope}/" in n for n in names), scope


@pytest.mark.parametrize("impl", ["tiled", "tiled_v2"])
def test_dense_program_names_both_phases(impl):
    plan = build_plan(*_dataset(2048), params=P, area=1.0, impl=impl, block_q=128, block_d=512)
    text = _compiled_text(_execute_with_stats_jit, plan)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("aidw.phase1", "aidw.phase2"):
        assert any(f"/{scope}/" in n for n in names), scope


def _pallas_calls():
    """``(file, kernel function, name=)`` of every ``pallas_call`` site."""
    for path in sorted(KERNELS.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Call) and getattr(node.func, "attr", None) == "pallas_call":
                kernel = node.args[0]
                if isinstance(kernel, ast.Call):  # functools.partial(kernel, ...)
                    kernel = kernel.args[0]
                name = {k.arg: k.value for k in node.keywords}.get("name")
                yield path.name, kernel.id, getattr(name, "value", None)


# Every kernel the package launches: the scan must find a call site of each.
KERNEL_FUNCTIONS = {
    "_fused_kernel", "_knn_kernel_soa", "_knn_kernel_aoas", "_knn_kernel_skip",
    "_knn_kernel_v2", "_weight_kernel_soa", "_weight_kernel_aoas", "_near_weight_kernel",
    "_near_weight_kernel_rows", "_far_node_kernel",
    "_naive_kernel_soa", "_naive_kernel_aoas", "_idw_kernel",
}


def test_every_pallas_call_is_named_for_its_kernel():
    sites = list(_pallas_calls())
    assert KERNEL_FUNCTIONS <= {k for _, k, _ in sites}
    assert [(f, k) for f, k, name in sites if name != k] == []

