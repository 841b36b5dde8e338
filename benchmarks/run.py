"""Benchmark harness — one function per paper table/figure.

CSV rows: ``table,name,value,derived`` on stdout; sections mirror the paper:
  table1  — execution time (measured XLA-CPU at reduced sizes + modeled TPU
            at the paper's sizes; this box has no GPU/TPU to time)
  fig4    — speedups on single precision (modeled TPU vs measured CPU)
  fig5    — double precision (measured f64/f32 CPU ratio; TPU has no f64)
  fig6    — SoA vs AoaS (measured CPU + analytic byte ratio)
  fig7    — tiled vs naive (measured CPU locality effect + the VMEM cliff)
  lm      — roofline summary of the dry-run artifacts (if present)

Run: PYTHONPATH=src python -m benchmarks.run [--quick]
"""

from __future__ import annotations

import argparse
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._timing import time_fn
from benchmarks.aidw_model import (
    VMEM_BYTES,
    modeled_tpu_seconds,
    naive_vmem_bytes,
)
from repro.compile_cache import use_persistent_compile_cache
from repro.core.aidw import AIDWParams, aidw_interpolate, brute_r_obs
from repro.core.grid import build_grid, grid_r_obs
from repro.core.idw import idw_interpolate
from repro.core.layouts import soa_to_aoas
from repro.data.spatial import clustered_points, uniform_points

K = 1024
PAPER_SIZES = {"10K": 10 * K, "50K": 50 * K, "100K": 100 * K, "500K": 500 * K, "1000K": 1000 * K}
# Paper Table 1 (ms), single precision — cited for comparison
PAPER_TABLE1 = {
    "cpu": {"10K": 6791, "50K": 168234, "100K": 673806, "500K": 16852984, "1000K": 67471402},
    "naive_soa": {"10K": 65.3, "50K": 863, "100K": 2884, "500K": 63599, "1000K": 250574},
    "tiled_soa": {"10K": 61.3, "50K": 714, "100K": 2242, "500K": 43843, "1000K": 168189},
}


def _row(table, name, value, derived=""):
    print(f"{table},{name},{value},{derived}")


def _points(m, dtype=np.float32, seed=0):
    dx, dy, dz = uniform_points(m, seed=seed, dtype=dtype)
    qx, qy, _ = uniform_points(m, seed=seed + 1, dtype=dtype)
    return map(jnp.asarray, (dx, dy, dz, qx, qy))


def table1_execution_time(quick=False):
    """Paper Table 1. Measured: XLA-CPU tiled AIDW at reduced sizes (the
    honest CPU baseline this box can run). Modeled: TPU-v5e roofline at the
    paper's sizes."""
    p = AIDWParams(k=10, area=1.0)
    sizes = [1 * K, 4 * K] if quick else [1 * K, 4 * K, 16 * K]
    for m in sizes:
        dx, dy, dz, qx, qy = _points(m)
        t = time_fn(lambda: aidw_interpolate(dx, dy, dz, qx, qy, p, area=1.0,
                                             q_chunk=min(1024, m), d_chunk=min(4096, m)))
        _row("table1", f"cpu_xla_aidw_{m//K}K", f"{t*1e3:.1f}ms", f"m=n={m}")
    for name, m in PAPER_SIZES.items():
        for impl in ("naive", "tiled"):
            sec, parts = modeled_tpu_seconds(m, m, impl=impl)
            feasible = naive_vmem_bytes(m) <= VMEM_BYTES if impl == "naive" else True
            _row("table1", f"tpu_modeled_{impl}_soa_{name}",
                 f"{sec*1e3:.1f}ms" if feasible else "VMEM-infeasible",
                 f"compute={parts['compute_s']*1e3:.1f}ms memory={parts['memory_s']*1e3:.1f}ms")
        _row("table1", f"paper_gpu_tiled_{name}", f"{PAPER_TABLE1['tiled_soa'][name]}ms", "paper value, GT 730M")


def fig4_speedups(quick=False):
    """Paper Fig. 4: speedup vs the CPU baseline, single precision.
    We report (a) the paper's own 270x/400x claims, (b) our modeled-TPU vs
    measured-CPU speedup at sizes this box can time."""
    p = AIDWParams(k=10, area=1.0)
    m = 4 * K if quick else 16 * K
    dx, dy, dz, qx, qy = _points(m)
    t_cpu = time_fn(lambda: aidw_interpolate(dx, dy, dz, qx, qy, p, area=1.0))
    t_tpu_naive, _ = modeled_tpu_seconds(m, m, impl="naive")
    t_tpu_tiled, _ = modeled_tpu_seconds(m, m, impl="tiled")
    _row("fig4", f"measured_cpu_{m//K}K", f"{t_cpu*1e3:.1f}ms")
    _row("fig4", "modeled_speedup_naive", f"{t_cpu/t_tpu_naive:.0f}x", "vs 1-core XLA-CPU")
    _row("fig4", "modeled_speedup_tiled", f"{t_cpu/t_tpu_tiled:.0f}x", "vs 1-core XLA-CPU")
    _row("fig4", "paper_speedup_naive", "270x", "paper: i7-4700MQ 1-thread vs GT 730M")
    _row("fig4", "paper_speedup_tiled", "400x", "paper")


def fig5_double_precision(quick=False):
    """Paper Fig. 5: f64 performance.  Measured f64/f32 ratio on CPU; on the
    TPU target f64 has no native unit (the paper's f64 cliff is absolute)."""
    import time

    p = AIDWParams(k=10, area=1.0)
    m = 2 * K if quick else 8 * K
    times = {}
    # in-process under enable_x64: a JAX child started from this (JAX-holding)
    # parent could not reach an accelerator the parent already holds
    with jax.enable_x64():
        for dt in (np.float32, np.float64):
            dx, dy, dz = uniform_points(m, seed=0, dtype=dt)
            qx, qy, _ = uniform_points(m, seed=1, dtype=dt)
            args = list(map(jnp.asarray, (dx, dy, dz, qx, qy)))
            f = lambda: aidw_interpolate(*args, p, area=1.0)
            jax.block_until_ready(f())
            t0 = time.perf_counter()
            jax.block_until_ready(f())
            name = np.dtype(dt).name
            times[name] = (time.perf_counter() - t0) * 1e3
            _row("fig5", f"measured_cpu_{name}_{m//K}K", f"{times[name]:.1f}ms")
    _row("fig5", "measured_f64_over_f32", f"{times['float64']/times['float32']:.2f}x", "CPU (SIMD width halves)")
    _row("fig5", "paper_f64_speedup", "~8x vs CPU", "GT 730M f64 at 1/24 rate")
    _row("fig5", "tpu_f64", "no native f64", "use Kahan-f32 instead (EXPERIMENTS §Accuracy)")


def fig6_layouts(quick=False):
    """Paper Fig. 6: SoA vs AoaS.  Analytic: AoaS moves 16/12 = 1.33x the
    HBM bytes.  Measured on CPU: strided struct loads vs contiguous."""
    p = AIDWParams(k=10, area=1.0)
    m = 4 * K if quick else 16 * K
    dx, dy, dz, qx, qy = _points(m)
    t_soa = time_fn(lambda: aidw_interpolate(dx, dy, dz, qx, qy, p, area=1.0))
    data_aoas = soa_to_aoas(dx, dy, dz)

    @jax.jit
    def aoas_path(a, qx, qy):
        return aidw_interpolate(a[:, 0], a[:, 1], a[:, 2], qx, qy, p, area=1.0)

    t_aoas = time_fn(lambda: aoas_path(data_aoas, qx, qy))
    _row("fig6", f"measured_cpu_soa_{m//K}K", f"{t_soa*1e3:.1f}ms")
    _row("fig6", f"measured_cpu_aoas_{m//K}K", f"{t_aoas*1e3:.1f}ms")
    _row("fig6", "analytic_tpu_byte_ratio", "1.33x", "16B vs 12B per data point per sweep")
    _row("fig6", "paper_soa_vs_aoas", "1.015x", "paper: SoA slightly faster")


def fig7_tiled_vs_naive(quick=False):
    """Paper Fig. 7: tiled vs naive.  Measured on CPU: cache-locality effect
    of tiling (full-matrix reference vs tiled interpolate).  Analytic on
    TPU: the naive kernel's VMEM working set crosses the 16 MiB cliff."""
    from repro.core.aidw import aidw_reference

    p = AIDWParams(k=10, area=1.0)
    m = 2 * K if quick else 8 * K
    dx, dy, dz, qx, qy = _points(m)
    ref = jax.jit(lambda *a: aidw_reference(*a, p, area=1.0))
    t_naive = time_fn(lambda: ref(dx, dy, dz, qx, qy))
    t_tiled = time_fn(lambda: aidw_interpolate(dx, dy, dz, qx, qy, p, area=1.0))
    _row("fig7", f"measured_cpu_fullmatrix_{m//K}K", f"{t_naive*1e3:.1f}ms", "naive analogue: O(n*m) matrix")
    _row("fig7", f"measured_cpu_tiled_{m//K}K", f"{t_tiled*1e3:.1f}ms")
    note = ("CPU cache locality favours tiling" if t_naive > t_tiled
            else "at this size the full matrix fits cache; tiled pays scan overhead")
    _row("fig7", "measured_naive_over_tiled", f"{t_naive/t_tiled:.2f}x", note)
    for name, m_ in PAPER_SIZES.items():
        fits = naive_vmem_bytes(m_) <= VMEM_BYTES
        _row("fig7", f"tpu_naive_vmem_{name}", f"{naive_vmem_bytes(m_)/2**20:.1f}MiB",
             "fits" if fits else "exceeds 16MiB VMEM -> naive unschedulable on TPU")
    _row("fig7", "paper_tiled_speedup", "1.3x", "paper: shared-memory tiling")


def grid_plan_reuse(quick=False, smoke=False, json_path=None):
    """Plan/execute engine (DESIGN.md §6): build-once serve-many amortisation
    for ``impl="grid"``, the serving shape the engine exists for.

    Protocol (everything recorded, nothing hidden): a fresh plan is built
    (``build_plan`` — grid + CSR snapshot + required_radius table + static
    capacity), the FIRST tile-local query batch executes through the jitted
    engine (this pays the one-time trace+compile that the static-shape
    refactor makes cacheable), then further same-shape batches hit the jit
    cache.  ``reuse_speedup`` = (build + first batch) / steady batch — what a
    per-request rebuild would cost vs an amortised request.  Also exercises
    the eager (unjitted) execute and asserts eager/jit/oracle parity, and
    records the plan-time autotune decisions (candidate ``block_d``,
    capacity, rebuilds) for the ROADMAP occupancy-autotuning item.
    """
    import time as _time

    from repro.core.aidw import aidw_reference
    from repro.engine import build_plan, execute
    from repro.engine.execute import _execute

    p = AIDWParams(k=10, area=1.0)
    # --quick shrinks sizes AND (like --smoke) skips the json write, so the
    # committed full-run numbers survive the dev loop
    m = 2048 if smoke else (4 * K if quick else 20 * K)
    nq = 128 if smoke else 256
    write_json = json_path and not (smoke or quick)
    dxn, dyn, dzn = uniform_points(m, seed=0)
    dx, dy, dz = map(jnp.asarray, (dxn, dyn, dzn))
    rng = np.random.default_rng(7)

    def tile_batch():
        # a map-tile-shaped serving request: queries local to a 0.1^2 patch
        corner = rng.random(2) * 0.9
        q = (corner + 0.1 * rng.random((nq, 2))).astype(np.float32)
        return jnp.asarray(q[:, 0]), jnp.asarray(q[:, 1])

    t0 = _time.perf_counter()
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid")
    t_build = _time.perf_counter() - t0

    qx1, qy1 = tile_batch()
    t0 = _time.perf_counter()
    z1, a1 = jax.block_until_ready(execute(plan, qx1, qy1))
    t_first = _time.perf_counter() - t0  # includes the one-time trace+compile

    t_steady = min(
        time_fn(lambda q=tile_batch(): execute(plan, *q), warmup=0, repeats=1)
        for _ in range(3)
    )

    # parity guard: eager execute, jitted execute and the oracle must agree
    z_e, _, stats = _execute(plan, qx1, qy1)
    z_ref, _ = aidw_reference(dx, dy, dz, qx1, qy1, p, area=1.0)
    err_jit = float(jnp.max(jnp.abs(z1 - z_ref)))
    err_eager = float(jnp.max(jnp.abs(z_e - z_ref)))
    assert err_jit < 1e-3 and err_eager < 1e-3, (err_jit, err_eager)

    ratio = (t_build + t_first) / t_steady
    _row("plan", f"build_{m//K}K", f"{t_build*1e3:.0f}ms",
         f"grid {plan.grid.gx}x{plan.grid.gy} rebuilds={plan.grid_rebuilds}")
    _row("plan", f"first_batch_{nq}q", f"{t_first*1e3:.0f}ms", "includes trace+compile")
    _row("plan", f"steady_batch_{nq}q", f"{t_steady*1e3:.0f}ms", "jit cache hit")
    _row("plan", "reuse_speedup", f"{ratio:.1f}x", "(build+first)/steady")
    _row("plan", "autotuned_block_d", str(plan.cand_block_d),
         f"cand_capacity={plan.cand_capacity} "
         f"overflow_queries={int(stats['overflow_queries'])}")
    _row("plan", "parity_max_abs_err", f"{max(err_jit, err_eager):.2e}", "eager+jit vs oracle")

    if write_json:
        os.makedirs(os.path.dirname(json_path), exist_ok=True)
        blob = {}
        if os.path.exists(json_path):
            with open(json_path) as f:
                blob = json.load(f)
        blob["plan_reuse"] = {
            "impl": "grid", "m": m, "nq_per_batch": nq, "k": p.k,
            "grid": f"{plan.grid.gx}x{plan.grid.gy}", "cap": plan.grid.cap,
            "autotuned_block_d": plan.cand_block_d,
            "cand_capacity": plan.cand_capacity,
            "grid_rebuilds": plan.grid_rebuilds,
            # PR-4 blend: per-query diagnostic replaces the old whole-batch
            # fallback_used flag (grid_fallback now means ALL queries overflowed)
            "overflow_queries": int(stats["overflow_queries"]),
            "build_ms": round(t_build * 1e3, 1),
            "first_batch_ms_incl_compile": round(t_first * 1e3, 1),
            "steady_batch_ms": round(t_steady * 1e3, 1),
            "reuse_speedup": round(ratio, 1),
            "max_abs_err_vs_oracle": max(err_jit, err_eager),
            "protocol": "(plan build + first batch incl jit compile) / steady "
                        "same-shape batch; tile-local serving batches",
        }
        with open(json_path, "w") as f:
            json.dump(blob, f, indent=2)
        _row("plan", "json", json_path)


def grid_phase1(quick=False, smoke=False, json_path=None):
    """Tentpole sweep: grid-partitioned vs brute-force Phase 1 (r_obs) on
    uniform and clustered data — the adaptive case the paper targets.  The
    grid row times build_grid + the ring search, so the speedup is end-to-end
    honest; JSON results land in benchmarks/results/grid_knn.json."""
    k = 10
    sizes = [2 * K] if smoke else ([20 * K] if quick else [20 * K, 100 * K])
    records = []
    for dist_name, gen in (("uniform", uniform_points), ("clustered", clustered_points)):
        for m in sizes:
            nq = max(m // 5, 1024)
            dxn, dyn, _ = gen(m, seed=0)
            qxn, qyn, _ = uniform_points(nq, seed=1)
            dx, dy, qx, qy = map(jnp.asarray, (dxn, dyn, qxn, qyn))
            # one warm+parity eval, one timed eval — the 100K brute baseline
            # is minutes per eval, so no repeats
            r_brute = jax.block_until_ready(brute_r_obs(dx, dy, qx, qy, k))
            t_brute = time_fn(lambda: brute_r_obs(dx, dy, qx, qy, k), warmup=0, repeats=1)
            grid = build_grid(dx, dy)
            r_grid = jax.block_until_ready(grid_r_obs(grid, qx, qy, k))

            def grid_pass():
                g = build_grid(dx, dy)
                return grid_r_obs(g, qx, qy, k)

            t_grid = time_fn(grid_pass, warmup=0, repeats=1)
            # parity guard: a benchmark of a wrong answer is worthless
            err = float(jnp.max(jnp.abs(r_grid - r_brute)))
            tag = f"{dist_name}_{m//K}K"
            _row("grid", f"brute_phase1_{tag}", f"{t_brute*1e3:.1f}ms", f"m={m} nq={nq} k={k}")
            _row("grid", f"grid_phase1_{tag}", f"{t_grid*1e3:.1f}ms",
                 f"build+search, {grid.gx}x{grid.gy} cells cap={grid.cap}")
            _row("grid", f"grid_speedup_{tag}", f"{t_brute/t_grid:.1f}x", f"max|dr_obs|={err:.2e}")
            records.append({
                "distribution": dist_name, "m": m, "nq": nq, "k": k,
                "grid": f"{grid.gx}x{grid.gy}", "cap": grid.cap,
                "brute_phase1_ms": round(t_brute * 1e3, 1),
                "grid_phase1_ms": round(t_grid * 1e3, 1),
                "speedup": round(t_brute / t_grid, 1),
                "max_abs_r_obs_err": err,
            })
    if json_path and not (smoke or quick):
        # full runs only: a --quick sweep would silently replace the
        # committed 100K full-sweep numbers with 20K quick rows
        os.makedirs(os.path.dirname(json_path), exist_ok=True)
        blob = {}
        if os.path.exists(json_path):
            with open(json_path) as f:
                blob = json.load(f)  # merge: keep the plan_reuse section
        blob.update(backend=jax.default_backend(), results=records)
        with open(json_path, "w") as f:
            json.dump(blob, f, indent=2)
        _row("grid", "json", json_path)


def grid_blend(quick=False, smoke=False, json_path=None):
    """Sparsity-skipping Phase 1 + per-block overflow blend (--only blend).

    Three serving-shaped scenarios against the grid plan, each parity-checked
    (eager AND jitted execute vs the exact chunked ring-search oracle):

      uniform   — full-bbox batch on uniform data: prefetch vs dense
                  Phase-1 pipelines (prefetch walks each block's CSR row
                  runs in place; dense gathers them into capacity rows).
      clustered — tile-local sparse batch on clustered data: the skip
                  fraction is highest here (most blocks need few tiles).
      seam      — mostly tile-local batch plus a small full-diagonal slice
                  (straddles Morton seams, leaves the bbox, crosses empty
                  regions): a couple of blocks overflow the static capacity.
                  PR-2's whole-batch ``lax.cond`` would ring-search ALL nq
                  queries (``ring_full_ms`` is a *lower bound* on its batch
                  latency — Phase 2 comes on top); the blend ring-searches
                  only the overflowed ones (``ring_masked_ms``) and keeps
                  the kernel result everywhere else, so ``blend_exec_ms``
                  (the full batch, Phase 2 included) undercuts it.

    CPU-interpret caveat (recorded in the json): Pallas kernels here run in
    interpret mode, which makes kernel arms look *slower* relative to the
    pure-jnp ring search than they are on TPU — the blend/skip wins below
    are therefore conservative for the compiled target.
    """
    from repro.core.grid import grid_r_obs as _ring
    from repro.engine import build_plan, execute, execute_with_stats
    from repro.engine.execute import _execute

    p = AIDWParams(k=10, area=1.0)
    m = 2048 if smoke else (4 * K if quick else 20 * K)
    nq = 256 if smoke else 4096
    k = p.k
    write_json = json_path and not (smoke or quick)
    rng = np.random.default_rng(3)
    results = {}

    def timed(f):
        return time_fn(f, warmup=1, repeats=1)  # 1 warm (compile) + 1 timed eval

    def parity(plan, qx, qy, dx, dy, dz, tag):
        # eager + jitted execute vs the exact chunked ring-search oracle
        z_jit, a_jit = execute(plan, qx, qy)
        z_e, a_e, _ = _execute(plan, qx, qy)
        z_ref, a_ref = aidw_interpolate(dx, dy, dz, qx, qy, p, area=1.0,
                                        knn="grid", grid=plan.grid)
        err = max(float(jnp.max(jnp.abs(z_jit - z_ref))), float(jnp.max(jnp.abs(z_e - z_ref))),
                  float(jnp.max(jnp.abs(a_jit - a_ref))), float(jnp.max(jnp.abs(a_e - a_ref))))
        assert err < 1e-3, (tag, err)
        return err

    # ---- uniform + clustered: dense vs prefetch (row-run) pipelines
    for dist, gen in (("uniform", uniform_points), ("clustered", clustered_points)):
        dxn, dyn, dzn = gen(m, seed=0)
        dx, dy, dz = map(jnp.asarray, (dxn, dyn, dzn))
        if dist == "uniform":
            qn = uniform_points(nq, seed=1)
            qx, qy = jnp.asarray(qn[0]), jnp.asarray(qn[1])
        else:  # tile-local sparse batch near the data clusters
            pick = rng.integers(0, m, nq)
            qq = (np.stack([dxn, dyn], 1)[pick] + rng.normal(0, 0.01, (nq, 2))).astype(np.float32)
            qx, qy = jnp.asarray(qq[:, 0]), jnp.asarray(qq[:, 1])
        plans = {pipe: build_plan(dx, dy, dz, params=p, area=1.0, impl="grid", pipeline=pipe)
                 for pipe in ("prefetch", "dense")}
        err = parity(plans["prefetch"], qx, qy, dx, dy, dz, dist)
        _, _, stats = execute_with_stats(plans["prefetch"], qx, qy)
        t_pre = timed(lambda: execute(plans["prefetch"], qx, qy))
        t_den = timed(lambda: execute(plans["dense"], qx, qy))
        skip = float(stats["skipped_tile_fraction"])
        _row("blend", f"{dist}_dense_exec", f"{t_den*1e3:.0f}ms", f"m={m} nq={nq}")
        _row("blend", f"{dist}_prefetch_exec", f"{t_pre*1e3:.0f}ms",
             f"skipped_tile_fraction={skip:.2f}")
        _row("blend", f"{dist}_skip_speedup", f"{t_den/t_pre:.2f}x", f"parity_err={err:.1e}")
        results[dist] = {
            "dense_exec_ms": round(t_den * 1e3, 1),
            "prefetch_exec_ms": round(t_pre * 1e3, 1),
            "skipped_tile_fraction": round(skip, 3),
            "overflow_queries": int(stats["overflow_queries"]),
            "parity_max_abs_err": err,
        }

    # ---- seam: the overflow worst case, cond-fallback vs per-block blend
    dxn, dyn, dzn = clustered_points(m, seed=0)
    dx, dy, dz = map(jnp.asarray, (dxn, dyn, dzn))
    n_far = max(nq // 16, 16)
    pick = rng.integers(0, m, nq - n_far)
    near = (np.stack([dxn, dyn], 1)[pick] + rng.normal(0, 0.01, (nq - n_far, 2))).astype(np.float32)
    t = np.linspace(-0.2, 1.2, n_far).astype(np.float32)
    q = np.concatenate([near, np.stack([t, t], 1)])
    rng.shuffle(q)
    qx, qy = jnp.asarray(q[:, 0]), jnp.asarray(q[:, 1])
    plan = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid")
    plan0 = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid", seam_level=0)
    err = parity(plan, qx, qy, dx, dy, dz, "seam")
    _, _, stats = execute_with_stats(plan, qx, qy)
    _, _, stats0 = execute_with_stats(plan0, qx, qy)
    mask = stats["overflow_query_mask"]
    t_blend = timed(lambda: execute(plan, qx, qy))
    t_full = timed(lambda: _ring(plan.grid, qx, qy, k))
    t_masked = timed(lambda: _ring(plan.grid, qx, qy, k, mask))
    ovf = int(stats["overflow_queries"])
    _row("blend", "seam_overflow_queries", str(ovf),
         f"of {nq}; seam_level={plan.seam_level} (vs {int(stats0['overflow_queries'])} unsplit)")
    _row("blend", "seam_blend_exec", f"{t_blend*1e3:.0f}ms", "full batch incl. Phase 2")
    _row("blend", "seam_ring_full", f"{t_full*1e3:.0f}ms",
         "PR-2 cond arm: ring search for ALL queries (lower bound, no Phase 2)")
    _row("blend", "seam_ring_masked", f"{t_masked*1e3:.0f}ms", "blend arm: overflowed queries only")
    _row("blend", "seam_worst_case_speedup", f"{t_full/t_blend:.1f}x",
         "whole-batch ring arm vs full blended batch"
         + ("" if t_blend < t_full else " [WARNING: blend did not undercut it]"))
    results["seam"] = {
        "overflow_queries": ovf,
        "overflow_blocks": int(stats["overflow_blocks"]),
        "overflow_queries_seam_level_0": int(stats0["overflow_queries"]),
        "seam_level": plan.seam_level,
        "blend_exec_ms": round(t_blend * 1e3, 1),
        "ring_full_ms_pr2_lower_bound": round(t_full * 1e3, 1),
        "ring_masked_ms": round(t_masked * 1e3, 1),
        "skipped_tile_fraction": round(float(stats["skipped_tile_fraction"]), 3),
        "parity_max_abs_err": err,
    }

    if write_json:
        os.makedirs(os.path.dirname(json_path), exist_ok=True)
        blob = {
            "backend": jax.default_backend(),
            "mode": "Pallas kernels in interpret mode on CPU (kernel arms are "
                    "emulated — slower relative to the pure-jnp ring search than "
                    "on TPU, so blend/skip speedups are conservative)",
            "m": m, "nq": nq, "k": k,
            "scenarios": results,
            "protocol": "jitted execute, steady state (1 warm + 1 timed eval); "
                        "ring_full is PR-2's whole-batch lax.cond exact arm (its "
                        "batch latency lower bound); blend_exec is the shipped "
                        "path end to end; dense vs prefetch differ only in the "
                        "Phase-1 pipeline (gathered rows vs CSR row runs read in place).",
        }
        with open(json_path, "w") as f:
            json.dump(blob, f, indent=2)
        _row("blend", "json", json_path)


def farfield_phase2(quick=False, smoke=False, json_path=None):
    """Far-field approximated Phase 2 vs the exact full sweep (--only farfield).

    The ROADMAP O(n*m) wall: Phase 2 weights ALL m points per query in every
    exact impl.  ``build_plan(phase2="farfield")`` sweeps exact weights only
    over each block's near rectangle and folds one aggregate term per far
    cell (DESIGN.md §7).  Protocol: uniform m-point dataset, a tile-local
    serving batch (the shape the capacity model sizes for); the two Phase-2
    paths are timed IN ISOLATION on identical inputs (same Morton-sorted
    padded queries, same exact Phase-1 alpha — so the ratio is purely the
    Phase-2 algorithm change), plus end-to-end execute times for context.
    Accuracy is measured against the Kahan oracle (farfield_error_report)
    and asserted within the plan's proved worst-case bound; requested rtol,
    proved bound and measured error are all recorded — single-level
    aggregates prove weak worst-case bounds (the plan warns), measured
    error runs orders of magnitude below them.

    CPU-interpret caveat (as grid_blend): kernel arms are emulated; the
    speedup is a step-count effect and is conservative vs compiled TPU.
    """
    import functools as _ft
    import warnings as _warnings

    from repro.core.accuracy import farfield_error_report
    from repro.core.grid import cell_of, morton_ids
    from repro.core.layouts import pad_tail
    from repro.engine import build_plan, execute, execute_with_stats
    from repro.engine.execute import _phase2_farfield
    from repro.kernels.aidw_grid import phase2_weights_full

    p = AIDWParams(k=10, area=1.0)
    m = 2048 if smoke else (20 * K if quick else 100 * K)
    nq = 256 if smoke else 4096
    rtol = 1e-3
    write_json = json_path and not (smoke or quick)
    rng = np.random.default_rng(11)
    dxn, dyn, dzn = uniform_points(m, seed=0)
    dx, dy, dz = map(jnp.asarray, (dxn, dyn, dzn))
    corner = rng.random(2) * 0.85
    q = (corner + 0.12 * rng.random((nq, 2))).astype(np.float32)
    qx, qy = jnp.asarray(q[:, 0]), jnp.asarray(q[:, 1])

    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore")  # unprovable-rtol warning: recorded below
        plan_ff = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                             phase2="farfield", farfield_rtol=rtol, block_q=64)
    # the chooser meets the target exactly when its proved bound does
    rtol_provable = plan_ff.farfield_bound <= rtol
    plan_ex = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid", block_q=64)

    def timed(f):
        return time_fn(f, warmup=1, repeats=1)

    # identical Phase-2 inputs for both arms: sorted/padded batch + exact alpha
    cx, cy = cell_of(plan_ff.grid, qx, qy)
    order = jnp.argsort(morton_ids(cx, cy), stable=True)
    n_pad = (-nq) % plan_ff.block_q
    qx_s = pad_tail(qx[order], n_pad)
    qy_s = pad_tail(qy[order], n_pad)
    _, alpha = execute(plan_ex, qx, qy)
    alpha_s = pad_tail(alpha[order], n_pad)[:, None]

    p2_ff = jax.jit(lambda pl_, a, b, c: _phase2_farfield(pl_, a, b, c)[0])
    dxp, dyp, dzp = plan_ex.data
    p2_ex = jax.jit(_ft.partial(
        phase2_weights_full, eps=p.exact_hit_eps, block_q=plan_ex.block_q,
        block_d=plan_ex.block_d, interpret=plan_ex.interpret))
    t_p2_ex = timed(lambda: p2_ex(qx_s, qy_s, alpha_s, dxp, dyp, dzp))
    t_p2_ff = timed(lambda: p2_ff(plan_ff, qx_s, qy_s, alpha_s))
    t_e2e_ex = timed(lambda: execute(plan_ex, qx, qy))
    t_e2e_ff = timed(lambda: execute(plan_ff, qx, qy))

    _, _, stats = execute_with_stats(plan_ff, qx, qy)
    if int(stats["p2_overflow_queries"]) > 0:
        _row("farfield", "WARNING", "near-capacity overflow",
             "batch partly fell back to the exact sweep")
    rep = farfield_error_report(plan_ff, qx, qy)
    assert rep["within_bound"], rep  # a benchmark of a broken budget is worthless
    # the smoke config proves no useful bound (inf), which would make the
    # assert above vacuous in CI — also gate on an empirical sanity ceiling
    # so a far-kernel regression fails the bench-smoke job too
    assert rep["max_rel_err"] <= 10 * rtol, rep
    speedup = t_p2_ex / t_p2_ff
    tag = f"{m//K}K"
    _row("farfield", f"phase2_exact_{tag}", f"{t_p2_ex*1e3:.0f}ms",
         f"nq={nq} full {m}-point sweep")
    _row("farfield", f"phase2_farfield_{tag}", f"{t_p2_ff*1e3:.0f}ms",
         f"radius={plan_ff.farfield_radius} near_mean={float(stats['near_points_mean']):.0f} "
         f"far_cells_mean={float(stats['far_cells_mean']):.0f}")
    _row("farfield", "phase2_speedup", f"{speedup:.1f}x",
         "isolated Phase 2, identical inputs"
         + ("" if speedup >= 3 or smoke or quick else " [WARNING: below 3x target]"))
    _row("farfield", "e2e_exact_vs_farfield",
         f"{t_e2e_ex*1e3:.0f}ms vs {t_e2e_ff*1e3:.0f}ms", "execute() incl. Phase 1")
    _row("farfield", "measured_max_rel_err", f"{rep['max_rel_err']:.2e}",
         f"requested rtol={rtol:g} proved bound={plan_ff.farfield_bound:.3g}")

    if write_json:
        os.makedirs(os.path.dirname(json_path), exist_ok=True)
        blob = {
            "backend": jax.default_backend(),
            "mode": "Pallas kernels in interpret mode on CPU (step-count "
                    "effect; conservative vs compiled TPU)",
            "m": m, "nq": nq, "k": p.k, "block_q": plan_ff.block_q,
            "grid": f"{plan_ff.grid.gx}x{plan_ff.grid.gy}",
            "farfield_rtol_requested": rtol,
            "farfield_rtol_provable_at_profitable_radius": rtol_provable,
            "farfield_radius": plan_ff.farfield_radius,
            "farfield_bound_proved": plan_ff.farfield_bound,
            "measured_max_rel_err": rep["max_rel_err"],
            "measured_rms_rel_err": rep["rms_rel_err"],
            "near_points_mean": float(stats["near_points_mean"]),
            "far_cells_mean": float(stats["far_cells_mean"]),
            "p2_capacity": plan_ff.p2_capacity,
            "phase2_exact_ms": round(t_p2_ex * 1e3, 1),
            "phase2_farfield_ms": round(t_p2_ff * 1e3, 1),
            "phase2_speedup": round(speedup, 2),
            "e2e_exact_ms": round(t_e2e_ex * 1e3, 1),
            "e2e_farfield_ms": round(t_e2e_ff * 1e3, 1),
            "protocol": "isolated Phase-2 arms jitted and timed on identical "
                        "Morton-sorted padded queries + exact Phase-1 alpha "
                        "(1 warm + 1 timed eval); error vs Kahan oracle on "
                        "the same tile-local serving batch, asserted within "
                        "the plan's proved worst-case bound",
        }
        with open(json_path, "w") as f:
            json.dump(blob, f, indent=2)
        _row("farfield", "json", json_path)


def quadtree_phase2(quick=False, smoke=False, json_path=None):
    """Multi-level quadtree Phase 2 vs the single-level far field and the
    exact sweep (--only quadtree).

    Two protocols (both recorded in the json):

    head-to-head at m=100K — sub-cell-clustered site data (the plan-chosen
    configuration where the dipole bound PROVES rtol=1e-3; the single-level
    model cannot prove it at any profitable radius), tile-local serving
    batch (the shape the capacity model sizes for — a full-bbox Morton
    batch straddles seams and overflows the near capacity).  The Phase-2
    arms (exact full sweep / single-level farfield at its own radius AND
    at the quadtree's radius / quadtree) are jitted and timed IN ISOLATION
    on identical Morton-sorted padded queries and identical exact Phase-1
    alpha.  The matched-radius pair is the algorithmic comparison (same
    exact near field, far field = all cells vs closed nodes); the
    own-radius pair is the shipped-plan comparison.  Eager vs jitted
    quadtree execute parity is asserted, and measured error vs the Kahan
    oracle is asserted within the proved bound.

    m-sweep 10K -> 1M — uniform data at a PINNED radius (provability not
    required here; the claim under test is WORK scaling, and the auto
    chooser's profitability-cap radius growing with m would conflate
    radius policy with level scaling), recording ``far_cells_mean`` (far
    TERMS per query: closed nodes for the quadtree; the single-level
    arm's count is ~n_cells ~ O(m)).  The quadtree's far-term count must
    grow sub-linearly (~O(log m)) while cells grow ~linearly — asserted
    as far-term growth <= sqrt(cells growth) across the sweep.

    CPU-interpret caveat (as farfield_phase2): kernel arms are emulated;
    speedups are step-count effects and conservative vs compiled TPU.
    """
    import functools as _ft
    import warnings as _warnings

    from repro.core.accuracy import farfield_error_report
    from repro.core.grid import cell_of, morton_ids
    from repro.core.layouts import pad_tail
    from repro.engine import build_plan, execute, execute_with_stats
    from repro.engine.execute import _execute, _phase2_farfield, _phase2_quadtree
    from repro.kernels.aidw_grid import phase2_weights_full

    p = AIDWParams(k=10, area=1.0)
    rtol = 1e-3
    write_json = json_path and not (smoke or quick)

    def timed(f):
        return time_fn(f, warmup=1, repeats=1)

    def site_points(m, n_side, sigma, seed=5):
        # z varies INSIDE each tight spatial cluster: first-order poison for
        # the single-level bound, second-order (harmless) for the dipole one
        rng = np.random.default_rng(seed)
        sites = (np.stack(np.meshgrid(np.arange(n_side), np.arange(n_side)), -1)
                 .reshape(-1, 2) + 0.5) / n_side
        pts = (sites[rng.integers(0, n_side * n_side, m)]
               + rng.normal(0, sigma, (m, 2)))
        pts = np.clip(pts, 0.0, 1.0).astype(np.float32)
        x, y = pts[:, 0], pts[:, 1]
        z = (np.sin(6 * x) * np.cos(6 * y) + 2.0
             + 0.3 * rng.standard_normal(m)).astype(np.float32)
        return x, y, z

    # ---- head-to-head at the provable configuration
    if smoke:
        m, gx, n_side, sigma, nq = 2048, 12, 12, 1e-4, 256
    elif quick:
        m, gx, n_side, sigma, nq = 20 * K, 32, 16, 5e-5, 1024
    else:
        m, gx, n_side, sigma, nq = 100 * K, 64, 16, 2e-5, 4096
    dxn, dyn, dzn = site_points(m, n_side, sigma)
    dx, dy, dz = map(jnp.asarray, (dxn, dyn, dzn))
    rng = np.random.default_rng(11)
    corner = rng.random(2) * 0.85
    q = (corner + 0.12 * rng.random((nq, 2))).astype(np.float32)
    qx, qy = jnp.asarray(q[:, 0]), jnp.asarray(q[:, 1])
    grid = build_grid(dx, dy, dz, gx=gx, gy=gx)
    qocc = max(nq / (0.12 * gx) ** 2, 0.5)  # tile-local serving density
    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore")
        plan_qt = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                             grid=grid, phase2="quadtree", farfield_rtol=rtol,
                             block_q=64, query_occupancy=qocc)
        plan_ff = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                             grid=grid, phase2="farfield", farfield_rtol=rtol,
                             block_q=64, query_occupancy=qocc)
        plan_ffm = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                              grid=grid, phase2="farfield", block_q=64,
                              farfield_radius=plan_qt.farfield_radius,
                              query_occupancy=qocc)
        plan_ex = build_plan(dx, dy, dz, params=p, area=1.0, impl="grid",
                             grid=grid, block_q=64, query_occupancy=qocc)
    qt_provable = plan_qt.farfield_bound <= rtol
    if not smoke:
        assert qt_provable, ("head-to-head config must be provable",
                             plan_qt.farfield_bound)

    # identical Phase-2 inputs for all three arms
    cx, cy = cell_of(grid, qx, qy)
    order = jnp.argsort(morton_ids(cx, cy), stable=True)
    n_pad = (-nq) % plan_qt.block_q
    qx_s = pad_tail(qx[order], n_pad)
    qy_s = pad_tail(qy[order], n_pad)
    _, alpha = execute(plan_ex, qx, qy)
    alpha_s = pad_tail(alpha[order], n_pad)[:, None]

    dxp, dyp, dzp = plan_ex.data
    p2_ex = jax.jit(_ft.partial(
        phase2_weights_full, eps=p.exact_hit_eps, block_q=plan_ex.block_q,
        block_d=plan_ex.block_d, interpret=plan_ex.interpret))
    p2_ff = jax.jit(lambda pl_, a, b, c: _phase2_farfield(pl_, a, b, c)[0])
    p2_qt = jax.jit(lambda pl_, a, b, c: _phase2_quadtree(pl_, a, b, c)[0])
    t_ex = timed(lambda: p2_ex(qx_s, qy_s, alpha_s, dxp, dyp, dzp))
    t_ff = timed(lambda: p2_ff(plan_ff, qx_s, qy_s, alpha_s))
    t_ffm = timed(lambda: p2_ff(plan_ffm, qx_s, qy_s, alpha_s))
    t_qt = timed(lambda: p2_qt(plan_qt, qx_s, qy_s, alpha_s))

    # eager/jit parity on the shipped end-to-end path
    z_jit, a_jit = execute(plan_qt, qx, qy)
    z_eag, a_eag, stats = _execute(plan_qt, qx, qy)
    par = max(float(jnp.max(jnp.abs(z_jit - z_eag))),
              float(jnp.max(jnp.abs(a_jit - a_eag))))
    assert par < 1e-5, ("eager/jit parity", par)
    ovf = int(stats["p2_overflow_queries"])
    if ovf > 0:
        _row("quadtree", "WARNING", "near-capacity overflow",
             f"{ovf} queries fell back to the exact sweep")
    assert smoke or quick or ovf == 0, (
        "committed head-to-head must be a clean fast-path batch", ovf)
    _, _, stats_ff = execute_with_stats(plan_ff, qx, qy)
    rep = farfield_error_report(plan_qt, qx, qy)
    assert rep["within_bound"], rep
    assert rep["max_rel_err"] <= 10 * rtol, rep  # empirical ceiling for smoke

    tag = f"{m//K}K"
    vs_ff = t_ff / t_qt
    vs_ffm = t_ffm / t_qt
    _row("quadtree", f"phase2_exact_{tag}", f"{t_ex*1e3:.0f}ms",
         f"nq={nq} full {m}-point sweep")
    _row("quadtree", f"phase2_farfield_{tag}", f"{t_ff*1e3:.0f}ms",
         f"own radius={plan_ff.farfield_radius} "
         f"far_cells_mean={float(stats_ff['far_cells_mean']):.0f} "
         f"proved_bound={plan_ff.farfield_bound:.3g}")
    _row("quadtree", f"phase2_farfield_matched_{tag}", f"{t_ffm*1e3:.0f}ms",
         f"quadtree's radius={plan_ffm.farfield_radius} (same exact near "
         f"field) proved_bound={plan_ffm.farfield_bound:.3g}")
    _row("quadtree", f"phase2_quadtree_{tag}", f"{t_qt*1e3:.0f}ms",
         f"radius={plan_qt.farfield_radius} levels={len(plan_qt.qt_levels)} "
         f"far_nodes_mean={float(stats['far_cells_mean']):.0f} "
         f"proved_bound={plan_qt.farfield_bound:.3g}")
    _row("quadtree", "quadtree_vs_farfield_matched", f"{vs_ffm:.2f}x",
         "same near field; far field all-cells vs closed nodes"
         + ("" if vs_ffm >= 1 or smoke or quick
            else " [WARNING: quadtree slower at matched radius]"))
    _row("quadtree", "quadtree_vs_farfield_own", f"{vs_ff:.2f}x",
         f"shipped plans (farfield's own radius proves only "
         f"{plan_ff.farfield_bound:.3g})")
    _row("quadtree", "quadtree_vs_exact", f"{t_ex/t_qt:.1f}x")
    _row("quadtree", "measured_max_rel_err", f"{rep['max_rel_err']:.2e}",
         f"requested rtol={rtol:g} proved_bound={plan_qt.farfield_bound:.3g} "
         f"provable={qt_provable}")
    _row("quadtree", "opened_fraction", f"{float(stats['opened_fraction']):.3f}",
         f"cells_per_level={[round(float(c), 1) for c in stats['cells_per_level']]}")

    # ---- m-sweep: far terms per query must grow ~O(log m), not O(m)
    sweep_sizes = ([2 * K] if smoke else
                   [10 * K, 50 * K] if quick else
                   [10 * K, 100 * K, 1000 * K])
    sweep = []
    sweep_radius = 2  # pinned: the sweep measures level scaling, not policy
    for m_ in sweep_sizes:
        dxn, dyn, dzn = uniform_points(m_, seed=0)
        dxs, dys, dzs = map(jnp.asarray, (dxn, dyn, dzn))
        nq_s = 256
        qs_ = (rng.random(2) * 0.85
               + 0.12 * rng.random((nq_s, 2))).astype(np.float32)
        qxs, qys = jnp.asarray(qs_[:, 0]), jnp.asarray(qs_[:, 1])
        g_ = build_grid(dxs, dys, dzs)
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")  # uniform data: honest bound
            pl_ = build_plan(dxn, dyn, dzn, params=p, area=1.0, impl="grid",
                             grid=g_, phase2="quadtree", block_q=64,
                             farfield_radius=sweep_radius,
                             query_occupancy=max(nq_s / (0.12 * g_.gx) ** 2,
                                                 0.5))
        _, _, st = execute_with_stats(pl_, qxs, qys)
        rec = {
            "m": m_, "grid": f"{g_.gx}x{g_.gy}", "n_cells": g_.n_cells,
            "levels": len(pl_.qt_levels),
            "radius": pl_.farfield_radius,
            "far_terms_mean": round(float(st["far_cells_mean"]), 1),
            "near_points_mean": round(float(st["near_points_mean"]), 1),
            "opened_fraction": round(float(st["opened_fraction"]), 3),
        }
        sweep.append(rec)
        _row("quadtree", f"sweep_far_terms_{m_//K}K", str(rec["far_terms_mean"]),
             f"n_cells={rec['n_cells']} levels={rec['levels']}")
    if len(sweep) > 1:
        cells_growth = sweep[-1]["n_cells"] / sweep[0]["n_cells"]
        work_growth = (sweep[-1]["far_terms_mean"]
                       / max(sweep[0]["far_terms_mean"], 1.0))
        _row("quadtree", "sweep_sublinear",
             f"far_terms x{work_growth:.1f} while cells x{cells_growth:.1f}",
             "quadtree far work must not track cell count")
        assert work_growth <= max(np.sqrt(cells_growth), 2.0), (
            "far-term growth is not sub-linear in cell count", sweep)

    if write_json:
        os.makedirs(os.path.dirname(json_path), exist_ok=True)
        blob = {
            "backend": jax.default_backend(),
            "mode": "Pallas kernels in interpret mode on CPU (step-count "
                    "effect; conservative vs compiled TPU)",
            "head_to_head": {
                "m": m, "nq": nq, "k": p.k, "grid": f"{gx}x{gx}",
                "block_q": plan_qt.block_q,
                "data": f"{n_side}x{n_side} sites, sigma={sigma:g}, "
                        "z noise 0.3 inside clusters",
                "farfield_rtol_requested": rtol,
                "quadtree_bound_proved": plan_qt.farfield_bound,
                "quadtree_provable": qt_provable,
                "farfield_bound_proved": plan_ff.farfield_bound,
                "farfield_provable": plan_ff.farfield_bound <= rtol,
                "quadtree_radius": plan_qt.farfield_radius,
                "quadtree_levels": len(plan_qt.qt_levels),
                "farfield_radius_own": plan_ff.farfield_radius,
                "measured_max_rel_err": rep["max_rel_err"],
                "far_nodes_mean_quadtree": float(stats["far_cells_mean"]),
                "far_cells_mean_farfield": float(stats_ff["far_cells_mean"]),
                "cells_per_level": [float(c) for c in stats["cells_per_level"]],
                "opened_fraction": float(stats["opened_fraction"]),
                "p2_overflow_queries": ovf,
                "phase2_exact_ms": round(t_ex * 1e3, 1),
                "phase2_farfield_own_radius_ms": round(t_ff * 1e3, 1),
                "phase2_farfield_matched_radius_ms": round(t_ffm * 1e3, 1),
                "phase2_quadtree_ms": round(t_qt * 1e3, 1),
                "quadtree_vs_farfield_matched_speedup": round(vs_ffm, 2),
                "quadtree_vs_farfield_own_speedup": round(vs_ff, 2),
                "quadtree_vs_exact_speedup": round(t_ex / t_qt, 2),
                "eager_jit_parity_max_abs_err": par,
            },
            "m_sweep": sweep,
            "m_sweep_radius_pinned": sweep_radius,
            "protocol": "head-to-head: Phase-2 arms jitted and timed in "
                        "isolation on identical Morton-sorted padded "
                        "tile-local queries + exact Phase-1 alpha (1 warm + "
                        "1 timed eval) at the provable site-clustered "
                        "config; matched-radius farfield shares the "
                        "quadtree's exact near field so that pair isolates "
                        "the far-field algorithm; error vs Kahan oracle "
                        "asserted within the proved dipole bound; m-sweep: "
                        "uniform data, radius pinned, far terms per query "
                        "from execute_with_stats, growth asserted sub-linear "
                        "in cell count",
        }
        with open(json_path, "w") as f:
            json.dump(blob, f, indent=2)
        _row("quadtree", "json", json_path)


def reestimator_heal(quick=False, smoke=False, json_path=None):
    """Self-healing serving loop (--only reestimator): a persistent-overflow
    storm drives the capacity re-estimator's background re-plan + atomic
    hot-swap (DESIGN.md §9).  The serving config is the known-overflow shape
    of tests/serving: a dense assumed query_occupancy undersizes the static
    candidate capacity, so every out-of-bbox batch overflows and the streak
    trigger fires after PERSISTENT_OVERFLOW_BATCHES batches.

    Measured per warmup variant (the registry can execute a warmup batch on
    the new plan BEFORE publishing it, keeping the jit compile off the
    serving thread): batches-to-recovery after the trigger,
    ``overflow_queries`` before/after the swap, and the p99 serving-batch
    latency during the re-plan window vs the steady post-swap latency —
    the swap stall.  Correctness is not re-proved here (the bitwise
    recovery proof lives in tests/serving/test_reestimator.py); the bench
    asserts only that recovery happens and overflow drops to zero.

    CPU-interpret caveat (as grid_blend): absolute latencies are emulated
    kernels; the warmup-on/off CONTRAST is the portable result.
    """
    import time as _time
    import warnings as _warnings

    from repro.engine import build_plan
    from repro.engine.execute import PERSISTENT_OVERFLOW_BATCHES
    from repro.serving import CapacityReestimator, PlanRegistry

    p = AIDWParams(k=10, area=1.0, r_max=64.0)
    m, nq = 4 * K, 64
    write_json = json_path and not (smoke or quick)
    # generous: recovery-in-batches here is wall-clock (the background build
    # competes for the GIL under CPU interpret), not the bounded-batch proof
    # — that one is join()-synchronised in tests/serving/test_reestimator.py
    max_batches = 40 * PERSISTENT_OVERFLOW_BATCHES
    rng = np.random.default_rng(13)
    dxn, dyn, dzn = uniform_points(m, seed=0)
    storm = (jnp.asarray((rng.random(nq) * 6 - 3).astype(np.float32)),
             jnp.asarray((rng.random(nq) * 6 - 3).astype(np.float32)))
    clean = (jnp.asarray((0.4 + 0.05 * rng.random(nq)).astype(np.float32)),
             jnp.asarray((0.4 + 0.05 * rng.random(nq)).astype(np.float32)))

    def heal_run(warmup):
        plan = build_plan(dxn, dyn, dzn, params=p, area=1.0, impl="grid",
                          query_occupancy=64.0)
        reg = PlanRegistry()
        re_ = CapacityReestimator(reg, "bench", plan, backoff=0.01,
                                  warmup=warmup)
        cap_before = plan.cand_capacity
        re_.execute(*storm)   # compile the batch shape on the old plan
        re_.execute(*clean)   # reset the streak the compile batch started
        lat, ovf = [], []
        trigger = recovered = None
        for i in range(1, max_batches + 1):
            t0 = _time.perf_counter()
            _, _, st = re_.execute(*storm)
            n = int(st["overflow_queries"])
            lat.append((_time.perf_counter() - t0) * 1e3)
            ovf.append(n)
            if trigger is None and bool(st["persistent_overflow"]):
                trigger = i
            if trigger is not None and n == 0:
                recovered = i
                break
        re_.join()
        assert trigger is not None and recovered is not None, (trigger, ovf)
        assert ovf[trigger - 1] > 0 and ovf[recovered - 1] == 0
        steady = [time_fn(lambda: re_.execute(*storm)[0], warmup=0, repeats=1)
                  * 1e3 for _ in range(3)]
        during = lat[trigger - 1:recovered]
        return {
            "trigger_batch": trigger,
            "batches_to_recovery": recovered - trigger,
            "overflow_queries_before_swap": ovf[trigger - 1],
            "overflow_queries_after_swap": ovf[recovered - 1],
            "cand_capacity_before": cap_before,
            "cand_capacity_after": re_.plan.cand_capacity,
            "swap_stall_p99_ms": round(float(np.percentile(during, 99)), 1),
            "steady_batch_ms": round(float(np.median(steady)), 1),
            "reestimator": re_.stats(),
        }

    variants = {"warmup": storm} if smoke or quick else \
        {"no_warmup": None, "warmup": storm}
    results = {}
    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore")  # the storm's overflow warning
        for name, warmup in variants.items():
            r = heal_run(warmup)
            results[name] = r
            assert r["reestimator"]["state"] == "healthy", r
            _row("reestimator", f"{name}_batches_to_recovery",
                 str(r["batches_to_recovery"]),
                 f"trigger at batch {r['trigger_batch']} "
                 f"(threshold={PERSISTENT_OVERFLOW_BATCHES})")
            _row("reestimator", f"{name}_overflow_before_after",
                 f"{r['overflow_queries_before_swap']} -> "
                 f"{r['overflow_queries_after_swap']}",
                 f"of {nq}; cand_capacity {r['cand_capacity_before']} -> "
                 f"{r['cand_capacity_after']}")
            _row("reestimator", f"{name}_swap_stall_p99",
                 f"{r['swap_stall_p99_ms']:.0f}ms",
                 f"steady post-swap batch {r['steady_batch_ms']:.0f}ms")
    if len(results) == 2:
        _row("reestimator", "warmup_stall_reduction",
             f"{results['no_warmup']['swap_stall_p99_ms'] / max(results['warmup']['swap_stall_p99_ms'], 1e-9):.1f}x",
             "warmup-before-publish keeps the new plan's compile off the serving thread")

    if write_json:
        os.makedirs(os.path.dirname(json_path), exist_ok=True)
        blob = {
            "backend": jax.default_backend(),
            "mode": "Pallas kernels in interpret mode on CPU (absolute "
                    "latencies emulated; the warmup contrast is the "
                    "portable result)",
            "m": m, "nq_per_batch": nq, "k": p.k,
            "persistent_overflow_batches": PERSISTENT_OVERFLOW_BATCHES,
            "variants": results,
            "protocol": "out-of-bbox storm batches against a plan whose "
                        "capacity model assumed query_occupancy=64; per-batch "
                        "wall latency on the serving thread; stall window = "
                        "batches from streak trigger to first zero-overflow "
                        "batch; bitwise recovery proof lives in "
                        "tests/serving/test_reestimator.py",
        }
        with open(json_path, "w") as f:
            json.dump(blob, f, indent=2)
        _row("reestimator", "json", json_path)


def lm_rooflines(quick=False):
    """Roofline summary from the dry-run artifacts (EXPERIMENTS §Roofline)."""
    art = os.path.join(os.path.dirname(__file__), "..", "artifacts")
    cm_dir = os.path.join(art, "costmodel")
    dr_dir = os.path.join(art, "dryrun")
    if not os.path.isdir(dr_dir):
        _row("lm", "dryrun_artifacts", "missing", "run repro.launch.dryrun first")
        return
    import json
    from repro.launch.roofline import PEAK_FLOPS, HBM_BW, ICI_BW

    n = 0
    for f in sorted(os.listdir(dr_dir)):
        if not f.endswith(".json") or f.count("__") > 2:
            continue  # tagged §Perf variants are reported in EXPERIMENTS.md
        rec = json.load(open(os.path.join(dr_dir, f)))
        if rec.get("status") != "ok":
            continue
        cm_path = os.path.join(cm_dir, f)
        flops = rec.get("cost_analysis", {}).get("flops", 0)
        byts = rec.get("cost_analysis", {}).get("bytes accessed", 0)
        coll = rec.get("collectives", {}).get("total_bytes", 0)
        src = "raw"
        if os.path.exists(cm_path):
            cm = json.load(open(cm_path))
            if cm.get("status") == "ok":
                flops = cm["corrected"]["flops"]
                byts = cm["corrected"]["bytes_accessed"]
                coll = cm["corrected"]["collectives"]["total_bytes"]
                src = "loop-corrected"
        terms = {"compute": flops / PEAK_FLOPS, "memory": byts / HBM_BW, "collective": coll / ICI_BW}
        dom = max(terms, key=terms.get)
        _row("lm", f"{rec['arch']}|{rec['shape']}|{rec['mesh']}",
             f"{terms[dom]*1e3:.1f}ms", f"dominant={dom} ({src})")
        n += 1
    _row("lm", "cells_ok", str(n))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes: tiny inputs, no json writes (implies --quick)")
    ap.add_argument("--only", default=None, help="comma-separated table names")
    args = ap.parse_args()
    use_persistent_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if args.smoke:
        args.quick = True
    grid_json = os.path.join(os.path.dirname(__file__), "results", "grid_knn.json")
    blend_json = os.path.join(os.path.dirname(__file__), "results", "grid_blend.json")
    farfield_json = os.path.join(os.path.dirname(__file__), "results", "farfield.json")
    quadtree_json = os.path.join(os.path.dirname(__file__), "results", "quadtree.json")
    reestimator_json = os.path.join(os.path.dirname(__file__), "results", "reestimator.json")
    tables = {
        "table1": table1_execution_time,
        "fig4": fig4_speedups,
        "fig5": fig5_double_precision,
        "fig6": fig6_layouts,
        "fig7": fig7_tiled_vs_naive,
        "grid": functools.partial(grid_phase1, smoke=args.smoke, json_path=grid_json),
        "plan": functools.partial(grid_plan_reuse, smoke=args.smoke, json_path=grid_json),
        "blend": functools.partial(grid_blend, smoke=args.smoke, json_path=blend_json),
        "farfield": functools.partial(farfield_phase2, smoke=args.smoke, json_path=farfield_json),
        "quadtree": functools.partial(quadtree_phase2, smoke=args.smoke, json_path=quadtree_json),
        "reestimator": functools.partial(reestimator_heal, smoke=args.smoke, json_path=reestimator_json),
        "lm": lm_rooflines,
    }
    only = set(args.only.split(",")) if args.only else None
    print("table,name,value,derived")
    for name, fn in tables.items():
        if only and name not in only:
            continue
        fn(quick=args.quick)


if __name__ == "__main__":
    main()
