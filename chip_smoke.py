"""Smoke run of the AIDW main path on a TPU: one chip, or a four-chip mesh.

    python chip_smoke.py                 # one chip, m = 1,024,000 points
    python chip_smoke.py --chips 4       # the multi-chip paths on a 2x2 host

One chip drives, at the paper's largest size ("1000K", 1K = 1024, k = 10):

* the paper's kernels through ``repro.kernels.aidw``: tiled SoA and AoaS on
  one batch of 65,536 queries, and the naive kernel at the largest paper
  size the TPU compiler accepts (it holds the whole data row in VMEM);
* the engine and serving path: ``build_plan(impl="grid")`` with the exact
  and the quadtree Phase 2, on a uniform and a clustered data set, each
  plan held by a ``PlanRegistry`` and served through
  ``CapacityReestimator.execute`` for 3 batches of 65,536 queries — batches
  2 and 3 must not recompile.

Every result is checked on a 2,048-query sample against the plain-jnp
``aidw_interpolate``: exact arms within rtol 5e-4, atol 5e-5 (the
golden-fixture gate), and each quadtree plan, which approximates the far
field, within its own proved bound (``farfield_error_report``).

``--chips 4`` runs only ``sharded_queries_aidw`` and ``ring_aidw`` on a
4-device mesh, plus the one-chip ``execute`` result they are compared with.

Times printed are smoke timings on whatever the host is doing, not
benchmarks.  Any failure — no TPU (JAX falls back to the CPU when the TPU
fails to start), a plan in interpret mode, a degraded re-estimator, an
error in any phase — exits non-zero before the last line, which is
otherwise ``{"ok": true, "device": {...}}``.

``--rehearse`` runs every phase on the CPU at a tiny size with the kernels
in interpret mode and never prints the ``ok`` line:

    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --chips 4 --rehearse
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    sys.exit(f"chip_smoke.py: no repro package under {ROOT / 'src'}; run it from a checkout")
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import use_persistent_compile_cache  # noqa: E402
from repro.configs.aidw import PAPER_SIZES  # noqa: E402
from repro.core.accuracy import farfield_error_report  # noqa: E402
from repro.core.aidw import AIDWParams, aidw_interpolate  # noqa: E402
from repro.data.spatial import clustered_points, uniform_points  # noqa: E402
from repro.engine import build_plan, execute, execute_with_stats  # noqa: E402
from repro.errors import PlanDegradedWarning  # noqa: E402
from repro.kernels import aidw  # noqa: E402
from repro.kernels.aidw_naive import aidw_naive_soa  # noqa: E402
from repro.serving import CapacityReestimator, PlanRegistry, default_registry  # noqa: E402

RTOL, ATOL = 5e-4, 5e-5          # tests/test_golden.py
PARAMS = AIDWParams(k=10, area=1.0)
FULL = dict(m=PAPER_SIZES["1000K"], batch=65_536, sample=2_048,
            naive_sizes=tuple(PAPER_SIZES.values()))
TINY = dict(m=4_096, batch=512, sample=128, naive_sizes=(1_024, 2_048))
N_BATCHES = 3


def log(phase: str, **fields):
    print(phase, " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def peak_bytes(dev) -> str:
    stats = dev.memory_stats()
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


def check_device(chips: int, rehearse: bool):
    """The backend must be the one asked for: JAX falls back to the CPU when
    the TPU fails to start, and that must fail here, not run slowly."""
    devs = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devs[0].platform != want:
        raise SystemExit(f"chip_smoke.py: expected platform {want!r}, JAX found "
                         f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke.py: --chips {chips} needs {chips} devices, "
                         f"JAX found {len(devs)}")
    return devs


def check_plan(plan, rehearse: bool):
    if plan.interpret != rehearse:
        raise SystemExit(f"chip_smoke.py: a {plan.impl} plan has interpret="
                         f"{plan.interpret} (expected {rehearse})")


def compare(tag: str, z, a, z_ref, a_ref, exact: bool = True):
    """Log the max errors against the plain-jnp reference; an exact arm must
    agree within the golden tolerances.  An approximating arm (quadtree) is
    held to its own proved bound instead, by ``farfield_error_report``."""
    z, a, z_ref, a_ref = (np.asarray(v, np.float64) for v in (z, a, z_ref, a_ref))
    np.testing.assert_allclose(a, a_ref, rtol=RTOL, atol=ATOL, err_msg=f"{tag} alpha")
    if exact:
        np.testing.assert_allclose(z, z_ref, rtol=RTOL, atol=ATOL, err_msg=f"{tag} z")
    err = np.abs(z - z_ref)
    log(f"{tag}.check", max_abs_err_z=float(err.max()),
        max_rel_err_z=float((err / np.abs(z_ref)).max()),
        max_abs_err_alpha=float(np.abs(a - a_ref).max()), rtol=RTOL, atol=ATOL)


def make_data(size, seed: int):
    m, batch = size["m"], size["batch"]
    t0 = time.perf_counter()
    datasets = {
        "uniform": tuple(map(jnp.asarray, uniform_points(m, seed=seed))),
        "clustered": tuple(map(jnp.asarray, clustered_points(m, seed=seed + 1))),
    }
    batches = []
    for b in range(N_BATCHES):
        qx, qy, _ = uniform_points(batch, seed=seed + 10 + b)
        batches.append((jnp.asarray(qx), jnp.asarray(qy)))
    # one fixed 2,048-query sample position set, reused for every batch
    idx = np.sort(np.random.default_rng(seed).choice(batch, size["sample"], replace=False))
    jax.block_until_ready((datasets, batches))
    log("data", m=m, batch=batch, batches=N_BATCHES, sample=size["sample"],
        seconds=f"{time.perf_counter() - t0:.3f}")
    return datasets, batches, idx


def reference(data, qx, qy, idx):
    dx, dy, dz = data
    return aidw_interpolate(dx, dy, dz, qx[idx], qy[idx], PARAMS, area=1.0)


def largest_naive_size(sizes, batch: int, rehearse: bool) -> int:
    """Largest paper size whose naive kernel the compiler accepts.

    Sizes go up and stop at the first refusal for lack of memory: the
    kernel's working set grows with m, so every larger size is refused too.
    Only that refusal is caught; any other error propagates.
    """
    chosen = None
    for m in sizes:
        fn = lambda dx, dy, dz, qx, qy, m=m: aidw_naive_soa(  # noqa: E731
            dx, dy, dz, qx, qy, params=PARAMS, area=1.0, m_real=m,
            block_q=64, interpret=rehearse)
        row = jax.ShapeDtypeStruct((1, m), jnp.float32)
        col = jax.ShapeDtypeStruct((batch, 1), jnp.float32)
        t0 = time.perf_counter()
        try:
            jax.jit(fn).lower(row, row, row, col, col).compile()
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            log("naive.compile", m=m, accepted=False, reason=str(e).splitlines()[0][:160])
            break
        log("naive.compile", m=m, accepted=True, seconds=f"{time.perf_counter() - t0:.3f}")
        chosen = m
    if chosen is None:
        raise SystemExit("chip_smoke.py: the naive kernel compiled at no paper size")
    return chosen


def paper_phase(size, datasets, batches, idx, refs, seed, rehearse, dev):
    qx, qy = batches[0]
    data = datasets["uniform"]
    for layout in ("soa", "aoas"):
        tag = f"paper.tiled_{layout}"
        for call in ("first", "second"):
            t0 = time.perf_counter()
            z, a = jax.block_until_ready(aidw(*data, qx, qy, params=PARAMS, area=1.0,
                                              impl="tiled", layout=layout))
            log(f"{tag}.{call}_call", seconds=f"{time.perf_counter() - t0:.3f}",
                note="smoke timing, not a benchmark"
                + ("; includes plan build and compile" if call == "first" else ""))
        compare(tag, z[idx], a[idx], *refs[("uniform", 0)])
        log(f"{tag}.memory", peak_bytes_in_use=peak_bytes(dev))

    m = largest_naive_size(size["naive_sizes"], size["batch"], rehearse)
    ndata = tuple(map(jnp.asarray, uniform_points(m, seed=seed + 2)))
    t0 = time.perf_counter()
    z, a = jax.block_until_ready(aidw(*ndata, qx, qy, params=PARAMS, area=1.0, impl="naive"))
    log("paper.naive_soa", m=m, seconds=f"{time.perf_counter() - t0:.3f}",
        note="smoke timing, not a benchmark; includes plan build and compile")
    compare("paper.naive_soa", z[idx], a[idx], *reference(ndata, qx, qy, idx))
    log("paper.naive_soa.memory", peak_bytes_in_use=peak_bytes(dev))
    for plan in default_registry().plans():
        check_plan(plan, rehearse)


def serving_phase(datasets, batches, idx, refs, rehearse, dev):
    registry = PlanRegistry()
    for name, data in datasets.items():
        for arm in ("exact", "quadtree"):
            tag = f"serve.{name}.{arm}"
            t0 = time.perf_counter()
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                plan = build_plan(*data, params=PARAMS, area=1.0, impl="grid",
                                  phase2=arm, farfield_rtol=1e-3)
                jax.block_until_ready(plan)
            log(f"{tag}.plan", build_seconds=f"{time.perf_counter() - t0:.3f}",
                cand_capacity=plan.cand_capacity, p2_capacity=plan.p2_capacity,
                farfield_radius=plan.farfield_radius, bound=plan.farfield_bound,
                warnings="|".join(type(w.message).__name__ for w in caught) or "none")
            check_plan(plan, rehearse)
            reest = CapacityReestimator(registry, (name, arm), plan)
            seconds = []
            for b, (qx, qy) in enumerate(batches):
                if b == 1:
                    cache_before = execute_with_stats._cache_size()
                t0 = time.perf_counter()
                z, a, stats = reest.execute(qx, qy)
                jax.block_until_ready((z, a))
                seconds.append(time.perf_counter() - t0)
                log(f"{tag}.batch{b + 1}", seconds=f"{seconds[-1]:.3f}",
                    note="smoke timing, not a benchmark"
                    + ("; includes trace and compile" if b == 0 else ""),
                    overflow_queries=int(stats["overflow_queries"]),
                    grid_fallback=bool(stats["grid_fallback"]),
                    p2_overflow_queries=int(stats.get("p2_overflow_queries", 0)))
                compare(f"{tag}.batch{b + 1}", z[idx], a[idx], *refs[(name, b)],
                        exact=arm == "exact")
            if execute_with_stats._cache_size() != cache_before:
                raise SystemExit(f"chip_smoke.py: {tag} recompiled on batches 2-3")
            log(f"{tag}.compile", seconds_estimate=f"{seconds[0] - min(seconds[1:]):.3f}",
                note="first batch minus fastest steady batch")
            if reest.join(timeout=600.0) == "degraded":
                raise SystemExit(f"chip_smoke.py: {tag} re-estimator degraded: "
                                 f"{reest.last_error}")
            for p in registry.plans():
                check_plan(p, rehearse)
            if arm == "quadtree":
                qx, qy = batches[0]
                rep = farfield_error_report(reest.plan, qx[idx], qy[idx])
                log(f"{tag}.bound", max_rel_err=rep["max_rel_err"], bound=rep["bound"],
                    fp_slack=rep["fp_slack"], within_bound=rep["within_bound"])
                if not rep["within_bound"]:
                    raise SystemExit(f"chip_smoke.py: {tag} error above its proved bound")
            log(f"{tag}.memory", peak_bytes_in_use=peak_bytes(dev),
                reestimator=json.dumps(reest.stats(), sort_keys=True).replace(" ", ""))


def one_chip(size, seed, rehearse, dev):
    datasets, batches, idx = make_data(size, seed)
    t0 = time.perf_counter()
    refs = {(name, b): jax.block_until_ready(reference(data, qx, qy, idx))
            for name, data in datasets.items() for b, (qx, qy) in enumerate(batches)}
    log("reference", calls=len(refs), seconds=f"{time.perf_counter() - t0:.3f}",
        note="plain-jnp aidw_interpolate on the sample; includes compile")
    paper_phase(size, datasets, batches, idx, refs, seed, rehearse, dev)
    serving_phase(datasets, batches, idx, refs, rehearse, dev)


def four_chips(size, seed, rehearse, devs):
    from jax.sharding import Mesh

    from repro.core.distributed import ring_aidw, sharded_queries_aidw

    datasets, batches, idx = make_data(size, seed)
    dx, dy, dz = datasets["uniform"]
    qx, qy = batches[0]
    t0 = time.perf_counter()
    plan = build_plan(dx, dy, dz, params=PARAMS, area=1.0, impl="grid")
    check_plan(plan, rehearse)
    z1, a1 = jax.block_until_ready(execute(plan, qx, qy))
    log("one_chip.execute", seconds=f"{time.perf_counter() - t0:.3f}",
        note="smoke timing, not a benchmark; includes plan build and compile")
    compare("one_chip.execute", z1[idx], a1[idx], *reference(datasets["uniform"], qx, qy, idx))
    mesh = Mesh(np.array(devs[:4]), ("chips",))
    for name, fn in (("sharded_queries_aidw", sharded_queries_aidw), ("ring_aidw", ring_aidw)):
        t0 = time.perf_counter()
        z, a = jax.block_until_ready(fn(mesh, dx, dy, dz, qx, qy, params=PARAMS, area=1.0))
        log(f"mesh.{name}", chips=4, seconds=f"{time.perf_counter() - t0:.3f}",
            note="smoke timing, not a benchmark; includes compile")
        compare(f"mesh.{name}", z, a, z1, a1)
        log(f"mesh.{name}.memory",
            peak_bytes_in_use=",".join(peak_bytes(d) for d in devs[:4]))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the multi-chip paths, on a 4-device mesh")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, interpret mode; never prints the ok line")
    args = ap.parse_args(argv)

    devs = check_device(args.chips, args.rehearse)
    cache = use_persistent_compile_cache(str(ROOT))
    log("device", platform=devs[0].platform, kind=repr(devs[0].device_kind),
        count=len(devs), jax=jax.__version__, compile_cache=cache)
    warnings.simplefilter("error", PlanDegradedWarning)
    size = TINY if args.rehearse else FULL
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(size, args.seed, args.rehearse, devs)
    else:
        one_chip(size, args.seed, args.rehearse, devs[0])
    log("done", seconds=f"{time.perf_counter() - t0:.3f}")
    if args.rehearse:
        print("rehearsal passed; no ok line off the chip", flush=True)
        return
    print(json.dumps({"ok": True, "device": {"platform": devs[0].platform,
                                             "kind": devs[0].device_kind,
                                             "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
