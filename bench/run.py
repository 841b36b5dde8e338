"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run loads the cell by name (``bench/manifest.py``), makes the data and
the queries from ``--seed``, builds and warms the cell's path, then drives
whole calls, one in flight, until ``--seconds`` have passed.  Afterwards it
checks a sample of the window's answers against the plain reference
(``bench/check.py``) and prints, as the last line of standard output, one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and with ``--trace 1`` a ``breakdown``), and last ``checks``, each number
compared with its limit.  The same numbers close standard error.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the window with the JAX profiler and reports its per-layer metrics.

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, and where the program it measures is not in
the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import check, gen, manifest, reference, roofline, tracing  # noqa: E402


class NoChip(RuntimeError):
    pass


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def require_chip(chips: int):
    """The devices, where JAX found at least ``chips`` TPUs.  JAX falls back
    to the CPU when the TPU fails to start; that is a failure here."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r}); "
                     "this benchmark measures the chip only")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def use_compile_cache():
    """The program's persistent compilation cache, in the checkout's fixed
    ``.jax_cache/`` (or ``JAX_COMPILATION_CACHE_DIR``), for every program
    however quickly it compiles."""
    import jax

    from repro.compile_cache import use_persistent_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return use_persistent_compile_cache(str(ROOT))


def _drive(server, batches, seconds: float, min_calls: int, annotate):
    """Whole calls, one in flight, cycling over ``batches``, until
    ``seconds`` have passed.  Returns ``(outputs, t0, t1)``."""
    import jax

    outs = []
    t0 = t = time.perf_counter()
    i = 0
    while True:
        qx, qy = batches[i % len(batches)]
        with annotate("bench.call"):
            z, a, marked = server.call(qx, qy)
            jax.block_until_ready((z, a))
        outs.append((i % len(batches), z, a, marked, time.perf_counter() - t))
        t = time.perf_counter()
        i += 1
        if i >= min_calls and t - t0 >= seconds:
            break
    return outs, t0, t


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *, t_start: float,
             device=None, min_calls: int = 1, fault=None, control: bool = False) -> dict:
    """One run of ``cell`` (as ``manifest.cell`` gives it); returns the
    result object.  ``fault``, where given, wraps the path's server before
    the window, so a test can break the timed path underneath.  ``control``
    also computes the control's gaps on the same sample (``bench/control.py``);
    the benchmark's own runs never do."""
    import jax
    import numpy as np

    config, traffic = cell["config"], cell["traffic"]
    device = device or jax.devices()[0]

    x, y, z, info = gen.make_data(config, seed)
    host_batches = gen.make_batches(traffic, config, seed)
    data = tuple(jax.device_put(v, device) for v in (x, y, z))
    batches = [tuple(jax.device_put(v, device) for v in b) for b in host_batches]
    jax.block_until_ready((data, batches))
    log(f"data: m={x.shape[0]} {info} calls of {host_batches[0][0].shape[0]} queries, "
        f"{len(batches)} distinct, t={time.perf_counter() - t_start:.3f}s")

    server = cell["path"].Server(config, data, batches, log)
    del data
    server.warm(batches)
    if fault is not None:
        server = fault(server)
    before = server.counters()
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f}s (plan build {server.build_s:.3f}s) {before}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
        annotate = jax.profiler.TraceAnnotation
        with annotate("bench.window"):
            outs, t0, t1 = _drive(server, batches, seconds, min_calls, annotate)
        jax.profiler.stop_trace()
    else:
        outs, t0, t1 = _drive(server, batches, seconds, min_calls, lambda name: contextlib.nullcontext())
    window_s = t1 - t0
    stats = device.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    after = server.counters()
    server.close()
    del server
    calls_s = [o[4] for o in outs]
    log(f"window: {len(outs)} calls in {window_s:.3f}s, each {min(calls_s):.3f}-{max(calls_s):.3f}s; "
        f"{after}")

    # every answer of the window to the host; the device copies go
    sizes = [host_batches[b][0].shape[0] for b, *_ in outs]
    z_all = np.concatenate([np.asarray(o[1]) for o in outs])
    a_all = np.concatenate([np.asarray(o[2]) for o in outs])
    marked = (np.concatenate([np.asarray(o[3]) for o in outs])
              if outs[0][3] is not None else None)
    qx_all = np.concatenate([host_batches[b][0] for b, *_ in outs])
    qy_all = np.concatenate([host_batches[b][1] for b, *_ in outs])
    del outs, batches
    gc.collect()
    attempted = int(sum(sizes))
    failed = int(np.sum(~(np.isfinite(z_all) & np.isfinite(a_all))))

    lim = cell["limits"]
    # strata beside the uniform draw: answers that took the other arm, and
    # answers whose alpha lies below the top level, where Phase 1's
    # neighbours set alpha (at the top level alpha is saturated)
    low_alpha = a_all < max(config["aidw"]["alpha_levels"]) - 1e-3
    strata = [(marked, lim["marked_sample"]), (low_alpha, lim["low_alpha_sample"])]
    idx = check.draw_sample(attempted, seed, lim["sample"], strata)
    sample = {"answers": int(idx.size),
              "other_arm": 0 if marked is None else int(marked[idx].sum()),
              "alpha_below_top": int(low_alpha[idx].sum())}
    t_ref = time.perf_counter()
    z_ref, a_ref = reference.aidw(x, y, z, qx_all[idx], qy_all[idx], config["aidw"])
    measured = check.gaps(z_all[idx], a_all[idx], z_ref, a_ref)
    correct, checks = check.verdict(measured, lim["limits"])
    log(f"check: {sample} against the reference in {time.perf_counter() - t_ref:.3f}s")
    if control:
        import jax.numpy as jnp

        z_c, a_c = reference.aidw(x, y, z, qx_all[idx], qy_all[idx], config["aidw"], dtype=jnp.bfloat16)
        control_gaps = check.gaps(z_c, a_c, z_ref, a_ref)

    counters = {
        "queries": attempted, "calls": len(sizes), "window_s": window_s,
        "sizes": sizes, "m": int(x.shape[0]), "plan_build_s": after["plan_build_s"],
        "marked": None if marked is None else int(marked.sum()),
        "replans_in_window": (after["replans"] - before["replans"]
                              if "replans" in after else None),
    }
    dev = {"platform": device.platform, "kind": device.device_kind, "count": len(jax.devices()),
           "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if trace:
        reduced = tracing.reduce_dir(trace_dir)
        tracing.remove(trace_dir)
        peak = roofline.peaks(device.device_kind) if device.platform == "tpu" else None
        ctx = {"trace": reduced, "counters": counters, "peaks": peak}
        metrics = {}
        for m in cell["per_layer"]:
            value = cell["readers"][m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        result.update(metrics=metrics, device=dev, breakdown=reduced["breakdown"])
    else:
        # one rate, named for the cell's path: served_queries_per_s through
        # the re-estimator, queries_per_s where the window calls execute
        rate = attempted / window_s
        e2e = {"setup_s": setup_s, "queries_per_s": rate, "served_queries_per_s": rate,
               "peak_hbm_mb": peak_bytes / 1e6}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
        result.update(metrics=metrics, device=dev)
    result["sample"] = sample
    if control:
        result["control"] = control_gaps
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        log(f"bench/run.py: the program under test (src/repro) is not in {ROOT}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    cell = manifest.cell(args.workload)
    try:
        devices = require_chip(int(cell["workload"]["chips"]))
    except NoChip as e:
        log(f"bench/run.py: {e}")
        return 3
    cache = use_compile_cache()
    import warnings

    from repro.errors import PlanDegradedWarning

    warnings.simplefilter("error", PlanDegradedWarning)
    log(f"device: {devices[0].platform} {devices[0].device_kind!r} x{len(devices)}; "
        f"compile cache {cache}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START, device=devices[0])
    log(f"correct: {result['correct']}")
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
