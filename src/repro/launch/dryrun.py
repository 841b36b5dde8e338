import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape x mesh) cell:
  jit(step).lower(*abstract_inputs).compile()
on the single-pod 16x16 mesh and the 2x16x16 multi-pod mesh, with the real
production shardings.  Records memory_analysis, cost_analysis and the
collective-byte census parsed from the compiled HLO into
``artifacts/dryrun/<arch>__<shape>__<mesh>.json`` (resumable; failures are
bugs, recorded with tracebacks and a nonzero exit).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
      [--mesh single|multi|both] [--only-missing] [--list]
"""

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import ARCHS, SHAPES, cell_is_applicable, get_arch, get_shape  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import build_cell  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.train.steps import make_prefill_step, make_serve_step, make_train_step  # noqa: E402

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")
_SHAPE_RE = re.compile(r"(bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|pred|c64|c128)\[([\d,]*)\]")


def _shape_bytes(match):
    dt, dims = match.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_census(hlo_text: str):
    """Sum output-shape bytes of every collective op in the compiled HLO.
    (Output bytes are the per-device traffic lower bound; the roofline's
    collective term divides by per-chip link bandwidth.)"""
    census = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.lstrip()
        # result line looks like: %name = TYPE[shape] opname(...)
        m = re.match(r"%?[\w.\-]+ = (.+?) (" + "|".join(_COLLECTIVES) + r")[\(\.]", s)
        if not m:
            continue
        op = m.group(2)
        ms = _SHAPE_RE.findall(m.group(1))
        total = 0
        for dt, dims in ms:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        census[op]["count"] += 1
        census[op]["bytes"] += total
    census["total_bytes"] = sum(v["bytes"] for k, v in census.items() if isinstance(v, dict))
    return census


def run_cell(arch_name: str, shape_name: str, mesh_name: str, out_dir: str,
             *, rules_name: str | None = None, accum: int | None = None,
             compress_grads: bool = False, tag: str = ""):
    """One dry-run cell; optional §Perf overrides (alternate rule set,
    accumulation depth, grad compression) write tagged artifacts."""
    import dataclasses

    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    if accum is not None and shape.kind == "train":
        shape = dataclasses.replace(shape, accum_steps=accum)
    ok, why = cell_is_applicable(cfg, shape)
    record = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": mesh_name,
        "kind": shape.kind,
        "status": None,
        "variant": {"rules": rules_name, "accum": accum, "compress_grads": compress_grads} if tag else None,
    }
    suffix = f"__{tag}" if tag else ""
    fname = os.path.join(out_dir, f"{arch_name}__{shape_name}__{mesh_name}{suffix}.json")
    if not ok:
        record.update(status="skipped", reason=why)
        _write(fname, record)
        print(f"[dryrun] SKIP  {arch_name} x {shape_name} x {mesh_name}: {why}")
        return True

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
        model = build_model(cfg)
        cell = build_cell(model, cfg, shape, mesh, rules_name=rules_name)
        if cell["kind"] == "train":
            fn = make_train_step(model, cfg, shape, mesh=mesh, rules=cell["rules"],
                                 compress_grads=compress_grads)
        elif cell["kind"] == "prefill":
            fn = make_prefill_step(model, cfg, mesh=mesh, rules=cell["rules"])
        else:
            fn = make_serve_step(model, cfg, mesh=mesh, rules=cell["rules"])

        # donation: train aliases params+opt state; decode aliases the KV/SSM
        # caches (without it the cache update double-buffers — +27 GiB temp on
        # the qwen2-vl decode cell)
        donate = {"train": (0, 1), "prefill": (), "decode": (1,), "long": (1,)}[cell["kind"]]
        jitted = jax.jit(
            fn, in_shardings=cell["in_shardings"], out_shardings=cell["out_shardings"],
            donate_argnums=donate,
        )
        with mesh:
            lowered = jitted.lower(*cell["args"])
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        try:
            mem = compiled.memory_analysis()
            record["memory_analysis"] = {
                k: int(getattr(mem, k))
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "generated_code_size_in_bytes",
                )
                if hasattr(mem, k)
            }
            record["memory_analysis_str"] = str(mem)
        except Exception as e:  # pragma: no cover
            record["memory_analysis_error"] = repr(e)

        try:
            ca = compiled.cost_analysis()
            record["cost_analysis"] = {
                k: float(v)
                for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    k in ("flops", "transcendentals", "bytes accessed")
                    or k.startswith("bytes accessed")
                )
            }
        except Exception as e:  # pragma: no cover
            record["cost_analysis_error"] = repr(e)

        hlo = compiled.as_text()
        record["collectives"] = collective_census(hlo)
        record["hlo_bytes"] = len(hlo)
        record["timings_s"] = {"lower": round(t_lower, 2), "compile": round(t_compile, 2)}
        record["devices"] = len(mesh.devices.flatten())
        record["status"] = "ok"
        _write(fname, record)
        print(
            f"[dryrun] OK    {arch_name} x {shape_name} x {mesh_name} "
            f"(lower {t_lower:.1f}s compile {t_compile:.1f}s, "
            f"flops/dev {record.get('cost_analysis', {}).get('flops', float('nan')):.3e}, "
            f"coll {record['collectives']['total_bytes']/1e9:.3f} GB)"
        )
        return True
    except Exception as e:
        record.update(status="failed", error=repr(e), traceback=traceback.format_exc())
        _write(fname, record)
        print(f"[dryrun] FAIL  {arch_name} x {shape_name} x {mesh_name}: {e!r}")
        return False


def run_aidw_cell(work_name: str, mesh_name: str, out_dir: str):
    """Dry-run the AIDW workloads (the paper's own technique) on the
    production meshes — ring-sharded data (collective-permute) or
    replicated-data/sharded-queries."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.aidw import AIDW_WORKLOADS
    from repro.core.distributed import (
        ring_aidw,
        ring_aidw_rotate_queries,
        sharded_queries_aidw,
    )

    w = AIDW_WORKLOADS[work_name]
    record = {"arch": work_name, "shape": w.mode, "mesh": mesh_name, "kind": "aidw", "status": None}
    fname = os.path.join(out_dir, f"{work_name}__{w.mode}__{mesh_name}.json")
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
        axes = tuple(mesh.axis_names)
        sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)
        args = (sds((w.m,)), sds((w.m,)), sds((w.m,)), sds((w.n,)), sds((w.n,)))
        qsh = NamedSharding(mesh, P(axes))
        if w.mode == "ring":
            fn = lambda dx, dy, dz, qx, qy: ring_aidw(
                mesh, dx, dy, dz, qx, qy, params=w.params, area=1.0,
                q_chunk=w.q_chunk, d_chunk=w.d_chunk,
            )
        elif w.mode == "ring_q":
            fn = lambda dx, dy, dz, qx, qy: ring_aidw_rotate_queries(
                mesh, dx, dy, dz, qx, qy, params=w.params, area=1.0,
                q_chunk=w.q_chunk, d_chunk=w.d_chunk,
            )
        else:
            fn = lambda dx, dy, dz, qx, qy: sharded_queries_aidw(
                mesh, dx, dy, dz, qx, qy, params=w.params, area=1.0
            )
        dsh = qsh if w.mode in ("ring", "ring_q") else NamedSharding(mesh, P())
        jitted = jax.jit(fn, in_shardings=(dsh, dsh, dsh, qsh, qsh), out_shardings=(qsh, qsh))
        with mesh:
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        try:
            mem = compiled.memory_analysis()
            record["memory_analysis_str"] = str(mem)
            record["memory_analysis"] = {
                k: int(getattr(mem, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes")
                if hasattr(mem, k)
            }
        except Exception as e:
            record["memory_analysis_error"] = repr(e)
        try:
            ca = compiled.cost_analysis()
            record["cost_analysis"] = {
                k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (k in ("flops", "transcendentals", "bytes accessed") or k.startswith("bytes accessed"))
            }
        except Exception as e:
            record["cost_analysis_error"] = repr(e)
        hlo = compiled.as_text()
        record["collectives"] = collective_census(hlo)
        record["hlo_bytes"] = len(hlo)
        record["timings_s"] = {"lower": round(t_lower, 2), "compile": round(t_compile, 2)}
        record["devices"] = len(mesh.devices.flatten())
        record["workload"] = {"m": w.m, "n": w.n, "k": w.k, "mode": w.mode}
        record["status"] = "ok"
        _write(fname, record)
        print(f"[dryrun] OK    {work_name} x {mesh_name} (lower {t_lower:.1f}s compile {t_compile:.1f}s, "
              f"coll {record['collectives']['total_bytes']/1e9:.3f} GB)")
        return True
    except Exception as e:
        record.update(status="failed", error=repr(e), traceback=traceback.format_exc())
        _write(fname, record)
        print(f"[dryrun] FAIL  {work_name} x {mesh_name}: {e!r}")
        return False


def _write(fname, record):
    os.makedirs(os.path.dirname(fname), exist_ok=True)
    with open(fname, "w") as f:
        json.dump(record, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--only-missing", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--aidw", action="store_true", help="run the AIDW workload cells too")
    ap.add_argument("--rules", default=None, help="override rule set (e.g. prefill_cp)")
    ap.add_argument("--accum", type=int, default=None, help="override train accum steps")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--tag", default="", help="artifact suffix for §Perf variants")
    args = ap.parse_args()

    out_dir = args.out or os.path.abspath(ART_DIR)
    if args.arch == "aidw":  # AIDW-only run
        archs = []
        args.aidw = True
    else:
        archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    cells = [(a, s, m) for a in archs for s in shapes for m in meshes]
    if args.list:
        for c in cells:
            print(*c)
        return

    n_fail = 0
    suffix = f"__{args.tag}" if args.tag else ""
    for a, s, m in cells:
        fname = os.path.join(out_dir, f"{a}__{s}__{m}{suffix}.json")
        if args.only_missing and os.path.exists(fname):
            with open(fname) as f:
                if json.load(f).get("status") in ("ok", "skipped"):
                    continue
        if not run_cell(a, s, m, out_dir, rules_name=args.rules, accum=args.accum,
                        compress_grads=args.compress_grads, tag=args.tag):
            n_fail += 1

    if args.aidw or not args.arch:
        from repro.configs.aidw import AIDW_WORKLOADS

        for wname, w in AIDW_WORKLOADS.items():
            for m in meshes:
                fname = os.path.join(out_dir, f"{wname}__{w.mode}__{m}.json")
                if args.only_missing and os.path.exists(fname):
                    with open(fname) as f:
                        if json.load(f).get("status") in ("ok", "skipped"):
                            continue
                if not run_aidw_cell(wname, m, out_dir):
                    n_fail += 1
    print(f"[dryrun] done; {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
