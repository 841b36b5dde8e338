import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

"""Loop-corrected cost model for the roofline (companion to dryrun.py).

XLA's HloCostAnalysis visits a while-loop body ONCE — scanned layer stacks
and the grad-accumulation loop are under-counted by their trip counts
(verified: scan(10 matmuls) reports the flops of one).  The production
artifact keeps its scans (that's the deployable program and the
memory_analysis source); THIS pass reconstructs exact per-step totals from
small **unrolled** compiles, exploiting that cost is exactly linear in group
repeats:

  variants:  base     — every GroupDef.repeats=1 (and 1 encoder layer)
             group_i  — group i at repeats=2 (marginal = one extra group body)
  F_micro  = F(base) + sum_i (G_i - 1) * (F(group_i) - F(base))
  F_step   = accum_steps * F_micro          (train; optimizer flops, ~1e-5 of
                                             a step, ride along per microbatch)
           = F_micro                        (prefill / decode)

The same linearity corrects "bytes accessed" and the collective census.
Known residual: the Mamba2 inter-chunk state scan stays a while loop inside
the body (its per-chunk state update is O(B*H*P*N), ~1e-4 of the chunk's
GEMMs — negligible and noted in EXPERIMENTS §Roofline).

Writes artifacts/costmodel/<arch>__<shape>__<mesh>.json.
"""

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import ARCHS, SHAPES, cell_is_applicable, get_arch, get_shape  # noqa: E402
from repro.launch.dryrun import collective_census, _write  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import build_cell  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.train.steps import make_prefill_step, make_serve_step, make_train_step  # noqa: E402

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "costmodel")


def _reduced_cfg(cfg, repeats_map, n_enc):
    groups = tuple(
        dataclasses.replace(g, repeats=repeats_map[i]) for i, g in enumerate(cfg.groups)
    )
    return dataclasses.replace(cfg, groups=groups, n_enc_layers=n_enc)


def _measure(cfg, shape, mesh, rules_name=None, compress_grads=False):
    """Compile one unrolled variant; return (flops, bytes, collective census)."""
    model = build_model(cfg)
    cell = build_cell(model, cfg, shape, mesh, rules_name=rules_name)
    if cell["kind"] == "train":
        fn = make_train_step(model, cfg, shape, mesh=mesh, rules=cell["rules"], unroll=True,
                             compress_grads=compress_grads)
    elif cell["kind"] == "prefill":
        fn = make_prefill_step(model, cfg, mesh=mesh, rules=cell["rules"], unroll=True)
    else:
        fn = make_serve_step(model, cfg, mesh=mesh, rules=cell["rules"], unroll=True)
    jitted = jax.jit(fn, in_shardings=cell["in_shardings"], out_shardings=cell["out_shardings"])
    with mesh:
        compiled = jitted.lower(*cell["args"]).compile()
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    census = collective_census(compiled.as_text())
    return flops, byts, census


def run_cell(arch_name, shape_name, mesh_name, out_dir, *, rules_name=None,
             accum_override=None, compress_grads=False, tag=""):
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    if accum_override is not None and shape.kind == "train":
        shape = dataclasses.replace(shape, accum_steps=accum_override)
    ok, why = cell_is_applicable(cfg, shape)
    suffix = f"__{tag}" if tag else ""
    fname = os.path.join(out_dir, f"{arch_name}__{shape_name}__{mesh_name}{suffix}.json")
    record = {"arch": arch_name, "shape": shape_name, "mesh": mesh_name, "status": None,
              "variant": {"rules": rules_name, "accum": accum_override,
                          "compress_grads": compress_grads} if tag else None}
    if not ok:
        record.update(status="skipped", reason=why)
        _write(fname, record)
        return True
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
        accum = max(shape.accum_steps, 1) if shape.kind == "train" else 1
        cost_shape = (
            dataclasses.replace(shape, global_batch=shape.global_batch // accum, accum_steps=1)
            if shape.kind == "train"
            else shape
        )
        ones = {i: 1 for i in range(len(cfg.groups))}
        enc1 = 1 if cfg.n_enc_layers else 0
        base_cfg = _reduced_cfg(cfg, ones, enc1)
        f0, b0, c0 = _measure(base_cfg, cost_shape, mesh, rules_name, compress_grads)
        flops, byts = f0, b0
        census = {k: dict(v) if isinstance(v, dict) else v for k, v in c0.items()}
        marginals = {}
        for i, g in enumerate(cfg.groups):
            if g.repeats <= 1:
                continue
            var_cfg = _reduced_cfg(cfg, {**ones, i: 2}, enc1)
            fi, bi, ci = _measure(var_cfg, cost_shape, mesh, rules_name, compress_grads)
            mult = g.repeats - 1
            flops += mult * (fi - f0)
            byts += mult * (bi - b0)
            for op in census:
                if isinstance(census[op], dict):
                    census[op]["bytes"] += mult * (ci[op]["bytes"] - c0[op]["bytes"])
                    census[op]["count"] += mult * (ci[op]["count"] - c0[op]["count"])
            marginals[f"g{i}"] = {"flops": fi - f0, "bytes": bi - b0, "repeats": g.repeats}
        if cfg.n_enc_layers > 1:
            var_cfg = _reduced_cfg(cfg, ones, 2)
            fe, be, ce = _measure(var_cfg, cost_shape, mesh, rules_name, compress_grads)
            mult = cfg.n_enc_layers - 1
            flops += mult * (fe - f0)
            byts += mult * (be - b0)
            for op in census:
                if isinstance(census[op], dict):
                    census[op]["bytes"] += mult * (ce[op]["bytes"] - c0[op]["bytes"])
                    census[op]["count"] += mult * (ce[op]["count"] - c0[op]["count"])
            marginals["enc"] = {"flops": fe - f0, "bytes": be - b0, "repeats": cfg.n_enc_layers}

        flops *= accum
        byts *= accum
        for op in census:
            if isinstance(census[op], dict):
                census[op]["bytes"] *= accum
                census[op]["count"] *= accum
        census["total_bytes"] = sum(
            v["bytes"] for v in census.values() if isinstance(v, dict)
        )
        record.update(
            status="ok",
            devices=len(mesh.devices.flatten()),
            accum=accum,
            corrected={"flops": flops, "bytes_accessed": byts, "collectives": census},
            base={"flops": f0, "bytes_accessed": b0},
            marginals=marginals,
            timings_s=round(time.time() - t0, 1),
        )
        _write(fname, record)
        print(
            f"[costmodel] OK   {arch_name} x {shape_name} x {mesh_name} "
            f"flops/dev {flops:.3e} coll {census['total_bytes']/1e9:.2f} GB ({record['timings_s']}s)"
        )
        return True
    except Exception as e:
        record.update(status="failed", error=repr(e), traceback=traceback.format_exc())
        _write(fname, record)
        print(f"[costmodel] FAIL {arch_name} x {shape_name} x {mesh_name}: {e!r}")
        return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--only-missing", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--rules", default=None)
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    out_dir = args.out or os.path.abspath(ART_DIR)
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    n_fail = 0
    for a in archs:
        for s in shapes:
            for m in meshes:
                suffix = f"__{args.tag}" if args.tag else ""
                fname = os.path.join(out_dir, f"{a}__{s}__{m}{suffix}.json")
                if args.only_missing and os.path.exists(fname):
                    with open(fname) as f:
                        if json.load(f).get("status") in ("ok", "skipped"):
                            continue
                if not run_cell(a, s, m, out_dir, rules_name=args.rules,
                                accum_override=args.accum,
                                compress_grads=args.compress_grads, tag=args.tag):
                    n_fail += 1
    print(f"[costmodel] done; {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
