"""Readings that set a cell's limits: the program's gaps and the control's.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --calls 4

For each seed, in one process: a run of the cell as ``bench/run.py`` makes
it, with a window of ``--calls`` calls, then its sample of answers against
the float32 reference (the program's gaps, the lower readings), and the
control on the same sample: the reference computed in bfloat16, the next
precision below the float32 the configurations state, in the program's
place (the upper readings).  One JSON line per seed on standard output.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

from run import ROOT, log, require_chip, run_cell, use_compile_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--calls", type=int, default=4)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from bench import manifest

    cell = manifest.cell(args.workload)
    devices = require_chip(int(cell["workload"]["chips"]))
    use_compile_cache()
    failures = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            r = run_cell(manifest.cell(args.workload), seed, 0.0, False, t_start=t0,
                         device=devices[0], min_calls=args.calls, control=True)
        except Exception:  # noqa: BLE001 — report the seed and go on to the next
            failures += 1
            log(f"seed {seed} failed:\n{traceback.format_exc()}")
            continue
        line = {"workload": args.workload, "seed": seed, "calls": args.calls,
                "attempted": r["attempted"], "failed": r["failed"],
                "program": {k: v["value"] for k, v in r["checks"].items()},
                "control": r["control"], "seconds": time.perf_counter() - t0}
        log(json.dumps(line))
        print(json.dumps(line), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
