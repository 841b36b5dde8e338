"""A traced window split by the program's own names.

The program names what it does (``repro.telemetry``): device scopes
``aidw.<stage>`` in the ``op_name`` metadata of the compiled program, host
spans ``aidw.<layer>.<step>`` on the profiler's clock, and compile
counters.  ``reduce`` reads them from one trace:

* ``scope_s``: device self time per innermost ``aidw.*`` scope, and
  ``unscoped`` for the rest; it sums to ``xla_s + pallas_s`` of
  ``bench/tracing.reduce``;
* ``span_s``: ``[count, seconds]`` of each host span (``bench.*`` and
  ``aidw.*``) that starts inside the window;
* ``host_ms_per_call``: the mean over the window's
  ``aidw.serving.execute`` spans of their length less their
  ``aidw.serving.sync`` child, in ms: host work the device waits for with
  one call in flight;
* ``idle_gaps``: the longest device idle gaps, each named by the innermost
  span of either prefix open at its middle;
* ``device_ops``: the operations that took most time, each with its scope.

A TPU v5e trace names each operation only by its HLO text, without the
``op_name`` metadata, so an operation's scope comes from ``op_names``,
``{(module, instruction): op_name}`` read from the compiled program's text
(``op_names_of``), by the instruction that heads the event's text.

Run as a script it runs one cell as ``bench/run.py --trace 1`` does, with
the compile counters read when set-up ends and when the window ends, and
prints one JSON line of both results:

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

``bench/run.py`` and ``bench/tracing.py`` do not call it.
"""

from __future__ import annotations

import re
import statistics
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import tracing  # noqa: E402

PREFIX = "aidw."
UNSCOPED = "unscoped"
SPAN_PREFIXES = (tracing.SPAN_PREFIX, PREFIX)
TOP = 10
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?" + _OP_NAME.pattern)
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")


def innermost(path: str) -> str:
    """The last ``aidw.*`` component of an ``op_name`` path."""
    scopes = [c for c in path.split("/") if c.startswith(PREFIX)]
    return scopes[-1] if scopes else UNSCOPED


def op_names_of(hlo_text: str) -> dict:
    """``{(module, instruction): op_name}`` from a compiled program's text."""
    out, module = {}, None
    for line in hlo_text.splitlines():
        head = _MODULE.match(line)
        if head:
            module = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m:
            out[(module, m.group(1))] = m.group(2)
    return out


def _unique_by_instruction(op_names: dict) -> dict:
    """``{instruction: op_name}`` for instruction names no two modules share."""
    paths: dict = {}
    for (_module, op), path in op_names.items():
        paths.setdefault(op, []).append(path)
    return {op: found[0] for op, found in paths.items() if len(found) == 1}


def scope_of(name: str, stats: dict, op_names: dict, unique: dict) -> str:
    # the instruction: a stat of the event (CPU traces), or the head of its
    # HLO text (TPU traces, which carry no module either)
    op = stats.get("hlo_op") or name.partition(" = ")[0].strip().lstrip("%")
    path = op_names.get((stats.get("hlo_module"), op)) or unique.get(op)
    return innermost(path) if path else UNSCOPED


def load(path: str):
    """``(ops, spans)``: device operations as ``(device, name, start_ns,
    end_ns, stats)`` and host spans of either prefix as ``(name, start_ns,
    end_ns, stats)``."""
    from jax.profiler import ProfileData

    ops, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name.startswith("/device:") and line.name == tracing.OPS_LINE:
                ops += [(plane.name, e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                        for e in line.events]
            elif plane.name.startswith("/host:"):
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                          for e in line.events if e.name.startswith(SPAN_PREFIXES)]
    return ops, spans


def _host_ms_per_call(spans) -> float | None:
    calls = [s for s in spans if s[0] == "aidw.serving.execute"]
    syncs = [s for s in spans if s[0] == "aidw.serving.sync"]
    if not calls:
        return None
    host = []
    for _n, s, e, _st in calls:
        sync = sum(se - ss for _m, ss, se, _t in syncs if s <= ss and se <= e)
        host.append((e - s - sync) / 1e6)
    return statistics.fmean(host)


def reduce(ops, spans, op_names: dict | None = None) -> dict:
    op_names = op_names or {}
    unique = _unique_by_instruction(op_names)
    windows = [s for s in spans if s[0] == tracing.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {tracing.WINDOW_SPAN!r} span")
    _, w0, w1, _ = windows[0]
    inside = [(d, n, max(s, w0), min(e, w1), st) for d, n, s, e, st in ops if e > w0 and s < w1]
    devices = sorted({op[0] for op in inside})
    n_dev = max(len(devices), 1)

    scope_ns: dict = {}
    by_op: dict = {}
    gaps = []
    for d in devices:
        mine = [(s, e, (n, st)) for dd, n, s, e, st in inside if dd == d]
        for _s, _e, (name, st), own in tracing._self_times(mine):
            key = scope_of(name, st, op_names, unique)
            scope_ns[key] = scope_ns.get(key, 0.0) + own
            op = (tracing.label(name, None), key)
            by_op[op] = by_op.get(op, 0.0) + own
        _total, dgaps = tracing._union([(s, e) for s, e, _p in mine])
        gaps += ([(w0, min(s for s, _e, _p in mine))] + dgaps
                 + [(max(e for _s, e, _p in mine), w1)])

    in_window = [s for s in spans if w0 <= s[1] <= w1 and s[0] != tracing.WINDOW_SPAN]
    span_s: dict = {}
    for name, s, e, _st in in_window:
        count, secs = span_s.get(name, (0, 0.0))
        span_s[name] = (count + 1, secs + (e - s) / 1e9)

    def open_at(t):
        found = [(s, e, n) for n, s, e, _st in spans if s <= t <= e and n != tracing.WINDOW_SPAN]
        return max(found)[2] if found else tracing.WINDOW_SPAN

    longest = sorted(((e - s, open_at((s + e) / 2)) for s, e in gaps if e > s), reverse=True)[:TOP]
    return {
        "scope_s": {k: v / n_dev / 1e9 for k, v in sorted(scope_ns.items(), key=lambda kv: -kv[1])},
        "span_s": {k: [c, v] for k, (c, v) in sorted(span_s.items())},
        "host_ms_per_call": _host_ms_per_call(in_window),
        "idle_gaps": [[name, ns / 1e9] for ns, name in longest],
        "device_ops": [[op, scope, ns / n_dev / 1e9] for (op, scope), ns in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]],
    }


class _Recorder:
    """The cell's server, with the compile counters read at each
    ``counters()`` call (set-up's end, then the window's end), the
    re-estimator's counters at set-up's end, and the plan it served and a
    batch kept for reading the compiled program."""

    def __init__(self, server):
        from repro import telemetry

        self._telemetry = telemetry
        self.server, self.snapshots, self.batch, self.plan = server, [], None, None
        reest = getattr(server, "reest", None)
        self.setup_reest = None if reest is None else reest.stats()

    def __getattr__(self, name):
        return getattr(self.server, name)

    def call(self, qx, qy):
        if self.batch is None:
            self.batch = (qx, qy)
        return self.server.call(qx, qy)

    def counters(self) -> dict:
        self.snapshots.append(self._telemetry.snapshot())
        return self.server.counters()

    def close(self):
        reest = getattr(self.server, "reest", None)
        self.plan = reest.plan if reest is not None else self.server.plan
        self.server.close()


def _compiled_text(plan, batch) -> str:
    from repro.engine.execute import _execute_with_stats_jit, execute

    fn = _execute_with_stats_jit if plan.impl == "grid" else execute
    return fn.lower(plan, *batch).compile().as_text()


def run(cell: dict, seed: int, seconds: float, device) -> dict:
    """One traced run of ``cell`` through ``bench/run.run_cell``, with this
    module's split of the same trace and the program's counters."""
    from bench import run as bench_run
    from repro import telemetry

    telemetry.snapshot()  # count from here on
    box = {}

    def wrap(server):
        box["server"] = _Recorder(server)
        return box["server"]

    def reduce_dir(trace_dir):
        ops, spans = load(tracing.find_xplane(trace_dir))
        rec = box["server"]
        box["scopes"] = reduce(ops, spans, op_names_of(_compiled_text(rec.plan, rec.batch)))
        reduced = reduce_dir.orig(trace_dir)
        box["scopes"]["tracing"] = {k: reduced[k] for k in ("window_s", "busy_s", "xla_s", "pallas_s")}
        return reduced

    reduce_dir.orig = tracing.reduce_dir
    tracing.reduce_dir = reduce_dir
    try:
        result = bench_run.run_cell(cell, seed, seconds, True, t_start=T_START,
                                    device=device, fault=wrap)
    finally:
        tracing.reduce_dir = reduce_dir.orig
    rec, split = box["server"], box["scopes"]
    setup, end = rec.snapshots[0], rec.snapshots[1]
    queries = result["attempted"]
    program = {
        "setup": setup, "window": {k: end[k] - setup[k] for k in setup},
        "setup_compile_s": setup["trace_s"] + setup["lower_s"] + setup["compile_s"],
        "setup_reestimator": rec.setup_reest,
    }
    derived = {
        "engine.gather.ms_per_kquery": 1e6 * split["scope_s"].get("aidw.gather", 0.0) / queries,
        "engine.sort.ms_per_kquery": 1e6 * split["scope_s"].get("aidw.sort", 0.0) / queries,
        "serving.host_ms_per_call": split["host_ms_per_call"],
        "engine.compiles_in_window": program["window"]["compiles"],
        "engine.setup_compile_s": program["setup_compile_s"],
        "serving.warm_replan_s": rec.setup_reest and rec.setup_reest.get("replan_s"),
    }
    return {"result": result, "scopes": split, "program": program, "derived": derived}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from bench import manifest
    from bench import run as bench_run

    cell = manifest.cell(args.workload)
    try:
        devices = bench_run.require_chip(int(cell["workload"]["chips"]))
    except bench_run.NoChip as e:
        bench_run.log(f"bench/scopes.py: {e}")
        return 3
    bench_run.use_compile_cache()
    import warnings

    from repro.errors import PlanDegradedWarning

    warnings.simplefilter("error", PlanDegradedWarning)
    print(json.dumps(run(cell, args.seed, args.seconds, devices[0])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
