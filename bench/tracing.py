"""From a JAX profiler trace to device times, idle share and a breakdown.

``load`` reads an ``.xplane.pb`` with nothing but JAX: the operations of
each device plane's ``XLA Ops`` line, and the benchmark's own host spans
(``bench.*``).  ``reduce`` turns them into numbers:

* ``window_s``: the length of the ``bench.window`` span;
* ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices that ran any;
* ``kernel_s``: per kernel group, the device self time of its operations;
* ``pallas_s`` / ``xla_s``: device self time of Pallas kernels (Mosaic
  custom calls) and of every other operation.  Operations nest (a
  ``while`` holds its body's fusions), so each one counts only the time
  no operation inside it covers;
* ``breakdown``: the ten device operations that took most time, and the
  ten longest idle gaps, each named by the innermost host span open at
  the gap's middle.

A kernel group is a file ``bench/kernels/<group>.json``.  Its kernels are
found by the names of their kernel functions, where the trace shows them,
and otherwise by the operand shapes of the custom call: the program names
none of its ``pallas_call`` sites yet, and the trace names each kernel
only by its HLO instruction.  A signature such as ``["f32[n,1]", "f32[1,m]"]``
matches operands of those dtypes and ranks, a letter standing for any size
and the same letter for the same size.
"""

from __future__ import annotations

import glob
import json
import re
import shutil
from pathlib import Path

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
TOP = 10
KERNELS = Path(__file__).resolve().parent / "kernels"
_OPERAND = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_SIGNATURE = re.compile(r"^([a-z]+[0-9]*)\[([0-9a-z,]*)\]$")


def kernel_groups() -> dict:
    """``{group: spec}`` from ``bench/kernels/*.json``."""
    return {p.stem: json.loads(p.read_text()) for p in sorted(KERNELS.glob("*.json"))}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    """``(ops, spans)``: device operations as ``(device, name, start_ns,
    end_ns)`` and host spans as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(plane.name, e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return ops, spans


def operands(name: str) -> list:
    """``[(dtype, dims)]`` of a custom call's operands, from its HLO text."""
    head, sep, rest = name.partition("custom-call(")
    if not sep:
        return []
    args = rest.split(", custom_call_target=")[0]
    return [(d, tuple(int(x) for x in dims.split(",") if x)) for d, dims in _OPERAND.findall(args)]


def signature_matches(ops: list, signature: list) -> bool:
    if len(ops) != len(signature):
        return False
    bound: dict = {}
    for (dtype, dims), token in zip(ops, signature):
        want_dtype, want = _SIGNATURE.match(token).groups()
        want = [w for w in want.split(",") if w]
        if dtype != want_dtype or len(dims) != len(want):
            return False
        for size, w in zip(dims, want):
            if w.isdigit():
                if size != int(w):
                    return False
            elif bound.setdefault(w, size) != size:
                return False
    return True


def group_of(name: str, groups: dict) -> str | None:
    if PALLAS_TARGET not in name:
        return None
    ops = operands(name)
    for group, spec in groups.items():
        if any(k in name for k in spec.get("kernels", ())):
            return group
    for group, spec in groups.items():
        if any(signature_matches(ops, sig) for sig in spec.get("signatures", ())):
            return group
    return None


def label(name: str, group: str | None) -> str:
    """A short name for the breakdown: the kernel group, or the HLO
    instruction's name and opcode."""
    if group is not None:
        return group
    short, _, rest = name.partition(" = ")
    opcode = re.search(r"\s([a-z][a-z\-]*)\(", rest)
    if PALLAS_TARGET in name:
        return f"{short} pallas"
    return f"{short} {opcode.group(1)}" if opcode else short


def _union(intervals) -> tuple[float, list]:
    """Total length of the union, and the gaps between merged intervals."""
    total, gaps = 0.0, []
    start = end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                total += end - start
                gaps.append((end, s))
            start, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        total += end - start
    return total, gaps


def _self_times(events) -> list:
    """Each event's duration less the time its directly nested events
    cover; ``events`` are ``(start, end, payload)`` on one device."""
    out = []
    stack = []  # indices into out of open events
    for s, e, payload in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and out[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= out[stack[-1]][1]:
            out[stack[-1]][3] -= e - s
        out.append([s, e, payload, e - s])
        stack.append(len(out) - 1)
    return [(s, e, payload, max(own, 0.0)) for s, e, payload, own in out]


def reduce(ops, spans, groups: dict) -> dict:
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    _, w0, w1 = windows[0]
    inside = [(d, n, max(s, w0), min(e, w1)) for d, n, s, e in ops if e > w0 and s < w1]
    devices = sorted({d for d, *_ in inside})
    n_dev = max(len(devices), 1)

    kernel_ns = {g: 0.0 for g in groups}
    pallas_ns = xla_ns = busy_ns = 0.0
    by_label: dict = {}
    gaps = []
    for d in devices:
        mine = [(s, e, n) for dd, n, s, e in inside if dd == d]
        for _s, _e, name, own in _self_times(mine):
            group = group_of(name, groups)
            key = label(name, group)
            by_label[key] = by_label.get(key, 0.0) + own
            if group is not None:
                kernel_ns[group] += own
            if PALLAS_TARGET in name:
                pallas_ns += own
            else:
                xla_ns += own
        total, dgaps = _union([(s, e) for s, e, _n in mine])
        busy_ns += total
        first = min(s for s, _e, _n in mine)
        last = max(e for _s, e, _n in mine)
        gaps += [(w0, first)] + dgaps + [(last, w1)]

    def host_at(t):
        open_spans = [(s, e, n) for n, s, e in spans if s <= t <= e and n != WINDOW_SPAN]
        return max(open_spans)[2] if open_spans else WINDOW_SPAN

    longest = sorted(((e - s, host_at((s + e) / 2)) for s, e in gaps if e > s), reverse=True)[:TOP]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "kernel_s": {g: v / n_dev / 1e9 for g, v in kernel_ns.items()},
        "pallas_s": pallas_ns / n_dev / 1e9,
        "xla_s": xla_ns / n_dev / 1e9,
        "breakdown": {
            "device_ops": [[n, v / n_dev / 1e9] for n, v in
                           sorted(by_label.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[name, ns / 1e9] for ns, name in longest],
        },
    }


def reduce_dir(trace_dir: str) -> dict:
    ops, spans = load(find_xplane(trace_dir))
    return reduce(ops, spans, kernel_groups())


def remove(trace_dir: str):
    shutil.rmtree(trace_dir, ignore_errors=True)
