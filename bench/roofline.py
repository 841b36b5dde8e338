"""Published peaks, and the least work of the Phase-2 exact sweep.

The sweep's work is counted from the AIDW formula, not from any
implementation: every (query, data point) pair of ``n`` queries against
``m`` points, whatever the block sizes or the layout that compute it.

Per pair, with each transcendental counted as one operation:

* the squared distance: 2 subtractions, 2 multiplications, 1 addition (5);
* the weight ``d^-alpha`` as ``exp(-alpha/2 * log d^2)``: log, multiply,
  exp (3);
* the two accumulations ``sum w`` and ``sum w z``: 1 addition, 1
  multiplication and 1 addition (3).

The least traffic of one call reads the data once (x, y, z) and the
queries and alpha once, and writes z once, all float32.
"""

from __future__ import annotations

import json
from pathlib import Path

OPS_PER_PAIR = 5 + 3 + 3
F32 = 4


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a kind missing from
    ``peaks.json`` is an error, never a default."""
    table = json.loads((Path(__file__).resolve().parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def phase2_work(n: int, m: int) -> tuple[float, float]:
    """``(operations, bytes)`` of one exact Phase-2 call: ``n`` queries
    against ``m`` data points."""
    ops = float(OPS_PER_PAIR) * n * m
    nbytes = float(F32) * (3 * m + 4 * n)
    return ops, nbytes


def least_time(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least seconds the chip could take, and which peak bounds it."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
