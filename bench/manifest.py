"""Find a cell's parts by name, as ``BENCHMARK.json`` names them.

* a configuration: the ``file`` its entry in ``configs`` gives; its
  ``data_kind`` names the data generator ``bench/data/<kind>.py``;
* a traffic mix: ``bench/traffic/<traffic>.json``, which names the path
  that serves it and, in its ``queries`` block, the query generator
  ``bench/queries/<kind>.py``;
* a path: ``bench/paths/<path>.py``, the entry the window drives;
* a per-layer metric: ``bench/metrics/<metric>.py``, its reader;
* a cell's limits: ``bench/limits/<workload>.json``.

A configuration and a traffic file may carry a ``small`` block: the
numbers that cut the cell to a size the tests run on the CPU.

A later cell, configuration, traffic mix, data or query kind, path or
metric is added as new files and new entries; this module stays as it is.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config(name: str, bench: dict | None = None) -> dict:
    bench = bench or benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == name)
    return json.loads((ROOT / entry["file"]).read_text())


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def path_module(name: str):
    return _load_module(BENCH / "paths" / f"{name}.py", f"bench_path_{name.replace('-', '_')}")


def metric_reader(name: str):
    return _load_module(BENCH / "metrics" / f"{name}.py",
                        f"bench_metric_{name.replace('.', '_').replace('-', '_')}")


def limits(workload: str) -> dict:
    return json.loads((BENCH / "limits" / f"{workload}.json").read_text())


def data_kind(name: str):
    return _load_module(BENCH / "data" / f"{name}.py", f"bench_data_{name}")


def query_kind(name: str):
    return _load_module(BENCH / "queries" / f"{name}.py", f"bench_queries_{name}")


def cell_metrics(workload: dict, bench: dict) -> tuple[list, list]:
    """The end-to-end metrics this cell reports (those with no
    ``workloads`` key, and those that list it) and the per-layer metrics
    that list it."""
    name = workload["name"]
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    layer = [m for m in bench["per_layer"] if name in m["workloads"]]
    return e2e, layer


def cell(name: str) -> dict:
    """Everything one cell needs, resolved by name."""
    bench = benchmark()
    matches = [w for w in bench["workloads"] if w["name"] == name]
    if not matches:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    workload = matches[0]
    tr = traffic(workload["traffic"])
    e2e, layer = cell_metrics(workload, bench)
    return {
        "workload": workload,
        "config": config(workload["config"], bench),
        "traffic": tr,
        "path": path_module(tr["path"]),
        "limits": limits(name),
        "end_to_end": e2e,
        "per_layer": layer,
        "readers": {m["name"]: metric_reader(m["name"]) for m in layer},
    }
