"""BENCHMARK.json and the files it names: every cell resolves by name to
its configuration, traffic mix, path, metric readers and limits, and the
manifest keeps to the benchmark's format.  Needs no chip."""

import json
import re

import pytest

import bench_cells  # noqa: F401  (the repository root on the path)
from bench import check, manifest

BENCH = manifest.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
E2E = {m["name"] for m in BENCH["end_to_end"]}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"][0] == "python3" and BENCH["command"][1].startswith("bench/")
    for p in BENCH["paths"]:
        assert (manifest.ROOT / p).is_dir()
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_entries():
    seen = set()
    for key, allowed in (("configs", {"name", "source", "file", "reduced", "why"}),
                         ("workloads", {"name", "config", "traffic", "chips", "why"}),
                         ("end_to_end", {"name", "unit", "better", "bound", "source", "workloads"}),
                         ("per_layer", {"name", "unit", "better", "source", "layer", "moves",
                                        "workloads"})):
        for entry in BENCH[key]:
            assert set(entry) <= allowed, (key, entry["name"])
            assert NAME.match(entry["name"]), entry["name"]
            assert (key, entry["name"]) not in seen
            seen.add((key, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
            if "why" in entry:
                assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


def test_end_to_end_cells():
    """An end-to-end metric of some cells only lists them; every cell
    reports set-up and at least one other end-to-end metric."""
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells and m.get("workloads", [1])
    for w in cells:
        e2e = {m["name"] for m in manifest.cell(w)["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2


def test_bounds():
    bounds = {m["name"]: m for m in BENCH["end_to_end"]}
    assert bounds["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(workload):
    cell = manifest.cell(workload)
    w = cell["workload"]
    assert w["chips"] in (1, 4)
    assert cell["config"]["name"] == w["config"]
    assert hasattr(cell["path"], "Server")
    for m in cell["per_layer"]:
        assert callable(cell["readers"][m["name"]].read)
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell["per_layer"]
    assert set(check.NUMBERS) <= set(cell["limits"]["limits"])
    # data and query generators by name, and the small sizes the tests run
    assert callable(manifest.data_kind(cell["config"]["data_kind"]).make)
    assert callable(manifest.query_kind(cell["traffic"]["queries"]["kind"]).make)
    assert set(cell["config"]["small"]) <= set(cell["config"])
    assert set(cell["traffic"]["small"]) <= set(cell["traffic"]["queries"])


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(config):
    path = manifest.ROOT / config["file"]
    assert path.is_file() and any(path.is_relative_to(manifest.ROOT / p) for p in BENCH["paths"])
    data = json.loads(path.read_text())
    assert data["name"] == config["name"] and data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    assert all(key in data for key in config["reduced"])
    assert data["dtype"] == "float32"
    assert {"k", "alpha_levels", "r_min", "r_max", "area", "exact_hit_eps"} <= set(data["aidw"])


def test_per_layer_metrics():
    """Each per-layer metric lists its cells, and each of them reports the
    end-to-end metric it moves."""
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in E2E
        assert "workloads" in m and set(m["workloads"]) <= cells
        for w in m["workloads"]:
            assert m["moves"] in {e["name"] for e in manifest.cell(w)["end_to_end"]}, (m["name"], w)
        assert (manifest.BENCH / "metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_config_is_used():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
