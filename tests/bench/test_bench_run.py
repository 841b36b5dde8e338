"""Whole runs of each cell at a small size on the CPU, past the harness's
look for a chip: a sound run is correct, and a run whose timed path is
broken underneath is not.  The faults a cell of this benchmark can have
are an answer altered where it is produced and half of a call's queries
left out; no cell trains (no state to leave unchanged) and none spans
chips (no exchange to leave out)."""

import itertools
import json
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from bench_cells import CELLS, ROOT, small_cell
from bench import check, run

SEED = 2 ** 31 + 99


class AlteredAnswers:
    """Every 16th answer's z off by ten times the cell's ``z_rel_gap``
    limit (relative)."""

    def __init__(self, server, off):
        self.server, self.off = server, off

    def call(self, qx, qy):
        z, a, marked = self.server.call(qx, qy)
        bump = jnp.where(jnp.arange(z.shape[0]) % 16 == 0, self.off, 0.0).astype(z.dtype)
        return z * (1 + bump), a, marked

    def __getattr__(self, name):
        return getattr(self.server, name)


class HalfLeftOut(AlteredAnswers):
    """Only the first half of each call is computed; its answers stand in
    for the second half's."""

    def call(self, qx, qy):
        h = qx.shape[0] // 2
        z, a, marked = self.server.call(qx[:h], qy[:h])
        both = (lambda v: None if v is None else jnp.concatenate([v, v]))
        return both(z), both(a), both(marked)


def small_run(name, fault=None, trace=False):
    return run.run_cell(small_cell(name), SEED, 0.0, trace, t_start=time.perf_counter(),
                        min_calls=3, fault=fault)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    r = small_run(name)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    assert {m["name"] for m in small_cell(name)["end_to_end"]} == set(r["metrics"])
    assert {"setup_s", "peak_hbm_mb"} < set(r["metrics"])
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert set(r["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.mark.parametrize("fault", [AlteredAnswers, HalfLeftOut])
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(name, fault):
    off = 10 * small_cell(name)["limits"]["limits"]["z_rel_gap"]
    r = small_run(name, fault=lambda server: fault(server, off))
    assert not r["correct"], r["checks"]


def test_warm_up_that_never_settles_ends_the_run():
    """A re-estimator that triggers on every warm-up call never settles: the
    run ends with an error, before any window, instead of timing a window
    that a re-plan or a compile could land in."""
    cell = small_cell("paper-uniform-1000k.scatter")
    path = cell["path"]
    path.MAX_WARM_CALLS = 5

    class Unsettled(path.Server):
        def warm(self, batches):
            stats, count = self.reest.stats, itertools.count(1)
            self.reest.stats = lambda: {**stats(), "triggers": next(count)}
            return super().warm(batches)

    path.Server = Unsettled
    with pytest.raises(RuntimeError, match="not settled after 5 calls"):
        run.run_cell(cell, SEED, 0.0, False, t_start=time.perf_counter(), min_calls=1)


def test_traced_run_reports_per_layer_metrics():
    r = small_run("paper-uniform-1000k.scatter", trace=True)
    assert r["correct"]
    assert {"plan.build_s", "serving.replans_in_window", "engine.phase1_overflow_share"} <= set(r["metrics"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert r["device"]["window_s"] > 0


def test_no_chip_means_no_result(capsys):
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
                          "--seconds", "1", "--trace", "0"], cwd=tmp_path, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode != 0 and out.stdout == ""


def test_control_readings_on_the_run_sample():
    """``bench/control.py``'s readings: the run's own sample, the program's
    gaps within the limits and the control's beyond them."""
    cell = small_cell("paper-uniform-1000k.tiled-soa")
    r = run.run_cell(cell, SEED, 0.0, False, t_start=time.perf_counter(), min_calls=2, control=True)
    assert r["correct"]
    ok, _ = check.verdict(r["control"], cell["limits"]["limits"])
    assert not ok
