"""Integration test of the dry-run machinery itself on a REAL multi-device
mesh (8 simulated devices): build_cell -> jit(in/out shardings) -> lower ->
compile for reduced configs of a dense and a MoE arch, train + decode kinds.
This is the same code path the 512-device production dry-run exercises."""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax
import numpy as np
from repro.configs import ARCHS, ShapeConfig, smoke
from repro.launch.specs import build_cell
from repro.models import build_model
from repro.train.steps import make_serve_step, make_train_step
from repro.launch.dryrun import collective_census

mesh = jax.make_mesh((2, 4), ("data", "model"), devices=jax.devices())

for arch_name in ("minitron-4b", "mixtral-8x7b"):
    cfg = dataclasses.replace(smoke(ARCHS[arch_name]), d_model=64, vocab_size=256)
    model = build_model(cfg)
    # train cell
    shape = ShapeConfig("t", "train", seq_len=32, global_batch=8, accum_steps=2)
    cell = build_cell(model, cfg, shape, mesh)
    fn = make_train_step(model, cfg, shape, mesh=mesh, rules=cell["rules"])
    compiled = jax.jit(fn, in_shardings=cell["in_shardings"],
                       out_shardings=cell["out_shardings"]).lower(*cell["args"]).compile()
    hlo = compiled.as_text()
    census = collective_census(hlo)
    assert census["all-reduce"]["count"] > 0, f"{arch_name}: train must all-reduce grads"
    # decode cell
    shape = ShapeConfig("d", "decode", seq_len=64, global_batch=8)
    cell = build_cell(model, cfg, shape, mesh)
    fn = make_serve_step(model, cfg, mesh=mesh, rules=cell["rules"])
    compiled = jax.jit(fn, in_shardings=cell["in_shardings"],
                       out_shardings=cell["out_shardings"]).lower(*cell["args"]).compile()
    assert compiled.cost_analysis().get("flops", 0) > 0
    print(f"OK {arch_name}")
print("OK dryrun-machinery")
"""


@pytest.mark.slow
def test_dryrun_machinery_8dev():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(__file__), "..", ".."), timeout=900,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK dryrun-machinery" in r.stdout
