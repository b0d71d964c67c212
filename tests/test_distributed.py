"""Distributed-path tests (subprocess with forced host devices): the
launcher trains on a real (data, model) mesh with shard_map MoE EP, and
the dry-run machinery lowers/compiles a cell end-to-end."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 4, timeout: int = 900):
    env = {**os.environ, "PYTHONPATH": SRC,
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_train_on_2x2_mesh_with_moe_ep():
    code = """
import sys
sys.argv = ["train", "--arch", "olmoe-1b-7b", "--smoke", "--steps", "3",
            "--batch", "4", "--seq", "32", "--mesh-data", "2",
            "--mesh-model", "2", "--moe-impl", "grouped"]
from repro.launch.train import main
main()
print("DIST_TRAIN_OK")
"""
    proc = _run(code)
    assert "DIST_TRAIN_OK" in proc.stdout, proc.stderr[-2000:]


def test_gradient_compression_on_mesh():
    code = """
import sys
sys.argv = ["train", "--arch", "olmo-1b", "--smoke", "--steps", "3",
            "--batch", "4", "--seq", "32", "--mesh-data", "4",
            "--mesh-model", "1", "--compress-grads"]
from repro.launch.train import main
main()
print("COMPRESS_OK")
"""
    proc = _run(code)
    assert "COMPRESS_OK" in proc.stdout, proc.stderr[-2000:]


def test_dryrun_machinery_small():
    """analyze_cell on a reduced arch x tiny mesh — exercises lowering,
    memory analysis and the HLO walker end to end (the production 512-dev
    sweep lives in experiments/dryrun)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
from repro.configs import get_arch, smoke_config, SHAPES
from repro.launch import dryrun as DR
from repro.launch.mesh import mesh_rules
from repro.models import build_model
from repro.train import optim as O, train_step as TS

cfg = smoke_config(get_arch("olmoe-1b-7b")).replace(
    spmd_constraints=True, mesh_axis_sizes=(("data", 2), ("model", 2)))
model = build_model(cfg)
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(2, 2)
rules = mesh_rules(False)
opt_cfg = O.AdamWConfig()
step = TS.make_train_step(model, opt_cfg)
pshard = TS.param_shardings(model, mesh, rules)
oshard = TS.opt_state_shardings(model, opt_cfg, mesh, rules)
abs_params = model.abstract_params()
abs_opt = jax.eval_shape(lambda p: O.adamw_init(opt_cfg, p), abs_params)
import jax.numpy as jnp
abs_batch = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
with jax.set_mesh(mesh):
    lowered = jax.jit(step, in_shardings=(pshard, oshard, None),
                      donate_argnums=(0, 1)).lower(
        abs_params, abs_opt, abs_batch)
compiled = lowered.compile()
stats = DR.analyze_hlo(compiled.as_text())
assert stats["flops"] > 0
assert compiled.memory_analysis() is not None
print("DRYRUN_OK", int(stats["flops"]),
      stats["collectives"]["total_bytes"])
"""
    proc = _run(code)
    assert "DRYRUN_OK" in proc.stdout, proc.stderr[-2000:]


def test_production_sweep_results_green():
    """The committed 512-device sweep must be all ok/skip (the deliverable:
    multi-pod compile succeeds for every cell)."""
    import glob
    import json
    jobs = glob.glob(os.path.join(os.path.dirname(__file__), "..",
                                  "experiments", "dryrun", "*.json"))
    if len(jobs) < 80:
        pytest.skip("sweep not yet complete")
    statuses = {}
    for f in jobs:
        d = json.load(open(f))
        statuses[os.path.basename(f)] = d["status"]
    bad = {k: v for k, v in statuses.items() if v not in ("ok", "skip")}
    assert not bad, bad
    n_multi_ok = sum(1 for k, v in statuses.items()
                     if v == "ok" and "__multi" in k)
    assert n_multi_ok >= 31   # every runnable cell compiles multi-pod
