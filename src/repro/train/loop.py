"""The training loop: jit'd step + checkpoint/restart + straggler hooks.

This is the driver used by examples/train_e2e.py and launch/train.py.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.factory import Model
from repro.train import optim as O
from repro.train import train_step as TS
from repro.train.checkpoint import Checkpointer
from repro.train.elastic import StragglerMonitor, heartbeat


@dataclasses.dataclass
class LoopConfig:
    steps: int = 100
    ckpt_every: int = 50
    log_every: int = 10
    ckpt_dir: Optional[str] = None
    resume: bool = True


def train_loop(model: Model, opt_cfg: O.AdamWConfig, loop_cfg: LoopConfig,
               batch_fn: Callable[[int], Dict[str, np.ndarray]],
               mesh=None, rules=None, params=None,
               emit: Callable[[str], None] = print) -> Dict[str, Any]:
    """Runs the loop; returns {params, opt_state, history, straggler}."""
    step_fn = TS.make_train_step(model, opt_cfg)
    mesh_ctx = None
    if mesh is not None:
        mesh_ctx = jax.set_mesh(mesh)
        mesh_ctx.__enter__()   # shard_map/constraints need the context mesh
        pshard = TS.param_shardings(model, mesh, rules)
        oshard = TS.opt_state_shardings(model, opt_cfg, mesh, rules)
        step_fn = jax.jit(step_fn,
                          in_shardings=(pshard, oshard, None),
                          out_shardings=(pshard, oshard, None),
                          donate_argnums=(0, 1))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    if params is None:
        params = model.init(jax.random.key(0))
    opt_state = O.adamw_init(opt_cfg, params)
    if mesh is not None:
        params = jax.tree.map(jax.device_put, params, pshard)
        opt_state = jax.tree.map(jax.device_put, opt_state, oshard)

    start_step = 0
    ckpt = None
    if loop_cfg.ckpt_dir:
        ckpt = Checkpointer(loop_cfg.ckpt_dir)
        latest = ckpt.latest_step() if loop_cfg.resume else None
        if latest is not None:
            state = ckpt.restore(latest, {"params": params,
                                          "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            start_step = latest
            emit(f"[restart] restored checkpoint step {latest}")

    mon = StragglerMonitor()
    history = []
    for step in range(start_step, loop_cfg.steps):
        batch = {k: jnp.asarray(v) for k, v in batch_fn(step).items()}
        t0 = time.perf_counter()
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        mon.observe(step, dt)
        history.append(float(metrics["loss"]))
        heartbeat(step, {**metrics, "sec": dt},
                  log_every=loop_cfg.log_every, emit=emit)
        if ckpt and (step + 1) % loop_cfg.ckpt_every == 0:
            ckpt.save(step + 1, {"params": params, "opt": opt_state})
    if ckpt:
        ckpt.save(loop_cfg.steps, {"params": params, "opt": opt_state},
                  blocking=True)
    if mesh_ctx is not None:
        mesh_ctx.__exit__(None, None, None)
    return {"params": params, "opt_state": opt_state, "history": history,
            "straggler": mon}
