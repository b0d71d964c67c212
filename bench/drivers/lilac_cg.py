"""Drives a CG solver whose SpMV is written naively and accelerated by the
LiLAC pass, as a user of the library runs it.

Set-up makes the stencil matrix on the device in CSR, compiles the naive
SpMV with ``lilac.compile`` (detect, tune, marshal, bake on the first
call), and warms the solver's own steps.  The window runs sets of CG
iterations back to back, each set from a fresh right-hand side drawn from
the seed, and reads the residual norm on the host after every iteration,
as HPCG's convergence check does.
"""
from __future__ import annotations

import gc
import itertools
import json
import math
import time
from typing import Any, Dict, List

import numpy as np


def stencil_csr(nx: int, ny: int, nz: int, diag: float, off: float):
    """HPCG's 27-point matrix in CSR, built on the device in one program:
    row i = (ix, iy, iz) holds ``diag`` at i and ``off`` at each neighbour
    inside the grid, columns ascending."""
    import jax
    import jax.numpy as jnp
    n = nx * ny * nz
    nnz = (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)

    @jax.jit
    def build():
        i = jnp.arange(n, dtype=jnp.int32)
        ix, iy, iz = i % nx, (i // nx) % ny, i // (nx * ny)
        cols, oks, vals = [], [], []
        for dz, dy, dx in itertools.product((-1, 0, 1), repeat=3):
            oks.append((ix + dx >= 0) & (ix + dx < nx) & (iy + dy >= 0)
                       & (iy + dy < ny) & (iz + dz >= 0) & (iz + dz < nz))
            cols.append(i + dz * nx * ny + dy * nx + dx)
            vals.append(diag if (dx, dy, dz) == (0, 0, 0) else off)
        ok = jnp.stack(oks, axis=1)
        col = jnp.stack(cols, axis=1)
        val = jnp.broadcast_to(jnp.asarray(vals, jnp.float32), col.shape)
        keep = jnp.nonzero(ok.reshape(-1), size=nnz)[0]
        row_ptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(ok.sum(axis=1, dtype=jnp.int32))])
        return val.reshape(-1)[keep], col.reshape(-1)[keep], row_ptr

    return n, nnz, build()


def naive_spmv_fn(n: int, nnz: int):
    """The SpMV as a user writes it in JAX: gather, multiply, segment sum."""
    import jax
    import jax.numpy as jnp

    def naive_spmv(val, col, row_ptr, v):
        row = jnp.repeat(jnp.arange(n, dtype=jnp.int32), jnp.diff(row_ptr),
                         total_repeat_length=nnz)
        return jax.ops.segment_sum(val * v[col], row, num_segments=n)
    return naive_spmv


def _solver_steps(n: int, seed: int):
    """The solver's own jitted steps and the seeded right-hand sides."""
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(seed)

    @jax.jit
    def bench_cg_rhs(k):
        b = jax.random.normal(jax.random.fold_in(key, k), (n,), jnp.float32)
        return b, jnp.dot(b, b)

    @jax.jit
    def bench_cg_update(x, r, p, ap, rs):
        alpha = rs / jnp.dot(p, ap)
        x = x + alpha * p
        r = r - alpha * ap
        return x, r, jnp.dot(r, r)

    @jax.jit
    def bench_cg_direction(r, p, rs_new, rs):
        return r + (rs_new / rs) * p

    return bench_cg_rhs, bench_cg_update, bench_cg_direction


WARM_SET = 1 << 30      # the warm-up's right-hand side; the window's are 0, 1, ...


class _CompileCounter:
    """Counts XLA compilations while ``active``."""

    def __init__(self):
        import jax
        self.active = False
        self.count = 0

        def on_event(event, duration, **kw):
            if self.active and event.endswith("backend_compile_duration"):
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)


class Run:
    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, log=print, reference=None):
        self.cfg, self.traffic, self.log = config, traffic, log
        self.iters = int(traffic["iterations_per_set"])
        self.seed = int(np.random.default_rng(seed).integers(2**31 - 1))
        self.notes: List[str] = []
        self.sets: List[Dict[str, Any]] = []
        self.dispatch_s: List[float] = []

    # -- set-up ------------------------------------------------------------

    def setup(self):
        import jax
        from repro import lilac
        c = self.cfg
        t0 = time.perf_counter()
        self.n, self.nnz, (val, col, row_ptr) = stencil_csr(
            c["nx"], c["ny"], c["nz"], c["diagonal"], c["off_diagonal"])
        self.matrix = (val, col, row_ptr)
        jax.block_until_ready(self.matrix)
        self.log(f"matrix: n={self.n} nnz={self.nnz} built in "
                 f"{time.perf_counter() - t0:.3f} s")
        timings: List[Dict[str, Any]] = []
        tuner = lilac.REGISTRY.autotuner
        time_pool = tuner._time_pool

        def observed(h, binding, ctx, mode, operands, schedule, fuse, reps):
            t = time_pool(h, binding, ctx, mode, operands, schedule, fuse,
                          reps)
            timings.append({"harness": h.name, "schedule": schedule,
                            "fuse": fuse, "reps": reps, "seconds": t})
            return t
        tuner._time_pool = observed
        self.spmv = lilac.compile(naive_spmv_fn(self.n, self.nnz),
                                  mode=c["lilac_mode"],
                                  policy=c["lilac_policy"])
        self.rhs, self.update, self.direction = _solver_steps(self.n,
                                                              self.seed)
        t0 = time.perf_counter()
        b, rs = self.rhs(0)
        jax.block_until_ready(self.spmv(*self.matrix, b))
        self.log(f"lilac first call (detect, tune, marshal, bake): "
                 f"{time.perf_counter() - t0:.3f} s")
        del tuner._time_pool
        pick = {"harness": [name for _, name in self.spmv.last_selections],
                "schedule": self.spmv.last_schedules,
                "fuse": getattr(tuner.last_decision, "fuse", None),
                "plan": {k: v for k, v in self.spmv.plan_info().items()
                         if k in ("baked", "bake_errors")}}
        self.notes.append("tuner pick: " + json.dumps(pick, default=str))
        self.notes.append("tuner timings: " + json.dumps(timings, default=str))
        self._run_set(WARM_SET, 2, lambda name: _null(), deadline=None)
        self.sets.clear()
        self.compiles = _CompileCounter()

    # -- window ------------------------------------------------------------

    def _run_set(self, k: int, iters: int, span, deadline):
        import jax.numpy as jnp
        b, rs = self.rhs(k)
        x, r, p = jnp.zeros_like(b), b, b
        hist: List[float] = []
        ap0 = None
        for it in range(iters):
            t = time.perf_counter()
            with span("bench.cg.spmv"):
                ap = self.spmv(*self.matrix, p)
            self.dispatch_s.append(time.perf_counter() - t)
            if it == 0:
                ap0 = ap
            with span("bench.cg.update"):
                x, r, rs_new = self.update(x, r, p, ap, rs)
            with span("bench.cg.sync"):
                hist.append(math.sqrt(float(rs_new)))
            p = self.direction(r, p, rs_new, rs)
            rs = rs_new
            if deadline is not None and time.perf_counter() >= deadline:
                break
        done = len(hist) == iters
        self.sets.append({"k": k, "done": done, "ap0": ap0, "x": x,
                          "hist": hist})
        return len(hist)

    def measure(self, seconds: float, span) -> Dict[str, Any]:
        self.dispatch_s = []
        self.compiles.active = True
        gc.collect()
        gc.disable()
        n_iters = 0
        with span("bench.window"):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            for k in itertools.count():
                n_iters += self._run_set(k, self.iters, span, deadline)
                if time.perf_counter() >= deadline:
                    break
            window = time.perf_counter() - t0
        gc.enable()
        self.compiles.active = False
        self.log(f"window: {window:.3f} s, {n_iters} iterations in "
                 f"{len(self.sets)} sets, compiles in window "
                 f"{self.compiles.count}")
        failed = sum(1 for s in self.sets
                     if not all(map(math.isfinite, s["hist"])))
        return {"metrics": {"cg_iter_ms": window / n_iters * 1e3},
                "counters": {"iterations": n_iters, "spmv_calls": n_iters,
                             "sets": len(self.sets), "window_s": window,
                             "dispatch_s": list(self.dispatch_s),
                             "n": self.n, "nnz": self.nnz,
                             "compiles_in_window": self.compiles.count},
                "attempted": len(self.sets), "failed": failed}

    def release(self):
        """Frees the program's state; keeps each finished set's first
        product and final iterate on the host for the check."""
        for s in self.sets:
            s["ap0"], s["x"] = np.asarray(s["ap0"]), np.asarray(s["x"])
        self.matrix = self.spmv = None
        gc.collect()

    # -- correctness -------------------------------------------------------

    def _readings(self, ref, control: bool) -> Dict[str, float]:
        """The compared numbers over every finished set, each against the
        reference's CG on the same right-hand side."""
        out = {"spmv_err": 0.0, "res_gap": 0.0, "x_err": 0.0}
        for s in [s for s in self.sets if s["done"]]:
            b, _ = self.rhs(s["k"])
            ap0, x, hist = _f64(ref.cg(self.cfg, b, self.iters))
            got = dict(zip(("ap0", "x", "hist"), _f64(ref.cg(
                self.cfg, b, self.iters, dtype=_bf16())))) if control else s
            out["spmv_err"] = max(out["spmv_err"], float(
                np.abs(got["ap0"] - ap0).max() / np.abs(ap0).max()))
            out["res_gap"] = max(out["res_gap"], float(
                np.max(np.abs(np.asarray(got["hist"]) - hist))
                / np.linalg.norm(np.asarray(b, np.float64))))
            out["x_err"] = max(out["x_err"], float(
                np.linalg.norm(got["x"] - x) / np.linalg.norm(x)))
        return out

    def check(self, ref):
        if not any(s["done"] for s in self.sets):
            return [("sets_compared", 0, -1)]
        got = self._readings(ref, control=False)
        lim = self.cfg["limits"]
        return [(name, got[name], lim[name]) for name in lim]

    def control(self, ref):
        """Readings of the control: the reference in bfloat16 in the
        program's place, compared as the program is."""
        return sorted(self._readings(ref, control=True).items())


def _null():
    import contextlib
    return contextlib.nullcontext()


def _bf16():
    import jax.numpy as jnp
    return jnp.bfloat16


def _f64(arrays):
    return tuple(np.asarray(a, np.float64) for a in arrays)
