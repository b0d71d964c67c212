"""Finds a cell by its name and runs it once: set-up, window, check.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

  <config file>                  sizes, as run, and the driver that runs them
  <config file minus .json>.ref.py   its plain reference and the comparison
  bench/traffic/<traffic>.json   parameters of the traffic mix
  bench/metrics/<metric>.py      ``read(ctx)``: one per-layer metric
  bench/drivers/<driver>.py      ``Run``: how a kind of system is driven

so a later change adds a cell, configuration or metric by adding files and
entries, and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

CACHE = ".bench_cache"          # inside the checkout, listed in .gitignore


def load_module(path: Path, name: Optional[str] = None):
    if not path.is_file():
        raise FileNotFoundError(f"missing benchmark file {path}")
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    root: Path
    bench: Dict[str, Any]
    workload: Dict[str, Any]
    config: Dict[str, Any]
    config_file: Path
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def home(self) -> Path:
        """The benchmark's own directory (the first of ``paths``)."""
        return self.root / self.bench["paths"][0]

    def driver(self):
        return load_module(self.home / "drivers" / f"{self.config['driver']}.py")

    def reference(self):
        return load_module(self.config_file.with_name(
            self.config_file.name[: -len(".json")] + ".ref.py"))

    def metric_reader(self, name: str):
        return load_module(self.home / "metrics" / f"{name}.py")


def _for_cell(metrics: List[Dict[str, Any]], cell: str) -> List[Dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def find_cell(root: Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfile = root / configs[w["config"]]["file"]
    home = root / bench["paths"][0]
    e2e = _for_cell(bench["end_to_end"], workload)
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in _for_cell(bench["per_layer"], workload)
                 if "workloads" in m or m["moves"] in reported]
    return Cell(root=root, bench=bench, workload=w,
                config=json.loads(cfile.read_text()), config_file=cfile,
                traffic=json.loads(
                    (home / "traffic" / f"{w['traffic']}.json").read_text()),
                end_to_end=e2e, per_layer=per_layer)


def prepare_env(root: Path) -> Path:
    """Caches inside the checkout at fixed paths: XLA's compile cache is
    kept between runs; the LiLAC tuner, plan and quarantine stores are
    emptied, so every run is the first process of a fresh deployment.
    Must run before jax is imported."""
    cache = root / CACHE
    stores = cache / "lilac"
    shutil.rmtree(stores, ignore_errors=True)
    stores.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache / "jax")
    os.environ["LILAC_AUTOTUNE_CACHE"] = str(stores / "autotune.json")
    os.environ["LILAC_PLAN_CACHE"] = str(stores / "plans.json")
    os.environ["LILAC_QUARANTINE_CACHE"] = str(stores / "quarantine.json")
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    return cache


def configure_jax():
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"this cell needs {chips} TPU chip(s); jax reports "
            f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def memory_peak(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclass
class MetricContext:
    """What a per-layer metric's ``read(ctx)`` may read."""
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    peak: Dict[str, float]
    summary: Any                       # trace.Summary
    counters: Dict[str, Any]
    end_to_end: Dict[str, float]
    work: Any = None                   # the bench/work.py module


@dataclass
class Outcome:
    result: Dict[str, Any]
    checks: List[Dict[str, Any]] = field(default_factory=list)
    control: List[Dict[str, Any]] = field(default_factory=list)
    notes: List[str] = field(default_factory=list)


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_chip: bool = True,
             control: bool = False,
             after_setup: Optional[Callable[[Any], None]] = None) -> Outcome:
    """One run of one cell.  ``t_start`` is the process's start on the
    ``time.perf_counter`` clock; ``after_setup`` lets a test break the
    timed path underneath before the window opens."""
    cell = find_cell(root, workload)
    cache = prepare_env(root)
    configure_jax()
    devs = devices_for(int(cell.workload["chips"]), require_chip)
    work = load_module(cell.home / "work.py", "bench_work")
    ref = cell.reference()
    run = cell.driver().Run(cell.config, cell.traffic, seed, log=log,
                            reference=ref)
    run.setup()
    if after_setup is not None:
        after_setup(run)
    setup_s = time.perf_counter() - t_start
    summary = None
    if trace:
        tr = load_module(cell.home / "trace.py", "bench_trace")
        tracer = tr.Tracer(str(cache / "trace"))
        # a serving window emits more device events than the profiler
        # keeps in one trace: a mix may ask for a shorter traced window
        traced = min(seconds, float(cell.traffic.get("trace_seconds",
                                                     seconds)))
        with tracer:
            measured = run.measure(traced, tracer.span)
        summary = tr.reduce(*tr.read_xplane(
            tracer.xplane(), host_ops=devs[0].platform == "cpu"))
        shutil.rmtree(tracer.log_dir, ignore_errors=True)
    else:
        from contextlib import nullcontext
        measured = run.measure(seconds, lambda name: nullcontext())
    peak_bytes = memory_peak(devs)
    run.release()
    checks = [check(*c) for c in run.check(ref)]
    ctl = [{"name": n, "value": float(v)}
           for n, v in run.control(ref)] if control else []
    e2e = dict(measured["metrics"], setup_s=setup_s)
    metrics: Dict[str, Dict[str, Any]] = {}
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        ctx = MetricContext(cell.config, cell.traffic,
                            work.peak_for(devs[0].device_kind,
                                          cell.home / "peaks.json")
                            if devs[0].platform == "tpu" else {},
                            summary, measured["counters"], e2e, work)
        for m in cell.per_layer:
            value = cell.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    result = {"correct": all(c["ok"] for c in checks) and bool(checks),
              "attempted": measured["attempted"],
              "failed": measured["failed"],
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return Outcome(result, checks, ctl, list(run.notes))


def check(name: str, value: float, limit: float) -> Dict[str, Any]:
    """One compared number beside its limit; it passes at or under it."""
    return {"name": name, "value": float(value), "limit": float(limit),
            "ok": bool(value <= limit)}
