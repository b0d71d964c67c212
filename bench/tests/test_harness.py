"""The harness finds a cell by its files, and refuses to run without the
program or the chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, REPO, run


def test_a_cell_added_by_files_and_entries_alone(checkout):
    """A new configuration, traffic mix and per-layer metric, each a file
    of its own, run as a new cell without an edit to any existing file."""
    home = checkout / "bench"
    cfg = json.loads((home / "configs" / "hpcg-104.json").read_text())
    (home / "configs" / "hpcg-6.json").write_text(
        json.dumps({**cfg, "nx": 6, "ny": 6, "nz": 6}))
    shutil.copy(home / "configs" / "hpcg-104.ref.py",
                home / "configs" / "hpcg-6.ref.py")
    (home / "traffic" / "cg-sets-5.json").write_text(
        json.dumps({"iterations_per_set": 5}))
    (home / "metrics" / "sets_per_s.py").write_text(
        "def read(ctx):\n"
        "    return ctx.counters['sets'] / ctx.counters['window_s']\n")
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "hpcg-6", "source": "test",
                             "file": "bench/configs/hpcg-6.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "cg.hpcg6", "config": "hpcg-6",
                               "traffic": "cg-sets-5", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "cg_iter_ms":
            m["workloads"].append("cg.hpcg6")
    bench["per_layer"].append({"name": "sets_per_s", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "CG iteration", "moves": "cg_iter_ms",
                               "workloads": ["cg.hpcg6"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))

    e2e = run(checkout, "cg.hpcg6").result
    assert e2e["correct"] and set(e2e["metrics"]) == {"cg_iter_ms", "setup_s"}
    traced = run(checkout, "cg.hpcg6", trace=True).result
    assert traced["correct"]
    assert set(traced["metrics"]) == {"sets_per_s"}
    assert traced["metrics"]["sets_per_s"]["value"] > 0
    assert traced["device"]["busy_s"] > 0
    assert list(traced)[-1] == "checks"


def test_without_the_program_the_command_fails_and_prints_nothing(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable] + bench["command"]
        + ["--workload", "cg.hpcg104", "--seed", str(2**31 + 3),
           "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not proc.stdout.strip()
