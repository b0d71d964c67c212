"""Helpers for the benchmark's own CPU tests: a checkout in a temporary
directory holding a copy of the benchmark at sizes a test can run."""
from __future__ import annotations

import json
import os
import shutil
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# each configuration at a size a CPU test holds; widths as small as the
# program's smoke configurations
SMALL = {
    "hpcg-104": {"nx": 12, "ny": 10, "nz": 8},
    "granite-moe-3b-a800m": {
        "hidden_size": 128, "intermediate_size": 64, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_local_experts": 16, "num_experts_per_tok": 4,
        "vocab_size": 2048},
}
SMALL_TRAFFIC = {"decode-closed-32": {"clients": 8, "batch_buckets": [8],
                                      "prompt_lens": [8, 16],
                                      "new_tokens": [4, 12],
                                      "new_token_levels": 5,
                                      "think_s": [0.0, 0.02],
                                      "seq_bucket": 32, "check_requests": 8}}


def small_checkout(root: Path) -> Path:
    """Copy of BENCHMARK.json and bench/ under ``root`` with every
    configuration and traffic mix cut to the sizes above; ``src`` links to
    the program."""
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    os.symlink(REPO / "src", root / "src")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        f = root / c["file"]
        f.write_text(json.dumps({**json.loads(f.read_text()),
                                 **SMALL.get(c["name"], {})}))
    for name, small in SMALL_TRAFFIC.items():
        f = root / "bench" / "traffic" / f"{name}.json"
        f.write_text(json.dumps({**json.loads(f.read_text()), **small}))
    return root


@pytest.fixture
def checkout(tmp_path):
    return small_checkout(tmp_path)


def run(root: Path, workload: str, **kw):
    import harness
    kw.setdefault("seconds", 1.0)
    return harness.run_cell(root, workload, kw.pop("seed", 2**31 + 7),
                            kw.pop("seconds"), kw.pop("trace", False),
                            t_start=time.perf_counter(), require_chip=False,
                            **kw)
