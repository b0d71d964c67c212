"""The reduction from a trace to busy time, idle gaps and op times."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

import trace as bench_trace

DATA = Path(__file__).resolve().parent / "data"


def test_reduce_by_hand():
    ops = [("/device:TPU:0", "jit_a", "A", 10, 20),
           ("/device:TPU:0", "jit_b", "B", 30, 50),
           ("/device:TPU:0", "jit_a", "C", 45, 60),
           ("/device:TPU:0", "jit_a", "late", 100, 120),
           ("/device:TPU:0", "jit_a", "early", -20, -10)]
    spans = [("bench.window", 0, 100), ("bench.cg.sync", 20, 30),
             ("bench.cg.spmv", 55, 100), ("bench.cg.spmv", 200, 300)]
    s = bench_trace.reduce(ops, spans)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(40e-9)         # [10,20] + [30,60]
    assert s.idle_share == pytest.approx(0.6)
    assert s.op_s == pytest.approx({"jit_a/A": 10e-9, "jit_b/B": 20e-9,
                                    "jit_a/C": 15e-9})
    assert s.module_s == pytest.approx({"jit_a": 25e-9, "jit_b": 20e-9})
    assert s.idle_gaps[0] == ("bench.cg.spmv", pytest.approx(40e-9))
    assert sorted(g[0] for g in s.idle_gaps[1:]) == ["bench.cg.sync", "none"]
    assert s.ops_matching("a") == pytest.approx(10e-9)
    assert s.breakdown()["device_ops"][0] == ["jit_b/B", pytest.approx(20e-9)]


def test_reduce_needs_a_window_and_a_device_op():
    with pytest.raises(ValueError):
        bench_trace.reduce([("/device:TPU:0", "m", "A", 0, 1)], [])
    with pytest.raises(ValueError):
        bench_trace.reduce([], [("bench.window", 0, 10)])


@pytest.mark.parametrize("name", sorted(p.stem for p in DATA.glob("*.json")))
def test_recorded_trace(name):
    """A slice of a real TPU trace of the cell, with the numbers its
    reduction gave when it was recorded."""
    rec = json.loads((DATA / f"{name}.json").read_text())
    s = bench_trace.reduce([tuple(o) for o in rec["ops"]],
                           [tuple(sp) for sp in rec["spans"]])
    for key, want in rec["expect"].items():
        assert getattr(s, key) == pytest.approx(want), key
    assert 0 < s.busy_s <= s.window_s
