"""The per-layer metrics that read the program's ``lilac.*`` and
``serve.*`` spans from its own span table (``repro.core.spans``): the
set-up totals, and the window's guard and engine host time from the spans
a profiler recorded."""
from __future__ import annotations

import time

import pytest

import harness
from conftest import BENCH

spans = pytest.importorskip("repro.core.spans")


@pytest.fixture(autouse=True)
def _fresh_table():
    spans.reset()
    yield
    spans.reset()


def _reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


def _ctx():
    return harness.MetricContext(config={}, traffic={}, peak={},
                                 summary=None, counters={}, end_to_end={})


def _record(*names, pause=0.0):
    """Nested spans, outermost first, around a pause."""
    if not names:
        time.sleep(pause)
        return
    with spans.span(names[0]):
        _record(*names[1:], pause=pause)


@pytest.mark.parametrize("name,scale", [("lilac_guard_us.cg", 1e6),
                                        ("lilac_guard_ms.decode", 1e3)])
def test_guard_readers(name, scale, monkeypatch):
    read = _reader(name).read
    # set-up and warm-up before the window: not read
    _record("lilac.dispatch", "lilac.detect", pause=0.02)
    _record("lilac.dispatch", "lilac.enqueue")
    assert read(_ctx()) is None
    monkeypatch.setattr(spans, "_profiling", lambda: True)
    _record("lilac.dispatch", pause=0.001)
    assert read(_ctx()) is None             # no enqueue in the window
    _record("lilac.dispatch", "lilac.enqueue", pause=0.002)
    got = spans.totals(traced=True)
    want = (got["lilac.dispatch"]["total_s"]
            - got["lilac.enqueue"]["total_s"]) / 2 * scale
    assert got["lilac.dispatch"]["count"] == 2
    assert read(_ctx()) == pytest.approx(want)
    assert 0.0005 * scale <= read(_ctx()) < 0.005 * scale


def test_engine_host_reader(monkeypatch):
    read = _reader("engine_host_ms.decode").read
    _record("serve.step", "serve.readback", pause=0.01)  # before the window
    assert read(_ctx()) is None
    monkeypatch.setattr(spans, "_profiling", lambda: True)
    _record("serve.step", "serve.sample", pause=0.001)
    assert read(_ctx()) is None             # no readback in the window
    for _ in range(2):
        with spans.span("serve.step"):
            _record("serve.readback", pause=0.002)
            _record("serve.sample", pause=0.001)
    got = spans.totals(traced=True)
    assert got["serve.step"]["count"] == 3
    want = (got["serve.step"]["total_s"]
            - got["serve.readback"]["total_s"]) / 3 * 1e3
    assert read(_ctx()) == pytest.approx(want)
    assert 1.0 <= read(_ctx()) < 5.0


@pytest.mark.parametrize("name,span", [
    ("lilac_detect_s.cg", "lilac.detect"), ("lilac_tune_s.cg", "lilac.tune"),
    ("lilac_marshal_s.cg", "lilac.marshal"), ("lilac_bake_s.cg", "lilac.bake")])
def test_set_up_readers(name, span):
    read = _reader(name).read
    with spans.span("lilac.dispatch"):
        pass
    assert read(_ctx()) is None
    for pause in (0.002, 0.003):
        with spans.span(span):
            time.sleep(pause)
    got = read(_ctx())
    assert got == spans.totals()[span]["total_s"]
    assert 0.005 <= got < 1.0


@pytest.mark.parametrize("name", [
    "lilac_detect_s.cg", "lilac_tune_s.cg", "lilac_marshal_s.cg",
    "lilac_bake_s.cg", "lilac_guard_us.cg", "lilac_guard_ms.decode",
    "engine_host_ms.decode"])
def test_readers_of_a_program_without_spans(name, monkeypatch):
    """On a program without ``repro.core.spans`` (an older checkout)
    every reader leaves its metric out of the line, and raises nothing."""
    import builtins
    real = builtins.__import__

    def no_spans(mod, globals=None, locals=None, fromlist=(), level=0):
        if mod == "repro.core" and "spans" in (fromlist or ()):
            raise ImportError("cannot import name 'spans'")
        return real(mod, globals, locals, fromlist, level)

    _record("lilac.dispatch", "lilac.enqueue")
    monkeypatch.setattr(builtins, "__import__", no_spans)
    assert _reader(name).read(_ctx()) is None
