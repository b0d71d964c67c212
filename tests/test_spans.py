"""The program's spans and counters (``repro.core.spans``): the in-memory
tables, the profiler round trip, and the spans the LiLAC pass and the
serving engine record."""
from __future__ import annotations

import importlib.util
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import spans

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _fresh_table():
    spans.reset()
    yield
    spans.reset()


def _bench_trace():
    """``bench/trace.py``, loaded by path (the benchmark is no package)."""
    spec = importlib.util.spec_from_file_location(
        "bench_trace_for_spans", REPO / "bench" / "trace.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _counts():
    return {k: v["count"] for k, v in spans.totals().items()}


def test_totals_of_nested_spans():
    for pause in (0.002, 0.006):
        with spans.span("lilac.dispatch"):
            with spans.span("lilac.enqueue"):
                time.sleep(pause)
    got = spans.totals()
    assert set(got) == {"lilac.dispatch", "lilac.enqueue"}
    outer, inner = got["lilac.dispatch"], got["lilac.enqueue"]
    assert outer["count"] == inner["count"] == 2
    assert inner["total_s"] >= 0.008
    assert outer["total_s"] >= inner["total_s"]
    assert 0.006 <= inner["max_s"] <= inner["total_s"] - 0.002
    assert outer["max_s"] >= inner["max_s"]
    spans.reset()
    assert spans.totals() == {}


def test_a_span_records_when_its_block_raises():
    with pytest.raises(ValueError):
        with spans.span("lilac.bake"):
            raise ValueError("bake failed")
    assert _counts() == {"lilac.bake": 1}


def test_count():
    spans.count("lilac.marshal_bytes", 100)
    spans.count("lilac.marshal_bytes", 28)
    spans.count("serve.things")
    assert spans.totals() == {"lilac.marshal_bytes": {"count": 128},
                              "serve.things": {"count": 1}}


def _host_spans(path):
    """The ``lilac.*`` and ``serve.*`` events of a trace's host planes, as
    (name, start ns, end ns)."""
    from jax.profiler import ProfileData
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(("lilac.", "serve."))]


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One CPU profiler trace, written by the benchmark's ``Tracer``,
    holding a span around a jitted op and two prefill spans that carry
    request ids; its program spans as read back from the trace file, the
    file, and the program's table of the spans the profiler recorded."""
    import jax
    import jax.numpy as jnp
    trace = _bench_trace()
    tracer = trace.Tracer(str(tmp_path_factory.mktemp("profile")))
    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.arange(1024.0)
    spans.reset()
    with spans.span("lilac.enqueue"):
        jax.block_until_ready(f(x))
    with tracer:
        with spans.span("lilac.enqueue"):
            jax.block_until_ready(f(x))
        for rid in (7, 8):
            with spans.span("serve.prefill", rid=rid):
                jax.block_until_ready(f(x))
    with spans.span("serve.prefill", rid=9):
        pass
    traced, every = spans.totals(traced=True), spans.totals()
    spans.reset()
    return _host_spans(tracer.xplane()), tracer.xplane(), traced, every


def test_profiler_round_trip_reads_the_bare_name(profiled):
    host = profiled[0]
    names = [name for name, _, _ in host]
    assert names.count("lilac.enqueue") == 1
    start, end = next((s, e) for n, s, e in host if n == "lilac.enqueue")
    assert end > start


def test_request_id_is_a_stat_not_part_of_the_name(profiled):
    host, path = profiled[:2]
    assert [n for n, _, _ in host].count("serve.prefill") == 2
    assert not any("rid" in n or "#" in n for n, _, _ in host)
    from jax.profiler import ProfileData
    rids = sorted(
        int(dict(ev.stats)["rid"])
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name == "serve.prefill")
    assert rids == [7, 8]
    with spans.span("serve.prefill", rid=9):
        pass
    assert "serve.prefill" in spans.totals()
    assert not any("9" in name for name in spans.totals())


def test_traced_table_holds_only_the_spans_a_profiler_recorded(profiled):
    _, _, traced, every = profiled
    assert {k: v["count"] for k, v in traced.items()} == {
        "lilac.enqueue": 1, "serve.prefill": 2}
    assert {k: v["count"] for k, v in every.items()} == {
        "lilac.enqueue": 2, "serve.prefill": 3}
    spans.count("lilac.marshal_bytes", 5)
    assert spans.totals(traced=True) == {}
    assert spans.totals() == {"lilac.marshal_bytes": {"count": 5}}


def test_engine_steps_record_their_spans():
    from repro.serve import BucketPolicy, Request, ServeConfig, build_engine
    eng = build_engine("granite-moe-3b-a800m", smoke=True, config=ServeConfig(
        buckets=BucketPolicy(batch=(2,), seq=(16,)), prefill_lengths=(4,)))
    rng = np.random.default_rng(0)
    for n in (3, 5):
        assert eng.submit(Request(prompt=rng.integers(
            1, eng.model.cfg.vocab, 4).astype(np.int32), max_new_tokens=n))
    spans.reset()
    steps = 0
    while not eng.scheduler.idle:
        before = _counts()
        eng.step()
        steps += 1
        after = _counts()
        new = {k: after[k] - before.get(k, 0) for k in after}
        assert new["serve.step"] == 1
        assert new["serve.readback"] >= 1
        # the decode runs the baked plan: one guard, one enqueue
        assert new["serve.decode"] == new["lilac.dispatch"] \
            == new["lilac.enqueue"] == 1
        assert "lilac.detect" not in new and "lilac.bake" not in new
    assert steps == 4
    got = eng.metrics.snapshot()["spans"]
    assert got["serve.step"]["count"] == steps
    assert got["serve.prefill"]["count"] == 2
    assert got["serve.step"]["total_s"] >= got["serve.readback"]["total_s"]


def test_lilac_first_call_records_set_up_and_later_calls_only_dispatch():
    import jax
    import jax.numpy as jnp
    from repro import lilac
    n, per_row = 64, 3
    nnz = n * per_row

    def naive_spmv(val, col, row_ptr, v):
        row = jnp.repeat(jnp.arange(n, dtype=jnp.int32), jnp.diff(row_ptr),
                         total_repeat_length=nnz)
        return jax.ops.segment_sum(val * v[col], row, num_segments=n)

    rng = np.random.default_rng(3)
    val = jnp.asarray(rng.normal(size=nnz), jnp.float32)
    col = jnp.asarray(rng.integers(0, n, nnz), jnp.int32)
    row_ptr = jnp.arange(0, nnz + 1, per_row, dtype=jnp.int32)
    v = jnp.asarray(rng.normal(size=n), jnp.float32)
    fast = lilac.compile(naive_spmv, mode="host", policy="autotune")
    want = np.asarray(naive_spmv(val, col, row_ptr, v))

    got = fast(val, col, row_ptr, v)
    first = _counts()
    assert first["lilac.dispatch"] == 1
    for name in ("lilac.detect", "lilac.tune", "lilac.bake"):
        assert first[name] == 1, (name, first)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    assert fast.plan_info()["baked"] == 1

    for k in range(1, 4):
        spans.reset()
        got = fast(val, col, row_ptr, v * k)
        assert _counts() == {"lilac.dispatch": 1, "lilac.enqueue": 1}
        np.testing.assert_allclose(np.asarray(got), want * k, rtol=1e-5,
                                   atol=1e-5)
