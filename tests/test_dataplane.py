"""The format-aware data plane (ISSUE 3): conversion-graph planning,
plan-level sharing, cost-aware LRU eviction, fingerprint semantics, and
marshal-cost-aware autotuning.

Property tests run under hypothesis when it is installed (CI extras) and
fall back to seeded parametrized sweeps otherwise, so the equivalence
guarantees are exercised in every environment.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import lilac
from repro.core import harness as H
from repro.core import marshal as M
from repro.core import spec as SP
from repro.sparse import random_csr
from repro.sparse.convert import DIARefused


def _csr_binding(csr, vec):
    return {"a": csr.val, "colidx": csr.col_ind, "rowstr": csr.row_ptr,
            "iv": vec, "rows": csr.rows, "nnz": csr.nnz}


def _tree_equal(a, b) -> bool:
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    if ta != tb:
        return False
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


# direct (single-hop) repack oracle per target format, as registered in the
# builtin REPACKS table
_ORACLES = {
    "ELL8": "ell_pack",
    "ELL128": "ell_pack128",
    "DENSE": "densify",
    "BCSR8x128": "bcsr_pack",
    "BCSR128x128": "bcsr_pack128",
    "DIA": "dia_pack",
}


def _check_planned_equals_direct(rows, cols, density, seed, dst):
    csr = random_csr(rows, cols, density=density, seed=seed)
    vec = jnp.ones(cols)
    binding = _csr_binding(csr, vec)
    keys = (binding["a"], binding["colidx"], binding["rowstr"])
    plane = M.DataPlane()
    try:
        direct = SP.REPACKS[_ORACLES[dst]](binding)
    except DIARefused:
        # too few nonzeros per diagonal for DIA (every random matrix here):
        # the planned path refuses it as the direct repack does
        with pytest.raises(DIARefused):
            plane.ensure("csr_binding", dst, keys, binding)
        return
    planned = plane.ensure("csr_binding", dst, keys, binding)
    assert _tree_equal(planned, direct), (dst, rows, cols, density, seed)


@pytest.mark.parametrize("dst", sorted(_ORACLES))
@pytest.mark.parametrize("rows,cols,density,seed", [
    (16, 16, 0.3, 0), (32, 24, 0.1, 1), (64, 48, 0.05, 2), (8, 40, 0.5, 3),
])
def test_planned_path_bit_identical_to_direct_repack(rows, cols, density,
                                                     seed, dst):
    """Any path the planner picks (including multi-hop CSR->DENSE->BCSR)
    produces bit-identical output to the legacy single-hop repack."""
    _check_planned_equals_direct(rows, cols, density, seed, dst)


def test_planned_path_property_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(rows=st.integers(4, 48), cols=st.integers(4, 48),
               density=st.floats(0.02, 0.6), seed=st.integers(0, 999),
               dst=st.sampled_from(sorted(_ORACLES)))
    @hyp.settings(max_examples=25, deadline=None)
    def prop(rows, cols, density, seed, dst):
        _check_planned_equals_direct(rows, cols, density, seed, dst)

    prop()


def test_plan_rides_cached_intermediate_bit_identical():
    """Priming DENSE then planning BCSR must reuse the cached DENSE buffer
    (shared prefix) and still equal the direct repack bit-for-bit."""
    csr = random_csr(32, 24, density=0.2, seed=0)
    binding = _csr_binding(csr, jnp.ones(24))
    keys = (binding["a"], binding["colidx"], binding["rowstr"])
    plane = M.DataPlane()
    plane.ensure("csr_binding", "DENSE", keys, binding)
    runs_before = plane.stats.loader_runs
    bcsr = plane.ensure("csr_binding", "BCSR8x128", keys, binding)
    assert plane.stats.loader_runs == runs_before      # no second load
    assert plane.stats.shared_edge_hits >= 1
    ps = plane.plans[("csr_binding", "BCSR8x128")]
    assert ps.last_path[0] == "DENSE"                  # started at the cache
    direct = SP.REPACKS["bcsr_pack"](binding)
    assert _tree_equal(bcsr, direct)


def test_plan_cache_shared_across_two_harnesses():
    """Two harnesses targeting overlapping formats on ONE DataPlane share
    buffers: jnp.bcsr's CSR->DENSE->BCSR path rides the DENSE intermediate
    jnp.dense cached, and a repeat call is a pure plan-cache hit."""
    csr = random_csr(32, 24, density=0.2, seed=0)
    vec = jnp.ones(24)

    def naive(val, col, row_ptr, vec):
        row = jnp.repeat(jnp.arange(32, dtype=jnp.int32), jnp.diff(row_ptr),
                         total_repeat_length=val.shape[0])
        return jax.ops.segment_sum(val * vec[col], row, num_segments=32)

    plane = lilac.DataPlane()
    # bake=False: this test asserts the INTERPRETER path's per-call cache
    # accounting; a baked plan hoists the buffers and never consults the
    # plane again (that fast path is covered in test_dispatch.py)
    dense_f = lilac.compile(naive, mode="host", policy="jnp.dense",
                            cache=plane, bake=False)
    bcsr_f = lilac.compile(naive, mode="host", policy="jnp.bcsr",
                           cache=plane, bake=False)
    out_d = dense_f(csr.val, csr.col_ind, csr.row_ptr, vec)
    loader_runs = plane.stats.loader_runs
    out_b = bcsr_f(csr.val, csr.col_ind, csr.row_ptr, vec)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_b),
                               rtol=1e-5, atol=1e-5)
    assert plane.stats.loader_runs == loader_runs       # binding loaded once
    ps = plane.plans[("csr_binding", "BCSR8x128")]
    assert ps.shared_prefix_hits == 1 and ps.last_path[0] == "DENSE"
    # steady state: repeat calls hit the plan cache, zero edge executions
    edges = plane.stats.edge_runs
    bcsr_f(csr.val, csr.col_ind, csr.row_ptr, vec)
    assert plane.stats.edge_runs == edges
    assert ps.hits == 1 and ps.bytes_avoided > 0


def test_sampled_fingerprint_collision_vs_exact():
    """Above the full-hash threshold the fingerprint samples: a change in
    an unsampled position collides under the default mode but is caught by
    exact=True (the documented trade-off apps opt into)."""
    n = (1 << 16) // 4 + 4096            # > _SMALL bytes of f32
    a = np.zeros(n, np.float32)
    step = max(1, n // 1024)
    # find an index the strided sample and the 64-element edges never read
    idx = next(i for i in range(65, n - 65) if i % step)
    b = a.copy()
    b[idx] = 42.0
    assert M.fingerprint(a)[0] == "sampled"
    assert M.fingerprint(a) == M.fingerprint(b)                  # collision
    assert M.fingerprint(a, exact=True) != M.fingerprint(b, exact=True)
    # and a DataPlane with exact=True keys distinguishes them
    plane = M.DataPlane(policy=M.MarshalPolicy(exact=True))
    assert plane._key("x", (a,)) != plane._key("x", (b,))


def test_tracked_array_versioning_keys_cache():
    """TrackedArray versions replace hashing: same buffer, bumped version
    -> different key; cache keyed on it recomputes exactly once."""
    cache = M.MarshalingCache()
    t = M.TrackedArray(np.ones(8))
    calls = []
    cache.get("p", (t,), lambda: calls.append(1) or "v0")
    cache.get("p", (t,), lambda: calls.append(1) or "v0")
    assert len(calls) == 1
    t2 = t.replace(np.ones(8))           # same CONTENT, new version
    cache.get("p", (t2,), lambda: calls.append(1) or "v1")
    assert len(calls) == 2


def test_cost_aware_lru_keeps_hot_entry_under_churn():
    """The seed cache popped next(iter(store)) — insertion order — so the
    hottest entry died under churn.  Cost-aware LRU keeps it alive."""
    cache = M.MarshalingCache(max_entries=4)
    hot = np.arange(16, dtype=np.float32)
    cache.get("hot", (hot,), lambda: "HOT")
    for i in range(16):
        cache.get("hot", (hot,), lambda: "HOT")     # refresh recency
        cold = np.full(16, float(i), np.float32)
        cache.get(f"cold{i}", (cold,), lambda: i)    # churn
    misses = cache.stats.misses
    cache.get("hot", (hot,), lambda: "HOT")
    assert cache.stats.misses == misses, "hot entry was evicted"


def test_eviction_prefers_cheap_to_recompute():
    """Among the LRU tail, the cheapest-to-recompute entry is evicted
    first, so an expensive repack outlives same-age cheap ones."""
    cache = M.MarshalingCache(max_entries=2)
    cache.EVICT_WINDOW = 2

    def expensive():
        import time
        time.sleep(0.02)
        return "exp"

    a, b, c = (np.full(8, v, np.float32) for v in (1.0, 2.0, 3.0))
    cache.get("exp", (a,), expensive)
    cache.get("cheap", (b,), lambda: "cheap")
    cache.get("new", (c,), lambda: "new")            # forces one eviction
    m = cache.stats.misses
    cache.get("exp", (a,), expensive)                # still cached
    assert cache.stats.misses == m
    cache.get("cheap", (b,), lambda: "cheap")        # this one was evicted
    assert cache.stats.misses == m + 1


class _NoMaterialize:
    """Array stand-in whose data can never be pulled to host."""
    shape = (128, 128)
    dtype = np.dtype(np.float32)
    nbytes = 128 * 128 * 4

    def __array__(self, *a, **k):
        raise AssertionError("cache hit materialized a device array")


def test_bytes_avoided_reads_metadata_only():
    """Satellite: CacheStats.bytes_avoided must come from nbytes/shape
    metadata, not np.asarray(...) (which forces a device->host sync)."""
    cache = M.MarshalingCache()
    t = M.TrackedArray(_NoMaterialize())     # O(1) fingerprint, no hashing
    cache.get("p", (t,), lambda: "packed")
    cache.get("p", (t,), lambda: "packed")   # hit: must NOT materialize
    assert cache.stats.hits == 1
    assert cache.stats.bytes_avoided == _NoMaterialize.nbytes
    assert M.nbytes_of(t) == _NoMaterialize.nbytes


def test_marshal_policy_parse_and_off():
    assert M.MarshalPolicy.parse(None) == M.MarshalPolicy()
    assert M.MarshalPolicy.parse("off").enabled is False
    assert M.MarshalPolicy.parse("exact").exact is True
    p = M.MarshalPolicy(reuse=7.0)
    assert M.MarshalPolicy.parse(p) is p
    with pytest.raises(ValueError):
        M.MarshalPolicy.parse("bogus")

    csr = random_csr(16, 16, density=0.3, seed=0)
    vec = jnp.ones(16)

    def naive(val, col, row_ptr, vec):
        row = jnp.repeat(jnp.arange(16, dtype=jnp.int32), jnp.diff(row_ptr),
                         total_repeat_length=val.shape[0])
        return jax.ops.segment_sum(val * vec[col], row, num_segments=16)

    acc = lilac.compile(naive, mode="host", policy="jnp.ell",
                        marshal_policy="off")
    assert acc.cache is None
    out = acc(csr.val, csr.col_ind, csr.row_ptr, vec)
    ref = naive(csr.val, csr.col_ind, csr.row_ptr, vec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    shared = lilac.compile(naive, mode="host", policy="jnp.ell",
                           marshal_policy=M.MarshalPolicy(reuse=5.0))
    assert isinstance(shared.cache, M.DataPlane)
    assert shared.cache.policy.reuse == 5.0


def test_unknown_marshal_formats_rejected_at_registration():
    with pytest.raises(SP.SpecError, match="unknown marshal source"):
        SP.register_spec(
            "HARNESS bad.src implements dotproduct\n"
            "  marshal x = ell_pack(a) from nowhere to ELL8;\n",
            {"bad.src": lambda b, c, **kw: 0.0},
            registry=H.HarnessRegistry())
    with pytest.raises(SP.SpecError, match="unknown marshal target"):
        SP.register_spec(
            "HARNESS bad.dst implements dotproduct\n"
            "  marshal x = ell_pack(a) from csr_binding to NOPE;\n",
            {"bad.dst": lambda b, c, **kw: 0.0},
            registry=H.HarnessRegistry())


def test_clause_without_formats_uses_legacy_cache_path():
    """Format-less marshal clauses (out-of-repo specs) keep the exact
    legacy MarshalingCache.get semantics on a DataPlane."""
    reg = H.HarnessRegistry()

    @SP.repack("plain_pack", override=True)
    def plain_pack(b):
        return float(np.asarray(b["a"]).sum())

    SP.register_spec(
        "HARNESS plain.h implements dotproduct\n"
        "  marshal s = plain_pack(a);\n",
        {"plain.h": lambda b, c, *, s: s},
        registry=reg)
    h = reg.get("dotproduct", "plain.h")
    plane = M.DataPlane()
    ctx = H.CallCtx(mode="host", cache=plane, format="DOT")
    a = np.arange(8, dtype=np.float32)
    assert h({"a": a, "b": a}, ctx) == a.sum()
    assert h({"a": a, "b": a}, ctx) == a.sum()
    assert plane.stats.hits == 1 and plane.stats.misses == 1
    assert plane.stats.edge_runs == 0


def test_format_and_edge_registries():
    assert "CSR" in M.FORMATS and "BCSR128x128" in M.FORMATS
    with pytest.raises(ValueError):
        M.register_format(M.SparseFormat("CSR", "different"))
    # planner: CSR reaches every builtin target
    for dst in _ORACLES:
        assert M.GRAPH.full_path_cost("CSR", dst) is not None
    # and an unknown start has no path
    assert M.GRAPH.plan({"NOPE": 0.0}, "DENSE") is None


# ---------------------------------------------------------------------------
# Marshal-aware autotuning + schema migration
# ---------------------------------------------------------------------------

def _mk_harness(name, fn, marshal=()):
    return H.Harness(name, "spmv_csr", fn, jit_safe=False, marshal=marshal)


def test_autotune_amortized_winner_folds_marshal_cost(tmp_path):
    """A harness with a fast kernel but a ruinous repack loses to a
    marshal-free harness once the repack is amortized at the declared call
    frequency — and wins when reuse is high enough to amortize it."""
    from repro.core.autotune import Autotuner

    timings = {"fastkernel": 1e-4, "nofuss": 5e-4}
    marshal_s = {"fastkernel": 1.0}
    low = Autotuner.amortized(timings, marshal_s, reuse=10.0)
    high = Autotuner.amortized(timings, marshal_s, reuse=1e7)
    assert min(low, key=low.get) == "nofuss"
    assert min(high, key=high.get) == "fastkernel"


def test_autotune_schema1_migration_no_stale_winners(tmp_path):
    """A schema-1 cache file is migrated (not discarded): its measurements
    survive as kernel_only records, served verbatim for marshal-free
    candidate sets but re-measured when a marshaling candidate is in play."""
    import json

    from repro.core.autotune import Autotuner, AutotuneCache

    path = tmp_path / "autotune.json"
    fp = "fp-test"
    sig_args = ("spmv_csr", "CSR", "cpu",
                {"rows": 64, "nnz": 256, "iv": np.ones(64, np.float32)})
    from repro.core.autotune import signature_of
    sig = signature_of(*sig_args)
    with open(path, "w") as f:
        json.dump({"schema": 1, "registry": fp,
                   "entries": {sig: {"host": {
                       "harness": "legacy.winner",
                       "best_s": 1e-4,
                       "timings": {"legacy.winner": 1e-4}}}}}, f)

    cache = AutotuneCache(path, registry_fingerprint=fp).load()
    assert cache.stats.migrations == 1
    rec = cache.get(sig, "host")
    assert rec["cost_model"] == "kernel_only"
    assert rec["harness"] == "legacy.winner"

    tuner = Autotuner(registry_fingerprint=fp, cache=cache, budget=4)
    plane = M.DataPlane()
    ctx = H.CallCtx(mode="host", cache=plane, format="CSR")
    binding = {"rows": 64, "nnz": 256, "iv": jnp.ones(64)}

    # marshal-free candidates: migrated record is served with zero re-timing
    free = [_mk_harness("legacy.winner", lambda b, c: jnp.zeros(64)),
            _mk_harness("other", lambda b, c: jnp.zeros(64))]
    chosen = tuner.select("spmv_csr", "CSR", "cpu", "host", free,
                          binding, ctx)
    assert chosen.name == "legacy.winner"
    assert tuner.stats.timing_calls == 0

    # a marshaling candidate appears: the kernel-only winner is NOT served
    # stale — the tuner re-measures and stores an amortized record
    clause = lilac.MarshalClause("x", "ell_pack", (("a",),),
                                 src="csr_binding", dst="ELL8")
    cands = free + [_mk_harness("marshaled", lambda b, c: jnp.zeros(64),
                                marshal=(clause,))]
    tuner.select("spmv_csr", "CSR", "cpu", "host", cands, binding, ctx)
    assert tuner.stats.remeasures == 1
    assert tuner.stats.timing_calls > 0
    rec2 = cache.get(sig, "host")
    assert rec2["cost_model"] == "amortized"


def test_autotune_schema_mismatch_invalidates(tmp_path):
    import json

    from repro.core.autotune import AutotuneCache

    path = tmp_path / "autotune.json"
    with open(path, "w") as f:
        json.dump({"schema": 99, "registry": "fp", "entries": {"x": {}}}, f)
    cache = AutotuneCache(path, registry_fingerprint="fp").load()
    assert cache.entries == {}
    assert cache.stats.invalidations == 1


def test_tiny_cache_never_evicts_fresh_insert():
    """max_entries < EVICT_WINDOW must not evict the value being inserted
    (and ensure's fallback path must return it, not re-read the store)."""
    cache = M.MarshalingCache(max_entries=2)
    import time as _t
    for i in range(6):
        a = np.full(8, float(i), np.float32)
        got = cache.get(f"k{i}", (a,), lambda i=i: (_t.sleep(0.001), i)[1])
        assert got == i
    plane = M.DataPlane(policy=M.MarshalPolicy(max_entries=2))
    for i in range(4):
        a = np.full(8, float(i), np.float32)
        slow = lambda i=i: (_t.sleep(0.002), f"fb{i}")[1]
        got = plane.ensure("csr_binding", "COO", (a,), {}, fallback=slow)
        assert got == f"fb{i}"       # COO unreachable -> fallback path


def test_reuse_change_rederives_winner_without_retiming(tmp_path):
    """A persisted amortized record tuned at one call frequency serves the
    CORRECT winner for a different declared frequency, arithmetically."""
    from repro.core.autotune import Autotuner, AutotuneCache, signature_of

    fp = "fp-reuse"
    binding = {"rows": 64, "nnz": 256, "iv": jnp.ones(64)}
    sig = signature_of("spmv_csr", "CSR", "cpu", binding)
    cache = AutotuneCache(tmp_path / "a.json", registry_fingerprint=fp)
    cache.loaded = True
    cache.put(sig, "host", {
        "harness": "fastkernel", "best_s": 1e-4,
        "timings": {"fastkernel": 1e-4, "nofuss": 5e-4},
        "marshal_s": {"fastkernel": 1.0}, "reuse": 1e7,
        "amortized_s": {}, "cost_model": "amortized",
    }, persist=False)
    tuner = Autotuner(registry_fingerprint=fp, cache=cache, budget=4)
    cands = [_mk_harness("fastkernel", lambda b, c: 0),
             _mk_harness("nofuss", lambda b, c: 0)]
    # declared frequency 10: the 1s repack no longer amortizes
    plane = M.DataPlane(policy=M.MarshalPolicy(reuse=10.0))
    ctx = H.CallCtx(mode="host", cache=plane, format="CSR")
    chosen = tuner.select("spmv_csr", "CSR", "cpu", "host", cands,
                          binding, ctx)
    assert chosen.name == "nofuss"
    assert tuner.stats.timing_calls == 0          # no re-timing
    # matching frequency: recorded winner served as-is
    plane7 = M.DataPlane(policy=M.MarshalPolicy(reuse=1e7))
    ctx7 = H.CallCtx(mode="host", cache=plane7, format="CSR")
    assert tuner.select("spmv_csr", "CSR", "cpu", "host", cands,
                        binding, ctx7).name == "fastkernel"


def test_fallback_repack_cost_visible_to_estimator():
    """A format clause served by its fallback (no graph path) still
    reports its measured cost to the autotuner's amortized model."""
    import time as _t
    empty = M.ConversionGraph()
    plane = M.DataPlane(graph=empty)
    a = np.arange(8, dtype=np.float32)
    plane.ensure("csr_binding", "ELL8", (a,), {},
                 fallback=lambda: (_t.sleep(0.005), "packed")[1])
    clause = lilac.MarshalClause("x", "ell_pack", (("a",),),
                                 src="csr_binding", dst="ELL8")
    assert plane.estimate_marshal_seconds([clause]) >= 0.005


def test_datapane_marshal_seconds_estimate():
    """After one ensure, the plane can price a harness's marshal clauses
    from measured edge costs (what the tuner amortizes)."""
    csr = random_csr(32, 24, density=0.2, seed=0)
    binding = _csr_binding(csr, jnp.ones(24))
    keys = (binding["a"], binding["colidx"], binding["rowstr"])
    plane = M.DataPlane()
    plane.ensure("csr_binding", "ELL8", keys, binding)
    clause = lilac.MarshalClause("ell", "ell_pack", (("a",),),
                                 src="csr_binding", dst="ELL8")
    est = plane.estimate_marshal_seconds([clause])
    assert est > 0.0
    # unknown formats fall back to last measured repack cost (0 here)
    legacy = dataclasses.replace(clause, src=None, dst=None)
    assert plane.estimate_marshal_seconds([legacy]) == 0.0
