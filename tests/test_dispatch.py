"""Zero-overhead steady-state dispatch (repro.core.plan):

  * a resolved rewrite bakes into ONE jitted ExecutablePlan and repeat
    calls take the guard-check fast path (no eqn interpretation)
  * baked and interpreted dispatch are bit-identical (fixed seed +
    hypothesis sweep)
  * guards: changing the vector keeps the fast path (it is data, not a
    marshal source); a TrackedArray matrix mutation busts the plan
  * match serialization round-trips through the persistent plan cache;
    registry-fingerprint or schema drift invalidates it
  * a warm SECOND process rehydrates detection + pins from disk with ZERO
    Detector.detect calls and goes straight to plan baking
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.dispatch_overhead import _spy_detect
from repro import lilac
from repro.core import plan as P
from repro.core.marshal import TrackedArray, version_token
from repro.core.rewrite import needed_eqn_ids
from repro.sparse import csr_from_dense
from repro.sparse.random import random_dense_sparse

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _problem(n=96, density=0.1, seed=0):
    csr = csr_from_dense(random_dense_sparse(n, n, density, seed))
    vec = jnp.asarray(np.random.default_rng(seed + 1)
                      .standard_normal(n).astype(np.float32))
    return csr, vec


def _naive_fn(rows, nnz):
    def naive(val, col, row_ptr, v):
        row = jnp.repeat(jnp.arange(rows, dtype=jnp.int32),
                         jnp.diff(row_ptr), total_repeat_length=nnz)
        return jax.ops.segment_sum(val * v[col], row, num_segments=rows)
    return naive


# ---------------------------------------------------------------------------
# baking + the fast path
# ---------------------------------------------------------------------------

def test_bakes_plan_and_hits_fast_path():
    csr, vec = _problem()
    acc = lilac.compile(_naive_fn(csr.rows, csr.nnz), mode="host",
                        policy="jnp.ell")
    a = (csr.val, csr.col_ind, csr.row_ptr, vec)
    ref = _naive_fn(csr.rows, csr.nnz)(*a)
    out1 = acc(*a)                      # interpreted + recorded + baked
    info = acc.plan_info()
    assert info["baked"] == 1 and not info["bake_errors"]
    out2 = acc(*a)                      # fast path
    out3 = acc(*a)
    assert acc.plan_info()["plan_hits"] == 2
    assert acc.last_selections[0][1] == "jnp.ell"
    for out in (out1, out2, out3):
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=1e-3)


def test_vector_churn_keeps_fast_path():
    """The dense vector is runtime data, not a marshal source: new array
    objects every call must NOT bust the plan."""
    csr, _ = _problem()
    acc = lilac.compile(_naive_fn(csr.rows, csr.nnz), mode="host",
                        policy="jnp.ell")
    rng = np.random.default_rng(7)
    vecs = [jnp.asarray(rng.standard_normal(csr.shape[1]).astype(np.float32))
            for _ in range(4)]
    acc(csr.val, csr.col_ind, csr.row_ptr, vecs[0])
    for v in vecs[1:]:
        out = acc(csr.val, csr.col_ind, csr.row_ptr, v)
        ref = _naive_fn(csr.rows, csr.nnz)(csr.val, csr.col_ind,
                                           csr.row_ptr, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=1e-3)
    info = acc.plan_info()
    assert info["plan_hits"] == 3 and info["rebakes"] == 0


def test_no_match_program_bakes_plain_jit():
    def fn(x):
        return x * 2.0 + 1.0

    acc = lilac.compile(fn, mode="host")
    x = jnp.arange(8.0)
    out1 = acc(x)
    assert acc.plan_info()["baked"] == 1
    assert acc.last_selections == []
    out2 = acc(x)
    assert acc.plan_info()["plan_hits"] == 1
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(fn(x)))
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(fn(x)))


def test_bake_false_keeps_interpreter():
    csr, vec = _problem()
    acc = lilac.compile(_naive_fn(csr.rows, csr.nnz), mode="host",
                        bake=False)
    for _ in range(3):
        acc(csr.val, csr.col_ind, csr.row_ptr, vec)
    info = acc.plan_info()
    assert info["baked"] == 0 and info["plan_hits"] == 0


def test_trace_mode_baked_function_still_jittable():
    """Under a user's jit the guard sees tracers and falls back to the
    traced interpreter — baking must not break re-tracing."""
    csr, vec = _problem()
    naive = _naive_fn(csr.rows, csr.nnz)
    opt = lilac.compile(naive, policy="autotune")
    a = (csr.val, csr.col_ind, csr.row_ptr, vec)
    out_eager = opt(*a)                 # concrete call: tunes, pins, bakes
    assert opt.plan_info()["baked"] == 1
    jitted = jax.jit(lambda *xs: opt(*xs))
    out_jit = jitted(*a)
    np.testing.assert_allclose(np.asarray(out_jit), np.asarray(out_eager),
                               atol=2e-3, rtol=1e-3)
    out_fast = opt(*a)                  # fast path still live afterwards
    assert opt.plan_info()["plan_hits"] >= 1
    np.testing.assert_allclose(np.asarray(out_fast), np.asarray(out_eager),
                               atol=2e-3, rtol=1e-3)


def test_signature_change_uses_separate_plans():
    acc = lilac.compile(lambda x: x * 1.5 + 1.0, mode="host")
    x1, x2 = jnp.arange(8.0), jnp.arange(16.0)
    acc(x1), acc(x1)
    acc(x2), acc(x2)
    info = acc.plan_info()
    assert info["entries"] == 2 and info["baked"] == 2
    # alternating signatures: the per-entry second-chance path finds each
    # entry's own plan even though the hot-plan slot points elsewhere
    np.testing.assert_array_equal(np.asarray(acc(x1)),
                                  np.asarray(x1 * 1.5 + 1.0))
    np.testing.assert_array_equal(np.asarray(acc(x2)),
                                  np.asarray(x2 * 1.5 + 1.0))
    assert acc.plan_info()["plan_hits"] == 4


# ---------------------------------------------------------------------------
# bit-identical dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["jnp.ell", "default"])
def test_baked_vs_interpreted_bit_identical(policy):
    csr, vec = _problem(n=128, density=0.08, seed=42)
    naive = _naive_fn(csr.rows, csr.nnz)
    a = (csr.val, csr.col_ind, csr.row_ptr, vec)
    interp = lilac.compile(naive, mode="host", policy=policy, bake=False)
    baked = lilac.compile(naive, mode="host", policy=policy)
    ref = np.asarray(interp(*a))
    baked(*a)
    assert baked.plan_info()["baked"] == 1
    out = np.asarray(baked(*a))
    np.testing.assert_array_equal(out, ref)


def test_baked_vs_interpreted_bit_identical_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=8, deadline=None)
    @given(n=st.sampled_from([32, 48, 64]), seed=st.integers(0, 100))
    def check(n, seed):
        csr, vec = _problem(n=n, density=0.15, seed=seed)
        if csr.nnz == 0:
            return
        naive = _naive_fn(csr.rows, csr.nnz)
        a = (csr.val, csr.col_ind, csr.row_ptr, vec)
        interp = lilac.compile(naive, mode="host", policy="jnp.ell",
                               bake=False, plan_cache=False)
        baked = lilac.compile(naive, mode="host", policy="jnp.ell",
                              plan_cache=False)
        ref = np.asarray(interp(*a))
        baked(*a)
        np.testing.assert_array_equal(np.asarray(baked(*a)), ref)

    check()


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------

def test_tracked_array_mutation_busts_plan():
    csr, vec = _problem()
    naive = _naive_fn(csr.rows, csr.nnz)
    acc = lilac.compile(naive, mode="host", policy="jnp.ell")
    ta = TrackedArray(csr.val)
    a = (ta, csr.col_ind, csr.row_ptr, vec)
    out1 = acc(*a)
    assert acc.plan_info()["baked"] == 1
    acc(*a)
    assert acc.plan_info()["plan_hits"] == 1          # fast path works
    np.testing.assert_allclose(np.asarray(out1),
                               np.asarray(naive(csr.val, csr.col_ind,
                                                csr.row_ptr, vec)),
                               atol=2e-3, rtol=1e-3)
    # functional update: same base token, bumped version
    ta2 = ta.replace(csr.val * 2.0)
    assert version_token(ta2) != version_token(ta)
    out2 = acc(ta2, csr.col_ind, csr.row_ptr, vec)
    ref2 = naive(csr.val * 2.0, csr.col_ind, csr.row_ptr, vec)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                               atol=2e-3, rtol=1e-3)
    info = acc.plan_info()
    assert info["rebakes"] == 1                       # plan was re-baked
    acc(ta2, csr.col_ind, csr.row_ptr, vec)           # and is hot again
    assert acc.plan_info()["plan_hits"] == 1          # (new plan's counter)


def test_numpy_inplace_mutation_busts_plan():
    """Writable numpy operands can mutate under an unchanged object
    identity, so their guards carry a content fingerprint — an in-place
    write must bust the plan exactly as it would have missed the
    interpreter's marshaling cache."""
    csr, vec = _problem()
    naive = _naive_fn(csr.rows, csr.nnz)
    val_np = np.array(np.asarray(csr.val))            # writable host buffer
    acc = lilac.compile(naive, mode="host", policy="jnp.ell")
    acc(val_np, csr.col_ind, csr.row_ptr, vec)
    assert acc.plan_info()["baked"] == 1
    acc(val_np, csr.col_ind, csr.row_ptr, vec)
    assert acc.plan_info()["plan_hits"] == 1
    val_np *= 2.0                                     # same object, new bytes
    out = acc(val_np, csr.col_ind, csr.row_ptr, vec)
    ref = naive(jnp.asarray(val_np), csr.col_ind, csr.row_ptr, vec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=1e-3)
    assert acc.plan_info()["rebakes"] == 1


def test_non_marshal_numpy_capture_mutation_busts_plan():
    """EVERY writable numpy closure capture is const-guarded, not just
    marshal sources.  jax keeps a numpy capture in the jaxpr's consts as a
    ``TypedNdArray`` around the caller's array, so eager ``fn`` and the
    interpreter see an in-place edit (e.g. of a captured bias) while
    ``jax.jit(fn)`` keeps the traced values; the plan answers as eager
    ``fn`` does."""
    bias = np.zeros(8, np.float32)

    def fn(x):
        return x * 2.0 + jnp.asarray(bias)

    acc = lilac.compile(fn, mode="host")
    x = jnp.arange(8.0, dtype=jnp.float32)
    out1 = np.asarray(acc(x))
    assert acc.plan_info()["baked"] == 1
    acc(x)
    assert acc.plan_info()["plan_hits"] == 1
    bias += 1.0                                       # in-place capture edit
    out2 = np.asarray(acc(x))
    np.testing.assert_allclose(out2, out1 + 1.0, rtol=1e-6)
    assert acc.plan_info()["rebakes"] == 1


def test_large_numpy_capture_single_element_edit_busts_plan():
    """Const guards fingerprint EXACTLY: a one-element edit of a capture
    above the 64KB sampled-hash threshold — invisible to the sampled
    fingerprint — must still bust the plan, because eager ``fn`` and the
    interpreter read the capture's current values every call."""
    n = (1 << 16) // 4 + 4096                         # > _SMALL bytes of f32
    bias = np.zeros(n, np.float32)

    def fn(x):
        return x + jnp.asarray(bias)

    acc = lilac.compile(fn, mode="host")
    x = jnp.ones(n, dtype=jnp.float32)
    out1 = np.asarray(acc(x))
    acc(x)
    assert acc.plan_info()["plan_hits"] == 1
    bias[100] += 5.0          # off the strided sample and the 64-edge runs
    out2 = np.asarray(acc(x))
    assert out2[100] == out1[100] + 5.0
    assert acc.plan_info()["rebakes"] == 1


def test_closure_captured_numpy_mutation_busts_plan():
    """jax keeps closure-captured numpy operands in ``consts`` as
    ``TypedNdArray`` wrappers of the caller's arrays, so eager ``naive``
    and the interpreter path see an in-place mutation (``jax.jit(naive)``
    does not) — a baked plan must see it too, via its const guards, and
    re-bake on the new values."""
    csr, vec = _problem()
    rows, nnz = csr.rows, csr.nnz
    val_np = np.array(np.asarray(csr.val))            # writable capture
    col_np = np.array(np.asarray(csr.col_ind))
    ptr_np = np.array(np.asarray(csr.row_ptr))

    def naive(v):
        row = jnp.repeat(jnp.arange(rows, dtype=jnp.int32),
                         jnp.diff(jnp.asarray(ptr_np)),
                         total_repeat_length=nnz)
        return jax.ops.segment_sum(
            jnp.asarray(val_np) * v[jnp.asarray(col_np)],
            row, num_segments=rows)

    acc = lilac.compile(naive, mode="host", policy="jnp.ell")
    out1 = np.asarray(acc(vec))
    assert acc.plan_info()["baked"] == 1
    acc(vec)
    assert acc.plan_info()["plan_hits"] == 1
    val_np *= 2.0                                     # mutate the capture
    out2 = np.asarray(acc(vec))
    np.testing.assert_allclose(out2, out1 * 2.0, rtol=1e-5, atol=1e-5)
    assert acc.plan_info()["rebakes"] == 1


def test_numpy_scalar_arg_keys_like_compile_dict():
    """np.float64 is a ``float`` instance but carries an aval: the plan's
    leaf specs must key it exactly like ``_leaf_templates`` does (as a
    0-d array), so the fast path serves it instead of silently falling
    back to the interpreter forever."""
    acc = lilac.compile(lambda x, s: x * s + 1.0, mode="host")
    x = jnp.arange(8.0)
    s = np.float64(0.85)
    acc(x, s)
    assert acc.plan_info()["baked"] == 1
    out = acc(x, s)
    assert acc.plan_info()["plan_hits"] == 1          # plan DID serve it
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(x) * 0.85 + 1.0, rtol=1e-6)
    assert len(acc._compiled) == 1                    # one entry, one plan


def test_huge_writable_capture_refuses_to_bake():
    """Exact-guarding a capture is O(bytes) per dispatch: past the bound
    the entry stays on the interpreter (visible in plan_info) instead of
    silently hashing the whole matrix every call.  The interpreter reads
    the capture through its ``TypedNdArray`` wrapper, so an in-place edit
    shows, as it does in eager ``fn``."""
    big = np.zeros(P.CONST_GUARD_MAX_BYTES // 4 + 1024, np.float32)

    def fn(x):
        return x + jnp.asarray(big)[: x.shape[0]]

    acc = lilac.compile(fn, mode="host")
    x = jnp.ones(16, dtype=jnp.float32)
    acc(x)
    acc(x)
    info = acc.plan_info()
    assert info["baked"] == 0 and info["no_bake"] == 1
    assert "exact-guard bound" in info["bake_errors"][0]
    big[3] = 7.0                                      # interpreter stays live
    assert float(np.asarray(acc(x))[3]) == 8.0


def test_content_identical_reupload_refreshes_guards_without_rebake():
    """New array objects with identical content: the data plane returns
    the same cached buffers, so the plan re-anchors its identity guards
    instead of paying a re-trace + re-compile."""
    csr, vec = _problem()
    acc = lilac.compile(_naive_fn(csr.rows, csr.nnz), mode="host",
                        policy="jnp.ell")
    acc(csr.val, csr.col_ind, csr.row_ptr, vec)
    assert acc.plan_info()["baked"] == 1
    val2 = jnp.array(np.asarray(csr.val))             # equal, new identity
    acc(val2, csr.col_ind, csr.row_ptr, vec)          # guard miss -> refresh
    assert acc.plan_info()["rebakes"] == 0
    acc(val2, csr.col_ind, csr.row_ptr, vec)
    assert acc.plan_info()["plan_hits"] >= 1


def test_marshal_policy_off_never_bakes_marshal_harnesses():
    """marshal_policy='off' documents 'every call repacks': hoisting the
    recorded repack into a plan would silently reinstate caching, so
    marshal-bearing selections must stay on the interpreter."""
    csr, vec = _problem()
    acc = lilac.compile(_naive_fn(csr.rows, csr.nnz), mode="host",
                        policy="jnp.ell", marshal_policy="off")
    a = (csr.val, csr.col_ind, csr.row_ptr, vec)
    out1 = acc(*a)
    acc(*a)
    info = acc.plan_info()
    assert info["baked"] == 0 and info["no_bake"] == 1
    assert "repack" in info["bake_errors"][0]
    # marshal-free selections still bake under the same policy
    acc2 = lilac.compile(_naive_fn(csr.rows, csr.nnz), mode="host",
                         policy="jnp.segment", marshal_policy="off")
    acc2(*a)
    assert acc2.plan_info()["baked"] == 1
    np.testing.assert_allclose(
        np.asarray(out1),
        np.asarray(_naive_fn(csr.rows, csr.nnz)(*a)), atol=2e-3, rtol=1e-3)


def test_harness_override_invalidates_baked_plan():
    """Replacing a harness in place (register override=True) moves the
    registry epoch: already-baked plans must re-bake with the new body —
    the fingerprint can't see a same-name body swap, the epoch can."""
    import dataclasses

    from repro.core.harness import REGISTRY

    csr, vec = _problem()
    acc = lilac.compile(_naive_fn(csr.rows, csr.nnz), mode="host",
                        policy="jnp.segment")
    a = (csr.val, csr.col_ind, csr.row_ptr, vec)
    out1 = np.asarray(acc(*a))
    acc(*a)
    assert acc.plan_info()["plan_hits"] == 1
    orig = REGISTRY.get("spmv_csr", "jnp.segment")
    doubled = dataclasses.replace(
        orig, fn=lambda b, ctx: orig.fn(b, ctx) * 2.0)
    REGISTRY.register(doubled, override=True)
    try:
        out2 = np.asarray(acc(*a))                    # epoch moved: re-bakes
        np.testing.assert_allclose(out2, out1 * 2.0, rtol=1e-5, atol=1e-5)
        out3 = np.asarray(acc(*a))                    # new plan serves
        np.testing.assert_allclose(out3, out2, rtol=0, atol=0)
    finally:
        REGISTRY.register(orig, override=True)


def test_stateful_or_opted_out_harness_never_bakes():
    """Backends with lifecycle hooks / persistent state / bakeable=False
    keep their per-call host-side behavior: the plan would freeze it at
    trace time, so they stay on the interpreter."""
    from repro.core.harness import REGISTRY

    h = REGISTRY.get("spmv_csr", "jnp.segment")
    orig = h.bakeable
    h.bakeable = False
    try:
        csr, vec = _problem()
        acc = lilac.compile(_naive_fn(csr.rows, csr.nnz), mode="host",
                            policy="jnp.segment")
        a = (csr.val, csr.col_ind, csr.row_ptr, vec)
        acc(*a)
        acc(*a)
        info = acc.plan_info()
        assert info["baked"] == 0 and info["no_bake"] == 1
        assert "opted out" in info["bake_errors"][0]
    finally:
        h.bakeable = orig


def test_donate_args_rejects_marshal_sources():
    csr, vec = _problem()
    acc = lilac.compile(_naive_fn(csr.rows, csr.nnz), mode="host",
                        policy="jnp.ell", donate_args=(0,))  # 0 = csr.val
    with pytest.raises(P.PlanDonationError):
        acc(csr.val, csr.col_ind, csr.row_ptr, vec)


# ---------------------------------------------------------------------------
# serialization + persistent plan cache
# ---------------------------------------------------------------------------

def test_match_serialization_round_trip():
    csr, vec = _problem()
    naive = _naive_fn(csr.rows, csr.nnz)
    acc = lilac.compile(naive, mode="host")
    report = acc.report_for(csr.val, csr.col_ind, csr.row_ptr, vec)
    entry = next(iter(acc._compiled.values()))
    ser = P.serialize_matches(entry.closed_jaxpr, report.matches)
    assert json.loads(json.dumps(ser)) == ser         # JSON-able
    got = P.rehydrate_matches(entry.closed_jaxpr, ser)
    assert got is not None and len(got) == len(report.matches)
    for a, b in zip(report.matches, got):
        assert (a.computation, a.variant, a.format, a.epilogue) == \
               (b.computation, b.variant, b.format, b.epilogue)
        assert a.anchor_eqn is b.anchor_eqn
        assert set(a.binding) == set(b.binding)
        for k in a.binding:
            va, vb = a.binding[k], b.binding[k]
            if isinstance(va, (int, float, bool)):
                assert va == vb
            else:
                assert va is vb or np.all(
                    np.asarray(getattr(va, "val", va))
                    == np.asarray(getattr(vb, "val", vb)))


def test_plan_cache_round_trip_and_detection_skip(tmp_path):
    csr, vec = _problem()
    naive = _naive_fn(csr.rows, csr.nnz)
    a = (csr.val, csr.col_ind, csr.row_ptr, vec)
    path = tmp_path / "plans.json"
    acc = lilac.compile(naive, mode="host", policy="autotune",
                        plan_cache=str(path))
    acc(*a)
    assert path.exists()
    doc = json.loads(path.read_text())
    assert doc["schema"] == P.SCHEMA_VERSION
    (key, rec), = doc["entries"].items()
    assert rec["pins"] and rec["matches"] and rec["detect_digest"]

    # a fresh LilacFunction over the same program: detection is skipped
    calls, restore = _spy_detect()
    try:
        acc2 = lilac.compile(naive, mode="host", policy="autotune",
                             plan_cache=str(path))
        out = acc2(*a)
    finally:
        restore()
    assert calls["n"] == 0
    assert acc2.plan_info()["baked"] == 1
    assert acc2.last_selections[0][1] == acc.last_selections[0][1]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(naive(*a)), atol=2e-3, rtol=1e-3)


def test_plan_cache_registry_fingerprint_invalidation(tmp_path):
    path = tmp_path / "plans.json"
    c1 = P.PlanCache(path, registry_fingerprint="fp-A")
    c1.put("some|key", {"matches": [], "pins": {}})
    assert path.exists()
    c2 = P.PlanCache(path, registry_fingerprint="fp-B")
    assert c2.get("some|key") is None
    assert c2.stats.invalidations == 1
    c3 = P.PlanCache(path, registry_fingerprint="fp-A")
    assert c3.get("some|key") is not None


def test_plan_cache_schema_drift_invalidates(tmp_path):
    path = tmp_path / "plans.json"
    path.write_text(json.dumps({"schema": 99, "registry": "fp",
                                "entries": {"k": {}}}))
    c = P.PlanCache(path, registry_fingerprint="fp")
    assert c.get("k") is None
    assert c.stats.invalidations == 1


def test_corrupt_plan_record_degrades_to_detection(tmp_path):
    """A record whose anchors no longer line up must fall back to a full
    detect, not produce a wrong rewrite."""
    csr, vec = _problem()
    naive = _naive_fn(csr.rows, csr.nnz)
    a = (csr.val, csr.col_ind, csr.row_ptr, vec)
    path = tmp_path / "plans.json"
    acc = lilac.compile(naive, mode="host", plan_cache=str(path))
    acc(*a)
    doc = json.loads(path.read_text())
    for rec in doc["entries"].values():
        for m in rec["matches"]:
            m["anchor_eqn"] = 99999
        # keep the digest consistent with the edit so the corruption is
        # caught by positional validation, not the integrity pre-check
        rec["detect_digest"] = P.detect_digest(rec["matches"])
    path.write_text(json.dumps(doc))
    # fresh (injected) cache instance: the shared per-path view would
    # still hold the pre-edit record in memory
    fresh = P.PlanCache(path,
                        registry_fingerprint=lilac.REGISTRY.fingerprint())
    acc2 = lilac.compile(naive, mode="host", plan_cache=fresh)
    out = acc2(*a)
    assert fresh.stats.rejected == 1
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(naive(*a)), atol=2e-3, rtol=1e-3)


def test_record_with_stale_digest_or_missing_fields_rejected(tmp_path):
    """The integrity pre-check: schema-1 records always carry
    n_eqns/detect_digest, so a record missing them (truncated/foreign) or
    whose digest disagrees with its own matches is rejected before any
    positional reference is resolved."""
    csr, vec = _problem()
    naive = _naive_fn(csr.rows, csr.nnz)
    a = (csr.val, csr.col_ind, csr.row_ptr, vec)
    path = tmp_path / "plans.json"
    acc = lilac.compile(naive, mode="host", plan_cache=str(path))
    acc(*a)
    doc = json.loads(path.read_text())
    for rec in doc["entries"].values():
        del rec["detect_digest"]                      # truncated record
    path.write_text(json.dumps(doc))
    fresh = P.PlanCache(path,
                        registry_fingerprint=lilac.REGISTRY.fingerprint())
    acc2 = lilac.compile(naive, mode="host", plan_cache=fresh)
    out = acc2(*a)
    assert fresh.stats.rejected == 1                  # fell back to detect
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(naive(*a)), atol=2e-3, rtol=1e-3)


_SUBPROC = textwrap.dedent("""
    import json, sys
    import numpy as np, jax, jax.numpy as jnp
    from repro import lilac
    from repro.core import REGISTRY
    from repro.sparse import csr_from_dense
    from repro.sparse.random import random_dense_sparse

    csr = csr_from_dense(random_dense_sparse(96, 96, 0.1, 0))
    rows, nnz = csr.rows, csr.nnz
    vec = jnp.asarray(np.random.default_rng(1)
                      .standard_normal(96).astype(np.float32))

    def naive(val, col, row_ptr, v):
        row = jnp.repeat(jnp.arange(rows, dtype=jnp.int32),
                         jnp.diff(row_ptr), total_repeat_length=nnz)
        return jax.ops.segment_sum(val * v[col], row, num_segments=rows)

    acc = lilac.compile(naive, mode="host", policy="autotune")
    acc(csr.val, csr.col_ind, csr.row_ptr, vec)
    acc(csr.val, csr.col_ind, csr.row_ptr, vec)
    print(json.dumps({
        "selected": acc.last_selections[0][1],
        "plan": acc.plan_info(),
        "tuner": REGISTRY.autotuner.stats.as_dict(),
    }))
""")


def test_cross_process_warm_start_zero_detect(tmp_path):
    """The acceptance criterion: a warm second process rehydrates the
    detection report + pins from the plan cache, performs ZERO
    Detector.detect calls and zero candidate timing, and reaches a baked
    plan."""
    env = dict(os.environ,
               LILAC_AUTOTUNE_CACHE=str(tmp_path / "autotune.json"),
               LILAC_PLAN_CACHE=str(tmp_path / "plans.json"),
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(ROOT, "src"),
                    os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep))
    p = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr
    first = json.loads(p.stdout.strip().splitlines()[-1])
    assert first["plan"]["baked"] == 1

    # warm start in THIS process with a spy on detection (the conftest
    # fixture already pointed both cache env vars at this tmp_path)
    csr, vec = _problem()
    naive = _naive_fn(csr.rows, csr.nnz)
    calls, restore = _spy_detect()
    try:
        from repro.core import REGISTRY
        REGISTRY.reset_autotuner()
        timing_before = REGISTRY.autotuner.stats.timing_calls
        acc = lilac.compile(naive, mode="host", policy="autotune")
        out = acc(csr.val, csr.col_ind, csr.row_ptr, vec)
    finally:
        restore()
    assert calls["n"] == 0                            # zero detection
    assert REGISTRY.autotuner.stats.timing_calls == timing_before
    assert acc.plan_info()["baked"] == 1              # straight to baking
    assert acc.last_selections[0][1] == first["selected"]
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(naive(csr.val, csr.col_ind, csr.row_ptr, vec)),
        atol=2e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# satellites: memoized liveness + compile fast path
# ---------------------------------------------------------------------------

def test_idx_of_and_needed_built_once_per_entry():
    csr, vec = _problem()
    acc = lilac.compile(_naive_fn(csr.rows, csr.nnz), mode="host",
                        bake=False)
    a = (csr.val, csr.col_ind, csr.row_ptr, vec)
    acc(*a)
    entry = next(iter(acc._compiled.values()))
    idx_of = entry.idx_of
    needed = entry.needed_for(entry.report.matches)
    assert idx_of and isinstance(needed, frozenset)
    acc(*a)
    assert entry.idx_of is idx_of                     # not rebuilt
    assert entry.needed_for(entry.report.matches) is needed
    assert needed == needed_eqn_ids(entry.closed_jaxpr,
                                    entry.report.matches)


def test_compile_last_entry_fast_path():
    csr, vec = _problem()
    acc = lilac.compile(_naive_fn(csr.rows, csr.nnz), mode="host",
                        bake=False)
    a = (csr.val, csr.col_ind, csr.row_ptr, vec)
    acc(*a)
    entry, _ = acc._compile(a, {})
    assert acc._last_compiled[0] is entry
    # same signature, different arrays: the last-entry template matches
    vec2 = jnp.asarray(np.random.default_rng(9)
                       .standard_normal(csr.shape[1]).astype(np.float32))
    entry2, _ = acc._compile((csr.val, csr.col_ind, csr.row_ptr, vec2), {})
    assert entry2 is entry
    assert len(acc._compiled) == 1
