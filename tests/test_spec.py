"""The declarative spec API (paper §3, Fig. 3 + §3.3): HARNESS-block
parsing with error positions, descriptor->Harness compilation with
generated marshaling, decorator registration, duplicate-registration
safety, the `lilac.compile` entry point, and parity of the spec-registered
builtin registry with the hand-wired layout it replaced."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import lilac
from repro.core import what_lang as W
from repro.core.harness import HarnessRegistry


# -- parsing ------------------------------------------------------------------

FULL_HARNESS = """
HARNESS mylib.spmv implements spmv_csr, spmv_coo
  platforms cpu;
  formats CSR, COO;
  host_only;
  default_for cpu;
  marshal packed = ell_pack(a, colidx, rowstr|rowidx);
  persistent handle, workspace;
  BeforeFirstExecution init_handle;
  AfterLastExecution free_handle;
"""


def test_parse_harness_block_full():
    decl = lilac.parse_harness(FULL_HARNESS)
    assert decl.name == "mylib.spmv"
    assert decl.implements == ("spmv_csr", "spmv_coo")
    assert decl.platforms == ("cpu",)
    assert decl.formats == ("CSR", "COO")
    assert not decl.jit_safe
    assert decl.default_for == ("cpu",)
    assert decl.marshal == (W.MarshalClause(
        "packed", "ell_pack", (("a",), ("colidx",), ("rowstr", "rowidx"))),)
    assert decl.persistent == ("handle", "workspace")
    assert decl.before_first == "init_handle"
    assert decl.after_last == "free_handle"


def test_parse_spec_roundtrip_builtins():
    """str(parse(text)) reparses to an equal AST for every builtin spec —
    the CI drift gate relies on this."""
    assert lilac.BUILTIN_SPECS
    for family, text in lilac.BUILTIN_SPECS.items():
        spec = lilac.parse_spec(text)
        assert lilac.parse_spec(str(spec)) == spec, family
    # and for a harness carrying every clause kind
    decl = lilac.parse_harness(FULL_HARNESS)
    assert lilac.parse_harness(str(decl)) == decl


def test_parse_error_positions():
    with pytest.raises(lilac.ParseError) as ei:
        lilac.parse_spec("COMPUTATION x\nresult = sum(0 <= i < n) a[i] * ;")
    assert ei.value.line == 2 and ei.value.col == 33
    assert "line 2" in str(ei.value)

    with pytest.raises(lilac.ParseError) as ei:
        lilac.parse_spec("HARNESS h implements dotproduct\n  bogus foo;")
    assert ei.value.line == 2 and ei.value.col == 3
    assert "bogus" in str(ei.value)

    with pytest.raises(lilac.ParseError) as ei:
        lilac.parse_spec("HARNESS h implements dotproduct\n  platforms cpu")
    assert ei.value.line == 2  # missing ';' reported at end of input

    with pytest.raises(lilac.ParseError):
        lilac.parse_spec("")


def test_comments_are_skipped():
    decl = lilac.parse_harness("""
    HARNESS c.mt implements dotproduct   -- trailing comment
      -- a whole-line comment
      formats DOT;
    """)
    assert decl.formats == ("DOT",)


def test_parse_keeps_computation_back_compat():
    comp = lilac.parse("COMPUTATION p r = sum(0 <= i < n) a[i] * b[i];")
    assert comp.name == "p"
    with pytest.raises(lilac.ParseError):
        lilac.parse(FULL_HARNESS)  # no COMPUTATION


# -- duplicate registration ---------------------------------------------------

def test_duplicate_registration_is_an_error():
    reg = HarnessRegistry()
    h1 = lilac.Harness("b.x", "dotproduct", lambda b, c: 1.0)
    h2 = lilac.Harness("b.x", "dotproduct", lambda b, c: 2.0)
    reg.register(h1)
    with pytest.raises(lilac.DuplicateHarnessError):
        reg.register(h2)
    # override replaces in place (same candidate-order slot)
    reg.register(lilac.Harness("b.y", "dotproduct", lambda b, c: 3.0))
    reg.register(h2, override=True)
    assert [h.name for h in reg.harnesses_for("dotproduct")] == ["b.x", "b.y"]
    assert reg.get("dotproduct", "b.x") is h2


def test_spec_reload_is_safe_with_override():
    reg = HarnessRegistry()
    text = """
    HARNESS t.dot implements dotproduct
      formats DOT;
    """
    lilac.register_spec(text, {"t.dot": lambda b, c: 1.0}, registry=reg)
    with pytest.raises(lilac.DuplicateHarnessError):
        lilac.register_spec(text, {"t.dot": lambda b, c: 1.0}, registry=reg)
    lilac.register_spec(text, {"t.dot": lambda b, c: 2.0}, registry=reg,
                        override=True)
    assert len(reg.harnesses_for("dotproduct")) == 1


# -- descriptor -> Harness compilation ---------------------------------------

def test_generated_marshaling_wrapper_uses_cache():
    """The marshal clause must route the repack through MarshalingCache:
    one miss on first call, hits afterwards, keyed on declared arrays."""
    reg = HarnessRegistry()
    packs = []

    @lilac.repack("t_double_pack", override=True)
    def _pack(b):
        packs.append(1)
        return np.asarray(b["a"]) * 2.0

    @lilac.harness("""
    HARNESS t.double implements dotproduct
      host_only;
      marshal doubled = t_double_pack(a);
    """, registry=reg)
    def t_double(b, ctx, *, doubled):
        return float(np.sum(doubled * np.asarray(b["b"])))

    h = reg.get("dotproduct", "t.double")
    cache = lilac.MarshalingCache()
    ctx = lilac.CallCtx(mode="host", cache=cache, format="DOT")
    binding = {"a": np.ones(4, np.float32), "b": np.ones(4, np.float32),
               "length": 4}
    assert h(binding, ctx) == pytest.approx(8.0)
    assert h(binding, ctx) == pytest.approx(8.0)
    assert len(packs) == 1 and cache.stats.hits == 1
    # changed key array -> repack reruns
    binding2 = dict(binding, a=np.full(4, 2.0, np.float32))
    assert h(binding2, ctx) == pytest.approx(16.0)
    assert len(packs) == 2
    # no cache available (ctx.cache None) -> direct computation still works
    assert h(binding, lilac.CallCtx(mode="host", cache=None, format="DOT")) \
        == pytest.approx(8.0)
    assert len(packs) == 3


def test_persistent_state_hooks():
    """BeforeFirstExecution runs once before the first call; AfterLastExecution
    runs on release — the paper's persistence template (Fig. 14)."""
    reg = HarnessRegistry()
    events = []

    @lilac.harness("""
    HARNESS t.persist implements dotproduct
      persistent handle;
      BeforeFirstExecution t_init;
      AfterLastExecution t_fini;
    """, registry=reg, hooks={
        "t_init": lambda state: (events.append("init"),
                                 state.__setitem__("handle", 42)),
        "t_fini": lambda state: events.append("fini"),
    })
    def t_persist(b, ctx):
        return b["a"] * 0 + ctx_handle(ctx)

    # the body can read the persistent dict through the harness object
    h = reg.get("dotproduct", "t.persist")

    def ctx_handle(ctx):
        return h.persistent["handle"]

    ctx = lilac.CallCtx(mode="host", cache=None, format="DOT")
    assert h.persistent == {"handle": None}
    np.testing.assert_array_equal(h({"a": np.zeros(2)}, ctx), [42, 42])
    h({"a": np.zeros(2)}, ctx)
    assert events == ["init"]
    h.release()
    assert events == ["init", "fini"]


def test_unknown_repack_and_hook_are_spec_errors():
    """Both misconfigurations fail eagerly at registration — a typo'd
    repack must not be silently disqualified by the autotuner later."""
    reg = HarnessRegistry()
    with pytest.raises(lilac.SpecError):
        @lilac.harness("""
        HARNESS t.nohook implements dotproduct
          BeforeFirstExecution missing_hook;
        """, registry=reg)
        def _a(b, ctx):
            return 0
    with pytest.raises(lilac.SpecError, match="unknown repack"):
        @lilac.harness("""
        HARNESS t.nopack implements dotproduct
          host_only;
          marshal x = missing_pack(a);
        """, registry=reg)
        def _b(b, ctx, *, x):
            return x
    assert not reg.harnesses_for("dotproduct")   # nothing half-registered


def test_harness_implements_unknown_computation():
    with pytest.raises(lilac.SpecError):
        lilac.register_spec("HARNESS t.x implements no_such_comp",
                            {"t.x": lambda b, c: 0},
                            registry=HarnessRegistry())


_CLONE_SPEC = """
COMPUTATION {name}
forall(0 <= i < r2) {{
  out2[i] = sum(ptr2[i] <= j < ptr2[i+1]) v2[j] * x2[c2[j]];
}}

HARNESS t.clone implements {name}
  formats CSR, COO;
  default_for cpu;
"""


def _cleanup_global(name):
    from repro.core import spec as S
    from repro.core.detect import reset_default_detector
    W.BUILTINS.pop(name, None)
    lilac.REGISTRY._by_comp.pop(name, None)
    lilac.REGISTRY._defaults.pop((name, "cpu"), None)
    lilac.REGISTRY.reset_autotuner()
    S._GLOBAL_SPEC_LOG[:] = [e for e in S._GLOBAL_SPEC_LOG
                             if not any(name in d.implements
                                        for d in e[0].harnesses)]
    reset_default_detector()


def test_spec_with_new_computation_extends_builtins_and_detector():
    """'Add a backend' = spec + function: registering against the global
    REGISTRY makes a new COMPUTATION detectable and its harness
    selectable, no compiler changes."""
    name = "spmv_csr_clone"
    assert name not in W.BUILTINS
    try:
        lilac.register_spec(_CLONE_SPEC.format(name=name),
                            {"t.clone": lambda b, c: 0})
        assert name in W.BUILTINS
        assert lilac.REGISTRY.default_name(name, "cpu") == "t.clone"
        from repro.core.detect import Detector, default_detector
        det = default_detector()
        assert any(m.computation == name for m in det.matchers)
        # explicit-computation detectors still work
        assert Detector([W.BUILTINS[name]]).matchers
    finally:
        _cleanup_global(name)


def test_failed_registration_leaves_no_trace():
    """register_spec is atomic: a spec that fails validation (missing
    body, unknown hook, duplicate) must not publish its computations,
    rebuild the detector, or register a prefix of its harnesses."""
    name = "spmv_atomic_clone"
    before = len(lilac.REGISTRY.harnesses_for("dotproduct"))
    with pytest.raises(lilac.SpecError):
        lilac.register_spec(f"""
        COMPUTATION {name}
        forall(0 <= i < r3) {{
          out3[i] = sum(p3[i] <= j < p3[i+1]) v3[j] * x3[c3[j]];
        }}

        HARNESS t.ok implements dotproduct
          formats DOT;

        HARNESS t.missing_body implements {name}
        """, {"t.ok": lambda b, c: 0})          # no body for t.missing_body
    assert name not in W.BUILTINS
    assert len(lilac.REGISTRY.harnesses_for("dotproduct")) == before
    # within-spec duplicates are caught before anything commits
    reg = HarnessRegistry()
    with pytest.raises(lilac.DuplicateHarnessError):
        lilac.register_spec("""
        HARNESS t.dup implements dotproduct
        HARNESS t.dup implements dotproduct
        """, {"t.dup": lambda b, c: 0}, registry=reg)
    assert not reg.harnesses_for("dotproduct")


def test_private_registry_stays_isolated():
    """A caller-supplied registry must not leak computations into the
    process-global builtins or rebuild the shared detector."""
    name = "spmv_private_clone"
    reg = HarnessRegistry()
    lilac.register_spec(_CLONE_SPEC.format(name=name),
                        {"t.clone": lambda b, c: 0}, registry=reg)
    assert name not in W.BUILTINS          # no global leak
    assert reg.default_name(name, "cpu") == "t.clone"
    from repro.core.detect import default_detector
    assert not any(m.computation == name
                   for m in default_detector().matchers)


def test_fresh_registry_replay_survives_global_override_reload():
    """Re-loading a spec globally with override=True must not break later
    register_builtins(fresh) replays (the log holds both entries; the
    later one wins, as it did globally)."""
    text = """
    HARNESS t.replay implements dotproduct
      formats DOT;
    """
    try:
        lilac.register_spec(text, {"t.replay": lambda b, c: 1.0})
        lilac.register_spec(text, {"t.replay": lambda b, c: 2.0},
                            override=True)
        fresh = lilac.register_builtins(HarnessRegistry())
        names = [h.name for h in fresh.harnesses_for("dotproduct")]
        assert names.count("t.replay") == 1
        assert fresh.get("dotproduct", "t.replay").fn({}, None) == 2.0
    finally:
        from repro.core import spec as S
        lilac.REGISTRY._by_comp["dotproduct"] = [
            h for h in lilac.REGISTRY._by_comp["dotproduct"]
            if h.name != "t.replay"]
        lilac.REGISTRY.reset_autotuner()
        S._GLOBAL_SPEC_LOG[:] = [e for e in S._GLOBAL_SPEC_LOG
                                 if not any(d.name == "t.replay"
                                            for d in e[0].harnesses)]


def test_multi_computation_harness_shares_persistent_state():
    """One HARNESS block implementing several computations is ONE backend:
    a single persistent dict, setup once on first call anywhere, teardown
    once on first release."""
    reg = HarnessRegistry()
    events = []

    @lilac.harness("""
    HARNESS t.shared implements spmv_csr, spmv_coo
      persistent handle;
      BeforeFirstExecution s_init;
      AfterLastExecution s_fini;
    """, registry=reg, hooks={
        "s_init": lambda state: events.append("init"),
        "s_fini": lambda state: events.append("fini"),
    })
    def t_shared(b, ctx):
        return 0

    h_csr = reg.get("spmv_csr", "t.shared")
    h_coo = reg.get("spmv_coo", "t.shared")
    assert h_csr.persistent is h_coo.persistent
    ctx = lilac.CallCtx(mode="host", cache=None, format="CSR")
    h_csr({}, ctx)
    h_coo({}, ctx)
    assert events == ["init"]              # once per backend, not per comp
    # release through a sibling that never ran still tears down the backend
    h_coo.release()
    h_csr.release()                        # already down -> no double fini
    assert events == ["init", "fini"]
    # after teardown, the next call through ANY sibling sets up again
    h_csr({}, ctx)
    assert events == ["init", "fini", "init"]
    h_csr.release()
    assert events == ["init", "fini", "init", "fini"]


def test_override_replacement_tears_down_live_harness():
    """register(..., override=True) on a live harness must run its
    AfterLastExecution hook before dropping it — no leaked handles."""
    reg = HarnessRegistry()
    events = []
    h1 = lilac.Harness("t.live", "dotproduct", lambda b, c: 1.0,
                       setup=lambda s: events.append("init"),
                       teardown=lambda s: events.append("fini"))
    reg.register(h1)
    h1({}, lilac.CallCtx(mode="host", cache=None, format="DOT"))
    assert events == ["init"]
    reg.register(lilac.Harness("t.live", "dotproduct", lambda b, c: 2.0),
                 override=True)
    assert events == ["init", "fini"]
    # replacing a never-started harness runs no hook
    reg.register(lilac.Harness("t.live", "dotproduct", lambda b, c: 3.0),
                 override=True)
    assert events == ["init", "fini"]


# -- entry point --------------------------------------------------------------

def _dot(a, b):
    return jnp.sum(a * b)


def test_compile_options_and_decorator_form():
    f = lilac.compile(_dot)
    assert isinstance(f, lilac.LilacFunction) and f.mode == "trace"
    f = lilac.compile(_dot, options=lilac.CompileOptions(mode="host"))
    assert f.mode == "host"
    # explicit kwargs override option fields
    f = lilac.compile(_dot, options=lilac.CompileOptions(mode="host"),
                      mode="trace", policy="jnp.dot")
    assert f.mode == "trace" and f.policy == "jnp.dot"

    @lilac.compile(mode="host")
    def g(a, b):
        return jnp.sum(a * b)

    assert isinstance(g, lilac.LilacFunction) and g.mode == "host"
    a = jnp.arange(4.0)
    np.testing.assert_allclose(g(a, a), _dot(a, a))

    with pytest.raises(TypeError):
        lilac.compile(_dot, bogus_option=1)
    with pytest.raises(ValueError):
        lilac.compile(_dot, mode="neither")


def test_deprecation_shims_still_work():
    a = jnp.arange(8.0)
    with pytest.warns(lilac.LilacDeprecationWarning):
        opt = lilac.lilac_optimize(_dot)
    assert opt.mode == "trace"
    np.testing.assert_allclose(opt(a, a), _dot(a, a))
    with pytest.warns(lilac.LilacDeprecationWarning):
        acc = lilac.lilac_accelerate(_dot, policy="jnp.dot")
    assert acc.mode == "host" and acc.policy == "jnp.dot"
    np.testing.assert_allclose(acc(a, a), _dot(a, a))
    # the old import path still resolves
    from repro.core import lilac_accelerate, lilac_optimize  # noqa: F401


# -- builtin parity -----------------------------------------------------------

# The hand-wired registry layout this redesign replaced (PR 1 state of
# harness._register_builtins), as (name, platforms, formats, jit_safe)
# per computation plus the per-platform defaults.  Spec-driven
# registration must reproduce it exactly — same fingerprint, same
# autotune cache keys.  One default has moved since: on TPU, ELL/JDS
# matches default to jnp.ell, because pallas.ell gathers the vector in
# XLA in front of its kernel and so only adds an HBM round trip.
_EXPECTED = {
    "spmv_csr": [
        ("jnp.segment", ("cpu", "tpu"), ("CSR", "COO"), True),
        ("jnp.ell", ("cpu", "tpu"), ("CSR", "COO"), False),
        ("jnp.bcsr", ("cpu", "tpu"), ("CSR", "COO"), False),
        ("jnp.dense", ("cpu", "tpu"), ("CSR", "COO"), False),
        ("jnp.dia", ("cpu", "tpu"), ("CSR", "COO"), False),
        ("pallas.ell", ("tpu",), ("CSR", "COO"), False),
        ("pallas.bcsr", ("tpu",), ("CSR", "COO"), False),
    ],
    "spmv_ell": [
        ("jnp.ell", ("cpu", "tpu"), ("ELL", "JDS"), True),
        ("pallas.ell", ("cpu", "tpu"), ("ELL", "JDS"), True),
    ],
    "spmm_csr": [
        ("jnp.segment", ("cpu", "tpu"), ("CSR", "COO"), True),
        ("jnp.bcsr", ("cpu", "tpu"), ("CSR", "COO"), False),
        ("pallas.bcsr", ("tpu",), ("CSR", "COO"), False),
    ],
    "dotproduct": [("jnp.dot", ("cpu", "tpu"), (), True)],
    "gemv": [("jnp.dot", ("cpu", "tpu"), (), True)],
    # order matters: the autotuner's exploration budget truncates in
    # registration order, so this must match the old hand-wiring exactly
    "moe_ffn": [
        ("jnp.capacity", ("cpu", "tpu"), (), True),
        ("pallas.gmm", ("cpu", "tpu"), (), True),
        ("dense", ("cpu", "tpu"), (), True),
    ],
}
_EXPECTED["spmv_coo"] = _EXPECTED["spmv_csr"]
_EXPECTED["spmv_jds"] = _EXPECTED["spmv_ell"]

_EXPECTED_DEFAULTS = {
    ("spmv_csr", "cpu"): "jnp.segment", ("spmv_csr", "tpu"): "jnp.segment",
    ("spmv_coo", "cpu"): "jnp.segment", ("spmv_coo", "tpu"): "jnp.segment",
    ("spmv_ell", "cpu"): "jnp.ell", ("spmv_ell", "tpu"): "jnp.ell",
    ("spmv_jds", "cpu"): "jnp.ell", ("spmv_jds", "tpu"): "jnp.ell",
    ("spmm_csr", "cpu"): "jnp.segment", ("spmm_csr", "tpu"): "pallas.bcsr",
    ("dotproduct", "cpu"): "jnp.dot", ("dotproduct", "tpu"): "jnp.dot",
    ("gemv", "cpu"): "jnp.dot", ("gemv", "tpu"): "jnp.dot",
    ("moe_ffn", "cpu"): "jnp.capacity", ("moe_ffn", "tpu"): "pallas.gmm",
}


def _layout(reg):
    return {comp: [(h.name, h.platforms, h.formats, h.jit_safe)
                   for h in reg.harnesses_for(comp)]
            for comp in _EXPECTED}


def test_spec_registered_builtins_match_hand_wired_layout():
    assert _layout(lilac.REGISTRY) == _EXPECTED
    assert dict(lilac.REGISTRY._defaults) == _EXPECTED_DEFAULTS
    # a fresh registry built from the same specs is fingerprint-identical,
    # so persisted autotune decisions remain valid across the redesign
    fresh = lilac.register_builtins(HarnessRegistry())
    assert _layout(fresh) == _layout(lilac.REGISTRY)
    assert fresh.fingerprint() == lilac.REGISTRY.fingerprint()


def test_selection_parity_spot_checks():
    r = lilac.REGISTRY
    assert r.select("spmv_csr", "CSR", "cpu", "trace").name == "jnp.segment"
    assert r.select("spmv_csr", "CSR", "cpu", "host",
                    policy="jnp.ell").name == "jnp.ell"
    assert r.select("spmv_ell", "ELL", "tpu", "trace").name == "jnp.ell"
    assert r.select("spmm_csr", "CSR", "tpu", "host").name == "pallas.bcsr"
    assert r.select("moe_ffn", "MOE", "cpu", "trace").name == "jnp.capacity"
    # trace mode still filters host-only harnesses
    assert all(h.jit_safe for h in r.candidates("spmv_csr", "CSR", "cpu",
                                                "trace"))


def test_tab2_quick_sweep_selection_parity():
    """The acceptance gate: the --quick sweep must run every backend under
    the spec-registered registry and report the same default selection as
    the hand-wired one did (jnp.segment on cpu)."""
    from benchmarks.tab2_backends import BACKENDS, run
    table = run(reps=2, quick=True, out=None)
    assert table
    for prob, row in table.items():
        for backend in BACKENDS:
            s = row[(backend, "steady")]
            assert s == s, (prob, backend, "backend failed under spec registry")
    from benchmarks.tab2_backends import _default_backend
    assert _default_backend("cpu") == "jnp.segment"
