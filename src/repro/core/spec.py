"""LiLAC spec compilation: How-descriptors -> executable harnesses (§3.3).

The paper's promise is that a library implementer writes a *one-off LiLAC
description* — a What-clause (the computation) and a How-clause (harness,
marshaling, persistence) — and the compiler does the rest.  This module is
the How-compiler:

* ``build_harnesses`` turns a parsed ``HarnessDecl`` plus a Python kernel
  body into registered :class:`~repro.core.harness.Harness` objects.  The
  marshaling wrapper is *generated* from the declared ``marshal`` clauses:
  each clause names a registered repack function and the binding keys whose
  content fingerprints gate recomputation, and the wrapper routes the
  repack through the per-call :class:`MarshalingCache` (the mprotect
  analogue, paper Fig. 8-10) — backends no longer open-code cache lookups.
* ``@harness(...)`` is the decorator form: put the HARNESS block text right
  above the kernel body (see ``repro/kernels/*/harness.py``); the body is
  compiled and registered at import time.  "Add a backend" is therefore a
  spec-plus-function change, which is the paper's whole point.
* ``@repack(name)`` / ``@hook(name)`` register the named format-conversion
  and BeforeFirstExecution/AfterLastExecution functions that spec texts
  refer to.
* ``register_builtins`` populates a registry from the builtin spec texts
  (``what_lang.BUILTIN_SPECS`` for the jnp.* backends, plus the HARNESS
  blocks declared next to the Pallas kernels), replacing the hand-wired
  ``register()`` calls of earlier revisions.  Spec-driven registration
  produces byte-identical registry fingerprints, so persisted autotune
  decisions carry over.

New COMPUTATION programs in a registered spec are added to
``what_lang.BUILTINS`` and the default detector is rebuilt, so detection
picks them up without touching compiler internals.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Union

from repro.core import harness as H
from repro.core import marshal as M
from repro.core import what_lang as W


class SpecError(ValueError):
    """A spec references something the How-compiler cannot resolve."""


# ---------------------------------------------------------------------------
# Repack + hook registries (the names spec texts refer to).
# ---------------------------------------------------------------------------

REPACKS: Dict[str, Callable[[H.Binding], Any]] = {}
HOOKS: Dict[str, Callable[[Dict[str, Any]], Any]] = {}
VJPS: Dict[str, Callable] = {}


def repack(name: str, *, override: bool = False):
    """Register a marshaling repack function ``binding -> packed value``
    under ``name`` so ``marshal x = name(...)`` clauses can refer to it."""
    def deco(fn):
        if name in REPACKS and REPACKS[name] is not fn and not override:
            raise SpecError(f"repack {name!r} is already registered")
        REPACKS[name] = fn
        return fn
    return deco


def hook(name: str, *, override: bool = False):
    """Register a persistence hook ``persistent_state_dict -> None`` for
    BeforeFirstExecution / AfterLastExecution clauses."""
    def deco(fn):
        if name in HOOKS and HOOKS[name] is not fn and not override:
            raise SpecError(f"hook {name!r} is already registered")
        HOOKS[name] = fn
        return fn
    return deco


def vjp(name: str, *, override: bool = False):
    """Register a custom backward body so ``vjp <name>(wrt...)`` clauses can
    refer to it.  The body has signature::

        bwd(binding, ctx, primal_out, cotangent) -> {wrt_key: grad, ...}

    It runs under the backward trace, so it must be traceable in
    ``cotangent`` (and the wrt binding values) — pure jnp over whatever
    concrete index structure it pulls from the binding / marshaling cache.
    The returned dict must supply a gradient for every declared wrt key."""
    def deco(fn):
        if name in VJPS and VJPS[name] is not fn and not override:
            raise SpecError(f"vjp {name!r} is already registered")
        VJPS[name] = fn
        return fn
    return deco


# Builtin backward bodies (repro.core.harness.BUILTIN_VJPS) enter the
# registry at import so every HARNESS block — builtin spec text or kernel
# package — can cite them without registration-order footwork.
VJPS.update(H.BUILTIN_VJPS)


# ---------------------------------------------------------------------------
# Descriptor -> Harness compilation
# ---------------------------------------------------------------------------

def _resolve_key(binding: H.Binding, alternatives) -> Any:
    for k in alternatives:
        if k in binding:
            return binding[k]
    raise KeyError(
        f"marshal key {'|'.join(alternatives)!r} not found in binding "
        f"(has {sorted(binding)})")


def _marshaled_fn(decl: W.HarnessDecl, body: Callable) -> Callable:
    """Generate the execution wrapper for a HARNESS descriptor: marshaled
    inputs and tuned schedule parameters both arrive at the kernel body as
    keyword arguments.

    *Marshal clauses*: each marshaled input is computed by its repack
    function, memoized in the call's cache on the fingerprints of the
    declared key arrays.  Clauses that declare ``from <src> to <dst>``
    route through the shared plan-level
    :class:`~repro.core.marshal.DataPlane`: the conversion graph plans the
    cheapest path to ``dst`` (riding intermediates another harness already
    cached), with the clause's repack function as the fallback when no
    path exists.

    *Tune clauses*: the body receives every declared tune param as a
    keyword argument — the default schedule (first declared values)
    overlaid with the caller's ``ctx.schedule``, which is how the
    autotuner's swept winner reaches the kernel.  Unknown schedule keys
    raise (a pinned variant must never silently no-op)."""
    clauses = decl.marshal
    default_schedule = decl.default_schedule()
    tune_names = frozenset(default_schedule)

    def fn(binding: H.Binding, ctx: H.CallCtx):
        marshaled = {}
        cache = ctx.cache if ctx is not None else None
        for cl in clauses:
            pack = REPACKS.get(cl.repack)
            if pack is None:
                raise SpecError(
                    f"harness {decl.name!r}: unknown repack {cl.repack!r}")
            keys = tuple(_resolve_key(binding, alts) for alts in cl.keys)
            if cache is None:
                marshaled[cl.name] = pack(binding)
            elif cl.src and cl.dst and hasattr(cache, "ensure"):
                marshaled[cl.name] = cache.ensure(
                    cl.src, cl.dst, keys, binding,
                    fallback=lambda p=pack: p(binding))
            else:
                marshaled[cl.name] = cache.get(
                    cl.repack, keys, lambda p=pack: p(binding))
        if tune_names:
            sched = dict(default_schedule)
            override = getattr(ctx, "schedule", None) if ctx is not None \
                else None
            if override:
                unknown = set(override) - tune_names
                if unknown:
                    raise SpecError(
                        f"harness {decl.name!r}: schedule has unknown "
                        f"param(s) {sorted(unknown)} "
                        f"(declared: {sorted(tune_names)})")
                sched.update(override)
            marshaled.update(sched)
        return body(binding, ctx, **marshaled)

    fn.__name__ = getattr(body, "__name__", decl.name)
    fn.__qualname__ = getattr(body, "__qualname__", decl.name)
    return fn


def build_harnesses(decl: W.HarnessDecl, body: Callable, *,
                    hooks: Optional[Dict[str, Callable]] = None,
                    ) -> List[H.Harness]:
    """Compile one HARNESS descriptor + kernel body into Harness objects
    (one per implemented computation)."""
    table = {**HOOKS, **(hooks or {})}
    setup = teardown = None
    if decl.before_first is not None:
        setup = table.get(decl.before_first)
        if setup is None:
            raise SpecError(f"harness {decl.name!r}: unknown "
                            f"BeforeFirstExecution hook {decl.before_first!r}")
    if decl.after_last is not None:
        teardown = table.get(decl.after_last)
        if teardown is None:
            raise SpecError(f"harness {decl.name!r}: unknown "
                            f"AfterLastExecution hook {decl.after_last!r}")
    # Eagerly materialize the schedule family: a tune/constraint mistake
    # (symbolic value in an arithmetic constraint, or constraints so tight
    # the default schedule itself is pruned) must fail at registration, not
    # mid-sweep inside the autotuner.
    schedules = ()
    if decl.tune:
        try:
            schedules = W.enumerate_schedules(decl.tune, decl.constraints)
        except W.ParseError as e:
            raise SpecError(f"harness {decl.name!r}: {e}")
        if not schedules:
            raise SpecError(
                f"harness {decl.name!r}: constraints prune every schedule "
                f"variant")
        if schedules[0] != decl.default_schedule():
            raise SpecError(
                f"harness {decl.name!r}: the default schedule (first "
                f"declared values) violates a constraint")
    fn = _marshaled_fn(decl, body) if (decl.marshal or decl.tune) else body
    # One HARNESS block describes ONE backend, however many computations it
    # implements: the Harness objects share a single persistent-state dict
    # and a single lifecycle flag, so the hooks run once per backend (first
    # call anywhere sets up, release anywhere tears down for all, and a
    # later call sets up again), not once per computation.
    persistent = {k: None for k in decl.persistent}
    lifecycle = {"up": False} if len(decl.implements) > 1 else None
    return [
        H.Harness(decl.name, comp, fn, jit_safe=decl.jit_safe,
                  platforms=decl.platforms, formats=decl.formats,
                  persistent=persistent, setup=setup, teardown=teardown,
                  lifecycle=lifecycle, marshal=decl.marshal,
                  tune=decl.tune, constraints=decl.constraints,
                  fuse_epilogue=decl.fuse_epilogue, vjp=decl.vjp,
                  _schedules=schedules or None)
        for comp in decl.implements
    ]


# Every spec registered against the global REGISTRY is logged so that
# register_builtins can replay the full builtin surface into a fresh
# registry (parity tests, isolated experiments).
_GLOBAL_SPEC_LOG: List[tuple] = []


def register_spec(spec: Union[str, W.Spec], bodies: Dict[str, Callable], *,
                  registry: Optional[H.HarnessRegistry] = None,
                  hooks: Optional[Dict[str, Callable]] = None,
                  override: bool = False) -> List[H.Harness]:
    """Register a full LiLAC spec: new computations go to the What-language
    builtins (rebuilding the default detector), and every HARNESS block is
    compiled against its kernel body from ``bodies`` and registered."""
    if isinstance(spec, str):
        spec = W.parse_spec(spec)
    reg = registry if registry is not None else H.REGISTRY
    is_global = reg is H.REGISTRY

    # Phase 1 — validate and build with NO side effects, so a bad spec
    # raises without leaving computations published, the detector rebuilt,
    # or a prefix of its harnesses registered.
    local_comps = {c.name for c in spec.computations}
    for comp in spec.computations:
        known = W.BUILTINS.get(comp.name)
        if known is not None and known != comp:
            raise SpecError(
                f"computation {comp.name!r} conflicts with an existing "
                f"definition; rename it or match the builtin text")
    staged: List[tuple] = []    # (decl, [Harness, ...])
    seen: set = set()           # (implements, name) within this spec
    for decl in spec.harnesses:
        for target in decl.implements:
            if target not in W.BUILTINS and target not in local_comps:
                raise SpecError(
                    f"HARNESS {decl.name!r} implements unknown computation "
                    f"{target!r}")
        body = bodies.get(decl.name)
        if body is None:
            raise SpecError(
                f"no kernel body bound for HARNESS {decl.name!r} "
                f"(bodies has {sorted(bodies)})")
        if decl.vjp is not None and decl.vjp.name not in VJPS:
            # eager, like repacks: a typo'd backward must fail at
            # registration, not the first time someone differentiates
            raise SpecError(
                f"HARNESS {decl.name!r}: unknown vjp {decl.vjp.name!r} "
                f"(register it with @vjp before the harness)")
        for cl in decl.marshal:
            # eager, like hooks: a typo'd repack must fail at registration,
            # not be silently disqualified by the autotuner at call time
            if cl.repack not in REPACKS:
                raise SpecError(
                    f"HARNESS {decl.name!r}: unknown repack {cl.repack!r} "
                    f"(register it with @repack before the harness)")
            # declared formats must resolve against the data plane so the
            # conversion graph is built from specs, not hand-wiring
            if cl.src is not None and cl.src not in M.SOURCES:
                raise SpecError(
                    f"HARNESS {decl.name!r}: unknown marshal source "
                    f"{cl.src!r} (register it with register_source)")
            if cl.dst is not None and cl.dst not in M.FORMATS:
                raise SpecError(
                    f"HARNESS {decl.name!r}: unknown marshal target format "
                    f"{cl.dst!r} (register it with register_format)")
            if cl.src is not None and cl.dst is not None:
                start = M.SOURCES[cl.src].fmt
                if M.GRAPH.full_path_cost(start, cl.dst) is None:
                    raise SpecError(
                        f"HARNESS {decl.name!r}: no conversion path "
                        f"{cl.src}({start}) -> {cl.dst} in the graph")
        hs = build_harnesses(decl, body, hooks=hooks)
        for h in hs:
            key = (h.implements, h.name)
            already = any(ex.name == h.name
                          for ex in reg.harnesses_for(h.implements))
            if key in seen or (already and not override):
                raise H.DuplicateHarnessError(
                    f"harness {h.name!r} is already registered for "
                    f"{h.implements!r}; pass override=True to replace it")
            seen.add(key)
        staged.append((decl, hs))

    # Phase 2 — commit.  Registering against the global REGISTRY publishes
    # new computations to the What-language builtins (and rebuilds the
    # default detector) so they become detectable everywhere.  A
    # caller-supplied registry stays fully isolated: its spec's
    # computations resolve locally and never touch process-global state.
    new_comp = False
    for comp in spec.computations:
        if comp.name not in W.BUILTINS and is_global:
            W.BUILTINS[comp.name] = comp
            new_comp = True
    if new_comp:
        from repro.core import detect as D
        D.reset_default_detector()
    registered: List[H.Harness] = []
    for decl, hs in staged:
        for h in hs:
            reg.register(h, default_for=decl.default_for, override=override)
            registered.append(h)
    if is_global:
        _GLOBAL_SPEC_LOG.append((spec, dict(bodies), dict(hooks or {})))
    return registered


def harness(decl: Union[str, W.HarnessDecl], *,
            registry: Optional[H.HarnessRegistry] = None,
            hooks: Optional[Dict[str, Callable]] = None,
            override: bool = False):
    """Decorator: compile and register the kernel body under a HARNESS
    declaration (text or parsed).  The text may also carry COMPUTATION
    blocks, making a new backend a self-contained spec-plus-function::

        @lilac.harness('''
        HARNESS pallas.ell implements spmv_ell, spmv_jds
          formats ELL, JDS;
          default_for tpu;
        ''')
        def pallas_ell(binding, ctx):
            ...
    """
    if isinstance(decl, W.HarnessDecl):
        spec = W.Spec((), (decl,))
    else:
        spec = W.parse_spec(decl)
    if len(spec.harnesses) != 1:
        raise SpecError("@harness expects exactly one HARNESS block")
    name = spec.harnesses[0].name

    def deco(body):
        register_spec(spec, {name: body}, registry=registry, hooks=hooks,
                      override=override)
        return body

    return deco


# ---------------------------------------------------------------------------
# The builtin data plane: source loaders (binding -> format) and conversion
# edges (format value -> format value).  Marshal clauses name these via
# ``from <source> to <format>``; the legacy repack functions below remain as
# single-hop fallbacks and as the reference implementations the property
# tests compare planned paths against.
# ---------------------------------------------------------------------------

M.register_source("csr_binding", "CSR", H._binding_to_csr)
M.register_source("csr_binding_mm", "CSR", H._binding_to_csr_spmm)


@M.edge("CSR", "ELL8", name="csr_to_ell8")
def _csr_to_ell8(csr):
    from repro.sparse.convert import csr_to_ell
    return csr_to_ell(csr)


@M.edge("CSR", "ELL128", name="csr_to_ell128")
def _csr_to_ell128(csr):
    from repro.sparse.convert import csr_to_ell
    return csr_to_ell(csr, lane=128)


@M.edge("CSR", "DENSE", name="csr_todense")
def _csr_todense(csr):
    return csr.todense()


@M.edge("CSR", "JDS", name="csr_to_jds")
def _csr_to_jds(csr):
    from repro.sparse.convert import csr_to_jds
    return csr_to_jds(csr)


def _dense_to_bcsr(dense, block_shape):
    """Pad to block multiples and tile (csr_to_bcsr's second half, so
    CSR->DENSE->BCSR* composes to exactly the legacy one-hop repack and
    the DENSE intermediate is shareable with the jnp.dense harness)."""
    import numpy as np

    from repro.sparse.formats import bcsr_from_dense
    d = np.asarray(dense)
    bm, bn = block_shape
    rows, cols = d.shape
    pr = (-rows) % bm
    pc = (-cols) % bn
    if pr or pc:
        d = np.pad(d, ((0, pr), (0, pc)))
    return bcsr_from_dense(d, block_shape)


@M.edge("DENSE", "BCSR8x128", name="dense_to_bcsr8x128")
def _dense_to_bcsr8(dense):
    return _dense_to_bcsr(dense, (8, 128))


@M.edge("DENSE", "BCSR128x128", name="dense_to_bcsr128x128")
def _dense_to_bcsr128(dense):
    return _dense_to_bcsr(dense, (128, 128))


@M.edge("CSR", "BCSR128x128", name="csr_to_bcsr128x128")
def _csr_to_bcsr128(csr):
    """Tiles the nonzeros directly: the MXU kernel's matrices are the ones
    too large to densify on the way."""
    from repro.sparse.convert import csr_to_bcsr
    return csr_to_bcsr(csr, block_shape=(128, 128))


# ---------------------------------------------------------------------------
# Builtin repacks (single-hop fallbacks; also the graph-equivalence oracle).
# ---------------------------------------------------------------------------

@repack("ell_pack")
def _ell_pack(b: H.Binding):
    from repro.sparse.convert import csr_to_ell
    return csr_to_ell(H._binding_to_csr(b))


@repack("ell_pack128")
def _ell_pack128(b: H.Binding):
    from repro.sparse.convert import csr_to_ell
    return csr_to_ell(H._binding_to_csr(b), lane=128)


@repack("bcsr_pack")
def _bcsr_pack(b: H.Binding):
    from repro.sparse.convert import csr_to_bcsr
    return csr_to_bcsr(H._binding_to_csr(b), block_shape=(8, 128))


@repack("bcsr_pack128")
def _bcsr_pack128(b: H.Binding):
    from repro.sparse.convert import csr_to_bcsr
    return csr_to_bcsr(H._binding_to_csr(b), block_shape=(128, 128))


@repack("densify")
def _densify(b: H.Binding):
    return H._binding_to_csr(b).todense()


@repack("bcsr_pack_mm")
def _bcsr_pack_mm(b: H.Binding):
    from repro.sparse.convert import csr_to_bcsr
    return csr_to_bcsr(H._binding_to_csr_spmm(b), block_shape=(8, 128))


@repack("bcsr_pack_mm128")
def _bcsr_pack_mm128(b: H.Binding):
    from repro.sparse.convert import csr_to_bcsr
    return csr_to_bcsr(H._binding_to_csr_spmm(b), block_shape=(128, 128))


# ---------------------------------------------------------------------------
# Builtin registration
# ---------------------------------------------------------------------------

_builtins_done = False


def register_builtins(registry: Optional[H.HarnessRegistry] = None):
    """Populate ``registry`` (default: the global REGISTRY) with every
    builtin backend, entirely from spec texts.

    Order matters for candidate enumeration: the jnp.* families from
    ``what_lang.BUILTIN_SPECS`` first, then the Pallas kernels' own HARNESS
    blocks (imported from the kernel packages, whose ``@harness``
    decorators register against the global REGISTRY and are logged for
    replay into custom registries)."""
    global _builtins_done
    if registry is None or registry is H.REGISTRY:
        if _builtins_done:
            return H.REGISTRY
        # override=True makes a retry after a mid-way failure (e.g. a
        # kernel-package ImportError) idempotent for the family specs; the
        # done flag is only set once everything registered, so a partial
        # first attempt fails loudly on retry instead of silently leaving
        # the pallas.* backends missing.
        for family, text in W.BUILTIN_SPECS.items():
            if family in W.POST_KERNEL_FAMILIES:
                continue
            register_spec(text, H.BUILTIN_BODIES.get(family, {}),
                          override=True)
        # The pallas.* backends self-register on import via @harness.
        from repro.kernels.spmv_ell import harness as _ell  # noqa: F401
        from repro.kernels.bsr_spmm import harness as _bsr  # noqa: F401
        from repro.kernels.moe_gmm import harness as _gmm   # noqa: F401
        # Baselines come last so candidate (and autotune-exploration)
        # order matches the pre-spec hand-wired registry exactly.
        for family in W.POST_KERNEL_FAMILIES:
            register_spec(W.BUILTIN_SPECS[family],
                          H.BUILTIN_BODIES.get(family, {}), override=True)
        _builtins_done = True
        return H.REGISTRY
    # Fresh registry: replay the global registration log.  Replay with
    # override=True — a spec re-loaded globally via the override escape
    # hatch appears twice in the log, and the later entry must win here
    # exactly as it did on the global registry.
    register_builtins(None)
    for spec, bodies, hooks in _GLOBAL_SPEC_LOG:
        register_spec(spec, bodies, registry=registry, hooks=hooks,
                      override=True)
    return registry
