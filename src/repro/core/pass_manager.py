"""The user-facing LiLAC pass (the paper's Fig. 1 compiler flow).

``compile(fn, mode=...)`` is the single entry point (exposed as
``repro.lilac.compile``); an optional :class:`CompileOptions` dataclass
carries the full configuration.

``mode="trace"`` — returns a function with the same signature whose jaxpr
    has detected computations replaced by jit-safe harnesses.  Wrap it in
    ``jax.jit`` exactly like the original; this is how the LM framework
    consumes LiLAC (MoE layers etc.).

``mode="host"`` — the paper's runtime model.  Each call executes the
    rewritten program eagerly; harnesses may be host-only and use the
    marshaling cache, so format repacks / derived invariants are amortized
    across calls exactly like the paper's mprotect machinery (Fig. 18).
    Use for solver-style apps that call the step repeatedly.

Both share: trace -> normalize -> detect (backtracking) -> rewrite.
Detection runs once per input-shape signature and is cached — and, when
the persistent plan cache (``repro.core.plan``) holds a record for the
jaxpr, it is skipped entirely: matches and autotune pins rehydrate from
disk.  Once every match has a definitive ``(harness, schedule)`` decision
and a concrete call has run, the rewrite is *baked* into an
:class:`~repro.core.plan.ExecutablePlan` — steady-state dispatch becomes a
guard check plus one ``jax.jit`` call instead of the eqn-by-eqn
interpreter (see ``docs/dispatch.md``).

``lilac_optimize`` / ``lilac_accelerate`` are deprecation shims over
``compile`` kept for out-of-repo callers; they warn with
:class:`LilacDeprecationWarning`, which the test suite escalates to an
error so in-repo code stays on the new surface.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Any, Callable, Dict, FrozenSet, List, Optional, Tuple

import jax

from repro.core import detect as D
from repro.core import faults
from repro.core import harness as H
from repro.core import plan as P
from repro.core import plan_search as PS
from repro.core import resilience as R
from repro.core import spans
from repro.core.autotune import autotune_disabled, variant_key
from repro.core.marshal import (DataPlane, MarshalingCache, MarshalPolicy,
                                TrackedArray)
from repro.core.rewrite import needed_eqn_ids, run_rewritten

_ENV_SHADOW = "LILAC_SHADOW_RATE"


def shadow_rate() -> float:
    """``LILAC_SHADOW_RATE`` in [0, 1]: the *floor* fraction of served
    dispatches that also run the un-rewritten reference for comparison.
    Since the adaptive controller landed this is re-read per dispatch
    (via an identity check on the cached env string, so the steady-state
    cost stays one dict lookup); divergence or quarantine incidents spike
    the effective rate above this floor — see
    :class:`repro.core.resilience.AdaptiveShadowRate`."""
    try:
        r = float(os.environ.get(_ENV_SHADOW, "0") or 0.0)
    except ValueError:
        return 0.0
    return min(max(r, 0.0), 1.0)


@dataclasses.dataclass
class CompiledEntry:
    closed_jaxpr: Any
    report: D.DetectionReport
    out_tree: Any
    # autotune pins: match index -> (harness name, schedule variant, fuse
    # realization), filled at first lowering for this signature so later
    # calls (and re-traces under jit) reuse the measured winner — including
    # its swept kernel schedule and epilogue-fusion decision — without
    # consulting the tuner again.  After the joint plan search runs, these
    # hold the jointly-optimal assignment, not the per-match argmins.
    pins: Dict[int, Tuple[str, Optional[Dict[str, Any]], Optional[bool]]] = \
        dataclasses.field(default_factory=dict)
    # id(anchor eqn) -> match index, built once at entry construction (the
    # pinned-select path used to rebuild it per call)
    idx_of: Dict[int, int] = dataclasses.field(default_factory=dict)
    # persistent-plan-cache plumbing
    cache_key: Optional[str] = None
    persisted: bool = False
    # the baked executable plan (None until the rewrite is resolved and a
    # concrete call has run; see docs/dispatch.md for the lifecycle)
    plan: Optional[P.ExecutablePlan] = None
    no_bake: bool = False
    bake_error: Optional[str] = None
    rebakes: int = 0
    # joint whole-program plan search (repro.core.plan_search): the report
    # of the last search and whether the search has run (or been skipped)
    # for this entry.  Entries rehydrated from the plan cache with complete
    # pins start done: the persisted pins already ARE the joint assignment,
    # so warm processes serve it with zero re-search.
    joint: Optional[Dict[str, Any]] = None
    joint_done: bool = False
    # match indices (into the flattened report) whose every harness
    # candidate failed under containment: these anchors evaluate as plain
    # jaxpr equations — the reference floor — until the entry is rebuilt
    disabled: set = dataclasses.field(default_factory=set)
    # memoized liveness (rewrite.needed_eqn_ids), keyed by the anchor-id
    # set of the match list actually evaluated — containment can disable
    # individual matches, so "full" and "empty" are just two of the keys
    _needed: Dict[FrozenSet[int], frozenset] = \
        dataclasses.field(default_factory=dict)

    def needed_for(self, matches) -> frozenset:
        key = frozenset(id(m.anchor_eqn) for m in matches)
        got = self._needed.get(key)
        if got is None:
            got = self._needed[key] = needed_eqn_ids(self.closed_jaxpr,
                                                     matches)
        return got


def _flat_matches(matches) -> List[D.Match]:
    """Flatten a detection report for selection bookkeeping: scan-body
    wrapper matches never select a harness themselves — their *inner*
    matches do, once per trace of the rebuilt ``lax.scan`` body — so pins,
    resolution counting and the anchor->index map all operate on the
    recursively flattened list."""
    out: List[D.Match] = []
    for m in matches:
        if m.variant == "scan_body" and m.body is not None:
            out.extend(_flat_matches(m.body[1]))
        else:
            out.append(m)
    return out


def _signature(flat_args) -> Tuple:
    """Hashable compile-dict key, derived from the single leaf-keying
    source (``plan.leaf_templates`` — also the basis of the last-entry
    fast path and the baked-plan guard specs) so the layers cannot
    drift."""
    return tuple(
        (t[1], str(t[2])) if t[0] == "a" else ("py", t[1].__name__, t[2])
        for t in P.leaf_templates(flat_args))


class LilacFunction:
    """A function passed through the LiLAC pass."""

    def __init__(self, fn: Callable, *, mode: str = "trace",
                 policy: str = "default",
                 registry: Optional[H.HarnessRegistry] = None,
                 detector: Optional[D.Detector] = None,
                 platform: Optional[str] = None,
                 cache: Optional[MarshalingCache] = None,
                 marshal_policy=None,
                 enabled: bool = True,
                 bake: bool = True,
                 plan_cache: Any = None,
                 donate_args: Tuple[int, ...] = ()):
        assert mode in ("trace", "host")
        self.fn = fn
        self.mode = mode
        self.policy = policy
        self.registry = registry or H.REGISTRY
        self.detector = detector or D.default_detector()
        self.platform = platform or jax.default_backend()
        self.marshal_policy = MarshalPolicy.parse(marshal_policy)
        if cache is not None:
            # caller-supplied cache (possibly shared with other compiled
            # functions: the cross-function plan-level sharing path)
            self.cache = cache
        elif self.marshal_policy.enabled:
            self.cache = DataPlane(policy=self.marshal_policy)
        else:
            self.cache = None       # every call repacks (A/B baseline)
        self.enabled = bool(enabled)
        self.bake_enabled = bool(bake)
        self.donate_args = tuple(donate_args or ())
        self._plan_cache_injected = isinstance(plan_cache, P.PlanCache)
        self._plan_cache = self._make_plan_cache(plan_cache)
        self._compiled: Dict[Tuple, CompiledEntry] = {}
        self._last_compiled: Optional[Tuple] = None  # (entry, in_tree, tmpl)
        self._last_plan: Optional[P.ExecutablePlan] = None
        # recently-served baked plans across ALL signatures, move-to-front.
        # Bucketed callers (the serving tier) rotate between a small set of
        # shapes every few calls; checking each hot plan's O(arity) guard
        # beats falling back to flatten -> template compare -> dict lookup
        # on every bucket switch.
        self._hot_plans: List[P.ExecutablePlan] = []
        self.last_report: Optional[D.DetectionReport] = None
        # (match, harness-name) pairs from the most recent call, in anchor
        # order — what actually ran, for benchmarks and tests.
        self.last_selections: List[Tuple[D.Match, str]] = []
        # the schedule variant each selection ran with (None = default /
        # untuned), aligned with last_selections — benchmarks record which
        # swept schedule a plan actually used.
        self.last_schedules: List[Optional[Dict[str, Any]]] = []
        # failure containment (repro.core.resilience): per-function
        # counters, the adaptive shadow-verification controller (the env
        # rate is a floor; incidents spike it, clean checks decay it —
        # rate 0 with no incidents must stay one dict lookup + float
        # compare per dispatch), and the recursion guard that keeps a
        # shadow's own dispatch from shadowing
        self.resilience_stats = R.ContainmentStats()
        self._shadow = R.AdaptiveShadowRate(_ENV_SHADOW)
        self._shadow_ctr = 0
        self._in_shadow = False

    def _make_plan_cache(self, opt) -> Optional[P.PlanCache]:
        if opt is False or (isinstance(opt, str)
                            and opt in ("off", "none", "disabled")):
            return None
        if isinstance(opt, P.PlanCache):
            return opt
        if opt in (None, True, "default", "on"):
            # only the default resolution honors the env kill-switch: an
            # explicitly passed path (like an injected instance) is a
            # stronger statement of intent than LILAC_PLAN_CACHE_DISABLE
            if P.plan_cache_disabled():
                return None
            return P.shared_plan_cache(None, self.registry.fingerprint())
        return P.shared_plan_cache(opt, self.registry.fingerprint())

    # -- compilation ---------------------------------------------------------

    def _validated_pins(self, raw: Dict[str, Any], matches) -> Dict[int, Tuple]:
        """Pins rehydrated from the plan cache, checked against the live
        registry: a vanished harness or a schedule outside the harness's
        current tune space drops the pin (the autotune policy re-tunes it)
        rather than ever pinning something unservable."""
        pins: Dict[int, Tuple] = {}
        flat = _flat_matches(matches)
        q = R.shared_quarantine()
        for k, v in (raw or {}).items():
            try:
                i, name, schedule = int(k), v[0], v[1]
            except (TypeError, ValueError, IndexError):
                continue
            # pre-joint-search records persisted [name, schedule] pairs;
            # fuse=None keeps the harness's declared realization
            fuse = v[2] if len(v) > 2 else None
            if not (0 <= i < len(flat)):
                continue
            try:
                h = self.registry.get(flat[i].computation, name)
            except KeyError:
                continue
            if schedule is not None and schedule not in (h.schedules or ()):
                continue
            # a quarantined (harness, variant) must never rehydrate into a
            # pin: the record predates the incident that quarantined it
            if q.is_quarantined(flat[i].computation, name,
                                variant_key(schedule, fuse)) \
                    or q.is_quarantined(flat[i].computation, name):
                continue
            pins[i] = (name, schedule, fuse)
        return pins

    def _build_entry(self, args, kwargs) -> CompiledEntry:
        cj, out_shape = jax.make_jaxpr(self.fn, return_shape=True)(*args, **kwargs)
        ncj = D.normalize_closed_jaxpr(cj)
        out_tree = jax.tree_util.tree_structure(out_shape)
        cache_key = None
        report = None
        pins: Dict[int, Tuple] = {}
        served = False
        joint_rec = None
        pc = self._plan_cache
        if pc is not None and not self._plan_cache_injected \
                and pc.registry_fingerprint != self.registry.fingerprint():
            # specs registered since this LilacFunction was built: re-key
            # the cache view so stale plans invalidate, fresh ones persist
            pc = self._plan_cache = P.shared_plan_cache(
                pc.path, self.registry.fingerprint())
        if pc is not None:
            cache_key = P.plan_key(ncj, self.platform, self.mode,
                                   self.policy,
                                   reuse=self.marshal_policy.reuse)
            rec = pc.get(cache_key)
            if rec is not None:
                got = None
                # integrity first: every schema-1 record carries n_eqns +
                # detect_digest, so both must be present AND agree with
                # the record's own matches / the live jaxpr before any
                # atom reference is resolved — truncated or hand-edited
                # records reject here
                ser = rec.get("matches", ())
                intact = (rec.get("n_eqns") == len(ncj.jaxpr.eqns)
                          and rec.get("detect_digest")
                          == P.detect_digest(ser))
                if intact:
                    got = P.rehydrate_matches(ncj, ser)
                if got is not None:
                    report = D.DetectionReport(
                        got, n_eqns=len(ncj.jaxpr.eqns),
                        log=["rehydrated from plan cache "
                             "(detection + tuning skipped)"])
                    pins = self._validated_pins(rec.get("pins"), got)
                    joint_rec = rec.get("joint")
                    served = True
                else:
                    pc.stats.rejected += 1
        if report is None:
            report = self.detector.detect(ncj, normalize=False)
        entry = CompiledEntry(ncj, report, out_tree)
        entry.pins = pins
        entry.idx_of = {id(m.anchor_eqn): i
                        for i, m in enumerate(_flat_matches(report.matches))}
        entry.cache_key = cache_key
        # a served record with complete pins never re-persists; a served
        # record whose pins were dropped (or never tuned) re-persists once
        # this process resolves them
        entry.persisted = served and (
            self.policy != "autotune" or not report.matches
            or len(pins) == len(report.matches))
        # warm start: served pins with full coverage already carry the
        # joint assignment from the process that searched it — serve with
        # zero re-search (the acceptance property the benchmark gates)
        if served and pins and len(pins) == len(
                _flat_matches(report.matches)):
            entry.joint_done = True
            entry.joint = joint_rec
        return entry

    def _entry_for(self, args, kwargs, flat, in_tree) -> CompiledEntry:
        last = self._last_compiled
        if (last is not None and last[1] == in_tree
                and P.leaves_match(last[2], flat)):
            entry = last[0]
        else:
            key = (_signature(flat), in_tree)
            entry = self._compiled.get(key)
            if entry is None:
                with spans.span("lilac.detect"):
                    entry = self._build_entry(args, kwargs)
                self._compiled[key] = entry
            self._last_compiled = (entry, in_tree, P.leaf_templates(flat))
        self.last_report = entry.report
        return entry

    def _prepare(self, args, kwargs, flat=None, in_tree=None):
        """Flatten, unwrap TrackedArray leaves, resolve the CompiledEntry.
        Returns (entry, raw leaves, unwrapped leaves, in_tree)."""
        if flat is None:
            flat, in_tree = jax.tree_util.tree_flatten((args, kwargs))
        raw_flat = flat
        if any(isinstance(x, TrackedArray) for x in flat):
            flat = [x.arr if isinstance(x, TrackedArray) else x for x in flat]
            args, kwargs = jax.tree_util.tree_unflatten(in_tree, flat)
        entry = self._entry_for(args, kwargs, flat, in_tree)
        return entry, raw_flat, flat, in_tree

    def _compile(self, args, kwargs) -> Tuple[CompiledEntry, List[Any]]:
        entry, _, flat, _ = self._prepare(args, kwargs)
        return entry, flat

    def report_for(self, *args, **kwargs) -> D.DetectionReport:
        entry, _ = self._compile(args, kwargs)
        return entry.report

    # -- execution -----------------------------------------------------------

    def _select(self, m: D.Match, binding=None, ctx=None) -> H.Harness:
        return self.registry.select(
            m.computation, m.format, self.platform, self.mode,
            policy=self.policy, binding=binding, ctx=ctx)

    def _pinned_select(self, entry: CompiledEntry):
        """Autotune policy: delegate to the persistent tuner once per match
        per input-signature, then pin the (winner, schedule) pair into the
        rewrite.  Pinning only happens for definitive decisions (measured
        or cache-hit) so a can't-measure fallback — e.g. the very first
        call happening under a user's jit trace — stays re-tunable on later
        concrete calls."""
        idx_of = entry.idx_of

        def select(m: D.Match, binding=None, ctx=None) -> H.Harness:
            i = idx_of.get(id(m.anchor_eqn))
            if i is None:
                # defensive: a match outside the entry's flattened report
                # (shouldn't happen) still selects, just without pinning
                return self._select(m, binding, ctx)
            pin = entry.pins.get(i)
            if pin is not None:
                name, schedule, fuse = pin
                try:
                    h = self.registry.get(m.computation, name)
                    if ctx is not None:
                        ctx.schedule = schedule
                        ctx.fuse = fuse
                    return h
                except KeyError:
                    del entry.pins[i]   # harness set changed; re-tune
            h = self._select(m, binding, ctx)
            tuner = self.registry.autotuner
            dec = tuner.last_decision
            if dec is not None and dec.definitive:
                entry.pins[i] = dec.as_pin()
            return h

        return select

    def _ctx_factory(self, m: D.Match) -> H.CallCtx:
        return H.CallCtx(mode=self.mode, cache=self.cache, format=m.format,
                         platform=self.platform, epilogue=m.epilogue)

    def _dispatch_plan(self, plan: P.ExecutablePlan, leaves):
        plan.hits += 1
        self.last_report = plan.report
        self.last_selections = plan.selections
        self.last_schedules = plan.schedules
        with spans.span("lilac.enqueue"):
            outs = plan.jitted(*leaves)
        return jax.tree_util.tree_unflatten(plan.out_tree, outs)

    def _enabled_matches(self, entry: CompiledEntry) -> List[D.Match]:
        """The report's matches minus containment-disabled ones.  A
        scan-body wrapper drops wholesale when any inner match is disabled
        — there is no per-iteration mix of harness and reference."""
        matches = entry.report.matches if self.enabled else []
        if not entry.disabled:
            return matches
        idx_of = entry.idx_of
        return [m for m in matches
                if not any(idx_of.get(id(fm.anchor_eqn)) in entry.disabled
                           for fm in _flat_matches([m]))]

    def _serve_plan(self, plan: P.ExecutablePlan, leaves, in_tree):
        out = self._dispatch_plan(plan, leaves)
        if not self._in_shadow:
            r = self._shadow.effective()
            if r > 0.0:
                out = self._maybe_shadow(plan, leaves, in_tree, out, r)
        return out

    def _maybe_shadow(self, plan, leaves, in_tree, out, r):
        """Sampled shadow verification: deterministically stratified so a
        rate of r checks dispatch n iff the integer part of n*r advances —
        every window of 1/r dispatches contains exactly one check, with no
        RNG state to perturb.  ``r`` is the adaptive *effective* rate, so
        an incident densifies checking immediately and a clean streak
        relaxes it back to the floor."""
        self._shadow_ctr = n = self._shadow_ctr + 1
        if int(n * r) == int((n - 1) * r):
            return out
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            return out          # values don't exist yet; nothing to compare
        self.resilience_stats.shadow_checks += 1
        args, kwargs = jax.tree_util.tree_unflatten(in_tree, leaves)
        self._in_shadow = True
        try:
            ref = self.fn(*args, **kwargs)
        except Exception:
            return out          # the reference itself failed; keep ours
        finally:
            self._in_shadow = False
        if R.outputs_close(out, ref) \
                and not faults.check("shadow_diverge", "dispatch"):
            self._shadow.clean()
            return out
        # divergence: the accelerated answer is wrong.  Serve the reference
        # for THIS call, quarantine everything the plan selected, and tear
        # the plan down so the next dispatch re-tunes and re-bakes.
        self.resilience_stats.shadow_divergences += 1
        self._shadow_divergence(plan)
        return ref

    def _shadow_divergence(self, plan: P.ExecutablePlan):
        self._shadow.spike("shadow divergence")
        q = R.shared_quarantine()
        for (m, name), sched in zip(plan.selections, plan.schedules):
            q.add(m.computation, name, variant_key(sched, None),
                  reason="shadow divergence", site=name)
        if self._last_plan is plan:
            self._last_plan = None
        self._drop_hot(plan)
        for entry in self._compiled.values():
            if entry.plan is plan:
                entry.plan = None
                entry.pins.clear()
                entry.persisted = False
                entry.joint_done = False
                entry.joint = None

    def report_divergence(self, reason: str = "external divergence"):
        """An out-of-band verifier (the serving tier's request-level shadow,
        an application-level checksum) observed this function producing a
        wrong answer that per-dispatch shadowing did not catch.  Responds
        exactly like an in-band divergence: quarantine what the live plans
        selected, tear the plans down so the next dispatch re-tunes, spike
        the adaptive shadow rate, and count the incident."""
        self.resilience_stats.shadow_divergences += 1
        plans = []
        for entry in self._compiled.values():
            if entry.plan is not None and entry.plan not in plans:
                plans.append(entry.plan)
        q = R.shared_quarantine()
        for entry in self._compiled.values():
            if entry.plan is None and entry.pins:
                # tuned but unbaked signature: quarantine its pinned
                # selections directly and force a re-tune
                flat = _flat_matches(entry.report.matches)
                for i, (name, sched, fuse) in list(entry.pins.items()):
                    comp = flat[i].computation if i < len(flat) else name
                    q.add(comp, name, variant_key(sched, None),
                          reason=reason, site=name)
                entry.pins.clear()
                entry.persisted = False
                entry.joint_done = False
                entry.joint = None
        for plan in plans:
            self._shadow_divergence(plan)
        if not plans:
            self._shadow.spike(reason)

    def resilience_info(self) -> Dict[str, Any]:
        """Containment / quarantine / shadow counters for this function
        plus the shared quarantine store's view — benchmarks and the chaos
        gate read this instead of poking privates."""
        q = R.shared_quarantine()
        return {
            "containment": self.resilience_stats.as_dict(),
            "quarantine": q.stats.as_dict(),
            "quarantine_active": len(q.active()),
            "quarantine_path": str(q.path),
            "shadow_rate": self._shadow.effective(),
            "shadow": self._shadow.snapshot(),
            "disabled_matches": sum(len(e.disabled)
                                    for e in self._compiled.values()),
        }

    _HOT_PLAN_LIMIT = 32

    def _note_hot(self, plan: P.ExecutablePlan):
        """Move-to-front a plan in the hot list (bounded)."""
        hot = self._hot_plans
        if hot and hot[0] is plan:
            return
        try:
            hot.remove(plan)
        except ValueError:
            pass
        hot.insert(0, plan)
        del hot[self._HOT_PLAN_LIMIT:]

    def _drop_hot(self, plan: P.ExecutablePlan):
        try:
            self._hot_plans.remove(plan)
        except ValueError:
            pass

    def __call__(self, *args, **kwargs):
        with spans.span("lilac.dispatch"):
            return self._call(args, kwargs)

    def _call(self, args, kwargs):
        flat, in_tree = jax.tree_util.tree_flatten((args, kwargs))
        # steady-state fast path: guard check -> one jitted dispatch.
        # A registry epoch moved by any (re-)registration refuses the
        # plan: a replaced harness body must never be served from a
        # stale jitted executable.
        epoch = self.registry.epoch
        plan = self._last_plan
        if plan is not None and plan.registry_epoch == epoch:
            leaves = plan.match_and_unwrap(in_tree, flat, self.enabled)
            if leaves is not None:
                return self._serve_plan(plan, leaves, in_tree)
        # hot-plan scan: bucketed callers rotate between a handful of
        # signatures; any of them can serve without re-keying the entry
        for hp in self._hot_plans:
            if hp is plan or hp.registry_epoch != epoch:
                continue
            leaves = hp.match_and_unwrap(in_tree, flat, self.enabled)
            if leaves is not None:
                self._last_plan = hp
                self._note_hot(hp)
                return self._serve_plan(hp, leaves, in_tree)
        entry, raw_flat, uflat, in_tree = self._prepare(
            args, kwargs, flat, in_tree)
        # second chance: another signature's plan was hot; this entry may
        # still hold a valid one
        plan = entry.plan
        if (plan is not None and plan is not self._last_plan
                and plan.registry_epoch == epoch):
            leaves = plan.match_and_unwrap(in_tree, raw_flat, self.enabled)
            if leaves is not None:
                self._last_plan = plan
                self._note_hot(plan)
                return self._serve_plan(plan, leaves, in_tree)

        matches = self._enabled_matches(entry)
        select = (self._pinned_select(entry) if self.policy == "autotune"
                  else self._select)
        # Recording runs even when leaves are tracers (the call sits under
        # jax.grad / vmap / a user jit): once the rewrite is resolved, the
        # plan bakes *under the transform trace* — no concrete call is ever
        # required — with warm-up deferred and hoisting skipped for
        # anything tracer-derived (see _maybe_bake / plan.bake_plan).
        recorder = (P.PlanRecorder()
                    if self.bake_enabled and not entry.no_bake
                    else None)
        # A donated signature records its first call under an abstract
        # trace of the donated operands (see below); marshaling then runs
        # outside the shared cache, so no traced value lands in it.
        abstract = (recorder is not None and bool(self.donate_args)
                    and self.policy != "autotune"
                    and not any(isinstance(x, jax.core.Tracer)
                                for x in uflat))

        def ctx_factory(m):
            ctx = self._ctx_factory(m)
            if recorder is not None:
                ctx.cache = P.recording_cache(
                    None if abstract else ctx.cache,
                    recorder.slot(m).buffers)
            return ctx

        selections: List[Tuple[D.Match, str]] = []
        schedules: List[Optional[Dict[str, Any]]] = []

        def on_select(m, h, ctx):
            sched = getattr(ctx, "schedule", None)
            if selections and selections[-1][0] is m:
                # containment retry: the previous candidate for this same
                # anchor failed — replace its record, don't append
                selections[-1] = (m, h.name)
                schedules[-1] = sched
            else:
                selections.append((m, h.name))
                schedules.append(sched)
            if recorder is not None:
                recorder.begin(m, h, sched, getattr(ctx, "fuse", None))

        def on_quarantine(m, h, vkey, reason):
            # the quarantined harness may be pinned, persisted, baked and
            # jointly-assigned for this entry: unwind all four so the next
            # selection re-tunes and the next resolution re-bakes.  A
            # quarantine is also an incident: densify shadow checking
            # until a clean streak restores trust.
            self._shadow.spike(f"quarantine: {reason}")
            i = entry.idx_of.get(id(m.anchor_eqn))
            pin = entry.pins.get(i) if i is not None else None
            if pin is not None and pin[0] == h.name:
                del entry.pins[i]
            entry.persisted = False
            entry.joint_done = False
            entry.joint = None
            entry.no_bake = False
            entry.bake_error = None
            if entry.plan is not None:
                if self._last_plan is entry.plan:
                    self._last_plan = None
                self._drop_hot(entry.plan)
                entry.plan = None

        contain = R.Containment(self.registry, R.shared_quarantine(),
                                on_quarantine=on_quarantine,
                                stats=self.resilience_stats)
        if abstract:
            # Evaluated eagerly, the interpreter would hold the donated
            # operands and their replacements at once (a serving cache
            # twice over).  So the first call of a donated signature
            # records the rewrite with the donated operands abstract, bakes,
            # and serves the baked plan, which consumes them in place.  The
            # recording runs without containment (a harness that fails on
            # an abstract operand is no incident); a failure, a marshaled
            # buffer or a plan that did not bake falls back to the eager
            # call below.
            def record(vals):
                return run_rewritten(
                    entry.closed_jaxpr, matches, select, vals, ctx_factory,
                    on_select=on_select, needed=entry.needed_for(matches))

            if self._record_abstract(record, uflat, recorder):
                self.last_selections = selections
                self.last_schedules = schedules
                self._maybe_persist(entry)
                self._maybe_bake(entry, matches, recorder, raw_flat, uflat,
                                 in_tree)
                plan = entry.plan
                leaves = (plan.match_and_unwrap(in_tree, raw_flat,
                                                self.enabled)
                          if plan is not None else None)
                if leaves is not None:
                    self._last_plan = plan
                    self._note_hot(plan)
                    return self._serve_plan(plan, leaves, in_tree)
            abstract = False
            recorder = (P.PlanRecorder()
                        if self.bake_enabled and not entry.no_bake
                        else None)
            selections.clear()
            schedules.clear()
        # containment retry loop: a ReferenceFallback disables ONE match
        # (its anchor then evaluates as a plain equation), so the loop is
        # bounded by the match count + the final all-reference pass
        for _ in range(len(_flat_matches(matches)) + 1):
            try:
                outs = run_rewritten(
                    entry.closed_jaxpr, matches, select, uflat, ctx_factory,
                    on_select=on_select, needed=entry.needed_for(matches),
                    contain=contain)
                break
            except R.ReferenceFallback as rf:
                i = entry.idx_of.get(id(rf.match.anchor_eqn))
                if i is None:
                    raise   # not this entry's match; nothing we can disable
                entry.disabled.add(i)
                matches = self._enabled_matches(entry)
                selections.clear()
                schedules.clear()
        self.last_selections = selections
        self.last_schedules = schedules
        joint_moved = self._maybe_joint(entry)
        self._maybe_persist(entry)
        if recorder is not None and not joint_moved:
            # pins just changed under the joint search: this call recorded
            # the pre-joint assignment, so baking it would freeze the wrong
            # plan — the next call records and bakes the joint one
            self._maybe_bake(entry, matches, recorder, raw_flat, uflat,
                             in_tree)
        return jax.tree_util.tree_unflatten(entry.out_tree, outs)

    # -- plan lifecycle ------------------------------------------------------

    def _record_abstract(self, run, uflat, recorder: P.PlanRecorder) -> bool:
        """``run`` the rewrite once with the donated operands abstract
        (the rest concrete), for its recording alone.  True when it ran
        and recorded no marshaled buffer (baking checks the rest)."""
        don = sorted(i for i in set(self.donate_args)
                     if 0 <= i < len(uflat))

        def record(*dv):
            vals = list(uflat)
            for i, v in zip(don, dv):
                vals[i] = v
            return run(vals)

        try:
            jax.eval_shape(record, *(
                jax.ShapeDtypeStruct(uflat[i].shape, uflat[i].dtype)
                for i in don))
        except Exception:
            return False
        return not any(s.buffers for s in recorder.slots.values())

    def _maybe_joint(self, entry: CompiledEntry) -> bool:
        """Run the joint whole-program plan search once per entry, after
        every match has a definitive per-match pin.  Returns True when the
        search moved any pin (the caller then skips baking this call — the
        recorded selections are the pre-joint ones).

        The search is pure bookkeeping over the autotune cache's measured
        components — zero re-timing — so it runs inline.  Entries served
        from the plan cache with complete pins arrive ``joint_done`` (the
        persisted pins are the previous process's joint assignment)."""
        if entry.joint_done or self.policy != "autotune":
            return False
        matches = entry.report.matches if self.enabled else []
        flat = _flat_matches(matches)
        if len(flat) < 2:
            # nothing to couple: the per-match winner (fuse dimension
            # included, swept by the schema-4 autotuner) is already joint
            entry.joint_done = True
            return False
        if len(entry.pins) != len(flat):
            return False        # not yet resolved; retry next call
        width = PS.beam_width()
        if width <= 0:
            entry.joint_done = True     # LILAC_SEARCH_BEAM=0: pure greedy
            return False
        tuner = getattr(self.registry, "autotuner", None)
        if tuner is None:
            entry.joint_done = True
            return False
        try:
            with spans.span("lilac.tune"):
                res = PS.optimize_entry(
                    flat, entry.pins, registry=self.registry, tuner=tuner,
                    platform=self.platform, mode=self.mode, cache=self.cache,
                    reuse=self.marshal_policy.reuse, width=width)
        except Exception:
            entry.joint_done = True     # cost model unavailable: pins stand
            return False
        entry.joint_done = True
        if res is None:
            return False
        entry.joint = res.report()
        moved = False
        for i, cand in enumerate(res.assignment):
            pin = cand.pin()
            if entry.pins.get(i) != pin:
                entry.pins[i] = pin
                moved = True
        if moved:
            entry.persisted = False     # re-persist the joint pins
            if entry.plan is not None:  # baked on pre-joint pins: stale
                if self._last_plan is entry.plan:
                    self._last_plan = None
                self._drop_hot(entry.plan)
                entry.plan = None
        return moved

    def _resolved(self, entry: CompiledEntry, matches) -> bool:
        """A rewrite is resolved once every selection is definitive: always
        for explicit/default policies, for autotune once every match is
        pinned (or tuning is disabled, making defaults deterministic)."""
        if self.policy != "autotune" or not matches:
            return True
        return (len(entry.pins) == len(_flat_matches(matches))
                or autotune_disabled())

    def _maybe_persist(self, entry: CompiledEntry):
        pc = self._plan_cache
        if pc is None or entry.persisted or entry.cache_key is None:
            return
        matches = entry.report.matches
        if not self._resolved(entry, matches):
            return
        if any(m.variant == "scan_body" for m in matches):
            # a scan-body match carries the normalized body jaxpr + inner
            # matches as live objects; there is no stable positional
            # address for them, and a rehydrated wrapper without its body
            # would be unservable — keep scan entries in-memory only
            entry.persisted = True
            return
        try:
            ser = P.serialize_matches(entry.closed_jaxpr, matches)
        except Exception:
            entry.persisted = True      # unaddressable match: don't retry
            return
        entry.persisted = True
        rec = {
            "matches": ser,
            "n_eqns": len(entry.closed_jaxpr.jaxpr.eqns),
            "detect_digest": P.detect_digest(ser),
            "pins": {str(i): [n, s, f]
                     for i, (n, s, f) in entry.pins.items()},
        }
        if entry.joint is not None:
            rec["joint"] = entry.joint
        pc.put(entry.cache_key, rec)

    def _disable_bake(self, entry: CompiledEntry, reason: str):
        """Stop baking this entry AND drop any existing plan: a retired
        plan would otherwise keep its jitted executable, hoisted device
        buffers and strong operand references resident (a silent leak on
        exactly the churning workloads baking gets disabled for) while
        its guards are certain to keep failing."""
        entry.no_bake = True
        entry.bake_error = reason
        if entry.plan is not None:
            if self._last_plan is entry.plan:
                self._last_plan = None
            self._drop_hot(entry.plan)
            entry.plan = None

    def _maybe_bake(self, entry: CompiledEntry, matches,
                    recorder: P.PlanRecorder, raw_flat, flat, in_tree):
        if entry.no_bake or not self._resolved(entry, matches):
            return
        if any(m.variant == "scan_body" for m in matches):
            # the rebuilt lax.scan already compiles the body once per call
            # and reuses kernels across iterations; a baked plan on top
            # could not guard body-internal marshal sources (their binding
            # atoms live in the body jaxpr, not the outer one)
            self._disable_bake(
                entry, "scan-body rewrite: lax.scan reconstruction "
                       "compiles per call; plan guards cannot cover "
                       "body-internal marshal sources")
            return
        if not recorder.complete_for(matches):
            return
        traced = any(isinstance(x, jax.core.Tracer) for x in flat)
        if traced:
            if any(s.buffers for s in recorder.slots.values()):
                # marshal products recorded under a transform trace are
                # (or depend on) tracers — not hoistable.  Skip this call
                # without disabling: a later concrete call records real
                # buffers
                return
            gpos = P.marshal_guard_positions(
                entry.closed_jaxpr,
                [(m, recorder.slots[id(m.anchor_eqn)].harness)
                 for m in matches])
            if any(isinstance(flat[i], jax.core.Tracer) for i in gpos):
                return                  # can't guard a tracer's contents
        # marshal_policy='off' promises "every call repacks" (the A/B
        # always-fresh baseline): hoisting a recorded repack into a plan
        # would silently reinstate caching, so any marshal-bearing
        # selection blocks baking under it
        if self.cache is None and any(
                s.buffers for s in recorder.slots.values()):
            self._disable_bake(entry, "marshal_policy='off' forbids "
                               "hoisting repacks; interpreter repacks "
                               "every call")
            return
        # stateful / opted-out backends: a baked plan freezes per-call
        # host-side behavior at trace time, so only bake bodies whose
        # host part is entirely their declared marshal clauses
        for m in matches:
            h = recorder.slots[id(m.anchor_eqn)].harness
            if (not getattr(h, "bakeable", True) or h.setup is not None
                    or h.teardown is not None or h.persistent):
                self._disable_bake(
                    entry, f"harness {h.name!r} is stateful or opted out "
                           f"of baking (bakeable=False / lifecycle hooks "
                           f"/ persistent)")
                return
        plan = entry.plan
        if plan is not None:
            if (plan.enabled == self.enabled and plan.consts_ok()
                    and plan.registry_epoch == self.registry.epoch
                    and plan.same_hoisted(recorder)):
                # content-identical operands under new identities (e.g. an
                # equal re-upload): the data plane served the same buffers,
                # so only the guards move — no re-trace, no re-compile
                plan.refresh_guards(raw_flat)
                self._last_plan = plan
                self._note_hot(plan)
                return
            if entry.rebakes >= 4 and plan.hits == 0:
                # operands churn faster than the plan pays off: stop
                # recompiling and stay on the interpreter
                self._disable_bake(
                    entry, "rebake thrash (operands change per call)")
                return
        try:
            with spans.span("lilac.bake"):
                baked = P.bake_plan(
                    closed_jaxpr=entry.closed_jaxpr, matches=matches,
                    needed=entry.needed_for(matches), recorder=recorder,
                    raw_flat=raw_flat, flat=flat, in_tree=in_tree,
                    out_tree=entry.out_tree, report=entry.report,
                    mode=self.mode, platform=self.platform,
                    enabled=self.enabled, donate=self.donate_args,
                    registry_epoch=self.registry.epoch)
        except P.PlanDonationError:
            raise                       # user error: surface it
        except Exception as e:          # untraceable body etc: interpreter
            self._disable_bake(entry, repr(e))
            return
        if plan is not None:
            entry.rebakes += 1
            self._drop_hot(plan)
        entry.plan = baked
        self._last_plan = baked
        self._note_hot(baked)

    def invalidate_plans(self):
        """Drop every baked plan (not the persistent cache): the next call
        per signature re-records and re-bakes.  Use after mutating harness
        persistent state or releasing backends out-of-band."""
        for entry in self._compiled.values():
            entry.plan = None
            entry.no_bake = False
            entry.bake_error = None
            entry.rebakes = 0     # fresh thrash tolerance, as documented
        self._last_plan = None
        self._hot_plans.clear()

    def executable_plan(self, *args, **kwargs) -> Optional[P.ExecutablePlan]:
        """The baked plan serving this call signature, or None (not yet
        resolved / bake disabled / unbakeable).  For benchmarks and tests;
        does not execute anything."""
        entry, _, _, _ = self._prepare(args, kwargs)
        return entry.plan

    def prewarm(self, *signatures) -> Dict[str, Any]:
        """Bake a plan per call signature ahead of traffic.

        Each signature is a tuple of positional arguments;
        ``jax.ShapeDtypeStruct`` leaves are materialized as zeros, so
        callers can prewarm from shape specs without allocating inputs
        themselves.  Runs one concrete call per signature — the full
        detect -> tune -> bake lifecycle happens HERE (or is skipped via
        the persistent plan cache), never later on the request path.

        Returns a report: per-signature ``{baked, detect_calls,
        from_plan_cache}`` plus totals.  ``detect_calls`` is counted by
        instrumenting this function's detector for the duration of the
        call — on a plan-cache warm start it stays 0, which is exactly
        the "pay detection once per fleet, not once per replica" property
        the serving benchmark gates on.
        """
        import jax.numpy as jnp

        def materialize(leaf):
            if isinstance(leaf, jax.ShapeDtypeStruct):
                return jnp.zeros(leaf.shape, leaf.dtype)
            return leaf

        detector = self.detector
        orig_detect = detector.detect
        calls = {"n": 0}

        def spy(*a, **k):
            calls["n"] += 1
            return orig_detect(*a, **k)

        detector.detect = spy       # instance attribute shadows the method
        per_sig: List[Dict[str, Any]] = []
        try:
            for sig in signatures:
                args = tuple(jax.tree.map(materialize, a) for a in sig)
                before = calls["n"]
                self(*args)
                entry, _, _, _ = self._prepare(args, {})
                rehydrated = bool(entry and any(
                    "rehydrated from plan cache" in line
                    for line in entry.report.log))
                per_sig.append({
                    "baked": bool(entry and entry.plan is not None),
                    "detect_calls": calls["n"] - before,
                    "from_plan_cache": rehydrated,
                })
        finally:
            detector.__dict__.pop("detect", None)
        return {
            "signatures": per_sig,
            "n_signatures": len(per_sig),
            "baked": sum(1 for s in per_sig if s["baked"]),
            "detect_calls": sum(s["detect_calls"] for s in per_sig),
            "plan_cache_hits": sum(1 for s in per_sig
                                   if s["from_plan_cache"]),
        }

    def plan_info(self) -> Dict[str, Any]:
        """Introspection for benchmarks/tests: bake status per function."""
        entries = list(self._compiled.values())
        plans = [e.plan for e in entries if e.plan is not None]
        return {
            "entries": len(entries),
            "baked": len(plans),
            "plan_hits": sum(p.hits for p in plans),
            "rebakes": sum(e.rebakes for e in entries),
            "no_bake": sum(1 for e in entries if e.no_bake),
            "bake_errors": [e.bake_error for e in entries if e.bake_error],
            "joint_searched": sum(1 for e in entries
                                  if e.joint is not None),
            "joint": [e.joint for e in entries if e.joint is not None],
            "plan_cache": (str(self._plan_cache.path)
                           if self._plan_cache is not None else None),
            "plan_cache_stats": (self._plan_cache.stats.as_dict()
                                 if self._plan_cache is not None else None),
        }


class LilacDeprecationWarning(DeprecationWarning):
    """Emitted by the pre-``lilac.compile`` entry-point shims."""


@dataclasses.dataclass
class CompileOptions:
    """Configuration for :func:`compile` (the paper's Fig. 1 pass).

    ``mode``      'trace' (jit-compatible rewrite) or 'host' (eager with
                  marshaling cache — the paper's runtime model).
    ``policy``    'default' | 'autotune' | an explicit harness name.
    ``platform``  target platform; None = ``jax.default_backend()``.
    ``enabled``   False runs the original computation (A/B baseline).
    ``marshal_policy``  data-plane configuration: a
                  :class:`~repro.core.marshal.MarshalPolicy`, or one of
                  'shared' (default: plan-level DataPlane with the
                  conversion graph), 'exact' (exact fingerprints), 'off'
                  (no caching — every call repacks).  The policy's
                  ``reuse`` is the declared call frequency the autotuner
                  amortizes repack cost at.
    ``bake``      True (default) bakes resolved rewrites into jitted
                  :class:`~repro.core.plan.ExecutablePlan`s; False keeps
                  the eqn-interpreter on every call (the A/B baseline for
                  dispatch-overhead benchmarks).
    ``plan_cache``  persistent plan cache: None/'default' resolves
                  ``LILAC_PLAN_CACHE`` (default ~/.cache/lilac/plans.json),
                  'off'/False disables persistence, a path or
                  :class:`~repro.core.plan.PlanCache` injects one.
    ``donate_args``  flat argument positions donated to the baked plan's
                  XLA executable (output may alias their buffers).  Only
                  donate operands you never reuse after the call; positions
                  feeding marshaled operands are rejected.  Outside the
                  ``autotune`` policy a signature's first call is recorded
                  with them abstract and served by the baked plan, so it
                  consumes them too and never holds them twice.
    ``registry``/``detector``/``cache``  dependency injection for tests
                  and benchmarks; None picks the global instances.  Pass
                  the same DataPlane as ``cache`` to several compiled
                  functions to share marshaled buffers across them.
    """
    mode: str = "trace"
    policy: str = "default"
    platform: Optional[str] = None
    enabled: bool = True
    marshal_policy: Optional[Any] = None
    bake: bool = True
    plan_cache: Any = None
    donate_args: Tuple[int, ...] = ()
    registry: Optional[H.HarnessRegistry] = None
    detector: Optional[D.Detector] = None
    cache: Optional[MarshalingCache] = None


_OPTION_FIELDS = {f.name for f in dataclasses.fields(CompileOptions)}


def compile(fn: Optional[Callable] = None, *,
            options: Optional[CompileOptions] = None,
            **overrides) -> LilacFunction:
    """The single LiLAC entry point: pass a function through the pass.

    Usable directly (``lilac.compile(fn, mode="host")``), with an options
    dataclass (``lilac.compile(fn, options=CompileOptions(...))``; explicit
    keyword arguments override option fields), or as a decorator
    (``@lilac.compile(policy="autotune")``).
    """
    bad = set(overrides) - _OPTION_FIELDS
    if bad:
        raise TypeError(f"unknown compile option(s): {sorted(bad)}")
    opts = options if options is not None else CompileOptions()
    if overrides:
        opts = dataclasses.replace(opts, **overrides)
    if fn is None:
        return lambda f: compile(f, options=opts)
    if opts.mode not in ("trace", "host"):
        raise ValueError(f"mode must be 'trace' or 'host', got {opts.mode!r}")
    return LilacFunction(fn, mode=opts.mode, policy=opts.policy,
                         registry=opts.registry, detector=opts.detector,
                         platform=opts.platform, cache=opts.cache,
                         marshal_policy=opts.marshal_policy,
                         enabled=opts.enabled, bake=opts.bake,
                         plan_cache=opts.plan_cache,
                         donate_args=opts.donate_args)


def lilac_optimize(fn: Callable, **kw) -> LilacFunction:
    """Deprecated: use ``repro.lilac.compile(fn, mode='trace', ...)``."""
    warnings.warn(
        "lilac_optimize() is deprecated; use "
        "repro.lilac.compile(fn, mode='trace', ...)",
        LilacDeprecationWarning, stacklevel=2)
    return compile(fn, mode="trace", **kw)


def lilac_accelerate(fn: Callable, **kw) -> LilacFunction:
    """Deprecated: use ``repro.lilac.compile(fn, mode='host', ...)``."""
    warnings.warn(
        "lilac_accelerate() is deprecated; use "
        "repro.lilac.compile(fn, mode='host', ...)",
        LilacDeprecationWarning, stacklevel=2)
    return compile(fn, mode="host", **kw)
