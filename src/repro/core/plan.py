"""Executable plans: zero-overhead steady-state dispatch (paper §4.2/§5).

The paper's runtime claim is that harness insertion is *free at run time*:
LiLAC "maintains state between calls and minimizes data transfers", so the
accelerated library call costs no more than a hand-written integration.
Our reproduction picks the right (harness, schedule) winners (autotune)
and amortizes repacks (the data plane), but the rewritten program itself
was still *interpreted* — every call re-walked the jaxpr equation by
equation in Python.  This module turns a fully resolved rewrite into a
compile-once artifact, in two layers:

* :class:`ExecutablePlan` — once every match in a ``CompiledEntry`` has a
  definitive ``(harness, schedule)`` selection, the rewritten program is
  baked into ONE ``jax.jit``-compiled callable.  Marshaled operands (the
  ELL/BCSR buffers the data plane built) are hoisted out of the traced
  body and passed to the jitted plan as device-array arguments; fused
  epilogues trace in-line.  Steady-state dispatch is then: cheap guard check → one jitted
  call.  Guards are O(arity): aval (shape/dtype) checks on every leaf,
  plus *identity* checks on the leaves that feed marshal clauses — JAX
  arrays are immutable, so object identity proves the hoisted buffers are
  still valid; :class:`~repro.core.marshal.TrackedArray` operands are
  guarded by their O(1) version instead, so a functional matrix update
  busts the baked plan exactly like an mprotect fault would.
* :class:`PlanCache` — a schema-versioned JSON store
  (``~/.cache/lilac/plans.json``, overridable via ``LILAC_PLAN_CACHE``)
  mapping ``(jaxpr fingerprint, platform, mode, policy, declared marshal
  reuse)`` — under a registry-fingerprint header — to the serialized
  detection report and the
  pinned ``(harness, schedule)`` decisions.  A warm process re-traces the
  user function (cheap), fingerprints the jaxpr, and rehydrates matches +
  pins from disk: detection and tuning are skipped entirely and the first
  call goes straight to plan baking.

Environment knobs:

  LILAC_PLAN_CACHE          plan-cache file path
                            (default ~/.cache/lilac/plans.json)
  LILAC_PLAN_CACHE_DISABLE  "1" -> never read or persist plans
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.jsonstore import JsonStore
from repro.core.marshal import TrackedArray, fingerprint, version_token

SCHEMA_VERSION = 1
_ENV_PATH = "LILAC_PLAN_CACHE"
_ENV_DISABLE = "LILAC_PLAN_CACHE_DISABLE"

#: writable numpy closure captures above this size refuse to bake: their
#: const guard must hash exactly (the interpreter re-reads captures
#: exactly), and exact hashing per dispatch would defeat the plan's
#: purpose.  Arguments and TrackedArray captures have no such bound.
CONST_GUARD_MAX_BYTES = 1 << 20

class PlanBakeError(RuntimeError):
    """Baking failed (untraceable harness body, drifted marshal clauses).
    The pass manager catches it and stays on the interpreter path."""


class PlanDonationError(ValueError):
    """``donate_args`` misuse (out-of-range position, or donating a leaf
    that feeds a marshaled operand).  Unlike other bake failures this is a
    user error, so the pass manager re-raises it."""


def default_plan_cache_path() -> Path:
    env = os.environ.get(_ENV_PATH)
    if env:
        return Path(env)
    xdg = os.environ.get("XDG_CACHE_HOME")
    base = Path(xdg) if xdg else Path.home() / ".cache"
    return base / "lilac" / "plans.json"


def plan_cache_disabled() -> bool:
    return os.environ.get(_ENV_DISABLE, "") == "1"


_SHARED_CACHES: Dict[Tuple[str, str], "PlanCache"] = {}


def shared_plan_cache(path, registry_fingerprint: str) -> "PlanCache":
    """Process-wide PlanCache per (file, registry-fingerprint): N compiled
    functions share one in-memory view instead of each re-reading and
    re-parsing the JSON file on their first call.  ``path=None`` resolves
    the env/default location.  Injected instances (tests) bypass this."""
    key = (str(Path(path) if path is not None else default_plan_cache_path()),
           registry_fingerprint)
    pc = _SHARED_CACHES.get(key)
    if pc is None:
        pc = _SHARED_CACHES[key] = PlanCache(
            key[0], registry_fingerprint=registry_fingerprint)
    return pc


def reset_shared_plan_caches():
    """Drop the process-wide PlanCache views (tests; a deleted or
    externally rewritten cache file is otherwise invisible to functions
    compiled afterwards in the same process)."""
    _SHARED_CACHES.clear()


# ---------------------------------------------------------------------------
# Match serialization: jaxpr atoms <-> stable positional references
# ---------------------------------------------------------------------------
#
# A detected Match points into a specific ClosedJaxpr: its anchor equation,
# claimed equations and binding atoms are *objects* of that jaxpr.  Both the
# normalized jaxpr and its pretty-printed form are deterministic for a given
# program, so every atom has a stable positional address:
#
#   ["cv", i]          i-th constvar
#   ["iv", i]          i-th invar
#   ["ev", ei, oi]     oi-th outvar of the ei-th equation
#   ["lit", v, dt, shape, weak]   a Literal (value + aval)
#   ["pyint"/"pybool"/"pyfloat", v]  a python scalar in the binding
#
# Rehydration resolves the addresses against a freshly traced jaxpr and
# validates the anchor primitive names, so a stale or colliding record
# degrades to a cache miss (full detection), never to a wrong rewrite.

def _atom_refs(jaxpr) -> Dict[Any, Tuple]:
    ref: Dict[Any, Tuple] = {}
    for i, v in enumerate(jaxpr.constvars):
        ref[v] = ("cv", i)
    for i, v in enumerate(jaxpr.invars):
        ref[v] = ("iv", i)
    for ei, eqn in enumerate(jaxpr.eqns):
        for oi, ov in enumerate(eqn.outvars):
            ref[ov] = ("ev", ei, oi)
    return ref


def _ser_atom(v, ref: Dict[Any, Tuple]) -> List:
    from jax.extend import core as jex_core

    if isinstance(v, bool):
        return ["pybool", v]
    if isinstance(v, (int, np.integer)):
        return ["pyint", int(v)]
    if isinstance(v, (float, np.floating)):
        return ["pyfloat", float(v)]
    if isinstance(v, jex_core.Literal):
        arr = np.asarray(v.val)
        return ["lit", arr.tolist(), str(arr.dtype), list(arr.shape),
                bool(getattr(v.aval, "weak_type", False))]
    r = ref.get(v)
    if r is None:
        raise PlanBakeError(f"binding atom {v!r} has no stable address")
    return list(r)


def _de_atom(spec: Sequence, jaxpr):
    from jax.extend import core as jex_core

    tag = spec[0]
    if tag == "pybool":
        return bool(spec[1])
    if tag == "pyint":
        return int(spec[1])
    if tag == "pyfloat":
        return float(spec[1])
    if tag == "lit":
        dt = np.dtype(spec[2])
        arr = np.asarray(spec[1], dtype=dt).reshape(spec[3])
        aval = jax.core.ShapedArray(tuple(spec[3]), dt, weak_type=spec[4])
        return jex_core.Literal(arr if arr.ndim else arr[()], aval)
    if tag == "cv":
        return jaxpr.constvars[spec[1]]
    if tag == "iv":
        return jaxpr.invars[spec[1]]
    if tag == "ev":
        return jaxpr.eqns[spec[1]].outvars[spec[2]]
    raise KeyError(f"unknown atom tag {tag!r}")


def serialize_matches(closed_jaxpr, matches) -> List[Dict[str, Any]]:
    """JSON-able form of a detection report against ``closed_jaxpr``.
    Raises :class:`PlanBakeError` when a match cannot be addressed."""
    jaxpr = closed_jaxpr.jaxpr
    ref = _atom_refs(jaxpr)
    eqn_idx = {id(e): i for i, e in enumerate(jaxpr.eqns)}
    out = []
    for m in matches:
        ei = eqn_idx.get(id(m.anchor_eqn))
        if ei is None:
            raise PlanBakeError("anchor equation not in jaxpr")
        try:
            anchor = _ser_atom(m.anchor, ref)
        except (PlanBakeError, TypeError):
            anchor = None
        out.append({
            "computation": m.computation,
            "variant": m.variant,
            "format": m.format,
            "epilogue": m.epilogue,
            "notes": m.notes,
            "anchor_eqn": ei,
            "anchor_prim": m.anchor_eqn.primitive.name,
            "anchor": anchor,
            "claimed_eqns": [eqn_idx[id(e)] for e in m.claimed_eqns
                             if id(e) in eqn_idx],
            "binding": {k: _ser_atom(v, ref) for k, v in m.binding.items()},
        })
    return out


def detect_digest(serialized: List[Dict[str, Any]]) -> str:
    """Content digest of a serialized detection report (integrity field of
    plan-cache records; also a cheap cross-process equality check)."""
    blob = json.dumps(serialized, sort_keys=True).encode()
    return hashlib.blake2b(blob, digest_size=8).hexdigest()


def rehydrate_matches(closed_jaxpr, serialized) -> Optional[List[Any]]:
    """Resolve serialized matches against a freshly traced ``closed_jaxpr``.
    Returns None (-> treat as a cache miss) when anything fails to line up
    with the live jaxpr."""
    from repro.core.detect import Match

    jaxpr = closed_jaxpr.jaxpr
    try:
        out = []
        for rec in serialized:
            ei = rec["anchor_eqn"]
            if not (0 <= ei < len(jaxpr.eqns)):
                return None
            eqn = jaxpr.eqns[ei]
            if eqn.primitive.name != rec["anchor_prim"]:
                return None
            binding = {k: _de_atom(v, jaxpr)
                       for k, v in rec["binding"].items()}
            anchor = (_de_atom(rec["anchor"], jaxpr)
                      if rec.get("anchor") else eqn.outvars[0])
            claimed = tuple(jaxpr.eqns[i] for i in rec.get("claimed_eqns", ())
                            if 0 <= i < len(jaxpr.eqns))
            out.append(Match(
                computation=rec["computation"], variant=rec["variant"],
                format=rec["format"], anchor=anchor, anchor_eqn=eqn,
                binding=binding, notes=rec.get("notes", ""),
                claimed_eqns=claimed, epilogue=rec.get("epilogue")))
        return out
    except (KeyError, IndexError, TypeError, ValueError):
        return None


def plan_key(closed_jaxpr, platform: str, mode: str, policy: str,
             reuse: float = 100.0) -> str:
    """Cache key for one compiled signature: a fingerprint of the
    normalized jaxpr (pretty-printed form + sampled const fingerprints)
    qualified by platform/mode/policy and the marshal policy's declared
    ``reuse`` frequency — the autotuner's repack-amortized argmin depends
    on reuse, so pins measured at one call frequency must never be served
    verbatim to a compile declaring another.  The registry fingerprint
    lives in the cache-file header, so a harness-set change drops every
    plan."""
    h = hashlib.blake2b(digest_size=16)
    h.update(str(closed_jaxpr.jaxpr).encode())
    for c in closed_jaxpr.consts:
        h.update(repr(fingerprint(c)).encode())
    return f"{h.hexdigest()}|{platform}|{mode}|{policy}|r{reuse:g}"


# ---------------------------------------------------------------------------
# Persistent plan cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PlanCacheStats:
    memory_hits: int = 0
    disk_hits: int = 0
    misses: int = 0
    stores: int = 0
    rejected: int = 0        # on-disk record failed rehydration
    invalidations: int = 0   # schema/registry-fingerprint drop
    save_errors: int = 0
    corrupt_recoveries: int = 0  # torn cache file quarantined, fresh start

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


class PlanCache(JsonStore):
    """Versioned JSON store of resolved plans — the flat-keyed
    :class:`repro.core.jsonstore.JsonStore` disk protocol.

    Layout::

        {"schema": 1, "registry": "<fingerprint>",
         "entries": {"<jaxpr-fp>|<platform>|<mode>|<policy>|r<reuse>": {
             "matches": [...], "pins": {"0": ["pallas.ell", {...}]},
             "n_eqns": 12, "detect_digest": "..."}}}

    Writes are atomic (tempfile + ``os.replace``) and merge-on-save under
    an advisory lock; a registry-fingerprint or schema mismatch drops the
    whole file (detection reports are only as durable as the harness set
    that produced their pins).
    """

    schema_version = SCHEMA_VERSION

    def __init__(self, path: Optional[os.PathLike] = None,
                 registry_fingerprint: str = ""):
        self.stats = PlanCacheStats()   # before super(): _note_* hooks
        super().__init__(path, registry_fingerprint)

    def default_path(self) -> Path:
        return default_plan_cache_path()

    def _note_invalidation(self):
        self.stats.invalidations += 1

    def _note_corrupt_recovery(self):
        self.stats.corrupt_recoveries += 1

    def _note_save_error(self):
        self.stats.save_errors += 1

    def get(self, key: str) -> Optional[Dict[str, Any]]:
        rec = self.entries.get(key)
        if rec is not None:
            self.stats.memory_hits += 1
            return rec
        if not self.loaded:
            self.load()
            rec = self.entries.get(key)
            if rec is not None:
                self.stats.disk_hits += 1
                return rec
        self.stats.misses += 1
        return None

    def put(self, key: str, record: Dict[str, Any], persist: bool = True):
        self.entries[key] = record
        self.stats.stores += 1
        if persist:
            self.save()


# ---------------------------------------------------------------------------
# Recording: capture one interpreted call's selections + marshaled buffers
# ---------------------------------------------------------------------------

class _Slot:
    """What one match contributed during the recorded call."""
    __slots__ = ("harness", "schedule", "fuse", "buffers")

    def __init__(self):
        self.harness = None
        self.schedule = None
        self.fuse = None
        self.buffers: List[Any] = []


class PlanRecorder:
    """Observes one interpreted call: per match, the finally selected
    harness, its schedule variant (schedule + epilogue-fusion decision),
    and the marshaled values its clauses produced (in clause order) —
    everything baking needs."""

    def __init__(self):
        self.slots: Dict[int, _Slot] = {}

    def slot(self, m) -> _Slot:
        return self.slots.setdefault(id(m.anchor_eqn), _Slot())

    def begin(self, m, harness, schedule, fuse=None):
        """Called by ``on_select`` AFTER selection: autotune measurement
        may have routed candidate repacks through the recording cache, so
        the buffer list restarts here — only the winner's final execution
        is recorded."""
        s = self.slot(m)
        s.harness = harness
        s.schedule = schedule
        s.fuse = fuse
        s.buffers.clear()

    def complete_for(self, matches) -> bool:
        return all(
            (s := self.slots.get(id(m.anchor_eqn))) is not None
            and s.harness is not None
            for m in matches)


class _RecordingNone:
    """Recording stand-in for ``cache=None`` (marshaling disabled): every
    repack recomputes, and the produced value is recorded."""
    __slots__ = ("_sink",)

    def __init__(self, sink: List[Any]):
        self._sink = sink

    def get(self, name, keys, compute):
        val = compute()
        self._sink.append(val)
        return val


class _RecordingCache:
    """Transparent recorder around a MarshalingCache (no ``ensure``)."""
    __slots__ = ("_inner", "_sink")

    def __init__(self, inner, sink: List[Any]):
        self._inner = inner
        self._sink = sink

    def get(self, name, keys, compute):
        val = self._inner.get(name, keys, compute)
        self._sink.append(val)
        return val

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _RecordingPlane(_RecordingCache):
    """Transparent recorder around a DataPlane (has ``ensure``)."""
    __slots__ = ()

    def ensure(self, src, dst, keys, binding, fallback=None):
        val = self._inner.ensure(src, dst, keys, binding, fallback=fallback)
        self._sink.append(val)
        return val


def recording_cache(inner, sink: List[Any]):
    """Wrap a call's marshaling cache so produced values are recorded.
    Mirrors the generated wrapper's dispatch exactly: the proxy exposes
    ``ensure`` only when the wrapped cache does."""
    if inner is None:
        return _RecordingNone(sink)
    if hasattr(inner, "ensure"):
        return _RecordingPlane(inner, sink)
    return _RecordingCache(inner, sink)


class _PlanBuffers:
    """The bake-time stand-in for the data plane: marshal clauses replay
    the recorded buffers (in clause order) as captured constants instead
    of fingerprinting traced operands."""
    __slots__ = ("_vals", "_i")

    def __init__(self, values: Sequence[Any]):
        self._vals = tuple(values)
        self._i = 0

    def _next(self):
        if self._i >= len(self._vals):
            raise PlanBakeError(
                "marshal clause count drifted between record and bake")
        v = self._vals[self._i]
        self._i += 1
        return v

    def get(self, name, keys, compute):
        return self._next()

    def ensure(self, src, dst, keys, binding, fallback=None):
        return self._next()


# ---------------------------------------------------------------------------
# Guards
# ---------------------------------------------------------------------------

class _Guard:
    """One marshal-source leaf, guarded by :func:`~repro.core.marshal.
    version_token`: object identity for immutable (jax) arrays, the O(1)
    (base-token, version) pair for TrackedArray operands.  A strong
    reference keeps the token's ``id`` unambiguous.

    Writable ``np.ndarray`` operands are the one case identity cannot
    cover — the same object can be mutated in place — so they carry a
    content fingerprint checked on every dispatch.  For marshal-source
    *leaves* the default (sampled-above-64KB) fingerprint keeps parity
    with the interpreter's marshaling-cache keying; const guards pass
    ``exact=True`` because the interpreter re-reads closure captures
    exactly on every call — a sampled hash would miss a single-element
    edit of a large capture that ``bake=False`` would honor."""
    __slots__ = ("pos", "exact", "ref", "token", "content_fp")

    def __init__(self, pos: int, leaf, exact: bool = False):
        self.pos = pos
        self.exact = exact
        self.rebind(leaf)

    def rebind(self, leaf):
        self.ref = leaf
        self.token = version_token(leaf)
        self.content_fp = (fingerprint(leaf, self.exact)
                           if isinstance(leaf, np.ndarray)
                           and leaf.flags.writeable else None)

    def ok(self, leaf) -> bool:
        if version_token(leaf) != self.token:
            return False
        if self.content_fp is not None and \
                fingerprint(leaf, self.exact) != self.content_fp:
            return False
        return True


def leaf_templates(flat) -> Tuple:
    """THE per-leaf keying semantics, shared by every dispatch layer:
    anything with shape+dtype — including numpy scalars like
    ``np.float64``, which ARE ``float`` instances but carry avals — keys
    as ``("a", shape, dtype)``; python ints/bools key on their value
    (they may steer control flow); any other python leaf keys on its
    type only (``("p", type, None)``).  ``pass_manager._signature`` (the
    compile-dict key), the last-entry fast path (:func:`leaves_match`)
    and the baked-plan guard specs are all derived from this one
    function, so they cannot drift."""
    out = []
    for a in flat:
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            out.append(("a", a.shape if type(a.shape) is tuple
                        else tuple(a.shape), a.dtype))
        else:
            out.append(("p", type(a),
                        a if isinstance(a, (int, bool)) else None))
    return tuple(out)


def leaves_match(templates: Tuple, flat) -> bool:
    """Loop-compare live leaves against stored templates (no tuple
    rebuild, no dict hash) — the last-entry fast path."""
    if len(templates) != len(flat):
        return False
    for t, a in zip(templates, flat):
        if t[0] == "a":
            if (not hasattr(a, "shape") or not hasattr(a, "dtype")
                    or a.shape != t[1] or a.dtype != t[2]):
                return False
        else:
            if type(a) is not t[1]:
                return False
            if t[2] is not None and a != t[2]:
                return False
    return True


def _aval_specs(raw_flat) -> Tuple:
    """Baked-plan guard templates: :func:`leaf_templates` over the
    TrackedArray-unwrapped leaves (plans guard the wrapped operand but
    dispatch the unwrapped array)."""
    return leaf_templates([x.arr if isinstance(x, TrackedArray) else x
                           for x in raw_flat])


def marshal_guard_positions(closed_jaxpr, match_harness_pairs) -> frozenset:
    """Flat-leaf positions whose content the hoisted marshal buffers were
    derived from: the binding atoms named by each selected harness's
    marshal-clause keys, closed transitively back to the jaxpr invars.
    (Closure-captured operands need no position: EVERY writable numpy
    const is fingerprint-guarded by ``bake_plan``, marshal source or
    not.)"""
    from jax.extend import core as jex_core

    jaxpr = closed_jaxpr.jaxpr
    targets = set()
    for m, h in match_harness_pairs:
        for cl in getattr(h, "marshal", ()) or ():
            for alts in cl.keys:
                for k in alts:
                    v = m.binding.get(k)
                    if v is not None and not isinstance(
                            v, (int, float, bool, jex_core.Literal)):
                        targets.add(v)
                        break
    if not targets:
        return frozenset()
    need = set(targets)
    for eqn in reversed(jaxpr.eqns):
        if any(ov in need for ov in eqn.outvars):
            for iv in eqn.invars:
                if not isinstance(iv, jex_core.Literal):
                    need.add(iv)
    invar_pos = {v: i for i, v in enumerate(jaxpr.invars)}
    return frozenset(invar_pos[v] for v in need if v in invar_pos)


# ---------------------------------------------------------------------------
# The plan itself
# ---------------------------------------------------------------------------

class ExecutablePlan:
    """A baked realization of one ``CompiledEntry``: the rewritten program
    as a single jitted callable plus the guards that keep it honest."""

    def __init__(self, jitted, in_tree, out_tree, avals, guards,
                 report, selections, schedules, hoisted, enabled: bool,
                 const_guards=(), registry_epoch: int = 0,
                 trace_servable: bool = False, fuses=None):
        # registry epoch at bake time: the pass manager refuses to serve
        # (or guard-refresh) this plan once any harness (re-)registration
        # has moved the registry on — a replaced kernel body must never
        # keep running from a stale jitted executable
        self.registry_epoch = registry_epoch
        self.jitted = jitted
        self.in_tree = in_tree
        self.out_tree = out_tree
        self.avals = avals                   # per-leaf templates
        self.guards = guards                 # marshal-source leaf guards
        # closure-captured writable-numpy marshal sources: re-checked by
        # content fingerprint each dispatch (no leaf carries them)
        self.const_guards = tuple(const_guards)
        self.report = report                 # the entry's DetectionReport
        self.selections = selections         # [(Match, harness name)]
        self.schedules = schedules           # aligned schedule variants
        # aligned epilogue-fusion decisions (None = declared default)
        self.fuses = list(fuses) if fuses is not None \
            else [None] * len(selections)
        self.hoisted = hoisted               # {anchor id: (buffers...)}
        self.enabled = enabled
        # True when every selected harness composes with transform traces
        # (jit_safe, or wrapped in a declared custom_vjp): the plan may
        # then serve abstract (tracer) leaves, EXCEPT at marshal-guarded
        # positions — hoisted buffers were derived from those leaves'
        # *contents*, which a tracer cannot attest to
        self.trace_servable = bool(trace_servable)
        self._guarded_pos = frozenset(g.pos for g in guards)
        self.hits = 0

    def match_and_unwrap(self, in_tree, leaves, enabled: bool):
        """The per-call guard: returns the (TrackedArray-unwrapped) leaf
        list when this plan can serve the call, else None.  One python
        loop over the arity — the whole point of baking."""
        if enabled is not self.enabled or in_tree != self.in_tree:
            return None
        specs = self.avals
        if len(leaves) != len(specs):
            return None
        out = list(leaves)
        for i, spec in enumerate(specs):
            x = out[i]
            if isinstance(x, TrackedArray):
                x = x.arr
                out[i] = x
            if spec[0] == "a":
                if isinstance(x, jax.core.Tracer) and (
                        not self.trace_servable or i in self._guarded_pos):
                    return None
                if (getattr(x, "shape", None) != spec[1]
                        or getattr(x, "dtype", None) != spec[2]):
                    return None
            else:
                if type(x) is not spec[1]:
                    return None
                if spec[2] is not None and x != spec[2]:
                    return None
        for g in self.guards:
            if not g.ok(leaves[g.pos]):
                return None
        for g in self.const_guards:
            if not g.ok(g.ref):
                return None
        return out

    def refresh_guards(self, raw_leaves):
        """Re-anchor the identity guards on new (content-identical) leaf
        objects: the data plane proved the hoisted buffers still apply, so
        only the expected identities move."""
        for g in self.guards:
            g.rebind(raw_leaves[g.pos])

    def consts_ok(self) -> bool:
        """True while no guarded closure capture has mutated.  Checked
        before the guard-refresh shortcut: a stale const means the jitted
        executable itself is stale, so the plan must re-bake rather than
        merely re-anchor its leaf guards."""
        return all(g.ok(g.ref) for g in self.const_guards)

    def same_hoisted(self, recorder: PlanRecorder) -> bool:
        """True when a recorded call produced exactly the buffers this
        plan captured (object identity: data-plane hits return the cached
        objects) — the plan survives, only its guards need re-anchoring."""
        for aid, bufs in self.hoisted.items():
            s = recorder.slots.get(aid)
            if s is None or len(s.buffers) != len(bufs):
                return False
            if any(a is not b for a, b in zip(s.buffers, bufs)):
                return False
        return True

    def hoisted_nbytes(self) -> int:
        """Total bytes of marshal products pinned by this plan (what the
        serving tier reports as per-plan resident overhead)."""
        total = 0
        for bufs in self.hoisted.values():
            for b in bufs:
                total += int(getattr(b, "nbytes", 0) or 0)
        return total

    def describe(self) -> Dict[str, Any]:
        """JSON-able summary for observability (serve/metrics, plan_info)."""
        arr = [s for s in self.avals if s[0] == "a"]
        return {
            "arity": len(self.avals),
            "array_leaves": [[list(s[1]), str(s[2])] for s in arr],
            "selections": [name for _, name in self.selections],
            "schedules": [s for s in self.schedules],
            "fuses": [f for f in self.fuses],
            "guards": len(self.guards),
            "const_guards": len(self.const_guards),
            "hoisted_nbytes": self.hoisted_nbytes(),
            "enabled": self.enabled,
            "hits": self.hits,
        }


def _host_array(const) -> Optional[np.ndarray]:
    """The numpy array behind a jaxpr const: the const itself, or the
    caller's array that a tracing-time ``TypedNdArray`` holds in ``val``.
    None for device (jax) arrays."""
    if isinstance(const, np.ndarray):
        return const
    val = getattr(const, "val", None)
    return val if isinstance(val, np.ndarray) else None


def _with_cached_facts(recorded, rebuilt):
    """``rebuilt`` (the traced stand-in for a recorded buffer) with the
    host-side facts the recorded object had cached
    (``functools.cached_property`` values such as
    ``BCSR.all_block_rows_nonempty``), which a tracer cannot recompute."""
    cached = getattr(recorded, "__dict__", None)
    if cached and rebuilt is not recorded and type(rebuilt) is type(recorded):
        for k, v in cached.items():
            rebuilt.__dict__.setdefault(k, v)
    return rebuilt


def bake_plan(*, closed_jaxpr, matches, needed, recorder: PlanRecorder,
              raw_flat, flat, in_tree, out_tree, report,
              mode: str, platform: str, enabled: bool,
              donate: Tuple[int, ...] = (),
              registry_epoch: int = 0) -> ExecutablePlan:
    """Bake one resolved rewrite into an :class:`ExecutablePlan`.

    ``raw_flat`` are the call's leaves as passed (possibly TrackedArray),
    ``flat`` the unwrapped ones the trace runs on.  Raises
    :class:`PlanBakeError` (or whatever the trace raises) on failure; the
    caller decides whether to disable baking for the entry."""
    import jax.numpy as jnp

    from repro.core import faults
    from repro.core.harness import CallCtx
    from repro.core.rewrite import run_rewritten

    if faults.ACTIVE is not None:
        faults.fail("bake_raise", "bake")
    if not recorder.complete_for(matches):
        raise PlanBakeError("recorded call is missing selections")
    slots = {id(m.anchor_eqn): recorder.slots[id(m.anchor_eqn)]
             for m in matches}

    donate = tuple(sorted(set(int(i) for i in donate)))
    for i in donate:
        if not (0 <= i < len(flat)):
            raise PlanDonationError(f"donate_args position {i} out of range "
                                    f"(call has {len(flat)} leaves)")

    guard_positions = marshal_guard_positions(
        closed_jaxpr, [(m, slots[id(m.anchor_eqn)].harness)
                       for m in matches])
    bad = set(donate) & guard_positions
    if bad:
        raise PlanDonationError(
            f"donate_args positions {sorted(bad)} feed marshaled operands; "
            f"donating them would invalidate the hoisted buffers")

    def select(m, binding=None, ctx=None):
        s = slots[id(m.anchor_eqn)]
        if ctx is not None:
            ctx.schedule = s.schedule
            ctx.fuse = s.fuse
        return s.harness

    # The marshaled device buffers enter the plan as jit ARGUMENTS: a
    # closed-over device array is embedded in the compiled program as a
    # literal, which for an HBM-scale repack means serializing it into the
    # HLO and holding a second copy on the device.
    hoisted = {aid: tuple(s.buffers) for aid, s in slots.items()}
    aids = list(hoisted)
    buf_flat, buf_tree = jax.tree_util.tree_flatten([hoisted[a] for a in aids])
    dyn = [i for i, x in enumerate(buf_flat) if isinstance(x, jax.Array)]
    live: Dict[int, Tuple] = {}

    def ctx_factory(m):
        s = slots[id(m.anchor_eqn)]
        return CallCtx(mode=mode, cache=_PlanBuffers(live[id(m.anchor_eqn)]),
                       format=m.format, platform=platform,
                       schedule=s.schedule, epilogue=m.epilogue,
                       fuse=s.fuse)

    def baked(buf_args, *leaves):
        vals = list(buf_flat)
        for i, x in zip(dyn, buf_args):
            vals[i] = x
        for aid, bufs in zip(aids, jax.tree_util.tree_unflatten(buf_tree,
                                                                vals)):
            live[aid] = tuple(_with_cached_facts(o, b)
                              for o, b in zip(hoisted[aid], bufs))
        return run_rewritten(closed_jaxpr, matches, select, list(leaves),
                             ctx_factory, needed=needed)

    jitted = functools.partial(
        jax.jit(baked, donate_argnums=tuple(i + 1 for i in donate)),
        tuple(buf_flat[i] for i in dyn))
    traced = any(isinstance(x, jax.core.Tracer) for x in flat)
    if traced:
        # Baking under a transform trace (the call that resolved the
        # rewrite ran inside jax.grad/vmap/jit): there are no concrete
        # leaves to warm up with, and a guard anchored on a tracer would
        # be meaningless.  The caller guaranteed no marshal-source
        # position holds a tracer, so guard construction below only ever
        # sees concrete leaves; warm-up is deferred to first dispatch.
        for pos in guard_positions:
            if isinstance(raw_flat[pos], jax.core.Tracer):
                raise PlanBakeError(
                    "marshal-source leaf is a tracer; cannot guard")
    else:
        # Warm-up compile now, so the first fast-path call is already
        # fast — and so an untraceable body fails HERE (the caller falls
        # back to the interpreter) rather than on a later dispatch.
        # Donated positions get copies: the caller's buffers must survive
        # the warm-up.
        warm = list(flat)
        for i in donate:
            warm[i] = jnp.array(warm[i])
        jax.block_until_ready(jitted(*warm))

    guards = [_Guard(pos, raw_flat[pos]) for pos in sorted(guard_positions)]
    # Closure captures: jax keeps a numpy capture in consts as a live
    # reference (wrapped in a TypedNdArray whose ``val`` is the caller's
    # array), so eager execution and the interpreter see an in-place
    # mutation while the jitted plan froze the values at trace time.
    # Immutable (jax) consts cannot diverge; EVERY writable numpy const —
    # marshal source or plain operand (e.g. a captured bias) — gets a
    # per-dispatch EXACT content fingerprint so in-place mutation busts
    # the plan and the call re-bakes on the new values, as eager would
    # compute.  Exact hashing is O(bytes) per dispatch, so captures past
    # the bound refuse to bake instead of silently making the
    # "zero-overhead" path slower than the interpreter — pass big
    # matrices as arguments (identity-guarded, free) or TrackedArray (O(1)
    # version) to get a plan.
    writable = [h for h in map(_host_array, closed_jaxpr.consts)
                if h is not None and h.flags.writeable]
    big = [c for c in writable if c.nbytes > CONST_GUARD_MAX_BYTES]
    if big:
        raise PlanBakeError(
            f"writable numpy closure capture of {big[0].nbytes} bytes "
            f"exceeds the exact-guard bound ({CONST_GUARD_MAX_BYTES}); "
            f"pass it as an argument or TrackedArray to enable baking")
    const_guards = [_Guard(-1, c, exact=True) for c in writable]
    selections = [(m, slots[id(m.anchor_eqn)].harness.name) for m in matches]
    schedules = [slots[id(m.anchor_eqn)].schedule for m in matches]
    fuses = [slots[id(m.anchor_eqn)].fuse for m in matches]
    trace_servable = all(
        s.harness.jit_safe or getattr(s.harness, "vjp", None) is not None
        for s in slots.values())
    return ExecutablePlan(jitted, in_tree, out_tree, _aval_specs(raw_flat),
                          guards, report, selections, schedules, hoisted,
                          enabled, const_guards=const_guards,
                          registry_epoch=registry_epoch,
                          trace_servable=trace_servable, fuses=fuses)
