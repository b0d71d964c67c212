"""Persistent, signature-keyed backend autotuning (paper §3.3 / Table 2).

The paper's central empirical claim is that no sparse backend wins
everywhere: the right harness depends on platform, format and input
structure.  SparseX answers this by tuning once per matrix and reusing the
decision; LiLAC inherits the idea at the harness-selection boundary.  This
module is the persistent half of that story:

* ``signature_of`` — buckets a harness-call binding into a stable key
  ``(computation, format, platform, shape-bucket, sparsity-bucket)``.
  Shapes are bucketed to powers of two and sparsity to decades so that
  "the same kind of problem" re-uses one tuning decision across runs,
  processes and slightly-different inputs.
* ``AutotuneCache`` — versioned on-disk JSON store
  (``~/.cache/lilac/autotune.json``, overridable via ``LILAC_AUTOTUNE_CACHE``)
  with warm-start load, atomic writes (tempfile + ``os.replace`` under an
  advisory ``flock``) and invalidation whenever the registered harness set
  or registry version changes.
* ``Autotuner`` — the selection policy.  On a cache miss it measures the
  top-``budget`` candidates (host mode: steady-state eager calls through
  the marshaling cache; trace mode: timed ``jax.jit`` compiles of each
  jit-safe candidate on operands synthesized from the traced avals), pins
  the winner, and persists it.  Under budget — or when measurement is
  impossible — it falls back to the per-platform default.

Winner selection is **repack-amortized** (since schema 2): for host-mode
candidates with declared marshal clauses, the measured steady-state kernel
time is combined with the data plane's measured conversion-path cost at
the declared call frequency (``MarshalPolicy.reuse`` — expected calls per
matrix change), so a backend with a blazing kernel but a ruinous repack
only wins when the repack actually amortizes.  Schema-1 cache files are
migrated on load: their kernel-only records stay valid for marshal-free
candidate sets and are re-measured (not silently trusted) whenever a
marshaling harness is in play — no stale winners.

Winner selection is also **schedule-swept** (schema 3): candidates whose
HARNESS blocks declare ``tune`` clauses contribute their whole
constraint-filtered variant family to the search, not just the default
schedule.  The cross-product is swept by *successive halving* — cheap
single-iteration elimination rounds shrink the pool until it fits the
existing exploration budget, and only the survivors get steady-state
timing — so a 40-variant space costs a handful of full measurements.  The
pinned decision is a ``(harness, schedule)`` pair; variants of one harness
share its marshaled format, so repack cost is measured once per harness.
Schema-2 records migrate as *priors*: their kernel-level winner ranks
first in the sweep, but the record is never served as-is when any live
candidate declares schedule variants — no stale winners, again.

Since schema 4 the sweep has a third dimension: at call sites with a
detected epilogue, every ``fuse epilogue`` candidate contributes BOTH its
fused (in-kernel) and unfused (``rewrite.apply_epilogue`` after the call)
realizations as variants, so fusion is pinned only where it measured
faster (``fused_epilogue_always_faster`` is false in practice).  Records
additionally expose per-candidate measured components (``variants``: every
surviving (schedule, fuse, seconds) triple per harness) — the inputs the
joint whole-program plan search (``repro.core.plan_search``) re-costs
without re-timing.  Schema-3 records migrate in place: served verbatim at
sites where the fuse dimension cannot change the answer (no epilogue, or
no fuse-capable candidate — cross-process zero re-timing preserved) and
demoted to sweep priors only where it can.

Environment knobs:

  LILAC_AUTOTUNE_CACHE         cache file path
                               (default ~/.cache/lilac/autotune.json)
  LILAC_AUTOTUNE_BUDGET        max candidates given steady-state timing
                               per signature (default 8)
  LILAC_AUTOTUNE_MAX_VARIANTS  cap on the swept variant pool per signature
                               (default 64; defaults survive the cap)
  LILAC_AUTOTUNE_DISABLE       "1" -> never measure or persist; defaults
                               only
"""
from __future__ import annotations

import dataclasses
import os
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import spans
from repro.core.jsonstore import JsonStore

SCHEMA_VERSION = 4
_ENV_PATH = "LILAC_AUTOTUNE_CACHE"
_ENV_BUDGET = "LILAC_AUTOTUNE_BUDGET"
_ENV_MAX_VARIANTS = "LILAC_AUTOTUNE_MAX_VARIANTS"
_ENV_DISABLE = "LILAC_AUTOTUNE_DISABLE"
_DEFAULT_BUDGET = 8
_DEFAULT_MAX_VARIANTS = 64


def default_cache_path() -> Path:
    env = os.environ.get(_ENV_PATH)
    if env:
        return Path(env)
    xdg = os.environ.get("XDG_CACHE_HOME")
    base = Path(xdg) if xdg else Path.home() / ".cache"
    return base / "lilac" / "autotune.json"


def autotune_disabled() -> bool:
    return os.environ.get(_ENV_DISABLE, "") == "1"


def exploration_budget() -> int:
    try:
        return int(os.environ.get(_ENV_BUDGET, _DEFAULT_BUDGET))
    except ValueError:
        return _DEFAULT_BUDGET


def variant_cap() -> int:
    """Cap on the swept (harness, schedule) pool per signature."""
    try:
        return int(os.environ.get(_ENV_MAX_VARIANTS, _DEFAULT_MAX_VARIANTS))
    except ValueError:
        return _DEFAULT_MAX_VARIANTS


def schedule_key(schedule: Optional[Dict[str, Any]]) -> str:
    """Canonical string form of a schedule variant ('default' for None/{})
    — JSON-record and report key for per-variant timings."""
    if not schedule:
        return "default"
    return ",".join(f"{k}={schedule[k]}" for k in sorted(schedule))


def variant_key(schedule: Optional[Dict[str, Any]],
                fuse: Optional[bool] = None) -> str:
    """Record key for a full (schedule, fuse) variant.  ``fuse=None``
    (no epilogue at the site, or a harness that can't fuse) keeps the
    historical ``schedule_key`` form, so schema-3 ``variant_s`` keys stay
    valid everywhere the fuse dimension doesn't exist."""
    k = schedule_key(schedule)
    if fuse is True:
        return k + "|fused"
    if fuse is False:
        return k + "|unfused"
    return k


# ---------------------------------------------------------------------------
# Signatures
# ---------------------------------------------------------------------------

def pow2_bucket(n: int) -> int:
    """Round a positive extent up to the next power of two (0 stays 0)."""
    n = int(n)
    if n <= 0:
        return 0
    return 1 << (n - 1).bit_length()


def sparsity_bucket(frac: float) -> str:
    """Decade bucket of a density fraction: 1e-4 -> 'd-4'; unknown -> 'd?'."""
    if not (frac > 0.0):
        return "d?"
    return f"d{int(np.floor(np.log10(min(frac, 1.0))))}"


def _shape_of(v: Any) -> Optional[Tuple[int, ...]]:
    shape = getattr(v, "shape", None)
    if shape is None:
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", None)
    if shape is None:
        return None
    return tuple(int(s) for s in shape)


def signature_of(comp: str, fmt: str, platform: str,
                 binding: Dict[str, Any],
                 epilogue: Optional[str] = None) -> str:
    """Stable string key for one harness call site.

    Works on concrete arrays and on tracers (shape/dtype only — no data is
    read), so trace-mode lowering and host-mode execution agree on the key.

    ``epilogue`` distinguishes fused-epilogue call sites (spmv+bias+relu)
    from the plain computation: the candidate cost structure differs (a
    fusing harness saves an output round-trip), so they tune separately.
    Plain call sites keep the historical key format.
    """
    dims: List[str] = []
    rows = nnz = cols = None
    for k in sorted(binding):
        v = binding[k]
        if isinstance(v, bool):
            dims.append(f"{k}={v}")
        elif isinstance(v, int):
            dims.append(f"{k}={pow2_bucket(v)}")
            if k == "rows":
                rows = v
            elif k == "nnz":
                nnz = v
        elif isinstance(v, float):
            continue
        else:
            shape = _shape_of(v)
            if shape is not None:
                dims.append(f"{k}={'x'.join(str(pow2_bucket(s)) for s in shape)}")
                if k in ("iv", "vector", "vec", "dense") and shape:
                    cols = shape[0]
    if rows and nnz and cols:
        sb = sparsity_bucket(nnz / float(rows * cols))
    else:
        sb = "d?"
    sig = "|".join([comp, fmt, platform, ",".join(dims), sb])
    if epilogue:
        sig += f"|ep:{epilogue}"
    return sig


# ---------------------------------------------------------------------------
# Persistent cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TuneStats:
    memory_hits: int = 0
    disk_hits: int = 0
    misses: int = 0
    timing_calls: int = 0      # candidate measurements performed
    stores: int = 0
    fallbacks: int = 0         # budget/measurability forced a default
    invalidations: int = 0     # on-disk entries dropped (version/fingerprint)
    migrations: int = 0        # schema-1/2 entries migrated to schema 3
    remeasures: int = 0        # stale records re-tuned (marshal/schedule)
    elimination_calls: int = 0  # cheap single-iteration sweep measurements
    save_errors: int = 0       # persistence failed (unwritable path)
    corrupt_recoveries: int = 0  # torn cache file quarantined, fresh start
    quarantine_skips: int = 0  # candidates/variants excluded by quarantine

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)

    def reset(self):
        for f in dataclasses.fields(self):
            setattr(self, f.name, 0)


class AutotuneCache(JsonStore):
    """Versioned JSON store of tuning decisions (the
    :class:`repro.core.jsonstore.JsonStore` disk protocol with nested
    per-``(signature, mode)`` entries and schema-1/2 migration).

    Layout (schema 4)::

        {"schema": 4, "registry": "<fingerprint>",
         "entries": {"<sig>": {"<mode>": {
             "harness": ..., "best_s": ..., "timings": {...},
             "marshal_s": {...}, "reuse": 100.0, "amortized_s": {...},
             "cost_model": "amortized" | "kernel_only",
             "schedule": {...} | null, "schedules": {...},
             "fuse": true | false | null, "fuses": {...},
             "variant_s": {...}, "variants": {...},
             "schedule_swept": true, "fuse_swept": true}}}}

    ``timings`` are steady-state kernel seconds per harness (its best
    variant); ``marshal_s`` the measured conversion-path seconds per
    candidate; ``amortized_s`` their combination at the declared call
    frequency (``reuse``), which is what the winner minimizes.
    ``schedule`` is the winning harness's swept tune-parameter assignment
    (null for untuned winners), ``schedules`` each harness's best variant,
    ``fuse``/``fuses`` the analogous fused-epilogue decisions (null where
    the dimension doesn't exist), ``variant_s`` per-variant steady-state
    seconds (``{harness: {variant_key: s}}``) for the survivors of the
    successive-halving sweep, and ``variants`` the same survivors as
    structured ``{harness: [[schedule, fuse, seconds], ...]}`` triples —
    the per-candidate component table the joint plan search
    (``repro.core.plan_search``) consumes.

    Schema-1 files are migrated in place on load: records become
    ``cost_model: "kernel_only"`` (their winner predates marshal-aware
    selection) and are re-measured instead of served when a marshaling
    candidate is present.  Schema-2 records gain
    ``schedule_swept: false``: their kernel-level winner is kept as a
    *prior* (it ranks first in the next sweep) but the record is
    re-measured instead of served whenever a live candidate declares
    schedule variants.  Schema-3 records gain ``fuse_swept: false``: they
    are served verbatim wherever the fused-epilogue dimension can't change
    the answer and demote to sweep priors at epilogue sites with a
    fuse-capable candidate.

    Writes are atomic (tempfile in the same directory + ``os.replace``) and
    merge-on-save under an advisory lock, so concurrent tuners never
    corrupt the file and rarely lose each other's entries.
    """

    schema_version = SCHEMA_VERSION
    readable_schemas = (1, 2, 3)

    def __init__(self, path: Optional[os.PathLike] = None,
                 registry_fingerprint: str = ""):
        self.stats = TuneStats()   # before super(): _note_* hooks need it
        super().__init__(path, registry_fingerprint)

    # -- disk (JsonStore hooks) ----------------------------------------------

    def default_path(self) -> Path:
        return default_cache_path()

    def _note_invalidation(self):
        self.stats.invalidations += 1

    def _note_save_error(self):
        self.stats.save_errors += 1

    def _note_corrupt_recovery(self):
        self.stats.corrupt_recoveries += 1

    def _migrate(self, entries, schema):
        if schema == 1:
            entries = self._migrate_v1(entries)
        if schema <= 2:
            entries = self._migrate_v2(entries)
        return self._migrate_v3(entries)

    def _merge(self, base, incoming, overwrite):
        """Entries nest per signature then mode: merge at the mode level so
        concurrent tuners working different modes of one signature don't
        clobber each other."""
        for sig, modes in incoming.items():
            if not isinstance(modes, dict):
                continue
            slot = base.setdefault(sig, {})
            for m, rec in modes.items():
                if overwrite or m not in slot:
                    slot[m] = rec

    def _migrate_v1(self, entries: Dict[str, Dict[str, Any]]
                    ) -> Dict[str, Dict[str, Any]]:
        """Schema 1 -> 2: keep the measured kernel timings (they are still
        valid measurements) but mark records ``kernel_only`` so the tuner
        re-measures — instead of serving a potentially stale winner —
        whenever marshal-aware selection would change the answer."""
        out: Dict[str, Dict[str, Any]] = {}
        for sig, modes in entries.items():
            if not isinstance(modes, dict):
                continue
            new_modes = {}
            for mode, rec in modes.items():
                if not isinstance(rec, dict) or "harness" not in rec:
                    continue
                rec = dict(rec)
                rec.setdefault("cost_model", "kernel_only")
                rec.setdefault("marshal_s", {})
                rec.setdefault("amortized_s", dict(rec.get("timings", {})))
                # counted once per record, in _migrate_v3 (every legacy
                # record passes through it)
                new_modes[mode] = rec
            if new_modes:
                out[sig] = new_modes
        return out

    def _migrate_v2(self, entries: Dict[str, Dict[str, Any]]
                    ) -> Dict[str, Dict[str, Any]]:
        """Schema 2 -> 3: the measured (possibly marshal-amortized) winner
        is still a valid *kernel-level* decision, but it predates schedule
        sweeping — mark it unswept so the tuner uses it as a sweep prior
        and never serves it against a variant-declaring candidate set."""
        for modes in entries.values():
            if not isinstance(modes, dict):
                continue
            for rec in modes.values():
                if not isinstance(rec, dict) or "harness" not in rec:
                    continue
                if "schedule_swept" not in rec:
                    rec.setdefault("schedule", None)
                    rec.setdefault("schedules", {})
                    rec.setdefault("variant_s", {})
                    rec["schedule_swept"] = False
                    # counted once per record, in _migrate_v3
        return entries

    def _migrate_v3(self, entries: Dict[str, Dict[str, Any]]
                    ) -> Dict[str, Dict[str, Any]]:
        """Schema 3 -> 4: records predate the fused-epilogue variant
        dimension and the structured per-candidate ``variants`` table.
        Their winner stays fully valid wherever fusion isn't a choice (no
        epilogue at the site, or no fuse-capable candidate) — those are
        served with zero re-timing; at epilogue sites with a fuse-capable
        candidate the winner demotes to a sweep *prior* (ranked first)."""
        for modes in entries.values():
            if not isinstance(modes, dict):
                continue
            for rec in modes.values():
                if not isinstance(rec, dict) or "harness" not in rec:
                    continue
                if "fuse_swept" not in rec:
                    rec.setdefault("fuse", None)
                    rec.setdefault("fuses", {})
                    rec.setdefault("variants", {})
                    rec["fuse_swept"] = False
                    self.stats.migrations += 1
        return entries

    # -- lookup --------------------------------------------------------------

    def get(self, sig: str, mode: str) -> Optional[Dict[str, Any]]:
        rec = self.entries.get(sig, {}).get(mode)
        if rec is not None:
            self.stats.memory_hits += 1
            return rec
        if not self.loaded:
            self.load()
            rec = self.entries.get(sig, {}).get(mode)
            if rec is not None:
                self.stats.disk_hits += 1
                return rec
        self.stats.misses += 1
        return None

    def put(self, sig: str, mode: str, record: Dict[str, Any],
            persist: bool = True):
        self.entries.setdefault(sig, {})[mode] = record
        self.stats.stores += 1
        if persist:
            self.save()


# ---------------------------------------------------------------------------
# Operand synthesis (trace-mode measurement)
# ---------------------------------------------------------------------------

def _infer_cols(binding: Dict[str, Any], shapes: Dict[str, Tuple[int, ...]]) -> int:
    for k in ("iv", "vector", "vec", "dense"):
        if k in shapes and shapes[k]:
            return shapes[k][0]
    return 0


def synthesize_operands(binding: Dict[str, Any], rng_seed: int = 0
                        ) -> Optional[Dict[str, Any]]:
    """Concrete, *semantically valid* stand-ins for traced binding atoms.

    Trace-mode tuning happens at lowering time, when the real operands are
    tracers.  We only know shapes/dtypes, so representative operands are
    synthesized; index-carrying What-names (``colidx``/``rowstr``/``idx``…)
    get valid index structure so candidate kernels exercise realistic
    gather/scatter paths.  Returns None if any atom's shape is unknown.
    """
    import jax.numpy as jnp

    rng = np.random.default_rng(rng_seed)
    shapes: Dict[str, Tuple[int, ...]] = {}
    dtypes: Dict[str, Any] = {}
    scalars: Dict[str, Any] = {}
    for k, v in binding.items():
        if isinstance(v, (int, float, bool)):
            scalars[k] = v
            continue
        shape = _shape_of(v)
        if shape is None:
            return None
        shapes[k] = shape
        aval = getattr(v, "aval", v)
        dtypes[k] = np.dtype(getattr(aval, "dtype", np.float32))

    rows = int(scalars.get("rows", 0))
    nnz = int(scalars.get("nnz", 0))
    experts = int(scalars.get("experts", 0))
    cols = _infer_cols(binding, shapes)

    out: Dict[str, Any] = dict(scalars)
    for k, shape in shapes.items():
        dt = dtypes[k]
        if k in ("colidx", "col_ind", "col"):
            hi = max(1, cols or (shape[-1] if shape else 1))
            arr = rng.integers(0, hi, shape)
        elif k in ("rowstr", "row_ptr"):
            # uniform monotone pointer: rows+1 entries from 0..nnz
            n = shape[0]
            arr = np.round(np.linspace(0, nnz, n)).astype(np.int64)
        elif k == "rowidx":
            arr = np.sort(rng.integers(0, max(1, rows), shape))
        elif k == "idx":
            arr = rng.integers(0, max(1, experts), shape)
        elif k == "perm":
            n = shape[0]
            arr = rng.permutation(n)
        elif np.issubdtype(dt, np.integer):
            arr = np.zeros(shape)
        else:
            arr = rng.standard_normal(shape)
        out[k] = jnp.asarray(arr.astype(dt))
    return out


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------

#: decision sources that are real tuning outcomes (measured now or served
#: from the cache) — the only ones the pass manager pins into a rewrite or
#: serializes into the persistent plan cache.
DEFINITIVE_SOURCES = ("memory", "disk", "measured")


@dataclasses.dataclass
class Decision:
    harness: str
    source: str     # 'memory' | 'disk' | 'measured' | 'fallback'
    sig: str
    # winning schedule variant (tune-param assignment); None when the
    # winner has no declared tune space
    schedule: Optional[Dict[str, Any]] = None
    # winning fused-epilogue realization: True/False where the dimension
    # was swept, None where it doesn't exist (no epilogue / can't fuse)
    fuse: Optional[bool] = None

    @property
    def definitive(self) -> bool:
        """True when this decision may be pinned/persisted: a fallback
        (can't-measure, budget 0, tracer-only first call) must stay
        re-tunable on later concrete calls."""
        return self.source in DEFINITIVE_SOURCES

    def as_pin(self) -> Tuple[str, Optional[Dict[str, Any]], Optional[bool]]:
        """The JSON-serializable ``(harness, schedule, fuse)`` triple the
        pass manager stores in ``CompiledEntry.pins`` and the plan cache."""
        return (self.harness, self.schedule, self.fuse)


class Autotuner:
    """Signature-keyed backend selection with an exploration budget.

    ``select`` is the single entry point; it is deterministic once the
    cache holds a winner for the signature (zero re-timing), which is what
    lets trace-mode pin the winner into the rewrite and lets a fresh
    process warm-start from disk.
    """

    def __init__(self, registry_fingerprint: str = "",
                 cache: Optional[AutotuneCache] = None,
                 budget: Optional[int] = None,
                 reps: int = 2,
                 max_variants: Optional[int] = None):
        self.registry_fingerprint = registry_fingerprint
        self._cache = cache
        self._cache_injected = cache is not None
        self.budget = budget
        self.reps = reps
        self.max_variants = max_variants
        self.stats = TuneStats()
        self.last_decision: Optional[Decision] = None
        #: injectable QuarantineStore; None -> the process-shared one
        self.quarantine = None

    # -- cache plumbing ------------------------------------------------------

    @property
    def cache(self) -> AutotuneCache:
        """The persistent cache.  An explicitly injected cache is pinned;
        an auto-created one re-resolves if LILAC_AUTOTUNE_CACHE moved."""
        if self._cache_injected:
            return self._cache
        want = default_cache_path()
        if self._cache is None or (self._cache.path != want
                                   and _ENV_PATH in os.environ):
            self._cache = AutotuneCache(
                want, registry_fingerprint=self.registry_fingerprint)
        return self._cache

    def _quarantine_store(self):
        if self.quarantine is not None:
            return self.quarantine
        from repro.core.resilience import shared_quarantine
        return shared_quarantine()

    def _budget(self) -> int:
        return self.budget if self.budget is not None else exploration_budget()

    def _max_variants(self) -> int:
        return (self.max_variants if self.max_variants is not None
                else variant_cap())

    # -- measurement ---------------------------------------------------------

    @staticmethod
    def _as_runtime(h, binding, ctx):
        """One candidate call exactly as the rewrite will run it: for a
        match with a detected epilogue, unfused realizations pay the
        bias+activation after the call (rewrite.apply_epilogue) while
        fused ones pay it in-kernel — timing both the same way would bias
        selection.  ``ctx.fuse`` selects the realization for fuse-capable
        harnesses (None = the declared default, i.e. fused), mirroring
        ``rewrite._eval_anchor``."""
        from repro.core.rewrite import apply_epilogue, effective_fuse

        ep = getattr(ctx, "epilogue", None)
        fused = effective_fuse(h, ctx)
        if ep is not None and not fused and getattr(h, "fuse_epilogue", False):
            # unfused realization of a fuse-capable harness: hide the
            # epilogue from the body, pay it at the jnp level below
            ctx.epilogue = None
            try:
                out = h(binding, ctx)
            finally:
                ctx.epilogue = ep
        else:
            out = h(binding, ctx)
        if ep is not None and not fused:
            out = apply_epilogue(out, binding.get("bias"), ep)
        return out

    def _time_host(self, h, binding, ctx, reps: Optional[int] = None) -> float:
        """Steady-state eager timing: first call pays compile+marshal, the
        repetitions after it are what a solver loop would see."""
        import jax

        out = self._as_runtime(h, binding, ctx)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(max(1, reps if reps is not None else self.reps)):
            t0 = time.perf_counter()
            out = self._as_runtime(h, binding, ctx)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return best

    def _time_trace(self, h, ctx, operands,
                    reps: Optional[int] = None) -> float:
        """Timed jax.jit candidate compile + steady-state run."""
        import jax

        static = {k: v for k, v in operands.items()
                  if isinstance(v, (int, float, bool))}
        arrays = {k: v for k, v in operands.items() if k not in static}

        def call(arrs):
            # through Harness.__call__ so BeforeFirstExecution setup runs,
            # same as the host-mode timing path (incl. the runtime epilogue
            # for non-fusing candidates)
            return self._as_runtime(h, {**static, **arrs}, ctx)

        f = jax.jit(call)
        out = f(arrays)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(max(1, reps if reps is not None else self.reps)):
            t0 = time.perf_counter()
            out = f(arrays)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return best

    def _time_variant(self, h, binding, ctx, mode, operands,
                      schedule: Optional[Dict[str, Any]],
                      reps: int) -> Optional[float]:
        """Time one (harness, schedule) variant; None on failure (a variant
        whose parameters are invalid for this problem — tile not dividing a
        dimension, VMEM overflow — is eliminated, not fatal)."""
        prev = getattr(ctx, "schedule", None)
        if hasattr(ctx, "schedule"):
            ctx.schedule = schedule
        try:
            from repro.core import faults
            if faults.ACTIVE is not None:
                faults.fail("tune_raise", h.name)
            if mode == "trace":
                return self._time_trace(h, ctx, operands, reps=reps)
            return self._time_host(h, binding, ctx, reps=reps)
        except Exception:
            return None
        finally:
            if hasattr(ctx, "schedule"):
                ctx.schedule = prev

    @staticmethod
    def _marshal_cost(h, ctx) -> float:
        """Measured conversion-path seconds for a harness's declared
        marshal clauses (0.0 for marshal-free harnesses or caches that
        don't track costs).  Queried AFTER timing, when the warmup call has
        populated the data plane's edge-cost EWMAs."""
        clauses = getattr(h, "marshal", ()) or ()
        cache = getattr(ctx, "cache", None)
        if not clauses or cache is None:
            return 0.0
        est = getattr(cache, "estimate_marshal_seconds", None)
        if est is None:
            return 0.0
        try:
            return float(est(clauses))
        except Exception:
            return 0.0

    @staticmethod
    def _reuse(ctx) -> float:
        """Declared call frequency (calls per matrix change) from the data
        plane's MarshalPolicy; the amortization rate for repack cost."""
        policy = getattr(getattr(ctx, "cache", None), "policy", None)
        reuse = getattr(policy, "reuse", None)
        return float(reuse) if reuse else 100.0

    @staticmethod
    def amortized(timings: Dict[str, float], marshal_s: Dict[str, float],
                  reuse: float) -> Dict[str, float]:
        """Steady-state repack-amortized cost per candidate: kernel seconds
        plus the conversion cost spread over ``reuse`` calls."""
        return {n: t + marshal_s.get(n, 0.0) / max(reuse, 1.0)
                for n, t in timings.items()}

    def _variant_pool(self, ranked: Sequence[Any],
                      epilogue: Optional[str] = None
                      ) -> List[Tuple[Any, Optional[Dict[str, Any]],
                                      Optional[bool]]]:
        """The sweep pool: every candidate contributes its schedule family
        (or a single ``None`` entry when untuned) crossed with its fusion
        realizations — at an epilogue site a ``fuse epilogue`` harness
        enters both fused and unfused (``fuse=None`` elsewhere) — capped at
        ``max_variants``.  Default variants (default schedule, fused)
        always survive the cap; the remainder fills round-robin so no
        harness monopolizes the budget."""
        q = self._quarantine_store()
        families = []
        for h in ranked:
            scheds = list(getattr(h, "schedules", ()) or ()) or [None]
            fuses = ([True, False]
                     if epilogue is not None
                     and getattr(h, "fuse_epilogue", False) else [None])
            fam = [(s, f) for s in scheds for f in fuses]
            if q is not None:
                comp = getattr(h, "implements", "")
                kept = [(s, f) for s, f in fam
                        if not q.is_quarantined(comp, h.name,
                                                variant_key(s, f))]
                self.stats.quarantine_skips += len(fam) - len(kept)
                if not kept:
                    continue
                fam = kept
            families.append((h, fam))
        cap = max(len(families), self._max_variants())
        total = sum(len(f) for _, f in families)
        if total <= cap:
            return [(h, s, f) for h, fam in families for s, f in fam]
        pool = [(h,) + fam[0] for h, fam in families]
        depth = 1
        while len(pool) < cap:
            added = False
            for h, fam in families:
                if depth < len(fam) and len(pool) < cap:
                    pool.append((h,) + fam[depth])
                    added = True
            if not added:
                break
            depth += 1
        return pool

    def _time_pool(self, h, binding, ctx, mode, operands,
                   schedule: Optional[Dict[str, Any]],
                   fuse: Optional[bool], reps: int) -> Optional[float]:
        """Time one (harness, schedule, fuse) pool entry.  The fusion
        choice travels on ``ctx.fuse`` (set/restored here) so
        ``_time_variant``'s signature — which external riggings patch —
        stays (harness, binding, ctx, mode, operands, schedule, reps)."""
        prev = getattr(ctx, "fuse", None)
        if hasattr(ctx, "fuse"):
            ctx.fuse = fuse
        try:
            return self._time_variant(h, binding, ctx, mode, operands,
                                      schedule, reps)
        finally:
            if hasattr(ctx, "fuse"):
                ctx.fuse = prev

    def _sweep(self, pool, binding, ctx, mode, operands
               ) -> Dict[Tuple[str, str],
                         Tuple[Any, Optional[Dict], Optional[bool], float]]:
        """Successive halving over the variant pool: cheap single-iteration
        elimination rounds shrink the pool to the steady-state budget, then
        the survivors are timed properly.  Returns
        ``(harness_name, variant_key) -> (harness, schedule, fuse,
        seconds)`` for the survivors."""
        budget = max(1, self._budget())
        survivors = list(pool)
        while len(survivors) > budget:
            scored = []
            for h, sched, fuse in survivors:
                self.stats.elimination_calls += 1
                t = self._time_pool(h, binding, ctx, mode, operands,
                                    sched, fuse, reps=1)
                if t is not None:
                    scored.append((t, h, sched, fuse))
            if not scored:
                return {}
            scored.sort(key=lambda x: x[0])
            keep = max(budget, (len(scored) + 1) // 2)
            if keep >= len(scored):
                survivors = [(h, s, f) for _, h, s, f in scored]
                break
            survivors = [(h, s, f) for _, h, s, f in scored[:keep]]
        out: Dict[Tuple[str, str],
                  Tuple[Any, Optional[Dict], Optional[bool], float]] = {}
        for h, sched, fuse in survivors:
            self.stats.timing_calls += 1
            t = self._time_pool(h, binding, ctx, mode, operands,
                                sched, fuse, reps=self.reps)
            if t is not None:
                out[(h.name, variant_key(sched, fuse))] = (h, sched, fuse, t)
        return out

    def measure(self, cands: Sequence[Any], binding: Dict[str, Any],
                ctx, mode: str,
                default_name: Optional[str] = None,
                prior_name: Optional[str] = None,
                ) -> Tuple[Optional[str], Dict[str, float],
                           Dict[str, float], Dict[str, Optional[Dict]],
                           Dict[str, Dict[str, float]],
                           Dict[str, Optional[bool]],
                           Dict[str, List[Tuple[Optional[Dict],
                                                Optional[bool], float]]]]:
        """Sweep the (harness, schedule, fuse) cross-product under the
        budget; returns (winner_name, per-harness best kernel timings,
        marshal-path seconds, per-harness best schedule, per-variant
        seconds, per-harness best fuse, per-harness surviving
        (schedule, fuse, seconds) triples).  The winner minimizes the
        repack-amortized cost of its best variant, not raw kernel time.
        ``prior_name`` (a migrated kernel-level winner) outranks even the
        platform default in sweep order, so budget truncation keeps the
        prior in play."""
        import jax

        ranked = sorted(
            cands, key=lambda h: (h.name != prior_name,
                                  h.name != default_name))
        ranked = ranked[: max(0, self._budget())]
        operands = None
        if mode == "trace":
            concrete = all(
                not isinstance(v, jax.core.Tracer) and _shape_of(v) is not None
                for v in binding.values()
                if not isinstance(v, (int, float, bool)))
            operands = (dict(binding) if concrete
                        else synthesize_operands(binding))
            if operands is None:
                return None, {}, {}, {}, {}, {}, {}
        pool = self._variant_pool(ranked, getattr(ctx, "epilogue", None))
        if len(pool) <= max(1, self._budget()):
            # no sweep needed: steady-state time everything directly
            measured = {}
            for h, sched, fuse in pool:
                self.stats.timing_calls += 1
                t = self._time_pool(h, binding, ctx, mode, operands,
                                    sched, fuse, reps=self.reps)
                if t is not None:
                    measured[(h.name, variant_key(sched, fuse))] = (
                        h, sched, fuse, t)
        else:
            measured = self._sweep(pool, binding, ctx, mode, operands)
        if not measured:
            return None, {}, {}, {}, {}, {}, {}
        timings: Dict[str, float] = {}
        schedules: Dict[str, Optional[Dict]] = {}
        fuses: Dict[str, Optional[bool]] = {}
        variant_s: Dict[str, Dict[str, float]] = {}
        variants: Dict[str, List[Tuple[Optional[Dict],
                                       Optional[bool], float]]] = {}
        marshal_s: Dict[str, float] = {}
        for (name, vkey), (h, sched, fuse, t) in measured.items():
            variant_s.setdefault(name, {})[vkey] = t
            variants.setdefault(name, []).append((sched, fuse, t))
            if name not in timings or t < timings[name]:
                timings[name] = t
                schedules[name] = sched
                fuses[name] = fuse
        if mode != "trace":
            by_name = {h.name: h for h, _, _ in pool}
            for name in timings:
                marshal_s[name] = self._marshal_cost(by_name[name], ctx)
        amort = self.amortized(timings, marshal_s, self._reuse(ctx))
        winner = min(amort, key=amort.get)
        return winner, timings, marshal_s, schedules, variant_s, fuses, variants

    # -- selection -----------------------------------------------------------

    def select(self, comp: str, fmt: str, platform: str, mode: str,
               cands: Sequence[Any], binding: Dict[str, Any], ctx,
               default_name: Optional[str] = None):
        """Pick a harness from ``cands`` for this call signature, under
        the ``lilac.tune`` span.

        Returns the chosen Harness, or None to tell the registry to fall
        back to its per-platform default path.
        """
        with spans.span("lilac.tune"):
            return self._select(comp, fmt, platform, mode, cands, binding,
                                ctx, default_name)

    def _select(self, comp, fmt, platform, mode, cands, binding, ctx,
                default_name):
        if not cands:
            return None
        q = self._quarantine_store()
        if q is not None:
            live = [h for h in cands if not q.is_quarantined(comp, h.name)]
            # all-quarantined keeps the full set: an answer is still owed,
            # and call-time containment is the real enforcement boundary
            if live and len(live) < len(cands):
                self.stats.quarantine_skips += len(cands) - len(live)
                cands = live
        by_name = {h.name: h for h in cands}
        sig = signature_of(comp, fmt, platform, binding,
                           epilogue=getattr(ctx, "epilogue", None))
        any_marshal = any(getattr(h, "marshal", ()) for h in cands)
        any_schedules = any(getattr(h, "schedules", ()) for h in cands)
        # the fused-epilogue dimension exists only at epilogue call sites
        # with a fuse-capable candidate — elsewhere pre-schema-4 records
        # stay servable verbatim (zero re-timing)
        fuse_dim = (getattr(ctx, "epilogue", None) is not None
                    and any(getattr(h, "fuse_epilogue", False)
                            for h in cands))
        prior_name = None

        if not autotune_disabled():
            disk_before = self.cache.stats.disk_hits
            rec = self.cache.get(sig, mode)
            if rec is not None and rec.get("harness") in by_name:
                # a migrated (schema-1, kernel-only) winner predates
                # marshal-aware selection: when a marshaling candidate is
                # in play the amortized argmin can differ, so re-measure
                # instead of serving a potentially stale winner
                stale = (rec.get("cost_model") == "kernel_only"
                         and any_marshal)
                # likewise a schema-2 (unswept) record against a candidate
                # set with declared schedule variants: the per-variant
                # argmin can differ, so the kernel-level winner demotes to
                # a sweep *prior* rather than being served
                stale = stale or (any_schedules
                                  and not rec.get("schedule_swept"))
                # a schema-3 (fuse-unswept) record at a site where the
                # fused-vs-unfused choice exists: the per-variant argmin
                # can differ, so demote to a sweep prior
                stale = stale or (fuse_dim and not rec.get("fuse_swept"))
                # a pinned schedule that no longer exists in the winner's
                # declared variant family (tune space changed) is stale too
                if not stale and rec.get("schedule") is not None:
                    fam = getattr(by_name[rec["harness"]], "schedules", ())
                    stale = rec["schedule"] not in fam
                # a quarantined (winner, variant): the record predates the
                # incident, so its measurement no longer speaks for the
                # candidate — demote to prior and re-measure (the sweep
                # pool filters the quarantined variant out)
                if not stale and q is not None and q.is_quarantined(
                        comp, rec["harness"],
                        variant_key(rec.get("schedule"), rec.get("fuse"))):
                    stale = True
                name = schedule = fuse = None
                if not stale:
                    # the record stores the raw kernel + marshal
                    # measurements, so a DIFFERENT declared call frequency
                    # re-derives its winner arithmetically — zero re-timing
                    name = rec["harness"]
                    reuse = self._reuse(ctx)
                    timings = rec.get("timings") or {}
                    if (rec.get("cost_model") == "amortized" and timings
                            and rec.get("reuse") not in (None, reuse)):
                        amort = self.amortized(
                            {n: t for n, t in timings.items()
                             if n in by_name},
                            rec.get("marshal_s") or {}, reuse)
                        if amort:
                            name = min(amort, key=amort.get)
                    schedule = (rec.get("schedule") if name == rec["harness"]
                                else (rec.get("schedules") or {}).get(name))
                    fuse = (rec.get("fuse") if name == rec["harness"]
                            else (rec.get("fuses") or {}).get(name))
                    # the same family check as above, but for the
                    # re-derived winner: a stored schedule from a since-
                    # changed tune space must never be pinned
                    if schedule is not None and schedule not in getattr(
                            by_name[name], "schedules", ()):
                        stale = True
                if stale and self._budget() > 0:
                    self.stats.remeasures += 1
                    prior_name = rec["harness"]
                elif not stale:
                    # the cache's own stats know whether this get had to
                    # read the file; mirror that classification here
                    src = ("disk" if self.cache.stats.disk_hits > disk_before
                           else "memory")
                    if src == "memory":
                        self.stats.memory_hits += 1
                    else:
                        self.stats.disk_hits += 1
                    if hasattr(ctx, "schedule"):
                        ctx.schedule = schedule
                    if hasattr(ctx, "fuse"):
                        ctx.fuse = fuse
                    self.last_decision = Decision(name, src, sig,
                                                  schedule=schedule,
                                                  fuse=fuse)
                    return by_name[name]

        if autotune_disabled() or self._budget() <= 0:
            self.stats.fallbacks += 1
            self.last_decision = Decision(default_name or cands[0].name,
                                          "fallback", sig)
            return None

        self.stats.misses += 1
        (winner, timings, marshal_s, schedules, variant_s, fuses,
         variants) = self.measure(
            cands, binding, ctx, mode, default_name=default_name,
            prior_name=prior_name)
        if winner is None:
            self.stats.fallbacks += 1
            self.last_decision = Decision(default_name or cands[0].name,
                                          "fallback", sig)
            return None
        reuse = self._reuse(ctx)
        amort = self.amortized(timings, marshal_s, reuse)
        win_schedule = schedules.get(winner)
        win_fuse = fuses.get(winner)
        record = {"harness": winner,
                  "best_s": timings[winner],
                  "timings": timings,
                  "marshal_s": marshal_s,
                  "reuse": reuse,
                  "amortized_s": amort,
                  "cost_model": "amortized",
                  "schedule": win_schedule,
                  "schedules": {n: s for n, s in schedules.items()
                                if s is not None},
                  "fuse": win_fuse,
                  "fuses": {n: f for n, f in fuses.items()
                            if f is not None},
                  "variant_s": variant_s,
                  "variants": {n: [[s, f, t] for s, f, t in vs]
                               for n, vs in variants.items()},
                  "schedule_swept": True,
                  "fuse_swept": True,
                  "platform": platform,
                  "format": fmt}
        self.cache.put(sig, mode, record, persist=True)
        self.stats.stores += 1
        if hasattr(ctx, "schedule"):
            ctx.schedule = win_schedule
        if hasattr(ctx, "fuse"):
            ctx.fuse = win_fuse
        self.last_decision = Decision(winner, "measured", sig,
                                      schedule=win_schedule, fuse=win_fuse)
        return by_name[winner]

    def record_external(self, comp: str, fmt: str, platform: str, mode: str,
                        binding: Dict[str, Any],
                        timings: Dict[str, float],
                        marshal_s: Optional[Dict[str, float]] = None,
                        reuse: float = 100.0,
                        schedules: Optional[Dict[str, Dict]] = None,
                        variant_s: Optional[Dict[str, Dict[str, float]]] = None,
                        epilogue: Optional[str] = None,
                        fuses: Optional[Dict[str, Optional[bool]]] = None,
                        ) -> str:
        """Seed the persistent cache from externally measured timings
        (e.g. a benchmark sweep acting as the tuner).  ``marshal_s`` (per
        candidate conversion-path seconds) makes the recorded winner the
        repack-amortized argmin at the declared ``reuse`` frequency; without
        it the record is kernel-only.  ``schedules`` (per-harness best
        variant) and ``variant_s`` (per-variant seconds) mark the record
        schedule-swept; without them it is a kernel-level prior that gets
        re-swept when a variant-declaring candidate appears.  ``fuses``
        (per-harness best fused-epilogue realization) likewise marks the
        record fuse-swept.  Returns the winner."""
        if not timings:
            raise ValueError("record_external needs at least one timing")
        sig = signature_of(comp, fmt, platform, binding, epilogue=epilogue)
        marshal_s = dict(marshal_s or {})
        amort = self.amortized(timings, marshal_s, reuse)
        winner = min(amort, key=amort.get)
        swept = schedules is not None or variant_s is not None
        schedules = dict(schedules or {})
        fuse_swept = fuses is not None or epilogue is None
        fuses = dict(fuses or {})
        self.cache.put(sig, mode, {"harness": winner,
                                   "best_s": timings[winner],
                                   "timings": dict(timings),
                                   "marshal_s": marshal_s,
                                   "reuse": reuse,
                                   "amortized_s": amort,
                                   "cost_model": ("amortized" if marshal_s
                                                  else "kernel_only"),
                                   "schedule": schedules.get(winner),
                                   "schedules": schedules,
                                   "fuse": fuses.get(winner),
                                   "fuses": {n: f for n, f in fuses.items()
                                             if f is not None},
                                   "variant_s": dict(variant_s or {}),
                                   "variants": {},
                                   "schedule_swept": swept,
                                   "fuse_swept": fuse_swept,
                                   "platform": platform,
                                   "format": fmt}, persist=True)
        self.stats.stores += 1
        return winner

    # -- introspection -------------------------------------------------------

    def pinned(self) -> Dict[Tuple[str, str], str]:
        """(signature, mode) -> winning harness name, in-memory view."""
        out = {}
        for sig, modes in self.cache.entries.items():
            for mode, rec in modes.items():
                out[(sig, mode)] = rec.get("harness")
        return out
