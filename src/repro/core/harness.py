"""LiLAC-How harnesses: how detected computations are executed (paper §3.3).

A ``Harness`` is the executable form of a spec's HARNESS block: a named
implementation of one What-computation, with marshaling, persistence and
platform constraints.  Multiple harnesses per computation reproduce the
paper's central observation (Table 2): no backend wins everywhere, so the
registry supports per-platform defaults, explicit pinning and an autotune
policy (the SparseX analogue).

This module holds the *mechanism* (Harness, HarnessRegistry, the global
REGISTRY) and the builtin jnp.* kernel bodies.  The *policy* — which
harness exists, its formats/platforms, and its marshaled inputs — lives in
the spec texts (``what_lang.BUILTIN_SPECS`` plus the HARNESS blocks
declared next to the Pallas kernels under ``repro/kernels/``); the spec
compiler (``repro.core.spec``) populates REGISTRY from them at import time
of ``repro.core``.  Kernel bodies receive marshaled inputs as keyword
arguments generated from the declared repack clauses instead of
open-coding the cache lookups.

Backends provided out of the box:

  spmv_*      jnp.segment   XLA-native segment-sum           (cpu + tpu)
              jnp.ell       marshaled CSR->ELL slab repack    (host calls)
              jnp.bcsr      marshaled CSR->BCSR tile repack   (host calls)
              jnp.dense     marshaled densify fallback        (host calls)
              jnp.dia       marshaled CSR->DIA diagonal slabs (host calls)
              pallas.ell    hand-tiled VPU row-slab kernel    (tpu target)
              pallas.bcsr   hand-tiled MXU block kernel       (tpu target)
  dotproduct  jnp.dot
  gemv        jnp.dot
  moe_ffn     jnp.capacity  sorted capacity-bucket dispatch   (cpu + tpu)
              pallas.gmm    ragged grouped matmul             (tpu target)
              dense         the naive einsum itself (baseline)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.marshal import DataPlane, MarshalingCache

Binding = Dict[str, Any]


class DuplicateHarnessError(ValueError):
    """A harness with the same (implements, name) is already registered."""


@dataclasses.dataclass
class CallCtx:
    mode: str                      # 'trace' | 'host'
    cache: Optional[MarshalingCache]   # usually a DataPlane (plan-level,
                                       # shared across a call's harnesses)
    format: str                    # match format: CSR/COO/ELL/JDS/DOT/...
    platform: str = "cpu"
    # Selected schedule variant: tune-param name -> value.  None (or {})
    # means the harness's declared default schedule.  Set by the autotuner
    # when it sweeps/pins a variant and by explicit callers; the generated
    # spec wrapper merges it over the defaults and passes the result to the
    # kernel body as keyword arguments.
    schedule: Optional[Dict[str, Any]] = None
    # Detected fused epilogue for this call site: 'relu' | 'silu' | 'none'
    # (bias only) | None (no epilogue).  Harnesses declaring
    # ``fuse epilogue`` apply it in-kernel (reading ``binding['bias']``
    # when present); for all others the rewriter applies it after the call.
    epilogue: Optional[str] = None
    # Fusion decision for this call: None = the harness's declared default
    # (fuse iff it declares ``fuse epilogue``); False pins the UNFUSED
    # realization of a fuse-capable harness (the epilogue is applied at the
    # jnp level after the call instead of in-kernel).  Swept as a variant
    # dimension by the autotuner and pinned by the joint plan search —
    # fusion is only applied where it measured faster (plan_search.py).
    fuse: Optional[bool] = None


@dataclasses.dataclass
class Harness:
    name: str
    implements: str                               # What-computation name
    fn: Callable[[Binding, CallCtx], Any]
    jit_safe: bool = True                         # can run under tracing
    platforms: Tuple[str, ...] = ("cpu", "tpu")
    formats: Tuple[str, ...] = ()                 # () = any
    persistent: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # declared marshal clauses (what_lang.MarshalClause): the autotuner
    # reads these to fold repack cost into winner selection; NOT part of
    # the registry fingerprint (formats/platforms/jit_safe identify the
    # harness, marshaling is an implementation detail of its data plane)
    marshal: Tuple[Any, ...] = ()
    # declared schedule space (what_lang.TuneClause / Constraint): the
    # autotuner sweeps the variant cross-product and pins (harness,
    # schedule) pairs.  Also NOT in the fingerprint: growing or shrinking
    # a tune space must not invalidate every persisted decision — stale
    # schedules are detected per-record instead (autotune.py).
    tune: Tuple[Any, ...] = ()
    constraints: Tuple[Any, ...] = ()
    # True when the body applies detected epilogues (ctx.epilogue +
    # binding['bias']) itself — in-register for Pallas kernels; False
    # harnesses get the epilogue applied by the rewriter after the call.
    fuse_epilogue: bool = False
    # Declared custom backward (what_lang.VjpClause): the rewriter wraps
    # the call in jax.custom_vjp over the clause's wrt keys, using the
    # registered backward body (spec.VJPS).  None means jax differentiates
    # straight through the body — fine for pure-jnp harnesses, fatal for
    # Pallas/host kernels, which is why those declare one.  NOT in the
    # fingerprint: adding a backward must not invalidate persisted tunings.
    vjp: Optional[Any] = None
    # Opt-out for executable-plan baking (repro.core.plan): set False for
    # a backend whose body has per-call HOST-side behavior beyond its
    # declared marshal clauses (RNG, mutable globals, external I/O) — a
    # baked plan would freeze the first call's behavior at trace time.
    # Harnesses with persistent state / lifecycle hooks are treated as
    # unbakeable automatically.
    bakeable: bool = True
    setup: Optional[Callable] = None              # BeforeFirstExecution
    teardown: Optional[Callable] = None           # AfterLastExecution
    # Shared mutable {"up": bool} when one HARNESS block implements several
    # computations: the sibling Harness objects are ONE backend, so setup
    # runs once on the first call through any of them, release through any
    # of them tears down for all, and a later call sets up again.
    lifecycle: Optional[Dict[str, bool]] = None
    _setup_done: bool = False
    _schedules: Optional[Tuple[Dict[str, Any], ...]] = None

    @property
    def schedules(self) -> Tuple[Dict[str, Any], ...]:
        """The lazy schedule-variant family: every constraint-satisfying
        assignment of the declared tune params, default first.  Empty for
        untuned harnesses."""
        if self._schedules is None:
            from repro.core.what_lang import enumerate_schedules
            self._schedules = enumerate_schedules(self.tune, self.constraints)
        return self._schedules

    @property
    def default_schedule(self) -> Dict[str, Any]:
        return {t.name: t.values[0] for t in self.tune}

    def _is_up(self) -> bool:
        if self.lifecycle is not None:
            return self.lifecycle["up"]
        return self._setup_done

    def _mark(self, up: bool):
        if self.lifecycle is not None:
            self.lifecycle["up"] = up
        self._setup_done = up

    def __call__(self, binding: Binding, ctx: CallCtx):
        from repro.core import faults
        if faults.ACTIVE is not None:
            faults.fail("kernel_raise", self.name)
        if not self._is_up() and self.setup is not None:
            self.setup(self.persistent)
            self._mark(True)
        out = self.fn(binding, ctx)
        if faults.ACTIVE is not None:
            out = faults.corrupt("nan_output", self.name, out)
        return out

    def release(self):
        if self._is_up() and self.teardown is not None:
            self.teardown(self.persistent)
            self._mark(False)


class HarnessRegistry:
    def __init__(self, version: int = 0):
        self._by_comp: Dict[str, List[Harness]] = {}
        self._defaults: Dict[Tuple[str, str], str] = {}  # (comp, platform) -> name
        self.version = version        # bump to invalidate persisted tunings
        # monotone registration counter: unlike the fingerprint (which
        # hashes declared metadata and cannot see a same-name body
        # replacement via override=True), the epoch moves on EVERY
        # register — baked executable plans compare it per dispatch so a
        # replaced kernel is never served from a stale jitted executable
        self.epoch = 0
        self._autotuner = None
        self._fp_cache: Optional[Tuple[int, str]] = None  # (version, fp)

    def register(self, h: Harness, default_for: Tuple[str, ...] = (),
                 override: bool = False):
        """Register a harness.  Re-registering the same ``(implements,
        name)`` is an error unless ``override=True``, which replaces the
        existing harness in place (same candidate-order slot) — the escape
        hatch that makes spec re-loading safe."""
        hs = self._by_comp.setdefault(h.implements, [])
        for i, existing in enumerate(hs):
            if existing.name == h.name:
                if not override:
                    raise DuplicateHarnessError(
                        f"harness {h.name!r} is already registered for "
                        f"{h.implements!r}; pass override=True to replace it")
                existing.release()   # run AfterLastExecution before dropping
                hs[i] = h
                break
        else:
            hs.append(h)
        for plat in default_for:
            self._defaults[(h.implements, plat)] = h.name
        self._autotuner = None        # harness set changed -> new fingerprint
        self._fp_cache = None
        self.epoch += 1
        return h

    def fingerprint(self) -> str:
        """Stable hash of (version, registered harness set).  Persisted
        tunings (and executable plans) are invalidated whenever this
        changes.  Memoized until the next ``register``/version bump: the
        pass manager reads it per compiled function and the steady-state
        path must not re-hash the whole registry."""
        cached = self._fp_cache
        if cached is not None and cached[0] == self.version:
            return cached[1]
        import hashlib

        items = sorted(
            (h.implements, h.name, h.platforms, h.formats, h.jit_safe)
            for hs in self._by_comp.values() for h in hs)
        blob = repr((self.version, items)).encode()
        fp = hashlib.blake2b(blob, digest_size=8).hexdigest()
        self._fp_cache = (self.version, fp)
        return fp

    @property
    def autotuner(self):
        from repro.core.autotune import Autotuner

        fp = self.fingerprint()
        if self._autotuner is None or self._autotuner.registry_fingerprint != fp:
            self._autotuner = Autotuner(registry_fingerprint=fp)
        return self._autotuner

    def reset_autotuner(self):
        self._autotuner = None

    @property
    def _autotune_cache(self) -> Dict[Tuple, str]:
        """Back-compat view: (signature, mode) -> winning harness name."""
        if self._autotuner is None:
            return {}
        return self._autotuner.pinned()

    def default_name(self, comp: str, platform: str) -> Optional[str]:
        return self._defaults.get((comp, platform))

    def harnesses_for(self, comp: str) -> List[Harness]:
        return list(self._by_comp.get(comp, []))

    def get(self, comp: str, name: str) -> Harness:
        for h in self._by_comp.get(comp, []):
            if h.name == name:
                return h
        raise KeyError(f"no harness {name!r} for {comp!r}")

    def candidates(self, comp: str, fmt: str, platform: str,
                   mode: str) -> List[Harness]:
        out = []
        for h in self._by_comp.get(comp, []):
            if platform not in h.platforms:
                continue
            if h.formats and fmt not in h.formats:
                continue
            if mode == "trace" and not h.jit_safe:
                continue
            out.append(h)
        return out

    def select(self, comp: str, fmt: str, platform: str, mode: str,
               policy: str = "default",
               binding: Optional[Binding] = None,
               ctx: Optional[CallCtx] = None) -> Harness:
        cands = self.candidates(comp, fmt, platform, mode)
        if not cands:
            raise KeyError(f"no harness for {comp}/{fmt} on {platform} ({mode})")
        if policy not in ("default", "autotune"):
            return self.get(comp, policy)  # explicit pin by name
        dname = self._defaults.get((comp, platform))
        if policy == "autotune" and binding is not None:
            # SparseX-style persistent tuning (autotune.py): signature-keyed
            # winner, measured once, reused across calls AND processes; in
            # trace mode the winner is pinned at first lowering.
            if ctx is None:
                ctx = CallCtx(mode=mode, cache=DataPlane(), format=fmt,
                              platform=platform)
            h = self.autotuner.select(comp, fmt, platform, mode, cands,
                                      binding, ctx, default_name=dname)
            if h is not None:
                return h
        if dname is not None:
            for h in cands:
                if h.name == dname:
                    return h
        return cands[0]


REGISTRY = HarnessRegistry()


# ---------------------------------------------------------------------------
# Builtin jnp.* kernel bodies.  Marshaled inputs (ell/bcsr/dense keyword
# args) are produced by the repack clauses declared in the spec texts and
# injected by the generated wrapper (repro.core.spec.build_harnesses).
# ---------------------------------------------------------------------------

def _row_ids(binding: Binding) -> jax.Array:
    """CSR binding carries `rowstr`; COO carries `rowidx`."""
    if "rowidx" in binding:
        return binding["rowidx"]
    row_ptr = binding["rowstr"]
    return jnp.repeat(
        jnp.arange(binding["rows"], dtype=jnp.int32),
        jnp.diff(row_ptr),
        total_repeat_length=binding["nnz"],
    )


def _spmv_segment(b: Binding, ctx: CallCtx):
    prod = b["a"] * b["iv"][b["colidx"]]
    return jax.ops.segment_sum(prod, _row_ids(b), num_segments=b["rows"])


@jax.jit
def _ell_spmv_jit(val, col, perm, vec):
    acc = jnp.sum(val * vec[col], axis=1)
    out = jnp.zeros((val.shape[0],), acc.dtype)
    return out.at[perm].set(acc)


def _spmv_ell_host(b: Binding, ctx: CallCtx, *, ell):
    """CSR/COO match with a marshaled ELL repack: the repack is the
    'transfer' that the cache amortizes across calls (paper Fig. 18)."""
    return _ell_spmv_jit(ell.val, ell.col, ell.perm, b["iv"])


def _binding_to_csr(b: Binding):
    from repro.sparse.formats import CSR

    cols = int(b["iv"].shape[0])
    if "rowstr" in b:
        return CSR(val=b["a"], col_ind=b["colidx"], row_ptr=b["rowstr"],
                   shape=(b["rows"], cols))
    # COO -> CSR on host (sorted by row)
    row = np.asarray(b["rowidx"])
    order = np.argsort(row, kind="stable")
    val = np.asarray(b["a"])[order]
    col = np.asarray(b["colidx"])[order]
    counts = np.bincount(row, minlength=b["rows"])
    row_ptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return CSR(val=jnp.asarray(val), col_ind=jnp.asarray(col.astype(np.int32)),
               row_ptr=jnp.asarray(row_ptr), shape=(b["rows"], cols))


def _spmv_bcsr_host(b: Binding, ctx: CallCtx, *, bcsr):
    from repro.sparse.ops import bcsr_spmm_ref

    vec = b["iv"]
    pad = bcsr.shape[1] - vec.shape[0]
    if pad > 0:
        vec = jnp.pad(vec, (0, pad))
    out = bcsr_spmm_ref(bcsr, vec[:, None])[:, 0]
    return out[: b["rows"]]


def _spmv_dense_host(b: Binding, ctx: CallCtx, *, dense):
    return dense @ b["iv"]


@jax.jit
def _dia_spmv_jit(dia, x):
    """``y[i] = sum_d slabs[d, i] * x[i + offsets[d]]``: each stored
    diagonal multiplies a statically shifted slice of x (padded by the
    halo the offsets need), so the product is ndiag elementwise
    multiply-adds that XLA fuses into one pass over ``data`` (each slab
    whole tiles, read in place), with no gather and no index arrays.
    Jitted so that a host-mode call (the tuner times candidates that way)
    is one dispatch, not 2 * ndiag; in a baked plan it is inlined into the
    plan's program."""
    rows, cols = dia.shape
    offs = dia.offsets
    if not offs:
        return jnp.zeros((rows,), jnp.result_type(dia.data, x))
    lo = max(0, -offs[0])
    xp = jnp.pad(x, (lo, max(0, offs[-1] + rows - cols)))
    return sum(dia.data[d].reshape(-1)[:rows] * xp[lo + o:lo + o + rows]
               for d, o in enumerate(offs))


def _spmv_dia_host(b: Binding, ctx: CallCtx, *, dia):
    """CSR/COO match with a marshaled CSR->DIA repack."""
    return _dia_spmv_jit(dia, b["iv"])


def _spmv_ell_direct(b: Binding, ctx: CallCtx):
    """For matches already in ELL/JDS layout (2D val/col binding)."""
    perm = b.get("perm")
    acc = jnp.sum(b["val"] * b["vector"][b["col_ind"]], axis=1)
    if perm is None:
        return acc
    out = jnp.zeros((b["rows"],), acc.dtype)
    return out.at[perm].set(acc)


def _spmm_segment(b: Binding, ctx: CallCtx):
    """CSR/COO x dense-matrix via segment-sum (trace-safe)."""
    prod = b["a"][:, None] * b["dense"][b["colidx"]]
    return jax.ops.segment_sum(prod, _row_ids(b), num_segments=b["rows"])


def _spmm_bcsr_host(b: Binding, ctx: CallCtx, *, bcsr):
    """Marshaled CSR->BCSR repack + block SpMM (cuSPARSE csrmm analogue;
    on TPU this is the bsr_spmm Pallas kernel's home case)."""
    from repro.sparse.ops import bcsr_spmm_ref

    dense = b["dense"]
    pad = bcsr.shape[1] - dense.shape[0]
    if pad > 0:
        dense = jnp.pad(dense, ((0, pad), (0, 0)))
    return bcsr_spmm_ref(bcsr, dense)[: b["rows"]]


def _binding_to_csr_spmm(b: Binding):
    """Like _binding_to_csr but the column count comes from the dense
    operand's leading dim (the paper's Fig. 9 `cols` invariant)."""
    bb = dict(b)
    bb["iv"] = jnp.zeros((int(b["dense"].shape[0]),))
    return _binding_to_csr(bb)


def _dot_jnp(b: Binding, ctx: CallCtx):
    return jnp.dot(b["a"], b["b"])


def _gemv_jnp(b: Binding, ctx: CallCtx):
    return b["mat"] @ b["vec"]


def _moe_capacity(b: Binding, ctx: CallCtx, capacity_factor: float = 2.0):
    """Sorted capacity-bucket dispatch: compute only routed tokens.

    Naive dense-dispatch FLOPs  ~ E * T * (3 D F)
    This implementation        ~ E * C * (3 D F), C = ceil(T*K/E * cf)
    -> compute reduction E/(K*cf): 4x (olmoe) to 2.5x (granite-moe).
    """
    x, gate, idx = b["x"], b["gate"], b["idx"]
    wg, wu, wd = b["wg"], b["wu"], b["wd"]
    T, K = idx.shape
    E = b["experts"]
    C = int(np.ceil(T * K / E * capacity_factor))
    C = max(8, min(C, T * K))
    flat_e = idx.reshape(-1)                                    # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)      # (T*K,)
    flat_g = gate.reshape(-1)
    # a pair routed to an expert outside [0, E) (held elsewhere) takes no
    # slot and adds nothing
    held = (flat_e >= 0) & (flat_e < E)
    flat_e = jnp.where(held, flat_e, 0)
    # position of each routed pair within its expert queue
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32) * held[:, None]
    pos = (jnp.cumsum(onehot, axis=0) - onehot)[jnp.arange(T * K), flat_e]
    keep = held & (pos < C)
    slot = jnp.where(keep, flat_e * C + pos, E * C)             # overflow -> drop
    # gather tokens into (E*C+1, D) buckets
    xb = jnp.zeros((E * C + 1, x.shape[1]), x.dtype).at[slot].set(x[flat_t])
    xb = xb[:-1].reshape(E, C, x.shape[1])
    g = jnp.einsum("ecd,edf->ecf", xb, wg)
    u = jnp.einsum("ecd,edf->ecf", xb, wu)
    h = jax.nn.silu(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, wd).reshape(E * C, -1)
    y = jnp.concatenate([y, jnp.zeros((1, y.shape[1]), y.dtype)])
    out = jax.ops.segment_sum(
        y[jnp.where(keep, slot, E * C)] * flat_g[:, None],
        flat_t, num_segments=T)
    return out.astype(x.dtype)


def _spmv_csr_bwd(b: Binding, ctx: CallCtx, primal, ct):
    """SpMV transpose-products for CSR/COO bindings: ``d_a`` is the
    per-nonzero product, ``d_iv`` the A^T @ ct scatter (the grad jaxpr's
    SpMVᵀ — itself a COO SpMV, re-detectable by an outer compiled grad).
    O(nnz) in both, never densifying A."""
    r = _row_ids(b)
    return {
        "a": ct[r] * b["iv"][b["colidx"]],
        "iv": jnp.zeros_like(b["iv"]).at[b["colidx"]].add(b["a"] * ct[r]),
    }


def _spmv_ell_bwd(b: Binding, ctx: CallCtx, primal, ct):
    """ELL/JDS direct-match backward: padded (val==0) slots receive the
    cotangent product like any other slot — that IS the gradient of the
    forward wrt the padded val array, matching the dense-jaxpr oracle."""
    perm = b.get("perm")
    dacc = ct if perm is None else ct[perm]
    return {
        "val": dacc[:, None] * b["vector"][b["col_ind"]],
        "vector": jnp.zeros_like(b["vector"]).at[b["col_ind"]].add(
            b["val"] * dacc[:, None]),
    }


def _spmm_csr_bwd(b: Binding, ctx: CallCtx, primal, ct):
    """BSR/CSR SpMM backward: ``d_dense = Aᵀ @ ct`` as an O(nnz·N)
    scatter, ``d_a`` the per-nonzero row-dot."""
    r = _row_ids(b)
    return {
        "a": jnp.sum(ct[r] * b["dense"][b["colidx"]], axis=-1),
        "dense": jnp.zeros_like(b["dense"]).at[b["colidx"]].add(
            b["a"][:, None] * ct[r]),
    }


def _moe_ffn_bwd(b: Binding, ctx: CallCtx, primal, ct):
    """MoE scatter-grad via capacity-bucket recomputation: the backward
    re-runs the E·C-token sorted dispatch (not the E·T dense form) and
    pulls the cotangent through it, so grads cost the same compute
    reduction as the sparse forward.  Exact whenever no token exceeds
    capacity (e.g. balanced routing); dropped tokens get zero grad, the
    standard capacity-truncation semantics."""
    def f(x, gate, wg, wu, wd):
        bb = dict(b)
        bb.update(x=x, gate=gate, wg=wg, wu=wu, wd=wd)
        return _moe_capacity(bb, ctx)

    _, pull = jax.vjp(f, b["x"], b["gate"], b["wg"], b["wu"], b["wd"])
    gx, gg, gwg, gwu, gwd = pull(ct)
    return {"x": gx, "gate": gg, "wg": gwg, "wu": gwu, "wd": gwd}


#: Builtin backward bodies for ``vjp`` clauses, keyed by the name the
#: clause cites.  ``repro.core.spec`` enters these into its VJPS registry
#: at import, so they are declarable from any HARNESS block (builtin spec
#: texts and the kernel packages alike).
BUILTIN_VJPS: Dict[str, Callable] = {
    "spmv_csr_bwd": _spmv_csr_bwd,
    "spmv_ell_bwd": _spmv_ell_bwd,
    "spmm_csr_bwd": _spmm_csr_bwd,
    "moe_ffn_bwd": _moe_ffn_bwd,
}


def _moe_dense(b: Binding, ctx: CallCtx):
    """The naive formulation itself — the paper's '-O2 baseline' harness."""
    x, gate, idx = b["x"], b["gate"], b["idx"]
    onehot = jax.nn.one_hot(idx, b["experts"], dtype=x.dtype)
    combine = jnp.einsum("tke,tk->te", onehot, gate)
    g = jnp.einsum("td,edf->etf", x, b["wg"])
    u = jnp.einsum("td,edf->etf", x, b["wu"])
    h = jax.nn.silu(g) * u
    y = jnp.einsum("etf,efd->etd", h, b["wd"])
    return jnp.einsum("te,etd->td", combine, y)


# Kernel bodies for the builtin spec texts, keyed by spec family then by
# harness name (repro.core.spec.register_builtins consumes this).
BUILTIN_BODIES: Dict[str, Dict[str, Callable]] = {
    "spmv": {
        "jnp.segment": _spmv_segment,
        "jnp.ell": _spmv_ell_host,
        "jnp.bcsr": _spmv_bcsr_host,
        "jnp.dense": _spmv_dense_host,
        "jnp.dia": _spmv_dia_host,
    },
    "spmv_padded": {"jnp.ell": _spmv_ell_direct},
    "spmm": {"jnp.segment": _spmm_segment, "jnp.bcsr": _spmm_bcsr_host},
    "dotproduct": {"jnp.dot": _dot_jnp},
    "gemv": {"jnp.dot": _gemv_jnp},
    "moe_ffn": {"jnp.capacity": _moe_capacity},
    "moe_ffn_baseline": {"dense": _moe_dense},
}
