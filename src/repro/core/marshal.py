"""LiLAC-How data plane: formats, conversion planning, invariant caching
(paper §3.3.2, §4.2, Fig. 8/9/10/14/18).

The paper tracks writes to host arrays with memory protection so that
device transfers and data-dependent invariants (`cols`, SparseX tuning,
format conversions) are recomputed only when the underlying memory changed.
JAX arrays are immutable, so "did this memory change?" becomes "is this the
same value?", answered with content fingerprints at the harness call
boundary.

Beyond the fingerprint cache, this module makes storage formats first-class
(Rietveld & Wijshoff: data-structure selection belongs to the compiler) and
plans *conversion paths* over a cost-weighted graph (Linnea-style planning
over call sequences instead of greedy local choices):

* ``fingerprint(arr)`` — cheap content hash (full bytes below a threshold,
  strided sample + shape/dtype above it; ``exact=True`` forces full bytes).
* ``SparseFormat`` / ``FORMATS`` — the format registry (dense, COO, CSR,
  ELL and BCSR variants, JDS, DIA) that marshal clauses refer to by name.
* ``ConversionGraph`` / ``GRAPH`` — edges are value-level repack functions
  with measured (EWMA) costs; ``plan`` picks the cheapest path from any
  already-cached intermediate to the requested target format.
* ``MarshalingCache`` — memoizes INPUT-derived values keyed on the
  fingerprints of their source arrays, with cost-aware LRU eviction;
  counts hits/misses/bytes-avoided for the Fig. 18 experiment.
* ``DataPlane`` — the shared plan-level cache: harnesses declare
  ``marshal x = repack(keys) from SRC to DST`` and ``ensure`` walks the
  conversion graph, so two harnesses targeting the same format share one
  cached buffer and a CSR->BCSR repack can ride an already-cached DENSE
  intermediate.
* ``MarshalPolicy`` — per-compile knobs (``CompileOptions.marshal_policy``):
  declared call frequency for repack amortization (what the autotuner folds
  into winner selection), cache capacity, device residency, exactness.
* ``ReadObject`` — the paper's Fig. 14 template: construct / update /
  destruct driven by fingerprint changes instead of mprotect faults.
* ``TrackedArray`` — optional explicit-version wrapper for apps that mutate
  matrices functionally; version bumps replace hashing entirely (zero
  overhead, the closest analogue to a clean mprotect page table).
"""
from __future__ import annotations

import dataclasses
import hashlib
import heapq
import itertools
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import spans

_SMALL = 1 << 16  # full-hash threshold in bytes

_MISSING = object()


def fingerprint(arr: Any, exact: bool = False) -> Tuple:
    """Content fingerprint of an array (or scalar / TrackedArray)."""
    if isinstance(arr, TrackedArray):
        return version_token(arr)   # THE O(1) token rule, defined once
    if isinstance(arr, (int, float, bool)):
        return ("scalar", arr)
    a = np.asarray(arr)
    meta = (a.shape, str(a.dtype))
    if exact or a.nbytes <= _SMALL:
        digest = hashlib.blake2b(a.tobytes(), digest_size=16).hexdigest()
        return ("full", meta, digest)
    # strided sample + edges: cheap, catches structural changes; apps that
    # need exactness use TrackedArray or exact=True.
    flat = a.reshape(-1)
    step = max(1, flat.shape[0] // 1024)
    sample = np.concatenate([flat[::step][:1024], flat[:64], flat[-64:]])
    digest = hashlib.blake2b(sample.tobytes(), digest_size=16).hexdigest()
    return ("sampled", meta, digest)


class TrackedArray:
    """Explicit-version wrapper: functional updates bump the version, so
    fingerprinting is O(1).  ``arr`` is the current value."""

    def __init__(self, arr, base_token: Optional[object] = None, version: int = 0):
        self.arr = arr
        self.base_token = base_token if base_token is not None else object()
        self.version = version

    def replace(self, new_arr) -> "TrackedArray":
        return TrackedArray(new_arr, self.base_token, self.version + 1)

    def __repr__(self):
        return f"TrackedArray(v{self.version}, {getattr(self.arr, 'shape', ())})"


def unwrap(x):
    return x.arr if isinstance(x, TrackedArray) else x


def version_token(x) -> Tuple:
    """O(1) change token for executable-plan guards (``repro.core.plan``):
    a TrackedArray yields its (base-token id, version) pair — a functional
    update bumps it — while plain (immutable) arrays yield their object
    identity, which proves content identity for jax arrays.  Unlike
    :func:`fingerprint`, no bytes are ever read."""
    if isinstance(x, TrackedArray):
        return ("tracked", id(x.base_token), x.version)
    return ("id", id(x))


def nbytes_of(x) -> int:
    """Size of an array-like WITHOUT materializing it: reads ``nbytes`` or
    shape/dtype metadata only, so a cache hit on a device array never
    forces a device->host transfer (the Fig. 18 stats used to)."""
    x = unwrap(x)
    if isinstance(x, (int, float, bool)) or x is None:
        return 0
    nb = getattr(x, "nbytes", None)
    if nb is not None:
        return int(nb)
    shape = getattr(x, "shape", None)
    if shape is None:
        aval = getattr(x, "aval", None)
        shape = getattr(aval, "shape", None)
        if shape is None:
            return 0
        x = aval
    try:
        itemsize = np.dtype(getattr(x, "dtype", np.float32)).itemsize
    except TypeError:
        itemsize = 4
    return int(np.prod(shape)) * itemsize if len(shape) else itemsize


def _produced(sp: spans.span, val):
    """The bytes of a conversion's product, on its ``lilac.marshal`` span
    and the ``lilac.marshal_bytes`` counter."""
    import jax
    n = sum(nbytes_of(x) for x in jax.tree_util.tree_leaves(val))
    sp.set(bytes=n)
    spans.count("lilac.marshal_bytes", n)


def tree_nbytes(val) -> int:
    """``nbytes_of`` summed over a container of arrays (marshaled values
    are often tuples of buffers — ELL/BCSR packs)."""
    if isinstance(val, (tuple, list)):
        return sum(tree_nbytes(v) for v in val)
    if isinstance(val, dict):
        return sum(tree_nbytes(v) for v in val.values())
    return nbytes_of(val)


# ---------------------------------------------------------------------------
# Format registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SparseFormat:
    """A first-class storage format marshal clauses can name.

    ``device_resident`` formats keep their cached buffers as device arrays
    (persistent across calls — the paper's "maintain state between calls"),
    host formats stay as numpy/python values.
    """
    name: str
    description: str = ""
    device_resident: bool = True


FORMATS: Dict[str, SparseFormat] = {}


def register_format(fmt: SparseFormat, override: bool = False) -> SparseFormat:
    if fmt.name in FORMATS and FORMATS[fmt.name] != fmt and not override:
        raise ValueError(f"format {fmt.name!r} already registered")
    FORMATS[fmt.name] = fmt
    return fmt


# Built-in format vocabulary (repro.sparse.formats containers + variants).
for _f in (
    SparseFormat("CSR", "val/col_ind/row_ptr (paper Fig. 4)"),
    SparseFormat("COO", "val/row/col triplets"),
    SparseFormat("DENSE", "densified matrix"),
    SparseFormat("ELL8", "row-padded slabs, lane=8 (VPU sublane)"),
    SparseFormat("ELL128", "row-padded slabs, lane=128 (TPU lane)"),
    SparseFormat("BCSR8x128", "block CSR, (8,128) VPU tiles"),
    SparseFormat("BCSR128x128", "block CSR, (128,128) MXU tiles"),
    SparseFormat("JDS", "jagged diagonal storage (paper Fig. 5)"),
    SparseFormat("DIA", "diagonal storage: one row-length slab per stored "
                        "diagonal"),
):
    register_format(_f)


# ---------------------------------------------------------------------------
# Conversion graph
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ConversionEdge:
    """One value-level repack ``src-format value -> dst-format value`` with
    a measured cost (EWMA of observed seconds; ``est_cost`` is the prior
    used before the first measurement)."""
    src: str
    dst: str
    fn: Callable[[Any], Any]
    name: str
    est_cost: float = 1.0
    measured: Optional[float] = None
    runs: int = 0

    def cost(self) -> float:
        return self.measured if self.measured is not None else self.est_cost

    def run(self, value) -> Tuple[Any, float]:
        t0 = time.perf_counter()
        out = self.fn(value)
        dt = time.perf_counter() - t0
        self.measured = dt if self.measured is None \
            else 0.7 * self.measured + 0.3 * dt
        self.runs += 1
        return out, dt


class ConversionGraph:
    """Cost-weighted directed graph over format names.  The planner picks
    the cheapest conversion *path* — possibly through an intermediate
    format that is already cached (Linnea-style: global plan over a space
    of conversion sequences, not a greedy single hop)."""

    def __init__(self):
        self._edges: Dict[str, List[ConversionEdge]] = {}

    def add(self, edge: ConversionEdge, override: bool = False) -> ConversionEdge:
        outs = self._edges.setdefault(edge.src, [])
        for i, e in enumerate(outs):
            if e.dst == edge.dst:
                if not override:
                    raise ValueError(
                        f"edge {edge.src}->{edge.dst} already registered")
                outs[i] = edge
                return edge
        outs.append(edge)
        return edge

    def edges(self) -> List[ConversionEdge]:
        return [e for outs in self._edges.values() for e in outs]

    def edges_from(self, src: str) -> List[ConversionEdge]:
        return list(self._edges.get(src, []))

    def plan(self, starts: Dict[str, float], dst: str
             ) -> Optional[Tuple[str, List[ConversionEdge], float]]:
        """Dijkstra from a set of start formats (each with an entry cost —
        0.0 for cached intermediates, the loader estimate for the source)
        to ``dst``.  Returns (chosen start, edge path, total cost)."""
        if dst in starts:
            return dst, [], starts[dst]
        best: Dict[str, float] = dict(starts)
        back: Dict[str, Tuple[Optional[str], Optional[ConversionEdge]]] = {
            s: (None, None) for s in starts}
        counter = itertools.count()
        heap = [(c, next(counter), s) for s, c in starts.items()]
        heapq.heapify(heap)
        seen = set()
        while heap:
            cost, _, node = heapq.heappop(heap)
            if node in seen:
                continue
            seen.add(node)
            if node == dst:
                break
            for e in self._edges.get(node, []):
                nc = cost + max(e.cost(), 0.0)
                if e.dst not in best or nc < best[e.dst]:
                    best[e.dst] = nc
                    back[e.dst] = (node, e)
                    heapq.heappush(heap, (nc, next(counter), e.dst))
        if dst not in back:
            return None
        path: List[ConversionEdge] = []
        node = dst
        while True:
            prev, edge = back[node]
            if edge is None:
                start = node
                break
            path.append(edge)
            node = prev
        path.reverse()
        return start, path, best[dst]

    def full_path_cost(self, src_fmt: str, dst: str,
                      entry_cost: float = 0.0) -> Optional[float]:
        """Cheapest-path cost src->dst from measured/estimated edge costs,
        ignoring cached intermediates (the deterministic, sharing-independent
        repack cost the autotuner amortizes)."""
        plan = self.plan({src_fmt: entry_cost}, dst)
        return None if plan is None else plan[2]

    def plan_cost(self, start_states: Dict[str, float], target: str
                  ) -> Optional[Tuple[float, Tuple[str, ...]]]:
        """Side-effect-free path costing for the joint plan optimizer
        (``repro.core.plan_search``): cheapest cost from any start format
        (each carrying its entry cost — 0.0 for an intermediate another
        assignment already builds) to ``target``, plus the formats the
        winning path would materialize along the way.  No edges run, no
        EWMAs update — this is the cost ORACLE, not the executor."""
        plan = self.plan(dict(start_states), target)
        if plan is None:
            return None
        start, path, cost = plan
        return cost, (start,) + tuple(e.dst for e in path)


GRAPH = ConversionGraph()


def edge(src: str, dst: str, *, name: Optional[str] = None,
         est_cost: float = 1.0, graph: Optional[ConversionGraph] = None,
         override: bool = False):
    """Decorator: register a value-level conversion as a graph edge."""
    def deco(fn):
        (graph or GRAPH).add(
            ConversionEdge(src, dst, fn, name or f"{src}->{dst}",
                           est_cost=est_cost), override=override)
        return fn
    return deco


# Binding loaders: how a marshal clause's *source* format is materialized
# from a harness binding.  Keyed by the clause's ``from`` name; the value
# is (produced format, fn, cost EWMA holder).
@dataclasses.dataclass
class SourceLoader:
    name: str
    fmt: str
    fn: Callable[[Dict[str, Any]], Any]
    measured: Optional[float] = None

    def cost(self) -> float:
        return self.measured if self.measured is not None else 0.1

    def run(self, binding) -> Tuple[Any, float]:
        t0 = time.perf_counter()
        out = self.fn(binding)
        dt = time.perf_counter() - t0
        self.measured = dt if self.measured is None \
            else 0.7 * self.measured + 0.3 * dt
        return out, dt


SOURCES: Dict[str, SourceLoader] = {}


def register_source(name: str, fmt: str, fn: Callable, override: bool = False
                    ) -> SourceLoader:
    if fmt not in FORMATS:
        raise ValueError(f"source {name!r} produces unknown format {fmt!r}")
    if name in SOURCES and not override:
        raise ValueError(f"source loader {name!r} already registered")
    loader = SourceLoader(name, fmt, fn)
    SOURCES[name] = loader
    return loader


# ---------------------------------------------------------------------------
# Policy + stats
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MarshalPolicy:
    """Knobs for the data plane (``CompileOptions.marshal_policy``).

    ``reuse``   declared call frequency: expected harness calls per matrix
                change.  The autotuner folds repack cost in at this rate
                (steady-state amortized cost = kernel + marshal/reuse).
    ``max_entries``      plan-cache capacity (cost-aware LRU beyond it).
    ``device_resident``  keep cached buffers as device arrays.
    ``exact``            exact fingerprints (no sampling) for cache keys.
    ``enabled``          False disables caching entirely (every call
                         repacks — the paper's "naive library call").
    """
    reuse: float = 100.0
    max_entries: int = 64
    device_resident: bool = True
    exact: bool = False
    enabled: bool = True

    @staticmethod
    def parse(val) -> "MarshalPolicy":
        if val is None:
            return MarshalPolicy()
        if isinstance(val, MarshalPolicy):
            return val
        if isinstance(val, str):
            if val in ("shared", "default", "on"):
                return MarshalPolicy()
            if val in ("off", "none", "disabled"):
                return MarshalPolicy(enabled=False)
            if val == "exact":
                return MarshalPolicy(exact=True)
            raise ValueError(f"unknown marshal_policy {val!r} "
                             "(use 'shared' | 'off' | 'exact' or a "
                             "MarshalPolicy instance)")
        raise TypeError(f"marshal_policy must be str or MarshalPolicy, "
                        f"got {type(val).__name__}")


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    bytes_avoided: int = 0
    recompute_seconds_avoided: float = 0.0
    edge_runs: int = 0          # conversion-graph edges executed
    loader_runs: int = 0        # binding->format source loads executed
    shared_edge_hits: int = 0   # planned paths that started from a cached
                                # intermediate instead of the binding
    evictions: int = 0

    def reset(self):
        self.hits = self.misses = self.bytes_avoided = 0
        self.recompute_seconds_avoided = 0.0
        self.edge_runs = self.loader_runs = self.shared_edge_hits = 0
        self.evictions = 0


@dataclasses.dataclass
class PlanStats:
    """Per-(source, target-format) cache accounting, surfaced by Fig. 18."""
    src: str
    dst: str
    hits: int = 0
    misses: int = 0
    bytes_avoided: int = 0
    seconds_avoided: float = 0.0
    build_seconds: float = 0.0
    last_path: Tuple[str, ...] = ()
    shared_prefix_hits: int = 0
    # joint-search observability: how often (and how many bytes' worth) a
    # planned path entered at an intermediate another plan already built —
    # the cost-0 sharing assumption plan_search's model relies on
    rides: int = 0
    shared_prefix_bytes: int = 0

    def as_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["last_path"] = list(self.last_path)
        return d


# ---------------------------------------------------------------------------
# The caches
# ---------------------------------------------------------------------------

class MarshalingCache:
    """Memoizes marshaled INPUTs (paper Fig. 8/9/10): format conversions,
    derived invariants, device-resident buffers.

    Eviction is cost-aware LRU: entries are kept in recency order (a hit
    refreshes), and when capacity is exceeded the *cheapest-to-recompute*
    entry among the least-recently-used window is dropped — a hot or
    expensive repack survives churn that a FIFO would evict it under.
    """

    #: how many LRU-tail entries compete on recompute cost at eviction
    EVICT_WINDOW = 8

    def __init__(self, exact: bool = False, max_entries: int = 64):
        self.exact = exact
        self.max_entries = max_entries
        self._store: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._cost: Dict[Tuple, float] = {}
        self._spec_cost: Dict[str, float] = {}   # repack name -> last seconds
        self.stats = CacheStats()

    def _key(self, spec_name: str, key_arrays: Sequence) -> Tuple:
        return (spec_name,) + tuple(
            fingerprint(a, self.exact) for a in key_arrays)

    def _hit(self, key: Tuple, key_arrays: Sequence):
        self._store.move_to_end(key)
        self.stats.hits += 1
        self.stats.bytes_avoided += sum(nbytes_of(a) for a in key_arrays)
        self.stats.recompute_seconds_avoided += self._cost.get(key, 0.0)

    def _evict(self):
        while len(self._store) > self.max_entries:
            # candidates come from the LRU head; the most-recently-used
            # entry is never eligible, so a just-inserted value cannot be
            # evicted out from under its own insert
            window = min(self.EVICT_WINDOW, len(self._store) - 1)
            tail = list(itertools.islice(iter(self._store), window))
            victim = min(tail, key=lambda k: self._cost.get(k, 0.0))
            self._store.pop(victim)
            self._cost.pop(victim, None)
            self.stats.evictions += 1

    def _insert(self, key: Tuple, val: Any, cost: float):
        self._store[key] = val
        self._store.move_to_end(key)
        self._cost[key] = cost
        self._evict()

    def get(self, spec_name: str, key_arrays: Tuple, compute: Callable[[], Any]):
        """Return cached value for ``spec_name`` derived from ``key_arrays``;
        recompute only if any source array changed (the mprotect analogue)."""
        key = self._key(spec_name, key_arrays)
        val = self._store.get(key, _MISSING)
        if val is not _MISSING:
            self._hit(key, key_arrays)
            return val
        self.stats.misses += 1
        from repro.core import faults
        if faults.ACTIVE is not None:
            faults.fail("marshal_raise", spec_name)
        with spans.span("lilac.marshal") as sp:
            t0 = time.perf_counter()
            val = compute()
            cost = time.perf_counter() - t0
            _produced(sp, val)
        self._spec_cost[spec_name] = cost
        self._insert(key, val, cost)
        return val

    def marshal_seconds(self, repack_names: Sequence[str]) -> float:
        """Last measured repack seconds for the named repacks (0.0 when a
        repack has not run through this cache) — what the autotuner folds
        into winner selection for legacy (format-less) marshal clauses."""
        return sum(self._spec_cost.get(n, 0.0) for n in repack_names)

    def estimate_marshal_seconds(self, clauses: Sequence[Any]) -> float:
        """Cold-repack cost estimate for a harness's marshal clauses."""
        return self.marshal_seconds(
            [getattr(cl, "repack", cl) for cl in clauses])

    def clear(self):
        self._store.clear()
        self._cost.clear()


class DataPlane(MarshalingCache):
    """The shared plan-level cache: format-aware marshaling over the
    conversion graph.

    ``ensure(src, dst, key_arrays, binding)`` materializes the ``dst``
    format for the matrix identified by ``key_arrays``' fingerprints:

    1. plan-cache hit -> return the persistent (device-resident) buffer;
    2. otherwise plan the cheapest conversion path over ``graph`` starting
       from any already-cached intermediate of the same matrix (cost 0) or
       from the binding loader, execute the remaining edges, and cache
       every intermediate produced — so a later harness targeting another
       format downstream of the same intermediates rides them for free.

    One ``ensure`` call counts as ONE hit or miss in ``stats`` (edge and
    loader executions are tracked separately), keeping hit/miss semantics
    identical to the legacy per-repack cache.
    """

    def __init__(self, policy: Optional[MarshalPolicy] = None,
                 graph: Optional[ConversionGraph] = None,
                 exact: Optional[bool] = None,
                 max_entries: Optional[int] = None):
        policy = policy or MarshalPolicy()
        super().__init__(
            exact=policy.exact if exact is None else exact,
            max_entries=policy.max_entries if max_entries is None
            else max_entries)
        self.policy = policy
        self.graph = graph or GRAPH
        self.plans: Dict[Tuple[str, str], PlanStats] = {}

    # -- plumbing ------------------------------------------------------------

    def _node_key(self, src: str, fmt: str, fps: Tuple) -> Tuple:
        return ("node", src, fmt) + fps

    def _plan_stats(self, src: str, dst: str) -> PlanStats:
        ps = self.plans.get((src, dst))
        if ps is None:
            ps = self.plans[(src, dst)] = PlanStats(src, dst)
        return ps

    def _maybe_device(self, fmt: str, val):
        if not self.policy.device_resident:
            return val
        f = FORMATS.get(fmt)
        if f is not None and not f.device_resident:
            return val
        try:
            import jax
            import jax.numpy as jnp
            return jax.tree_util.tree_map(jnp.asarray, val)
        except Exception:
            return val

    # -- the planner ---------------------------------------------------------

    def ensure(self, src: str, dst: str, key_arrays: Sequence,
               binding: Dict[str, Any],
               fallback: Optional[Callable[[], Any]] = None):
        """Materialize format ``dst`` for the matrix identified by the
        fingerprints of ``key_arrays``, via the cheapest conversion path.
        ``fallback`` (the clause's legacy repack) runs when no path exists."""
        loader = SOURCES.get(src)
        if loader is None or dst not in FORMATS:
            if fallback is None:
                raise KeyError(f"unknown marshal source {src!r} or "
                               f"format {dst!r} and no fallback repack")
            return self.get(f"{src}->{dst}", tuple(key_arrays), fallback)

        fps = tuple(fingerprint(a, self.exact) for a in key_arrays)
        key = self._node_key(src, dst, fps)
        ps = self._plan_stats(src, dst)
        val = self._store.get(key, _MISSING)
        if val is not _MISSING:
            self._hit(key, key_arrays)
            ps.hits += 1
            ps.bytes_avoided += sum(nbytes_of(a) for a in key_arrays)
            ps.seconds_avoided += self._cost.get(key, 0.0)
            return val

        self.stats.misses += 1
        ps.misses += 1
        from repro.core import faults
        if faults.ACTIVE is not None:
            faults.fail("marshal_raise", f"{src}->{dst}")

        # start set: cached intermediates of the SAME matrix (cost 0) plus
        # the binding loader at its measured cost
        starts: Dict[str, float] = {}
        cached_vals: Dict[str, Tuple] = {}
        for k in self._store:
            if (isinstance(k, tuple) and len(k) == 3 + len(fps)
                    and k[0] == "node" and k[1] == src and k[3:] == fps):
                starts[k[2]] = 0.0
                cached_vals[k[2]] = k
        loader_start = loader.fmt not in starts
        if loader_start:
            starts.setdefault(loader.fmt, loader.cost())

        plan = self.graph.plan(starts, dst)
        if plan is None:
            if fallback is None:
                raise KeyError(f"no conversion path {src}({loader.fmt})"
                               f"->{dst} and no fallback repack")
            with spans.span("lilac.marshal") as sp:
                t0 = time.perf_counter()
                val = fallback()
                cost = time.perf_counter() - t0
                val = self._maybe_device(dst, val)
                _produced(sp, val)
            self._spec_cost[f"{src}->{dst}"] = cost
            ps.build_seconds += cost
            ps.last_path = (f"{src}!fallback", dst)
            self._insert(key, val, cost)
            return val

        start_fmt, path, _ = plan
        paid = 0.0
        path_names = [start_fmt] + [e.dst for e in path]
        with spans.span("lilac.marshal") as sp:
            if start_fmt in cached_vals:
                # ride an already-cached intermediate (possibly built for a
                # DIFFERENT harness) — the plan-level sharing win
                val = self._store[cached_vals[start_fmt]]
                self._store.move_to_end(cached_vals[start_fmt])
                self.stats.shared_edge_hits += 1
                ps.shared_prefix_hits += 1
                ps.rides += 1
                ps.shared_prefix_bytes += tree_nbytes(val)
            else:
                val, dt = loader.run(binding)
                paid += dt
                self.stats.loader_runs += 1
                val = self._maybe_device(start_fmt, val)
                self._insert(self._node_key(src, start_fmt, fps), val, paid)
            for e in path:
                val, dt = e.run(val)
                paid += dt
                self.stats.edge_runs += 1
                val = self._maybe_device(e.dst, val)
                # cache every intermediate: cost = cumulative seconds paid
                # to produce it in THIS ensure (what a hit on it will avoid)
                self._insert(self._node_key(src, e.dst, fps), val, paid)
            _produced(sp, val)
        ps.build_seconds += paid
        ps.last_path = tuple(path_names)
        return val

    # -- autotuner interface -------------------------------------------------

    def estimate_marshal_seconds(self, clauses: Sequence[Any]) -> float:
        """Steady-state repack cost of a harness's marshal clauses: the
        cheapest full conversion path from the binding (measured EWMA edge
        costs; sharing-independent so tuning decisions are stable).  Legacy
        clauses without formats fall back to their last measured cost."""
        total = 0.0
        for cl in clauses:
            src = getattr(cl, "src", None)
            dst = getattr(cl, "dst", None)
            if src and dst and src in SOURCES and dst in FORMATS:
                loader = SOURCES[src]
                c = self.graph.full_path_cost(loader.fmt, dst,
                                             entry_cost=loader.cost())
                if c is not None:
                    total += c
                    continue
                # no graph path: ensure() served this clause via its
                # fallback repack and recorded the cost under "src->dst"
                fb = self._spec_cost.get(f"{src}->{dst}")
                if fb is not None:
                    total += fb
                    continue
            total += self._spec_cost.get(getattr(cl, "repack", str(cl)), 0.0)
        return total

    def plan_stats(self) -> Dict[str, Dict[str, Any]]:
        """Per-plan accounting for benchmarks: '{src}->{dst}' -> stats."""
        return {f"{src}->{dst}": ps.as_dict()
                for (src, dst), ps in sorted(self.plans.items())}


class ReadObject:
    """Paper Fig. 14: specializes (construct, update, destruct) with change
    tracking.  ``construct`` runs before first use and when shape changes;
    ``update`` when content changes; ``destruct`` on release."""

    def __init__(self, construct: Callable, update: Callable,
                 destruct: Optional[Callable] = None, exact: bool = False):
        self.construct = construct
        self.update = update
        self.destruct = destruct
        self.exact = exact
        self._state: Optional[Any] = None
        self._fp: Optional[Tuple] = None
        self._shape: Optional[Tuple] = None

    def read(self, arr):
        fp = fingerprint(arr, self.exact)
        shape = tuple(np.asarray(unwrap(arr)).shape)
        if self._state is None or shape != self._shape:
            if self._state is not None and self.destruct is not None:
                self.destruct(self._state)
            self._state = self.construct(unwrap(arr))
            self._fp, self._shape = fp, shape
        elif fp != self._fp:
            self._state = self.update(unwrap(arr), self._state)
            self._fp = fp
        return self._state

    def release(self):
        if self._state is not None and self.destruct is not None:
            self.destruct(self._state)
        self._state = self._fp = self._shape = None
