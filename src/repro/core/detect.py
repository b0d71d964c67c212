"""LiLAC detection: backtracking search for What-computations in jaxprs.

The paper (§4.1) detects computations in LLVM IR after -O2 normalization:
first the control-flow skeleton is recognized, then a backtracking search
(Fig. 13) assigns the What-program's expressions to IR values one by one.

The JAX adaptation:

* Normalization  — JAX tracing is the language-independent normalizer
  (Fig. 11/12 analogue); on top of it we inline nested call primitives
  (pjit / custom_jvp / remat) so the matcher sees one flat equation list
  (`normalize_closed_jaxpr`).
* Skeletons      — vectorized JAX has two kinds of "loop nest": the batched
  dimension structure of gather/mul/scatter-add/reduce chains, and actual
  `scan` bodies for loop-style user code.  Both are matched.
* Backtracking   — pattern matching is generator-based: every commutative
  operand order, alternative idiom and candidate assignment is a backtrack
  point; the first complete, semantically validated assignment wins.
* Semantic validation — where the paper relies on exact structural match,
  we additionally *execute* risky sub-graphs (row-pointer expansion, one-hot
  dispatch construction) on random concrete inputs via eval_jaxpr and check
  them against the What-semantics.  A structural false positive therefore
  cannot silently corrupt results.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import core as jcore
from jax.extend import core as jex_core

from repro.core import what_lang as W

Atom = Any   # jex_core.Var | jex_core.Literal
Eqn = Any    # jex_core.JaxprEqn


# ---------------------------------------------------------------------------
# Normalization (the -O2 analogue): inline call-like primitives.
# ---------------------------------------------------------------------------

_INLINE_PRIMS = {
    "jit": lambda p: (p["jaxpr"].jaxpr, p["jaxpr"].consts),
    "pjit": lambda p: (p["jaxpr"].jaxpr, p["jaxpr"].consts),
    "custom_jvp_call": lambda p: (p["call_jaxpr"].jaxpr, p["call_jaxpr"].consts),
    "custom_vjp_call": lambda p: (p["call_jaxpr"].jaxpr, p["call_jaxpr"].consts),
    "remat2": lambda p: (p["jaxpr"], ()),
    "checkpoint": lambda p: (p["jaxpr"], ()),
    "closed_call": lambda p: (p["call_jaxpr"].jaxpr, p["call_jaxpr"].consts),
}


def _inlinable(eqn: Eqn):
    fn = _INLINE_PRIMS.get(eqn.primitive.name)
    if fn is None:
        return None
    try:
        return fn(eqn.params)
    except (KeyError, AttributeError):
        return None


def normalize_closed_jaxpr(cj) -> "jex_core.ClosedJaxpr":
    """Inline nested call primitives into one flat equation list."""
    gen = jcore.gensym()
    out_eqns: List[Eqn] = []
    const_vars: List[Any] = []
    const_vals: List[Any] = []

    def emit(jaxpr, consts, in_atoms):
        env: Dict[Any, Atom] = {}

        def read(atom):
            if isinstance(atom, jex_core.Literal):
                return atom
            return env[atom]

        for cv, cval in zip(jaxpr.constvars, consts):
            v = gen(cv.aval)
            const_vars.append(v)
            const_vals.append(cval)
            env[cv] = v
        for iv, at in zip(jaxpr.invars, in_atoms):
            env[iv] = at
        for eqn in jaxpr.eqns:
            sub = _inlinable(eqn)
            if sub is not None:
                inner, iconsts = sub
                outs = emit(inner, iconsts, [read(x) for x in eqn.invars])
                for ov, o in zip(eqn.outvars, outs):
                    env[ov] = o
            else:
                new_in = [read(x) for x in eqn.invars]
                new_out = [gen(ov.aval) for ov in eqn.outvars]
                out_eqns.append(eqn.replace(invars=new_in, outvars=new_out))
                for ov, nv in zip(eqn.outvars, new_out):
                    env[ov] = nv
        return [read(x) for x in jaxpr.outvars]

    new_invars = [gen(v.aval) for v in cj.jaxpr.invars]
    outs = emit(cj.jaxpr, cj.consts, new_invars)
    # Jaxpr outvars must be atoms; literals are permitted.
    new_jaxpr = jex_core.Jaxpr(
        constvars=const_vars, invars=new_invars, outvars=outs, eqns=out_eqns,
        debug_info=cj.jaxpr.debug_info,
    )
    return jex_core.ClosedJaxpr(new_jaxpr, const_vals)


# ---------------------------------------------------------------------------
# Match context: producer maps, peeling, provenance.
# ---------------------------------------------------------------------------

class Ctx:
    def __init__(self, closed: "jex_core.ClosedJaxpr"):
        self.closed = closed
        self.jaxpr = closed.jaxpr
        self.producer: Dict[Any, Eqn] = {}
        self.eqn_index: Dict[int, int] = {}
        self.consumers: Dict[Any, List[Eqn]] = {}
        for i, eqn in enumerate(self.jaxpr.eqns):
            self.eqn_index[id(eqn)] = i
            for ov in eqn.outvars:
                self.producer[ov] = eqn
            seen_here = set()
            for iv in eqn.invars:
                if isinstance(iv, jex_core.Literal) or id(iv) in seen_here:
                    continue
                seen_here.add(id(iv))
                self.consumers.setdefault(iv, []).append(eqn)
        self.invars = set(self.jaxpr.invars)
        self.outvars = {v for v in self.jaxpr.outvars
                        if not isinstance(v, jex_core.Literal)}
        self.constvar_vals = dict(zip(self.jaxpr.constvars, closed.consts))
        self.log: List[str] = []
        # Per-atom memoization: detection runs every matcher over every
        # anchor, so the same peel chains and provenance closures are
        # requested many times per jaxpr.  Keyed on id() — the atoms are
        # owned by self.jaxpr, which we hold, so ids are stable.
        self._peel_cache: Dict[int, Atom] = {}
        self._prov_cache: Dict[Tuple, Tuple[List[Any], List[Eqn]]] = {}
        self._subjaxpr_cache: Dict[Tuple, Any] = {}
        # semantic-validation verdicts, keyed by the validator on the
        # participating atom ids: identical subgraphs reached through
        # different patterns validate once (and reuse one sampled input
        # set) instead of re-executing per candidate
        self.validation_cache: Dict[Tuple, bool] = {}

    def prod(self, atom) -> Optional[Eqn]:
        if isinstance(atom, jex_core.Literal):
            return None
        return self.producer.get(atom)

    def sole_consumer(self, var) -> Optional[Eqn]:
        """The unique consuming equation of ``var``, or None when the value
        is multiply-consumed or escapes as a function output (fusing it
        away would then change observable results)."""
        if var in self.outvars:
            return None
        cons = self.consumers.get(var, [])
        return cons[0] if len(cons) == 1 else None

    # -- peeling ------------------------------------------------------------

    def peel(self, atom) -> Atom:
        """See through semantics-preserving wrappers:
        convert_element_type, copy, reshape-like broadcast_in_dim (adding a
        trailing unit dim), squeeze, and the negative-index normalization
        triple select_n(lt(x,0), x, x+N) -> x.  Memoized per atom."""
        cached = self._peel_cache.get(id(atom))
        if cached is not None:
            return cached
        visited = [atom]
        out = self._peel(atom, visited)
        for a in visited:
            self._peel_cache[id(a)] = out
        return out

    def _peel(self, atom, visited: List[Atom]) -> Atom:
        while True:
            cached = self._peel_cache.get(id(atom))
            if cached is not None:
                return cached
            if visited and visited[-1] is not atom:
                visited.append(atom)
            eqn = self.prod(atom)
            if eqn is None:
                return atom
            p = eqn.primitive.name
            if p in ("convert_element_type", "copy", "stop_gradient"):
                atom = eqn.invars[0]
                continue
            if p == "squeeze":
                atom = eqn.invars[0]
                continue
            if p == "reshape":
                src = eqn.invars[0]
                if _nonunit_dims(src.aval.shape) == _nonunit_dims(eqn.outvars[0].aval.shape):
                    atom = src
                    continue
                return atom
            if p == "broadcast_in_dim":
                src = eqn.invars[0]
                in_shape = getattr(src.aval, "shape", ())
                out_shape = eqn.outvars[0].aval.shape
                bdims = eqn.params["broadcast_dimensions"]
                # reshape-like: all input dims mapped in order, added dims unit
                if (tuple(bdims) == tuple(range(len(in_shape)))
                        and _nonunit_dims(in_shape) == _nonunit_dims(out_shape)):
                    atom = src
                    continue
                return atom
            if p == "select_n" and len(eqn.invars) == 3:
                pred, case_f, case_t = eqn.invars
                pe = self.prod(pred)
                if pe is not None and pe.primitive.name == "lt":
                    x, zero = pe.invars
                    x = self.peel(x)
                    if _literal_value(zero) == 0 and self.peel(case_f) is x:
                        te = self.prod(self.peel(case_t))
                        if te is not None and te.primitive.name == "add" \
                                and self.peel(te.invars[0]) is x:
                            atom = x
                            continue
                return atom
            return atom

    def is_zeros(self, atom) -> bool:
        atom = self.peel(atom)
        lit = _literal_value(atom)
        if lit is not None:
            return bool(np.all(np.asarray(lit) == 0))
        eqn = self.prod(atom)
        if eqn is not None and eqn.primitive.name == "broadcast_in_dim":
            return self.is_zeros(eqn.invars[0])
        if eqn is None and atom in self.constvar_vals:
            return bool(np.all(np.asarray(self.constvar_vals[atom]) == 0))
        return False

    # -- provenance ----------------------------------------------------------

    def provenance(self, atom, stop: Tuple[Atom, ...] = ()
                   ) -> Tuple[List[Any], List[Eqn]]:
        """Transitive producer closure: (leaf vars [invars/constvars, and
        the atoms in ``stop``], eqns in original topological order).
        Memoized per (atom, stop) — callers must not mutate the returned
        lists."""
        key = (id(atom),) + tuple(id(a) for a in stop)
        cached = self._prov_cache.get(key)
        if cached is not None:
            return cached
        stop_ids = {id(a) for a in stop}
        eqns: Dict[int, Eqn] = {}
        leaves: List[Any] = []
        seen = set()
        stack = [atom]
        while stack:
            a = stack.pop()
            if isinstance(a, jex_core.Literal) or id(a) in seen:
                continue
            seen.add(id(a))
            eqn = None if id(a) in stop_ids else self.prod(a)
            if eqn is None:
                if a not in leaves:
                    leaves.append(a)
                continue
            eqns[self.eqn_index[id(eqn)]] = eqn
            for iv in eqn.invars:
                stack.append(iv)
        ordered = [eqns[i] for i in sorted(eqns)]
        self._prov_cache[key] = (leaves, ordered)
        return leaves, ordered

    def eval_subgraph(self, out_atom, leaf_values: Dict[Any, np.ndarray],
                      stop: Tuple[Atom, ...] = ()):
        """Concretely evaluate the provenance subgraph of ``out_atom`` given
        values for its leaves (cut at the ``stop`` atoms) — the semantic
        validation step.  The built sub-jaxpr is cached per (atom, stop),
        so repeated validations (multiple trials, multiple candidate
        patterns over the same subgraph) only pay jaxpr construction
        once."""
        key = (id(out_atom),) + tuple(id(a) for a in stop)
        sub = self._subjaxpr_cache.get(key)
        if sub is None:
            leaves, eqns = self.provenance(out_atom, stop)
            # The parent's debug_info describes the parent's arity; newer
            # jax asserts arg_names/result_paths lengths match, so the
            # sub-jaxpr must drop it entirely.
            sub = jex_core.Jaxpr(
                constvars=(), invars=list(leaves), outvars=[out_atom],
                eqns=eqns, debug_info=None,
            )
            self._subjaxpr_cache[key] = sub
        vals = []
        for lf in sub.invars:
            if lf in leaf_values:
                vals.append(leaf_values[lf])
            elif lf in self.constvar_vals:
                vals.append(self.constvar_vals[lf])
            else:
                raise KeyError(f"no value for leaf {lf}")
        # Force concrete evaluation even when detection runs under an
        # ambient trace (jax.grad / make_jaxpr of a caller that invokes a
        # LilacFunction): all leaf values here are numpy trial inputs or
        # concrete constvars, so the binds must not be swept into the
        # outer trace — a Tracer result would fail np.asarray and make
        # semantic validation spuriously reject.
        with jax.ensure_compile_time_eval():
            (out,) = jcore.eval_jaxpr(sub, [], *vals)
        return np.asarray(out)


def _nonunit_dims(shape) -> Tuple[int, ...]:
    return tuple(d for d in shape if d != 1)


def _literal_value(atom):
    if isinstance(atom, jex_core.Literal):
        return atom.val
    return None


# ---------------------------------------------------------------------------
# Pattern combinators (generator-based backtracking — Fig. 13).
# ---------------------------------------------------------------------------

class Pat:
    def match(self, ctx: Ctx, atom, env: Dict[str, Any]) -> Iterator[Dict[str, Any]]:
        raise NotImplementedError


class B(Pat):
    """Bind the (peeled) atom to a name; if already bound, require identity.
    ``peel=False`` binds the operand exactly as the equation consumes it."""

    def __init__(self, name: str, pred: Optional[Callable] = None,
                 peel: bool = True):
        self.name = name
        self.pred = pred
        self.peel = peel

    def match(self, ctx, atom, env):
        a = ctx.peel(atom) if self.peel else atom
        if self.pred is not None and not self.pred(ctx, a):
            return
        if self.name in env:
            if env[self.name] is a:
                yield env
            return
        e2 = dict(env)
        e2[self.name] = a
        ctx.log.append(f"  bind {self.name} := {a}")
        yield e2


class AnyP(Pat):
    def match(self, ctx, atom, env):
        yield env


class P(Pat):
    """Match the producer equation of the atom."""

    def __init__(self, prims, *operands: Pat,
                 params: Optional[Callable[[Dict], bool]] = None,
                 peel: bool = True):
        self.prims = (prims,) if isinstance(prims, str) else tuple(prims)
        self.operands = operands
        self.params = params
        self.do_peel = peel

    def match(self, ctx, atom, env):
        a = ctx.peel(atom) if self.do_peel else atom
        eqn = ctx.prod(a)
        if eqn is None or eqn.primitive.name not in self.prims:
            return
        if self.params is not None and not self.params(eqn.params):
            return
        if len(eqn.invars) < len(self.operands):
            return

        def rec(i, e):
            if i == len(self.operands):
                yield e
                return
            for e2 in self.operands[i].match(ctx, eqn.invars[i], e):
                yield from rec(i + 1, e2)

        yield from rec(0, env)


class Comm(Pat):
    """Commutative binary op: try both operand orders (backtrack point)."""

    def __init__(self, prims, p1: Pat, p2: Pat):
        self.prims = (prims,) if isinstance(prims, str) else tuple(prims)
        self.p1, self.p2 = p1, p2

    def match(self, ctx, atom, env):
        a = ctx.peel(atom)
        eqn = ctx.prod(a)
        if eqn is None or eqn.primitive.name not in self.prims or len(eqn.invars) != 2:
            return
        x, y = eqn.invars
        for first, second in ((x, y), (y, x)):
            ctx.log.append(f"  try {eqn.primitive.name}({first},{second})")
            for e1 in self.p1.match(ctx, first, env):
                for e2 in self.p2.match(ctx, second, e1):
                    yield e2
            ctx.log.append("  backtrack")


class Alt(Pat):
    def __init__(self, *pats: Pat):
        self.pats = pats

    def match(self, ctx, atom, env):
        for p in self.pats:
            yield from p.match(ctx, atom, env)


class ZerosP(Pat):
    def match(self, ctx, atom, env):
        if ctx.is_zeros(atom):
            yield env


def _is_row_gather(params) -> bool:
    dn = params.get("dimension_numbers")
    return (dn is not None
            and tuple(dn.offset_dims) == ()
            and tuple(dn.collapsed_slice_dims) == (0,)
            and tuple(dn.start_index_map) == (0,)
            and tuple(params.get("slice_sizes", ())) == (1,))


def _is_row_scatter(params) -> bool:
    dn = params.get("dimension_numbers")
    return (dn is not None
            and tuple(dn.update_window_dims) == ()
            and tuple(dn.inserted_window_dims) == (0,)
            and tuple(dn.scatter_dims_to_operand_dims) == (0,))


def _is_rowwindow_scatter(params) -> bool:
    """scatter of (nnz, n) row-windows into (rows, n) — the SpMM skeleton."""
    dn = params.get("dimension_numbers")
    return (dn is not None
            and tuple(dn.update_window_dims) == (1,)
            and tuple(dn.inserted_window_dims) == (0,)
            and tuple(dn.scatter_dims_to_operand_dims) == (0,))


def _is_rowwindow_gather(params) -> bool:
    """dense[col] with dense (C, n): rows of a matrix gathered by index."""
    dn = params.get("dimension_numbers")
    ss = tuple(params.get("slice_sizes", ()))
    return (dn is not None
            and tuple(dn.offset_dims) == (1,)
            and tuple(dn.collapsed_slice_dims) == (0,)
            and tuple(dn.start_index_map) == (0,)
            and len(ss) == 2 and ss[0] == 1)


def Gather1D(arr: Pat, idx: Pat) -> Pat:
    """vec[idx] — embedding-style row gather (any index rank)."""
    return P("gather", arr, idx, params=_is_row_gather)


# ---------------------------------------------------------------------------
# Match result
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Match:
    computation: str          # What-program name
    variant: str              # 'vectorized' | 'loop'
    format: str               # CSR / COO / ELL / JDS / DOT / GEMV / MOE
    anchor: Any               # var whose producer eqn gets replaced
    anchor_eqn: Eqn
    binding: Dict[str, Any]   # What-name -> jaxpr atom or python int
    notes: str = ""
    claimed_eqns: Tuple[Any, ...] = ()  # extra eqns covered by this match
    # Detected fused epilogue covering the consumer chain of the core
    # computation: 'relu' | 'silu' (activation, possibly after a bias add
    # bound as binding['bias']) | 'none' (bias only) | None (no epilogue).
    # The anchor is then the *final* epilogue equation: harnesses declaring
    # ``fuse epilogue`` apply it in-kernel, others get it applied by the
    # rewriter — either way the intermediate arrays never materialize in
    # host mode.
    epilogue: Optional[str] = None
    # For variant='scan_body': (normalized body ClosedJaxpr, inner matches).
    # The body was detected ONCE; the rewriter reconstructs the scan around
    # a rewritten body, so the selected kernels are reused every iteration.
    body: Optional[Tuple[Any, List["Match"]]] = None

    def __repr__(self):
        names = {k: (v if isinstance(v, int) else str(v))
                 for k, v in self.binding.items()}
        ep = f" +{self.epilogue}" if self.epilogue else ""
        return (f"Match({self.computation}/{self.format} [{self.variant}]"
                f"{ep} @ {self.anchor} {names})")


@dataclasses.dataclass
class DetectionReport:
    matches: List[Match]
    n_eqns: int
    log: List[str]

    def by_computation(self) -> Dict[str, List[Match]]:
        out: Dict[str, List[Match]] = {}
        for m in self.matches:
            out.setdefault(m.computation, []).append(m)
        return out

    def summary(self) -> str:
        lines = [f"{len(self.matches)} match(es) in {self.n_eqns} equations"]
        lines += [f"  {m!r}" for m in self.matches]
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Semantic validators
# ---------------------------------------------------------------------------

def _validate_row_expansion(ctx: Ctx, row_atom, row_ptr_var, nnz: int,
                            rows: int, trials: int = 2) -> bool:
    """Check the subgraph row_ptr -> row_ids really is CSR row expansion:
    out == repeat(arange(rows), diff(row_ptr)) for random valid row_ptrs.

    Verdicts are memoized on the (row_atom, row_ptr_var) identity: every
    pattern that reaches the same expansion subgraph (CSR SpMV, SpMM, the
    COO fallback probing) shares one concrete evaluation instead of
    re-sampling and re-executing it per candidate."""
    key = ("row_expansion", id(row_atom), id(row_ptr_var), nnz, rows)
    cached = ctx.validation_cache.get(key)
    if cached is not None:
        return cached
    rng = np.random.default_rng(0)
    ok = True
    for _ in range(trials):
        cuts = np.sort(rng.integers(0, nnz + 1, size=max(rows - 1, 0)))
        rp = np.concatenate([[0], cuts, [nnz]]).astype(np.int32)
        expect = np.repeat(np.arange(rows, dtype=np.int32), np.diff(rp))
        try:
            got = ctx.eval_subgraph(row_atom, {row_ptr_var: rp})
        except Exception:
            ok = False
            break
        if got.shape != (nnz,) or not np.array_equal(got.astype(np.int64),
                                                     expect.astype(np.int64)):
            ok = False
            break
    ctx.validation_cache[key] = ok
    return ok


def _dispatch_sources(ctx: Ctx, combine) -> Optional[Tuple[Atom, Atom]]:
    """``(idx, gate)`` of a combine ``einsum('tke,tk->te', onehot(idx),
    gate)``: the gate is the 2-D operand of its dot_general, idx the one
    (T, K) integer array nearest upstream of the one-hot operand.  Either
    may be computed in the program (a router), not only passed in."""
    eqn = ctx.prod(ctx.peel(combine))
    if eqn is None or eqn.primitive.name != "dot_general":
        return None
    ops = sorted(eqn.invars, key=lambda a: getattr(a.aval, "ndim", 0))
    if [getattr(a.aval, "ndim", 0) for a in ops] != [2, 3]:
        return None
    gate, onehot = ops
    shape = onehot.aval.shape[:2]
    found, seen, stack = [], set(), [onehot]
    while stack:
        a = stack.pop()
        if isinstance(a, jex_core.Literal) or id(a) in seen:
            continue
        seen.add(id(a))
        if a.aval.shape == shape and np.issubdtype(a.aval.dtype, np.integer):
            found.append(a)
            continue
        producer = ctx.prod(a)
        if producer is not None:
            stack.extend(producer.invars)
    return (found[0], gate) if len(found) == 1 else None


def _validate_onehot_dispatch(ctx: Ctx, combine_atom, idx_var, gate_var,
                              n_experts: int) -> bool:
    """combine[t,e] must equal sum_k gate[t,k] * (idx[t,k] == e).
    Verdict memoized per (combine, idx, gate) subgraph."""
    key = ("onehot", id(combine_atom), id(idx_var), id(gate_var), n_experts)
    cached = ctx.validation_cache.get(key)
    if cached is not None:
        return cached
    t, k = idx_var.aval.shape
    rng = np.random.default_rng(0)
    idx = rng.integers(0, n_experts, size=(t, k)).astype(idx_var.aval.dtype)
    gate = rng.standard_normal((t, k)).astype(gate_var.aval.dtype)
    expect = np.zeros((t, n_experts), np.float32)
    for ti in range(t):
        for ki in range(k):
            expect[ti, idx[ti, ki]] += np.float32(gate[ti, ki])
    try:
        got = ctx.eval_subgraph(combine_atom, {idx_var: idx, gate_var: gate},
                                stop=(idx_var, gate_var))
    except Exception:
        ctx.validation_cache[key] = False
        return False
    # a bf16 program rounds the gates: allow the dtype's own rounding, far
    # below the O(1) error of any dispatch that is not the top-k one-hot
    eps = float(jnp.finfo(got.dtype).eps) if jnp.issubdtype(
        got.dtype, jnp.floating) else 0.0
    tol = max(1e-5, 2 * eps)
    ok = got.shape == expect.shape and np.allclose(
        np.asarray(got, np.float32), expect, rtol=tol, atol=tol)
    ctx.validation_cache[key] = ok
    return ok


# ---------------------------------------------------------------------------
# Matchers, generated from What-ASTs.
# ---------------------------------------------------------------------------

def _updates_pattern_from_expr(expr: W.Expr, loopvar: str) -> Pat:
    """Compile the What reduction body into a vectorized-updates pattern:
    loads indexed by the loop variable become whole-array binds; loads
    indexed through another array become gathers (Fig. 13's assignment
    targets)."""
    if isinstance(expr, W.Mul):
        return Comm("mul",
                    _updates_pattern_from_expr(expr.lhs, loopvar),
                    _updates_pattern_from_expr(expr.rhs, loopvar))
    if isinstance(expr, W.Add):
        return Comm("add",
                    _updates_pattern_from_expr(expr.lhs, loopvar),
                    _updates_pattern_from_expr(expr.rhs, loopvar))
    if isinstance(expr, W.Load):
        idx = expr.index
        if isinstance(idx, W.Var) and idx.name == loopvar:
            return B(expr.array)                       # a[j] -> whole array
        if isinstance(idx, W.Load) and isinstance(idx.index, W.Var) \
                and idx.index.name == loopvar:
            return Gather1D(B(expr.array), B(idx.array))  # iv[colidx[j]]
        # composite index (2D padded layouts): bind the whole array; the
        # skeleton match constrains the shape.
        return B(expr.array)
    if isinstance(expr, W.Var):
        return B(expr.name)
    if isinstance(expr, W.Const):
        return AnyP()
    raise TypeError(expr)


def _range_is_ragged(rng: W.Range, outer_var: str) -> bool:
    def uses_outer_load(e: W.Expr) -> bool:
        if isinstance(e, W.Load):
            return True
        if isinstance(e, (W.Add, W.Mul)):
            return uses_outer_load(e.lhs) or uses_outer_load(e.rhs)
        return False
    return uses_outer_load(rng.lo) or uses_outer_load(rng.hi)


class Matcher:
    """A generated detection function for one What-program."""

    computation: str
    anchor_prims: Tuple[str, ...] = ()

    def match_eqn(self, ctx: Ctx, eqn: Eqn) -> Optional[Match]:
        raise NotImplementedError


class RaggedRowMatcher(Matcher):
    """CSR / COO SpMV: the vectorized realization of

        forall(i) { out[i] = sum(ragged range(i)) expr(j) }

    is scatter-add(zeros, row_ids, updates).  row_ids provenance decides the
    format: a raw vector input -> COO; a validated expansion of a single
    (rows+1,) pointer vector -> CSR (binding the paper's `rowstr`)."""

    anchor_prims = ("scatter-add",)

    def __init__(self, comp: W.Computation):
        self.computation = comp.name
        stmt = comp.stmt()
        self.updates_pat = _updates_pattern_from_expr(stmt.expr, stmt.range.var)
        self.row_ptr_name = (stmt.range.lo.array
                             if isinstance(stmt.range.lo, W.Load) else "rowstr")
        self.out_name = (stmt.target.array
                         if isinstance(stmt.target, W.Load) else "output")

    def match_eqn(self, ctx, eqn):
        if eqn.primitive.name != "scatter-add" or not _is_row_scatter(eqn.params):
            return None
        operand, indices, updates = eqn.invars[:3]
        if updates.aval.ndim != 1:
            return None
        if not ctx.is_zeros(operand):
            return None
        env0: Dict[str, Any] = {}
        for env in self.updates_pat.match(ctx, updates, env0):
            row_atom = ctx.peel(indices)
            nnz = updates.aval.shape[0]
            rows = eqn.outvars[0].aval.shape[0]
            fmt, binding = self._classify_rows(ctx, row_atom, nnz, rows, env)
            if fmt is None:
                continue
            binding = dict(binding)
            binding["rows"] = rows
            binding["nnz"] = nnz
            return Match(self.computation, "vectorized", fmt,
                         eqn.outvars[0], eqn, binding)
        return None

    def _classify_rows(self, ctx, row_atom, nnz, rows, env):
        prod = ctx.prod(row_atom)
        if prod is None:
            b = dict(env)
            b["rowidx"] = row_atom
            return "COO", b
        leaves, _ = ctx.provenance(row_atom)
        ptr_leaves = [l for l in leaves
                      if getattr(l.aval, "shape", None) == (rows + 1,)
                      and np.issubdtype(l.aval.dtype, np.integer)]
        if len(ptr_leaves) == 1 and _validate_row_expansion(
                ctx, row_atom, ptr_leaves[0], nnz, rows):
            b = dict(env)
            b[self.row_ptr_name] = ptr_leaves[0]
            return "CSR", b
        # derived row vector: still COO with the intermediate var
        b = dict(env)
        b["rowidx"] = row_atom
        return "COO", b


class SpmmMatcher(Matcher):
    """SpMM (CSR x dense matrix): the doubly-forall What-program realizes
    as scatter-add of row windows:

        out = scatter-add(zeros(rows,n), row_ids,
                          mul(broadcast(a), gather_rows(dense, colidx)))
    """

    anchor_prims = ("scatter-add",)

    def __init__(self, comp: W.Computation):
        self.computation = comp.name
        self.updates_pat = Comm(
            "mul",
            P("broadcast_in_dim", B("a", pred=_is_1d), peel=False),
            P("gather", B("dense", pred=_is_2d), B("colidx"),
              params=_is_rowwindow_gather),
        )

    def match_eqn(self, ctx, eqn):
        if eqn.primitive.name != "scatter-add" \
                or not _is_rowwindow_scatter(eqn.params):
            return None
        operand, indices, updates = eqn.invars[:3]
        if updates.aval.ndim != 2 or not ctx.is_zeros(operand):
            return None
        for env in self.updates_pat.match(ctx, updates, {}):
            row_atom = ctx.peel(indices)
            nnz = updates.aval.shape[0]
            rows = eqn.outvars[0].aval.shape[0]
            leaves, _ = ctx.provenance(row_atom) \
                if ctx.prod(row_atom) is not None else ([], [])
            binding = dict(env)
            binding.update(rows=rows, nnz=nnz,
                           ncols=updates.aval.shape[1])
            ptr_leaves = [l for l in leaves
                          if getattr(l.aval, "shape", None) == (rows + 1,)
                          and np.issubdtype(l.aval.dtype, np.integer)]
            if len(ptr_leaves) == 1 and _validate_row_expansion(
                    ctx, row_atom, ptr_leaves[0], nnz, rows):
                binding["rowstr"] = ptr_leaves[0]
                return Match(self.computation, "vectorized", "CSR",
                             eqn.outvars[0], eqn, binding)
            binding["rowidx"] = row_atom
            return Match(self.computation, "vectorized", "COO",
                         eqn.outvars[0], eqn, binding)
        return None


class PaddedRowMatcher(Matcher):
    """ELL (and JDS, which adds a perm scatter on the output):

        forall(i) { out[i] = sum(0<=j<width) val2d[i,j]*vec[col2d[i,j]] }

    vectorized: reduce_sum(axis=1)(mul(val2d, gather(vec, col2d)))."""

    anchor_prims = ("reduce_sum", "scatter")

    def __init__(self, comp: W.Computation, jds: bool):
        self.computation = comp.name
        self.jds = jds
        self.core_pat = P(
            "reduce_sum",
            Comm("mul", B("val", pred=_is_2d), Gather1D(B("vector"), B("col_ind"))),
            params=lambda p: tuple(p.get("axes", ())) == (1,),
        )

    def match_eqn(self, ctx, eqn):
        if self.jds:
            # scatter(zeros, perm, core): out[perm[i]] = core[i]
            if eqn.primitive.name != "scatter" or not _is_row_scatter(eqn.params):
                return None
            operand, indices, updates = eqn.invars[:3]
            if not ctx.is_zeros(operand):
                return None
            for env in self.core_pat.match(ctx, updates, {}):
                env = dict(env)
                env["perm"] = ctx.peel(indices)
                env["rows"] = eqn.outvars[0].aval.shape[0]
                core_eqn = ctx.prod(ctx.peel(updates))
                return Match(self.computation, "vectorized", "JDS",
                             eqn.outvars[0], eqn, env,
                             claimed_eqns=(core_eqn,) if core_eqn else ())
            return None
        if eqn.primitive.name != "reduce_sum":
            return None
        for env in self.core_pat.match(ctx, eqn.outvars[0], {}):
            env = dict(env)
            env["rows"] = eqn.outvars[0].aval.shape[0]
            return Match(self.computation, "vectorized", "ELL",
                         eqn.outvars[0], eqn, env)
        return None


def _is_2d(ctx, atom):
    return getattr(atom.aval, "ndim", 0) == 2


def _is_1d(ctx, atom):
    return getattr(atom.aval, "ndim", 0) == 1


class DotMatcher(Matcher):
    """result = sum(i) a[i]*b[i] — vectorized (reduce_sum∘mul or dot_general)
    and loop (scan accumulating a[i]*b[i]) skeletons."""

    anchor_prims = ("reduce_sum", "dot_general", "scan")

    def __init__(self, comp: W.Computation):
        self.computation = comp.name
        stmt = comp.stmt()
        self.vec_pat = Alt(
            P("reduce_sum",
              Comm("mul", B("a", pred=_is_1d), B("b", pred=_is_1d)),
              params=lambda p: tuple(p.get("axes", ())) == (0,)),
            P("dot_general", B("a", pred=_is_1d), B("b", pred=_is_1d),
              params=lambda p: p.get("dimension_numbers")
              == (((0,), (0,)), ((), ()))),
        )

    def match_eqn(self, ctx, eqn):
        if eqn.primitive.name == "scan":
            return _match_scan_dot(ctx, eqn, self.computation)
        if eqn.outvars[0].aval.ndim != 0:
            return None
        for env in self.vec_pat.match(ctx, eqn.outvars[0], {}):
            env = dict(env)
            env["length"] = env["a"].aval.shape[0]
            return Match(self.computation, "vectorized", "DOT",
                         eqn.outvars[0], eqn, env)
        return None


class GemvMatcher(Matcher):
    """Dense matrix-vector product (paper: 'we fully support dense')."""

    anchor_prims = ("dot_general", "reduce_sum")

    def __init__(self, comp: W.Computation):
        self.computation = comp.name

    def match_eqn(self, ctx, eqn):
        if eqn.primitive.name == "dot_general":
            dn = eqn.params.get("dimension_numbers")
            lhs, rhs = eqn.invars
            if (dn == (((1,), (0,)), ((), ()))
                    and lhs.aval.ndim == 2 and rhs.aval.ndim == 1):
                return Match(self.computation, "vectorized", "GEMV",
                             eqn.outvars[0], eqn,
                             {"mat": ctx.peel(lhs), "vec": ctx.peel(rhs),
                              "rows": lhs.aval.shape[0],
                              "cols": lhs.aval.shape[1]})
            return None
        if eqn.primitive.name == "reduce_sum" \
                and tuple(eqn.params.get("axes", ())) == (1,):
            pat = Comm("mul", B("mat", pred=_is_2d),
                       P("broadcast_in_dim", B("vec", pred=_is_1d),
                         params=lambda p: tuple(p["broadcast_dimensions"]) == (1,),
                         peel=False))
            for env in pat.match(ctx, eqn.outvars[0], {}):
                env = dict(env)
                env["rows"] = env["mat"].aval.shape[0]
                env["cols"] = env["mat"].aval.shape[1]
                return Match(self.computation, "vectorized", "GEMV",
                             eqn.outvars[0], eqn, env)
        return None


# -- scan (loop skeleton) matching ------------------------------------------

def _elem_load(ctx: Ctx, body_ctx: "Ctx", atom, counter_var):
    """Match squeeze(dynamic_slice(ARR, counter)) inside a scan body; return
    ARR (a body var) or None."""
    a = body_ctx.peel(atom)
    eqn = body_ctx.prod(a)
    if eqn is None or eqn.primitive.name != "dynamic_slice":
        return None
    arr, idx = eqn.invars[0], eqn.invars[1]
    if body_ctx.peel(idx) is not counter_var:
        return None
    return arr


def _match_scan_coo(ctx: Ctx, eqn: Eqn, computation: str) -> Optional[Match]:
    """fori_loop COO accumulation:
        body: (i, acc) -> (i+1, scatter-add(acc, row[i], val[i]*vec[col[i]]))
    """
    params = eqn.params
    if params.get("num_carry", 0) != 2:
        return None
    body = params["jaxpr"].jaxpr
    nconsts = params["num_consts"]
    body_ctx = Ctx(jex_core.ClosedJaxpr(body, params["jaxpr"].consts))
    counter_in, acc_in = body.invars[nconsts], body.invars[nconsts + 1]
    counter_out, acc_out = body.outvars[0], body.outvars[1]
    # counter increments by one
    ce = body_ctx.prod(body_ctx.peel(counter_out))
    if ce is None or ce.primitive.name != "add" \
            or body_ctx.peel(ce.invars[0]) is not counter_in:
        return None
    se = body_ctx.prod(body_ctx.peel(acc_out))
    if se is None or se.primitive.name != "scatter-add" \
            or not _is_row_scatter(se.params):
        return None
    operand, indices, updates = se.invars[:3]
    if body_ctx.peel(operand) is not acc_in:
        return None
    row_arr = _elem_load(ctx, body_ctx, indices, counter_in)
    if row_arr is None:
        return None
    ue = body_ctx.prod(body_ctx.peel(updates))
    if ue is None or ue.primitive.name != "mul":
        return None
    for val_at, gather_at in ((ue.invars[0], ue.invars[1]),
                              (ue.invars[1], ue.invars[0])):
        val_arr = _elem_load(ctx, body_ctx, val_at, counter_in)
        if val_arr is None:
            continue
        ge = body_ctx.prod(body_ctx.peel(gather_at))
        if ge is None or ge.primitive.name != "dynamic_slice":
            continue
        vec_arr, vidx = ge.invars[0], body_ctx.peel(ge.invars[1])
        col_arr = _elem_load(ctx, body_ctx, vidx, counter_in)
        if col_arr is None:
            continue
        # map body consts back to outer atoms
        def outer(v):
            i = body.invars.index(v)
            if i >= nconsts:
                return None
            return eqn.invars[i]
        o_row, o_val, o_col, o_vec = map(outer, (row_arr, val_arr, col_arr, vec_arr))
        if None in (o_row, o_val, o_col, o_vec):
            continue
        init_acc = eqn.invars[nconsts + 1]
        if not ctx.is_zeros(init_acc):
            continue
        binding = {"a": ctx.peel(o_val), "rowidx": ctx.peel(o_row),
                   "colidx": ctx.peel(o_col), "iv": ctx.peel(o_vec),
                   "rows": eqn.outvars[1].aval.shape[0],
                   "nnz": params["length"]}
        return Match(computation, "loop", "COO", eqn.outvars[1], eqn, binding,
                     notes="fori_loop skeleton")
    return None


def _match_scan_dot(ctx: Ctx, eqn: Eqn, computation: str) -> Optional[Match]:
    """fori_loop dot product: body: (i, acc) -> (i+1, acc + a[i]*b[i])."""
    params = eqn.params
    if params.get("num_carry", 0) != 2:
        return None
    body = params["jaxpr"].jaxpr
    nconsts = params["num_consts"]
    body_ctx = Ctx(jex_core.ClosedJaxpr(body, params["jaxpr"].consts))
    counter_in, acc_in = body.invars[nconsts], body.invars[nconsts + 1]
    acc_out = body.outvars[1]
    if getattr(acc_out.aval, "ndim", None) != 0:
        return None
    ae = body_ctx.prod(body_ctx.peel(acc_out))
    if ae is None or ae.primitive.name != "add":
        return None
    for acc_at, prod_at in ((ae.invars[0], ae.invars[1]),
                            (ae.invars[1], ae.invars[0])):
        if body_ctx.peel(acc_at) is not acc_in:
            continue
        me = body_ctx.prod(body_ctx.peel(prod_at))
        if me is None or me.primitive.name != "mul":
            continue
        a_arr = _elem_load(ctx, body_ctx, me.invars[0], counter_in)
        b_arr = _elem_load(ctx, body_ctx, me.invars[1], counter_in)
        if a_arr is None or b_arr is None:
            continue

        def outer(v):
            i = body.invars.index(v)
            return eqn.invars[i] if i < nconsts else None

        o_a, o_b = outer(a_arr), outer(b_arr)
        if o_a is None or o_b is None:
            continue
        if not ctx.is_zeros(eqn.invars[nconsts + 1]):
            continue
        return Match(computation, "loop", "DOT", eqn.outvars[1], eqn,
                     {"a": ctx.peel(o_a), "b": ctx.peel(o_b),
                      "length": params["length"]},
                     notes="fori_loop skeleton")
    return None


class CooLoopMatcher(Matcher):
    anchor_prims = ("scan",)

    def __init__(self, comp: W.Computation):
        self.computation = comp.name

    def match_eqn(self, ctx, eqn):
        if eqn.primitive.name != "scan":
            return None
        return _match_scan_coo(ctx, eqn, self.computation)


class MoeMatcher(Matcher):
    """The MoE expert FFN with one-hot dispatch (naive dense realization):

        combine (T,E) = einsum('tke,tk->te', onehot(idx), gate)
        g = einsum('td,edf->etf', x, wg); u = einsum('td,edf->etf', x, wu)
        y = einsum('etf,efd->etd', silu(g)*u, wd)
        out = einsum('te,etd->td', combine, y)

    Anchored at the final batched dot_general; the combine operand is
    semantically validated to be a top-k one-hot dispatch of (idx, gate)."""

    anchor_prims = ("dot_general",)

    def __init__(self, comp: W.Computation):
        self.computation = comp.name

        def expert_mm(w):
            # einsum('td,edf->etf', x, w) lowers to
            # transpose(0,2,1)(dot_general(w, x; contract d, no batch)).
            # operands bind unpeeled, as the matmuls consume them: in a
            # model x is the (T, D) reshape of the (B, S, D) activations and
            # each weight a squeezed slice of the layer-stacked parameters
            x = B("x", pred=_is_2d, peel=False)
            inner = P("dot_general", B(w, peel=False), x,
                      params=lambda p: p.get("dimension_numbers")
                      == (((1,), (1,)), ((), ())))
            return Alt(
                P("transpose", inner,
                  params=lambda p: tuple(p.get("permutation", ())) == (0, 2, 1)),
                P("dot_general", x, B(w, peel=False),
                  params=lambda p: p.get("dimension_numbers")
                  == (((1,), (1,)), ((), ()))),
            )

        h_pat = Comm("mul",
                     Comm("mul", expert_mm("wg"), P("logistic", expert_mm("wg"))),
                     expert_mm("wu"))
        self.y_pat = P(
            "dot_general", h_pat, B("wd", peel=False),
            params=lambda p: p.get("dimension_numbers")
            == (((2,), (1,)), ((0,), (0,))))

    def match_eqn(self, ctx, eqn):
        if eqn.primitive.name != "dot_general":
            return None
        dn = eqn.params.get("dimension_numbers")
        # einsum('te,etd->td'): contract e, batch t
        if dn != (((1,), (0,)), ((0,), (1,))):
            return None
        combine, y = eqn.invars
        if combine.aval.ndim != 2 or y.aval.ndim != 3:
            return None
        n_experts = combine.aval.shape[1]
        for env in self.y_pat.match(ctx, y, {}):
            sources = _dispatch_sources(ctx, combine)
            if sources is None:
                continue
            idx_var, gate_var = sources
            if not _validate_onehot_dispatch(ctx, ctx.peel(combine),
                                             idx_var, gate_var, n_experts):
                continue
            binding = dict(env)
            binding.update(idx=idx_var, gate=gate_var,
                           experts=n_experts,
                           tokens=combine.aval.shape[0],
                           topk=idx_var.aval.shape[-1])
            return Match(self.computation, "vectorized", "MOE",
                         eqn.outvars[0], eqn, binding)
        return None


# ---------------------------------------------------------------------------
# Matcher generation (What-AST -> detection function) + top-level detect().
# ---------------------------------------------------------------------------

def generate_matcher(comp: W.Computation) -> List[Matcher]:
    """The paper generates C++ detection functions from LiLAC-What at LLVM
    build time; we generate matcher objects from the AST at import time."""
    if comp.name == "moe_ffn":
        return [MoeMatcher(comp)]
    foralls = comp.foralls()
    stmt = comp.stmt()
    if len(foralls) == 2 and _range_is_ragged(stmt.range, foralls[0].range.var):
        return [SpmmMatcher(comp)]   # doubly-parallel ragged = SpMM
    if not foralls and isinstance(stmt.target, W.Var):
        return [DotMatcher(comp)]
    if len(foralls) == 1:
        # permuted output target (JDS) takes precedence: its inner range is
        # "ragged" in the What-text (nzcnt[i]) but the vectorized realization
        # is the padded 2D layout with a perm scatter.
        if isinstance(stmt.target, W.Load) and isinstance(stmt.target.index, W.Load):
            return [PaddedRowMatcher(comp, jds=True)]
        if _range_is_ragged(stmt.range, foralls[0].range.var):
            return [RaggedRowMatcher(comp), CooLoopMatcher(comp)]
        if comp.name == "gemv":
            return [GemvMatcher(comp)]
        # dense inner range with 2D loads -> padded rows
        return [PaddedRowMatcher(comp, jds=False)]
    raise NotImplementedError(f"cannot generate matcher for {comp.name}")


# ---------------------------------------------------------------------------
# Fused-epilogue extension: grow spmv/spmm matches down their consumer chain
# through (+bias) -> (relu | silu), so the harness replaces the whole fused
# subgraph and the intermediate output-size arrays never round-trip memory.
# ---------------------------------------------------------------------------

_EPILOGUE_COMPS = ("spmv_csr", "spmv_coo", "spmm_csr", "spmv_ell", "spmv_jds")


def _broadcastable_to(shape, out_shape) -> bool:
    try:
        return np.broadcast_shapes(tuple(shape), tuple(out_shape)) \
            == tuple(out_shape)
    except ValueError:
        return False


def _is_relu(ctx: Ctx, eqn: Eqn, cur) -> bool:
    """max(cur, 0) in either operand order (jax.nn.relu normalizes here)."""
    if eqn.primitive.name != "max" or len(eqn.invars) != 2:
        return False
    x, y = eqn.invars
    if ctx.peel(x) is cur:
        return ctx.is_zeros(y)
    if ctx.peel(y) is cur:
        return ctx.is_zeros(x)
    return False


def extend_epilogue(ctx: Ctx, m: Match) -> Match:
    """Walk the sole-consumer chain of a vectorized spmv/spmm match through
    an optional bias add and an optional relu/silu activation; on success,
    return a widened match anchored at the chain's last equation with the
    original anchor (and intermediates) claimed.  Escaping values (multiple
    consumers, function outputs) stop the walk — fusing them away would
    change observable results."""
    if m.computation not in _EPILOGUE_COMPS or m.variant != "vectorized":
        return m
    cur_eqn = m.anchor_eqn
    cur = cur_eqn.outvars[0]
    out_shape = tuple(getattr(cur.aval, "shape", ()))
    claimed: List[Eqn] = []
    bias = None
    epilogue: Optional[str] = None
    while epilogue is None:
        if cur in ctx.outvars:
            break
        cons = [e for e in ctx.consumers.get(cur, ())]
        if len(cons) == 1:
            e = cons[0]
            p = e.primitive.name
            if p in ("convert_element_type", "copy"):
                claimed.append(e)
                cur_eqn, cur = e, e.outvars[0]
                continue
            if p == "add" and bias is None:
                x, y = e.invars
                other = y if ctx.peel(x) is cur else (
                    x if ctx.peel(y) is cur else None)
                if other is None:
                    break
                b = ctx.peel(other)
                bshape = tuple(getattr(b.aval, "shape", ()))
                if not _broadcastable_to(bshape, out_shape):
                    break
                bias = b
                claimed.append(e)
                cur_eqn, cur = e, e.outvars[0]
                continue
            if _is_relu(ctx, e, cur):
                epilogue = "relu"
                claimed.append(e)
                cur_eqn, cur = e, e.outvars[0]
                continue
            break
        if len(cons) == 2:
            # silu: cur feeds both logistic(cur) and mul(cur, logistic(cur))
            log_e = next((e for e in cons
                          if e.primitive.name == "logistic"), None)
            mul_e = next((e for e in cons if e.primitive.name == "mul"), None)
            if log_e is None or mul_e is None:
                break
            log_out = log_e.outvars[0]
            if ctx.sole_consumer(log_out) is not mul_e:
                break
            operands = {id(ctx.peel(v)) for v in mul_e.invars}
            if operands != {id(cur), id(ctx.peel(log_out))}:
                break
            epilogue = "silu"
            claimed.extend([log_e, mul_e])
            cur_eqn, cur = mul_e, mul_e.outvars[0]
            continue
        break
    if bias is None and epilogue is None:
        return m
    binding = dict(m.binding)
    if bias is not None:
        binding["bias"] = bias
    return dataclasses.replace(
        m, anchor=cur, anchor_eqn=cur_eqn, binding=binding,
        epilogue=epilogue or "none",
        claimed_eqns=m.claimed_eqns + (m.anchor_eqn,)
        + tuple(e for e in claimed if e is not cur_eqn),
        notes=(m.notes + " " if m.notes else "") + "fused epilogue")


_DEFAULT_PRIORITY = ["moe_ffn", "spmm_csr", "spmv_csr", "spmv_jds",
                     "spmv_ell", "spmv_coo", "gemv", "dotproduct"]


class Detector:
    def __init__(self, computations: Optional[Sequence[W.Computation]] = None,
                 fuse_epilogues: bool = True, scan_bodies: bool = True):
        self.fuse_epilogues = fuse_epilogues
        self.scan_bodies = scan_bodies
        if computations is not None:
            comps = list(computations)
            lenient = False
        else:
            # priority order first, then any spec-registered extras
            names = [n for n in _DEFAULT_PRIORITY if n in W.BUILTINS]
            names += [n for n in W.BUILTINS if n not in names]
            comps = [W.BUILTINS[n] for n in names]
            lenient = True
        self.matchers: List[Matcher] = []
        self.unmatchable: List[str] = []
        for c in comps:
            try:
                self.matchers.extend(generate_matcher(c))
            except NotImplementedError:
                # a spec-registered computation with no matcher skeleton
                # must not break detection of everything else
                if not lenient:
                    raise
                self.unmatchable.append(c.name)

    def detect(self, closed_jaxpr, normalize: bool = True) -> DetectionReport:
        cj = normalize_closed_jaxpr(closed_jaxpr) if normalize else closed_jaxpr
        ctx = Ctx(cj)
        matches: List[Match] = []
        claimed: set = set()
        # matcher-major iteration: matchers are in priority order (e.g. JDS
        # outranks its own ELL core; CSR outranks COO-as-fallback).
        for m in self.matchers:
            for eqn in cj.jaxpr.eqns:
                if m.anchor_prims and eqn.primitive.name not in m.anchor_prims:
                    continue
                if id(eqn) in claimed:
                    continue
                found = m.match_eqn(ctx, eqn)
                if found is not None:
                    matches.append(found)
                    claimed.add(id(eqn))
                    for ce in found.claimed_eqns:
                        claimed.add(id(ce))
        if self.scan_bodies:
            matches += self._detect_scan_bodies(cj, claimed)
        if self.fuse_epilogues:
            matches = [extend_epilogue(ctx, m) for m in matches]
        matches.sort(key=lambda mm: ctx.eqn_index.get(id(mm.anchor_eqn), 0))
        return DetectionReport(matches=matches, n_eqns=len(cj.jaxpr.eqns),
                               log=ctx.log)

    def _detect_scan_bodies(self, cj, claimed: set) -> List[Match]:
        """Descend into unclaimed ``scan`` equations (training loops,
        microbatch accumulation) and detect inside the body jaxpr — once.
        The whole scan becomes one ``variant='scan_body'`` match carrying
        the normalized body and its inner matches; the rewriter rebuilds
        the scan around a rewritten body, so the kernels selected here are
        reused on every iteration instead of being re-detected."""
        out: List[Match] = []
        for eqn in cj.jaxpr.eqns:
            if eqn.primitive.name != "scan" or id(eqn) in claimed:
                continue
            try:
                body_closed = eqn.params["jaxpr"]
                norm = normalize_closed_jaxpr(body_closed)
            except Exception:
                continue
            sub = self.detect(norm, normalize=False)
            if not sub.matches:
                continue
            # the scan's operands must stay live through the rewrite: bind
            # them so needed_eqn_ids keeps their producers
            binding = {f"scan_in{i}": v for i, v in enumerate(eqn.invars)
                       if not isinstance(v, jex_core.Literal)}
            out.append(Match(
                computation="scan_body", variant="scan_body", format="SCAN",
                anchor=eqn.outvars[0], anchor_eqn=eqn, binding=binding,
                notes=f"{len(sub.matches)} match(es) in scan body",
                body=(norm, sub.matches)))
            claimed.add(id(eqn))
        return out

    def detect_fn(self, fn: Callable, *example_args, **kw) -> DetectionReport:
        cj = jax.make_jaxpr(fn)(*example_args, **kw)
        return self.detect(cj)


_default_detector: Optional[Detector] = None


def default_detector() -> Detector:
    global _default_detector
    if _default_detector is None:
        _default_detector = Detector()
    return _default_detector


def reset_default_detector() -> None:
    """Drop the cached detector so newly spec-registered computations are
    picked up by the next ``default_detector()`` call."""
    global _default_detector
    _default_detector = None
