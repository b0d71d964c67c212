"""Code replacement (paper §4.1.2): splice harness calls into jaxprs.

The paper inserts a harness call before the matched loop nest, removes the
result store, and lets DCE sweep the rest.  Here the rewritten program is a
re-interpretation of the normalized jaxpr: every equation is re-emitted
except the matched anchors, whose outputs come from the selected harness.
Orphaned producers are removed by XLA DCE at jit time (trace mode) or simply
never contribute (their values are still computed in host mode only if
needed by unmatched consumers — the interpreter is demand-agnostic but XLA
under jit removes them; host mode runs eqn-by-eqn and skips equations whose
outputs feed only matched anchors).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import core as jcore
from jax.extend import core as jex_core

from repro.core.detect import Match
from repro.core.harness import CallCtx, Harness


def needed_eqn_ids(closed_jaxpr, matches: List[Match]) -> frozenset:
    """``id``s of the equations the rewritten program must still evaluate:
    everything live through the function outputs or a harness binding atom,
    minus the replaced anchors and the producers that only fed them.

    Pure function of ``(closed_jaxpr, matches)`` — the pass manager
    memoizes it per ``CompiledEntry`` so repeat host-mode calls (and every
    baked-plan trace) skip the backward liveness walk."""
    jaxpr = closed_jaxpr.jaxpr
    anchor_ids = {id(m.anchor_eqn) for m in matches}
    # keep anything a harness binding refers to
    binding_atoms = set()
    for m in matches:
        for v in m.binding.values():
            # Literals (e.g. a scalar epilogue bias) are constants: they
            # need no liveness root and are unhashable anyway
            if not isinstance(v, (int, float, bool, jex_core.Literal)):
                binding_atoms.add(v)
    live = {v for v in jaxpr.outvars if not isinstance(v, jex_core.Literal)}
    live |= binding_atoms
    needed = set()
    for eqn in reversed(jaxpr.eqns):
        if id(eqn) in anchor_ids:
            continue
        if any(ov in live for ov in eqn.outvars):
            needed.add(id(eqn))
            for iv in eqn.invars:
                if not isinstance(iv, jex_core.Literal):
                    live.add(iv)
    return frozenset(needed)


def run_rewritten(closed_jaxpr,
                  matches: List[Match],
                  select: Callable[[Match], Harness],
                  args: List[Any],
                  ctx_factory: Callable[[Match], CallCtx],
                  on_select: Optional[Callable[[Match, Harness], None]] = None,
                  needed: Optional[frozenset] = None,
                  contain: Optional[Callable] = None,
                  ) -> List[Any]:
    """Evaluate ``closed_jaxpr`` with matched anchors replaced by harness
    calls.  Traceable: under jit this builds the rewritten HLO.

    ``on_select`` (if given) observes every (match, chosen harness, call
    ctx) triple — the pass manager uses it to pin autotuned winners (and
    their schedule variants, carried on ``ctx.schedule``) into the rewrite
    and benchmarks use it to report which backend actually ran.

    ``needed`` (if given) is a precomputed :func:`needed_eqn_ids` result
    for exactly this ``(closed_jaxpr, matches)`` pair.

    ``contain`` (if given) is a :class:`repro.core.resilience.Containment`
    -shaped callable ``(m, harness, ctx, binding_vals, attempt, on_select)
    -> out``: every anchor invocation routes through it so a failing
    harness can be retried with another candidate or escalated to
    :class:`~repro.core.resilience.ReferenceFallback` instead of
    surfacing to the user.  When containment retries, it re-issues
    ``on_select`` for each candidate it tries — observers must treat a
    repeated (match, ...) as a replacement, not a new site."""
    jaxpr = closed_jaxpr.jaxpr
    env: Dict[Any, Any] = {}

    def read(atom):
        if isinstance(atom, jex_core.Literal):
            return atom.val
        return env[atom]

    def write(var, val):
        env[var] = val

    for cv, cval in zip(jaxpr.constvars, closed_jaxpr.consts):
        write(cv, cval)
    assert len(jaxpr.invars) == len(args), (len(jaxpr.invars), len(args))
    for iv, a in zip(jaxpr.invars, args):
        write(iv, a)

    anchor_map = {id(m.anchor_eqn): m for m in matches}
    if needed is None:
        needed = needed_eqn_ids(closed_jaxpr, matches)
    dead = _dead_after(jaxpr, anchor_map)

    for i, eqn in enumerate(jaxpr.eqns):
        m = anchor_map.get(id(eqn))
        if m is not None:
            if m.variant == "scan_body":
                _eval_scan_body(eqn, m, select, read, write, ctx_factory,
                                on_select, contain)
            else:
                _eval_anchor(eqn, m, select, read, write, ctx_factory,
                             on_select, contain)
        elif id(eqn) in needed:
            subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
            ans = eqn.primitive.bind(*subfuns, *map(read, eqn.invars),
                                     **bind_params)
            if eqn.primitive.multiple_results:
                for ov, v in zip(eqn.outvars, ans):
                    write(ov, v)
            else:
                write(eqn.outvars[0], ans)
        for v in dead[i]:
            env.pop(v, None)

    return [read(v) for v in jaxpr.outvars]


def _dead_after(jaxpr, anchor_map) -> List[List[Any]]:
    """For each equation, the variables no later equation (or harness
    binding) reads.  Host mode evaluates eagerly, so dropping them frees
    their device buffers as it goes: an unrolled decode slices every
    layer's weights out of the stacked params, and holding all the slices
    at once would need a second copy of the model."""
    last: Dict[Any, int] = {}

    def use(atom, i):
        if not isinstance(atom, (int, float, bool, jex_core.Literal)):
            last[atom] = i

    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            use(v, i)
        m = anchor_map.get(id(eqn))
        if m is not None:
            for v in m.binding.values():
                use(v, i)
        for v in eqn.outvars:
            last.setdefault(v, i)
    for v in jaxpr.outvars:
        if not isinstance(v, jex_core.Literal):
            last.pop(v, None)
    dead: List[List[Any]] = [[] for _ in jaxpr.eqns]
    for v, i in last.items():
        dead[i].append(v)
    return dead


def apply_epilogue(out, bias, epilogue: str):
    """The detected fused epilogue, applied at the jnp level: the unfused
    realization for harnesses that don't declare ``fuse epilogue`` (and the
    reference semantics the fused kernels must reproduce).  ``epilogue`` is
    'relu' | 'silu' | 'none' (bias only)."""
    if bias is not None:
        out = out + bias
    if epilogue == "relu":
        out = jnp.maximum(out, 0)
    elif epilogue == "silu":
        out = out * jax.nn.sigmoid(out)
    return out


def effective_fuse(harness, ctx) -> bool:
    """Whether this call applies the detected epilogue IN-KERNEL.  The
    harness must be fuse-capable (``fuse epilogue`` in its spec); given
    that, ``ctx.fuse`` overrides the declared default — the autotuner
    sweeps both realizations and the joint plan search pins the faster
    one, so fusion is a measured decision, not a flag."""
    if not getattr(harness, "fuse_epilogue", False):
        return False
    f = getattr(ctx, "fuse", None)
    return True if f is None else bool(f)


def _call_with_vjp(harness: Harness, binding_vals: Dict[str, Any],
                   ctx: CallCtx):
    """Wrap the harness call in ``jax.custom_vjp`` per its declared vjp
    clause: the forward becomes opaque to AD (host marshaling and Pallas
    bodies are never differentiated through) and the registered backward
    body supplies sparse-aware gradients for the wrt binding keys.  Keys
    not listed — index structure, routing tables, shape ints — are closed
    over as non-differentiable constants."""
    from repro.core.spec import VJPS
    clause = harness.vjp
    bwd_body = VJPS[clause.name]
    # Only values that are live tracers become custom_vjp formal args:
    # a concrete operand (say, a constant sparse matrix) stays a closure
    # capture, so marshal clauses can still fingerprint and repack it —
    # custom_vjp abstracts ALL formal args inside its fwd trace, which
    # would otherwise break host marshaling for operands that were never
    # differentiated in the first place.
    wrt = tuple(k for k in clause.wrt if k in binding_vals
                and isinstance(binding_vals[k], jcore.Tracer))
    nondiff = {k: v for k, v in binding_vals.items() if k not in wrt}

    def base(*dv):
        b = dict(nondiff)
        b.update(zip(wrt, dv))
        return harness(b, ctx)

    def fwd(*dv):
        return base(*dv), dv

    def bwd(res, ct):
        b = dict(nondiff)
        b.update(zip(wrt, res))
        grads = bwd_body(b, ctx, None, ct)
        missing = [k for k in wrt if k not in grads]
        if missing:
            raise ValueError(
                f"vjp {clause.name!r} returned no gradient for "
                f"{missing} (declared wrt: {list(clause.wrt)})")
        return tuple(grads[k] for k in wrt)

    run = jax.custom_vjp(base)
    run.defvjp(fwd, bwd)
    return run(*(binding_vals[k] for k in wrt))


def _eval_anchor(eqn, m: Match, select, read, write, ctx_factory,
                 on_select=None, contain=None):
    binding_vals = {
        k: (v if isinstance(v, (int, float, bool)) else read(v))
        for k, v in m.binding.items()
    }
    ctx = ctx_factory(m)
    harness = select(m, binding_vals, ctx)

    def attempt(h: Harness, c: CallCtx):
        """The full invoke path for one candidate — containment retries
        this with other (harness, ctx) pairs on failure."""
        clause = getattr(h, "vjp", None)
        wrap = clause is not None and any(
            isinstance(binding_vals.get(k), jcore.Tracer) for k in clause.wrt)
        if wrap:
            # Unfuse any detected epilogue under differentiation: the
            # declared backward covers the core computation only, so the
            # epilogue is applied outside the opaque call where jax can
            # transpose it.
            inner_ctx = (dataclasses.replace(c, epilogue=None)
                         if c.epilogue is not None else c)
            out = _call_with_vjp(h, binding_vals, inner_ctx)
            if m.epilogue is not None:
                out = apply_epilogue(out, binding_vals.get("bias"),
                                     m.epilogue)
        else:
            fused = effective_fuse(h, c)
            if (m.epilogue is not None and not fused
                    and getattr(h, "fuse_epilogue", False)
                    and c.epilogue is not None):
                # fuse-capable harness pinned UNFUSED: the body must not
                # see the epilogue (it would apply it in-kernel)
                c = dataclasses.replace(c, epilogue=None)
            out = h(binding_vals, c)
            if m.epilogue is not None and not fused:
                out = apply_epilogue(out, binding_vals.get("bias"),
                                     m.epilogue)
        return out

    if contain is not None:
        out = contain(m, harness, ctx, binding_vals, attempt, on_select)
    else:
        if on_select is not None:
            on_select(m, harness, ctx)
        out = attempt(harness, ctx)
    if m.variant == "loop":
        # scan anchor: outvars = (final counter, final accumulator)
        counter_init = None
        nconsts = eqn.params["num_consts"]
        counter_init = read(eqn.invars[nconsts])
        length = eqn.params["length"]
        counter_fin = (jnp.asarray(counter_init)
                       + jnp.asarray(length).astype(eqn.outvars[0].aval.dtype))
        write(eqn.outvars[0], counter_fin.astype(eqn.outvars[0].aval.dtype))
        anchor_var = eqn.outvars[1]
        write(anchor_var, _coerce(out, anchor_var.aval))
        # any extra outvars (shouldn't exist for matched skeleta)
        for ov in eqn.outvars[2:]:
            raise NotImplementedError("unexpected extra scan outputs")
    else:
        write(eqn.outvars[0], _coerce(out, eqn.outvars[0].aval))


def _eval_scan_body(eqn, m: Match, select, read, write, ctx_factory,
                    on_select=None, contain=None):
    """Rebuild a ``lax.scan`` around a rewritten body (variant='scan_body'
    matches): the body was detected once; tracing it here selects kernels
    once, and the compiled loop reuses them on every iteration.  Operands
    closed over as scan consts stay concrete inside the body trace, so
    host-marshaling harnesses still work for loop-invariant sparse data."""
    params = eqn.params
    nconsts = params["num_consts"]
    ncarry = params["num_carry"]
    invals = [read(x) for x in eqn.invars]
    consts = invals[:nconsts]
    init = invals[nconsts:nconsts + ncarry]
    xs = invals[nconsts + ncarry:]
    body_cj, body_matches = m.body
    needed = needed_eqn_ids(body_cj, body_matches)

    def body_fn(carry, x):
        flat = list(consts) + list(carry) + list(x)
        outs = run_rewritten(body_cj, body_matches, select, flat,
                             ctx_factory, on_select, needed, contain)
        return tuple(outs[:ncarry]), tuple(outs[ncarry:])

    carry_out, ys = jax.lax.scan(
        body_fn, tuple(init), tuple(xs),
        length=params["length"], reverse=params["reverse"],
        unroll=params.get("unroll", 1))
    for ov, v in zip(eqn.outvars, list(carry_out) + list(ys)):
        write(ov, _coerce(v, ov.aval))


def _coerce(val, aval):
    val = jnp.asarray(val)
    if val.dtype != aval.dtype:
        val = val.astype(aval.dtype)
    if tuple(val.shape) != tuple(aval.shape):
        val = val.reshape(aval.shape)
    return val
