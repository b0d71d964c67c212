"""Rewrite + harness correctness (paper §4.1.2): the optimized program
must compute the same values as the original, for every backend."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import lilac
from repro.core import REGISTRY
from repro.sparse import random_csr


ROWS, COLS = 64, 48


@pytest.fixture(scope="module")
def problem():
    csr = random_csr(ROWS, COLS, density=0.12, seed=1)
    rng = np.random.default_rng(2)
    vec = jnp.asarray(rng.standard_normal(COLS).astype(np.float32))
    return csr, vec


def naive_spmv(val, col, row_ptr, vec):
    row = jnp.repeat(jnp.arange(ROWS, dtype=jnp.int32), jnp.diff(row_ptr),
                     total_repeat_length=val.shape[0])
    return jax.ops.segment_sum(val * vec[col], row, num_segments=ROWS)


def test_trace_mode_equivalence(problem):
    csr, vec = problem
    ref = naive_spmv(csr.val, csr.col_ind, csr.row_ptr, vec)
    opt = lilac.compile(naive_spmv)
    out = opt(csr.val, csr.col_ind, csr.row_ptr, vec)
    assert len(opt.last_report.matches) == 1
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_trace_mode_is_jittable(problem):
    csr, vec = problem
    ref = naive_spmv(csr.val, csr.col_ind, csr.row_ptr, vec)
    opt = lilac.compile(naive_spmv)
    out = jax.jit(lambda *a: opt(*a))(csr.val, csr.col_ind, csr.row_ptr, vec)
    np.testing.assert_allclose(out, ref, atol=1e-5)


@pytest.mark.parametrize("backend", ["jnp.segment", "jnp.ell", "jnp.bcsr",
                                     "jnp.dense", "pallas.ell", "pallas.bcsr"])
def test_every_backend_equivalent(problem, backend):
    """Table 2's premise: all harnesses compute the same function."""
    csr, vec = problem
    ref = naive_spmv(csr.val, csr.col_ind, csr.row_ptr, vec)
    acc = lilac.compile(naive_spmv, mode="host", policy=backend)
    out = acc(csr.val, csr.col_ind, csr.row_ptr, vec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_unmatched_code_passes_through(problem):
    csr, vec = problem

    def f(val, col, row_ptr, vec):
        y = naive_spmv(val, col, row_ptr, vec)
        return jnp.tanh(y) + 1.0, y.sum()

    opt = lilac.compile(f)
    out, s = opt(csr.val, csr.col_ind, csr.row_ptr, vec)
    ref_y = naive_spmv(csr.val, csr.col_ind, csr.row_ptr, vec)
    np.testing.assert_allclose(out, jnp.tanh(ref_y) + 1.0, atol=1e-5)
    np.testing.assert_allclose(s, ref_y.sum(), rtol=1e-5)


def test_disabled_pass_is_identity(problem):
    csr, vec = problem
    opt = lilac.compile(naive_spmv, enabled=False)
    out = opt(csr.val, csr.col_ind, csr.row_ptr, vec)
    ref = naive_spmv(csr.val, csr.col_ind, csr.row_ptr, vec)
    np.testing.assert_allclose(out, ref, atol=0)


def test_loop_form_rewrite():
    rng = np.random.default_rng(3)
    val = jnp.asarray(rng.standard_normal(40).astype(np.float32))
    row = jnp.asarray(rng.integers(0, 16, 40).astype(np.int32))
    col = jnp.asarray(rng.integers(0, 8, 40).astype(np.int32))
    vec = jnp.asarray(rng.standard_normal(8).astype(np.float32))

    def f(val, row, col, vec):
        def body(j, out):
            return out.at[row[j]].add(val[j] * vec[col[j]])
        return jax.lax.fori_loop(0, 40, body, jnp.zeros(16))

    ref = f(val, row, col, vec)
    opt = lilac.compile(f)
    out = opt(val, row, col, vec)
    assert opt.last_report.matches[0].variant == "loop"
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_moe_rewrite_flop_reduction():
    """The rewritten MoE must be numerically equal AND compile to fewer
    FLOPs (the paper's speedup, visible in cost_analysis)."""
    from repro.models.layers import _moe_naive_2d
    rng = np.random.default_rng(0)
    T, D, F, E, K = 64, 32, 64, 8, 2
    args = (jnp.asarray(rng.standard_normal((T, D)).astype(np.float32)),
            jnp.asarray(rng.random((T, K)).astype(np.float32)),
            jnp.asarray(rng.integers(0, E, (T, K)).astype(np.int32)),
            jnp.asarray(rng.standard_normal((E, D, F)).astype(np.float32) * .1),
            jnp.asarray(rng.standard_normal((E, D, F)).astype(np.float32) * .1),
            jnp.asarray(rng.standard_normal((E, F, D)).astype(np.float32) * .1))
    ref = _moe_naive_2d(*args)
    opt = lilac.compile(_moe_naive_2d)
    out = opt(*args)
    np.testing.assert_allclose(out, ref, atol=1e-4)
    c0 = jax.jit(_moe_naive_2d).lower(*args).compile().cost_analysis()
    c1 = jax.jit(lambda *a: opt(*a)).lower(*args).compile().cost_analysis()
    # older jaxlibs return a per-device list, newer ones a flat dict
    c0 = c0[0] if isinstance(c0, (list, tuple)) else c0
    c1 = c1[0] if isinstance(c1, (list, tuple)) else c1
    assert c1["flops"] < 0.7 * c0["flops"]


def test_autotune_policy(problem):
    csr, vec = problem
    acc = lilac.compile(naive_spmv, mode="host", policy="autotune")
    out = acc(csr.val, csr.col_ind, csr.row_ptr, vec)
    ref = naive_spmv(csr.val, csr.col_ind, csr.row_ptr, vec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
    # winner is cached per signature
    assert len(REGISTRY._autotune_cache) >= 1


def test_host_interpreter_frees_dead_values():
    """Host mode runs the program eagerly: a value no later equation reads
    is dropped as it dies, so an unrolled loop over stacked weights holds
    one layer's slice at a time, not one per layer."""
    from jax.extend import core as jex_core

    from repro.core.rewrite import run_rewritten
    live = []
    # an identity whose eager rule runs in Python when the interpreter binds
    # it (a host callback would run whenever the asynchronous dispatch
    # reaches it), counting the arrays alive at that point of the program
    count_p = jex_core.Primitive("count_live_arrays")
    count_p.def_impl(lambda x: live.append(len(jax.live_arrays())) or x)
    count_p.def_abstract_eval(lambda x: x)

    def f(w, x):
        for j in range(w.shape[0]):
            x = count_p.bind(jnp.tanh(x @ w[j]))
        return x

    w = jnp.full((6, 16, 16), 0.1)
    x = jnp.ones((2, 16))
    jaxpr = jax.make_jaxpr(f)(w, x)
    out, = run_rewritten(jaxpr, [], None, [w, x], None)
    # the six slices of w and their products never pile up
    assert len(live) == 6 and max(live) <= live[0] + 2, live
    np.testing.assert_allclose(out, f(w, x), rtol=1e-6)
