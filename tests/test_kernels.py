"""Pallas kernel allclose sweeps vs the pure-jnp oracles (interpret=True).

Shapes and dtypes are swept per kernel; tolerance accounts for f32
accumulation differences only.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sparse import ell_from_csr, random_bcsr, random_csr
from repro.kernels.bsr_spmm import ops as bsr_ops
from repro.kernels.spmv_ell import ops as ell_ops
from repro.kernels.moe_gmm import ops as gmm_ops
from repro.kernels.moe_gmm.kernel import gmm_pallas
from repro.kernels.moe_gmm.ref import gmm_ref


@pytest.mark.parametrize("rows,cols,n,bm,density", [
    (256, 384, 256, 128, 0.3),
    (128, 128, 128, 64, 0.5),
    (384, 256, 128, 128, 0.1),
])
def test_bsr_spmm_shapes(rows, cols, n, bm, density):
    bcsr = random_bcsr(rows, cols, block_shape=(bm, 128),
                       block_density=density, seed=rows + n)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (cols, n)).astype(np.float32))
    ref = bsr_ops.bsr_spmm_oracle(bcsr, x)
    out = bsr_ops.bsr_spmm(bcsr, x, interpret=True)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bsr_spmm_dtypes(dtype):
    bcsr = random_bcsr(256, 256, block_shape=(128, 128), block_density=0.4,
                       seed=7)
    bcsr = jax.tree.map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a, bcsr)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (256, 128)).astype(np.float32)).astype(dtype)
    ref = bsr_ops.bsr_spmm_oracle(bcsr, x)
    out = bsr_ops.bsr_spmm(bcsr, x, interpret=True)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=tol, rtol=tol)


def test_bsr_spmm_empty_block_rows():
    """Rows with no stored blocks must produce zeros (explicit zero block)."""
    d = np.zeros((256, 256), np.float32)
    d[:128] = np.random.default_rng(0).standard_normal((128, 256))
    from repro.sparse import bcsr_from_dense
    bcsr = bcsr_from_dense(d, (128, 128))
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (256, 128)).astype(np.float32))
    out = bsr_ops.bsr_spmm(bcsr, x, interpret=True)
    np.testing.assert_allclose(np.asarray(out)[128:], 0.0)
    np.testing.assert_allclose(out, d @ np.asarray(x), atol=1e-3)


@pytest.mark.parametrize("rows,cols,density,skew", [
    (200, 300, 0.05, 0.0),
    (64, 64, 0.2, 0.0),
    (512, 128, 0.02, 1.0),      # power-law rows (graph-like)
])
def test_spmv_ell_shapes(rows, cols, density, skew):
    csr = random_csr(rows, cols, density=density, seed=rows, skew=skew)
    ell = ell_from_csr(csr)
    vec = jnp.asarray(np.random.default_rng(3).standard_normal(
        cols).astype(np.float32))
    ref = ell_ops.spmv_ell_oracle(ell.val, ell.col, vec)
    out = ell_ops.spmv_ell(ell.val, ell.col, vec, interpret=True)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_spmv_ell_windowed():
    """Columns far past the row count (the case a column window once
    served): the gather in front of the kernel reads the whole vector,
    however long."""
    csr = random_csr(128, 4096, density=0.01, seed=11)
    ell = ell_from_csr(csr, lane=128)
    vec = jnp.asarray(np.random.default_rng(4).standard_normal(
        4096).astype(np.float32))
    ref = ell_ops.spmv_ell_oracle(ell.val, ell.col, vec)
    out = ell_ops.spmv_ell(ell.val, ell.col, vec, rows_per_slab=64,
                           dimension_semantics="parallel", interpret=True)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_spmv_ell_fused_bias_relu():
    csr = random_csr(200, 300, density=0.05, seed=5)
    ell = ell_from_csr(csr)
    rng = np.random.default_rng(6)
    vec = jnp.asarray(rng.standard_normal(300).astype(np.float32))
    bias = jnp.asarray(rng.standard_normal(200).astype(np.float32))
    ref = jnp.maximum(ell_ops.spmv_ell_oracle(ell.val, ell.col, vec) + bias,
                      0.0)
    out = ell_ops.spmv_ell(ell.val, ell.col, vec, epilogue="relu", bias=bias,
                           interpret=True)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def _ell_by_row_loop(csr, lane, sort_rows):
    """The row-at-a-time ELL packing ``ell_from_csr`` vectorizes."""
    row_ptr, valv, colv = (np.asarray(a) for a in
                           (csr.row_ptr, csr.val, csr.col_ind))
    nnz = np.diff(row_ptr)
    perm = (np.argsort(-nnz, kind="stable") if sort_rows
            else np.arange(csr.rows))
    w = max(lane, -(-int(nnz.max()) // lane) * lane)
    val = np.zeros((csr.rows, w), valv.dtype)
    col = np.zeros((csr.rows, w), np.int32)
    for i, src in enumerate(perm):
        n = int(nnz[src])
        val[i, :n] = valv[row_ptr[src]:row_ptr[src] + n]
        col[i, :n] = colv[row_ptr[src]:row_ptr[src] + n]
    return val, col


@pytest.mark.parametrize("sort_rows", [True, False])
@pytest.mark.parametrize("seed", range(4))
def test_ell_from_csr_matches_row_loop(seed, sort_rows):
    csr = random_csr(100 + seed, 80, density=0.1, seed=seed,
                     skew=float(seed % 2))
    ell = ell_from_csr(csr, lane=8, sort_rows=sort_rows)
    val, col = _ell_by_row_loop(csr, 8, sort_rows)
    np.testing.assert_array_equal(np.asarray(ell.val), val)
    np.testing.assert_array_equal(np.asarray(ell.col), col)


@pytest.mark.parametrize("block_shape", [(8, 128), (16, 128), (128, 128)])
@pytest.mark.parametrize("seed", range(3))
def test_bcsr_from_csr_matches_dense_tiling(seed, block_shape):
    """Tiling the nonzeros gives the BCSR of the padded dense matrix, with
    its explicit zero tile for every empty block-row."""
    from repro.sparse.formats import bcsr_from_csr, bcsr_from_dense
    rng = np.random.default_rng(seed)
    r, c = (int(v) for v in rng.integers(1, 300, 2))
    csr = random_csr(r, c, density=float(rng.random() * 0.1), seed=seed)
    bm, bn = block_shape
    dense = np.pad(np.asarray(csr.todense()), ((0, -r % bm), (0, -c % bn)))
    got, want = bcsr_from_csr(csr, block_shape), bcsr_from_dense(
        dense, block_shape)
    assert got.shape == want.shape and got.block_shape == want.block_shape
    for a, b in ((got.blocks, want.blocks), (got.block_col, want.block_col),
                 (got.block_rowptr, want.block_rowptr)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_random_graph_csr_is_column_stochastic():
    from repro.sparse.random import random_graph_csr
    d = np.asarray(random_graph_csr(64, 6, seed=3).todense())
    col_sums = d.sum(axis=0)
    np.testing.assert_allclose(col_sums[col_sums > 0], 1.0, rtol=1e-6)
    assert not np.diag(d).any()


@pytest.mark.parametrize("T,D,F,E,K,tm", [
    (64, 128, 256, 8, 2, 16),
    (32, 96, 192, 4, 4, 8),
    (128, 64, 128, 16, 2, 32),
])
def test_moe_gmm_shapes(T, D, F, E, K, tm):
    rng = np.random.default_rng(T + E)
    x = jnp.asarray(rng.standard_normal((T, D)).astype(np.float32))
    gate = jnp.asarray(rng.random((T, K)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, E, (T, K)).astype(np.int32))
    wg = jnp.asarray(rng.standard_normal((E, D, F)).astype(np.float32) * .05)
    wu = jnp.asarray(rng.standard_normal((E, D, F)).astype(np.float32) * .05)
    wd = jnp.asarray(rng.standard_normal((E, F, D)).astype(np.float32) * .05)
    ref = gmm_ops.moe_ffn_oracle(x, gate, idx, wg, wu, wd)
    out = gmm_ops.moe_ffn(x, gate, idx, wg, wu, wd, tm=tm, interpret=True)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-3)


def test_gmm_kernel_direct():
    """The raw group-aligned gmm vs its oracle."""
    rng = np.random.default_rng(0)
    Tp, D, F, E, tm = 64, 32, 64, 4, 16
    xs = jnp.asarray(rng.standard_normal((Tp, D)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((E, D, F)).astype(np.float32))
    te = jnp.asarray(rng.integers(0, E, Tp // tm).astype(np.int32))
    ref = gmm_ref(xs, w, te, tm)
    out = gmm_pallas(xs, w, te, tm=tm, fn=32, dk=16, interpret=True)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)
