"""The yardstick's arithmetic: problem sizes and work counts by hand."""
from __future__ import annotations

import itertools
import json
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import work
from conftest import BENCH, run

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def dense_stencil(nx, ny, nz, diag=26.0, off=-1.0):
    n = nx * ny * nz
    a = np.zeros((n, n))
    for iz, iy, ix in itertools.product(range(nz), range(ny), range(nx)):
        i = (iz * ny + iy) * nx + ix
        for dz, dy, dx in itertools.product((-1, 0, 1), repeat=3):
            jx, jy, jz = ix + dx, iy + dy, iz + dz
            if 0 <= jx < nx and 0 <= jy < ny and 0 <= jz < nz:
                a[i, (jz * ny + jy) * nx + jx] = diag if dx == dy == dz == 0 \
                    else off
    return a


def test_hpcg_104_size_by_formula():
    assert work.stencil27_size(104, 104, 104) == (1_124_864, 29_791_000)


def test_stencil_matrix_and_reference_match_a_dense_build():
    nx, ny, nz = 4, 3, 5
    dense = dense_stencil(nx, ny, nz)
    cg = harness.load_module(BENCH / "drivers" / "lilac_cg.py")
    n, nnz, (val, col, row_ptr) = cg.stencil_csr(nx, ny, nz, 26.0, -1.0)
    assert (n, nnz) == work.stencil27_size(nx, ny, nz)
    assert nnz == np.count_nonzero(dense)
    built = np.zeros_like(dense)
    ptr = np.asarray(row_ptr)
    for i in range(n):
        built[i, np.asarray(col)[ptr[i]:ptr[i + 1]]] = \
            np.asarray(val)[ptr[i]:ptr[i + 1]]
    np.testing.assert_array_equal(built, dense)
    ref = harness.load_module(BENCH / "configs" / "hpcg-104.ref.py")
    cfg = {"nx": nx, "ny": ny, "nz": nz, "diagonal": 26.0,
           "off_diagonal": -1.0}
    x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
    np.testing.assert_allclose(np.asarray(ref.apply(cfg, x)), dense @ x,
                               rtol=1e-5, atol=1e-4)


def test_spmv_and_cg_iteration_by_hand():
    assert work.spmv(10, 30) == work.Work(60.0, 200.0)
    # SpMV + dots (4n flops, 3 vectors) + axpys (6n flops, 9 vectors)
    assert work.cg_iteration(10, 30) == work.Work(160.0, 680.0)
    w = work.cg_iteration(1_124_864, 29_791_000)
    assert w.least_s(PEAK) == pytest.approx(w.bytes / 819e9)
    assert w.bytes == 29_791_000 * 4 + 14 * 1_124_864 * 4


def test_moe_counts_by_hand():
    w = work.routed_experts(2, d_model=4, d_expert=3, topk=2, n_experts=5)
    assert w == work.Work(288.0, 320.0)
    # more tokens x top-k than experts: each expert's weights once
    assert work.experts_hit(32, 8, 40) == 40
    cfg = {"hidden_size": 8, "num_hidden_layers": 2,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "num_local_experts": 4, "num_experts_per_tok": 2,
           "intermediate_size": 3, "vocab_size": 10}
    assert work.decode_step(cfg, tokens=3, context=7) == \
        work.Work(5344.0, 3128.0)


def test_unknown_device_has_no_peaks():
    assert work.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peak_for("cpu")


def test_spmv_roofline_counts_the_matrix_whatever_the_harness(checkout):
    """Two runs that serve the SpMV through different harnesses count the
    same work: the metric reads only the matrix's n and nnz."""
    cfile = checkout / "bench" / "configs" / "hpcg-104.json"
    reader = harness.load_module(checkout / "bench" / "metrics"
                                 / "spmv_roofline.py")
    shares = {}
    for policy in ("jnp.segment", "jnp.ell"):
        cfile.write_text(json.dumps({**json.loads(cfile.read_text()),
                                     "lilac_policy": policy}))
        out = run(checkout, "cg.hpcg104", seconds=0.5)
        assert out.result["correct"], out.result
        assert f'"harness": ["{policy}"]' in out.notes[0]
        counters = {"n": 960, "nnz": work.stencil27_size(12, 10, 8)[1],
                    "spmv_calls": 100}
        ctx = SimpleNamespace(peak=PEAK, work=work, counters=counters,
                              summary=SimpleNamespace(
                                  module_s={"jit_baked": 1e-3}))
        shares[policy] = reader.read(ctx)
    assert shares["jnp.segment"] == shares["jnp.ell"]
    assert shares["jnp.ell"] == pytest.approx(
        work.spmv(960, 34 * 28 * 22).least_s(PEAK) / 1e-5 * 100)
