"""The diagonal (DIA) harness of the SpMV site: the CSR -> DIA conversion
and its refusal rule, the ``jnp.dia`` product against the naive
segment-sum SpMV, the baked plan it builds, the tuner's timing of it, and
its counters."""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import lilac
from repro.core import harness as H
from repro.core import marshal as M
from repro.core import spans
from repro.core import spec as SP
from repro.core.autotune import Autotuner
from repro.sparse import random_csr
from repro.sparse.convert import DIARefused, csr_to_dia
from repro.sparse.formats import CSR


@pytest.fixture(autouse=True)
def _fresh_table():
    spans.reset()
    yield
    spans.reset()


def _csr(rows, cols, r, c, v) -> CSR:
    """CSR of the triplets (r, c, v), sorted row-major."""
    order = np.lexsort((c, r))
    r, c, v = np.asarray(r)[order], np.asarray(c)[order], np.asarray(v)[order]
    row_ptr = np.concatenate([[0], np.cumsum(np.bincount(r, minlength=rows))])
    return CSR(val=jnp.asarray(v, jnp.float32),
               col_ind=jnp.asarray(c, jnp.int32),
               row_ptr=jnp.asarray(row_ptr, jnp.int32), shape=(rows, cols))


def stencil(nx, ny, nz) -> CSR:
    """HPCG's 27-point matrix in natural order: 26 on the diagonal, -1 at
    every neighbour inside the grid (edge rows hold fewer)."""
    n = nx * ny * nz
    i = np.arange(n)
    ix, iy, iz = i % nx, (i // nx) % ny, i // (nx * ny)
    r, c, v = [], [], []
    for dz, dy, dx in itertools.product((-1, 0, 1), repeat=3):
        ok = ((ix + dx >= 0) & (ix + dx < nx) & (iy + dy >= 0)
              & (iy + dy < ny) & (iz + dz >= 0) & (iz + dz < nz))
        r.append(i[ok])
        c.append(i[ok] + dz * nx * ny + dy * nx + dx)
        v.append(np.full(ok.sum(), 26.0 if (dx, dy, dz) == (0, 0, 0)
                         else -1.0))
    return _csr(n, n, np.concatenate(r), np.concatenate(c),
                np.concatenate(v))


def banded(rows, cols, offsets, seed) -> CSR:
    """Every in-range entry of the given diagonals, small integer values."""
    rng = np.random.default_rng(seed)
    r, c = [], []
    for o in offsets:
        i = np.arange(rows)
        ok = (i + o >= 0) & (i + o < cols)
        r.append(i[ok])
        c.append(i[ok] + o)
    r, c = np.concatenate(r), np.concatenate(c)
    v = rng.integers(-4, 5, r.shape[0]).astype(np.float32)
    v[v == 0] = 1.0
    return _csr(rows, cols, r, c, v)


MATRICES = {
    "stencil_5x4x3": lambda: stencil(5, 4, 3),
    "stencil_12x10x8": lambda: stencil(12, 10, 8),
    "tridiagonal": lambda: banded(50, 50, (-1, 0, 1), seed=1),
    "rect_banded": lambda: banded(30, 45, (-3, -1, 0, 2, 5, 14), seed=2),
}


def _row_ids(csr):
    return np.repeat(np.arange(csr.rows), np.diff(np.asarray(csr.row_ptr)))


def _naive(csr, x):
    return jax.ops.segment_sum(csr.val * x[csr.col_ind],
                               jnp.asarray(_row_ids(csr), jnp.int32),
                               num_segments=csr.rows)


def _x(cols, seed=0):
    # integer values: every product and partial sum is exact in float32,
    # so an offset off by one shows as a difference, never as rounding
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(-8, 9, cols), jnp.float32)


def _binding(csr, x, coo=False):
    b = {"a": csr.val, "colidx": csr.col_ind, "iv": x, "rows": csr.rows,
         "nnz": csr.nnz}
    if coo:
        b["rowidx"] = jnp.asarray(_row_ids(csr), jnp.int32)
    else:
        b["rowstr"] = csr.row_ptr
    return b


@pytest.mark.parametrize("coo", [False, True], ids=["csr", "coo"])
@pytest.mark.parametrize("name", sorted(MATRICES))
def test_dia_product_equals_naive_segment_sum(name, coo):
    csr = MATRICES[name]()
    x = _x(csr.cols)
    h = lilac.REGISTRY.get("spmv_coo" if coo else "spmv_csr", "jnp.dia")
    ctx = H.CallCtx(mode="host", cache=M.DataPlane(),
                    format="COO" if coo else "CSR")
    got = h(_binding(csr, x, coo), ctx)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(_naive(csr, x)),
                               rtol=1e-6)


def test_stencil_dia_stores_the_27_diagonals():
    nx, ny, nz = 12, 10, 8
    csr = stencil(nx, ny, nz)
    dia = csr_to_dia(csr)
    want = sorted(dz * nx * ny + dy * nx + dx for dz, dy, dx
                  in itertools.product((-1, 0, 1), repeat=3))
    assert dia.offsets == tuple(want)
    # each slab whole (8, 128) float32 tiles: 960 rows pad to 1024
    assert dia.data.shape == (27, 8, 128)
    assert dia.slabs.shape == (27, csr.rows)
    # every stored value is a nonzero of the matrix or an explicit zero,
    # and the padding holds zeros
    assert int(np.count_nonzero(np.asarray(dia.slabs))) == csr.nnz
    assert int(np.count_nonzero(np.asarray(dia.data))) == csr.nnz


@pytest.mark.parametrize("coo", [False, True], ids=["csr", "coo"])
def test_pinned_dia_plan_equals_naive_program(coo):
    csr = stencil(5, 4, 3)
    n, nnz = csr.rows, csr.nnz
    rows = jnp.asarray(_row_ids(csr), jnp.int32)

    def naive_csr(val, col, row_ptr, v):
        row = jnp.repeat(jnp.arange(n, dtype=jnp.int32), jnp.diff(row_ptr),
                         total_repeat_length=nnz)
        return jax.ops.segment_sum(val * v[col], row, num_segments=n)

    def naive_coo(val, col, row, v):
        return jax.ops.segment_sum(val * v[col], row, num_segments=n)

    naive, third = (naive_coo, rows) if coo else (naive_csr, csr.row_ptr)
    fast = lilac.compile(naive, mode="host", policy="jnp.dia")
    for k in range(3):
        x = _x(n, seed=k)
        got = fast(csr.val, csr.col_ind, third, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(naive(csr.val, csr.col_ind, third, x)),
            rtol=1e-6)
    info = fast.plan_info()
    assert info["baked"] == 1 and info["plan_hits"] >= 1
    assert [name for _, name in fast.last_selections] == ["jnp.dia"]
    assert spans.totals()["lilac.dia_packed"]["count"] == 1


@pytest.mark.parametrize("via", ["edge", "repack"])
def test_random_csr_refused_before_any_dia_buffer(via, monkeypatch):
    csr = random_csr(64, 64, density=0.05, seed=0)
    b = _binding(csr, jnp.ones(64))
    made = []
    zeros = np.zeros

    def spy(shape, *a, **kw):
        made.append(shape)
        return zeros(shape, *a, **kw)

    monkeypatch.setattr(np, "zeros", spy)
    with pytest.raises(DIARefused):
        if via == "edge":
            M.DataPlane().ensure("csr_binding", "DIA",
                                 (b["a"], b["colidx"], b["rowstr"]), b)
        else:
            SP.REPACKS["dia_pack"](b)
    monkeypatch.undo()
    assert not [s for s in made if np.ndim(s) == 1 and len(s) == 2], made
    got = spans.totals()
    assert got["lilac.dia_refused"]["count"] == 1
    assert "lilac.dia_packed" not in got


@pytest.mark.parametrize("far,refused", [(1, False), (2, True)])
def test_refusal_rule_is_bytes_of_dia_against_csr(far, refused):
    """A full diagonal of 10 rows plus ``far`` diagonals of one entry each:
    DIA stores (1 + far) * 10 float32 values; CSR stores 10 + far values
    and as many int32 indices.  One far diagonal fits (20 <= 22 values'
    worth), two do not (30 > 24)."""
    r = list(range(10)) + [0, 1][:far]
    c = list(range(10)) + [9, 8][:far]
    csr = _csr(10, 10, r, c, np.ones(len(r)))
    if refused:
        with pytest.raises(DIARefused):
            csr_to_dia(csr)
    else:
        assert csr_to_dia(csr).slabs.shape == (1 + far, 10)


def test_duplicate_entries_add():
    csr = _csr(4, 4, [0, 0, 1, 2, 3], [0, 0, 1, 2, 3], [1.0, 2.0, 1, 1, 1])
    dia = csr_to_dia(csr)
    assert dia.offsets == (0,)
    np.testing.assert_array_equal(np.asarray(dia.slabs), [[3, 1, 1, 1]])


@pytest.mark.parametrize("name,timed", [("stencil", True), ("random", False)])
def test_tuner_times_dia_where_it_packs(name, timed):
    """On a stencil the tuner measures ``jnp.dia`` like any candidate; on a
    random matrix the refusal drops it and the others are still timed."""
    csr = (stencil(6, 5, 4) if name == "stencil"
           else random_csr(120, 120, density=0.05, seed=3))
    b = _binding(csr, _x(csr.cols))
    cands = lilac.REGISTRY.candidates("spmv_csr", "CSR", "cpu", "host")
    assert "jnp.dia" in [h.name for h in cands]
    ctx = H.CallCtx(mode="host", cache=M.DataPlane(), format="CSR")
    winner, timings, marshal_s = Autotuner(budget=8).measure(
        cands, b, ctx, "host")[:3]
    assert ("jnp.dia" in timings) == timed
    assert "jnp.segment" in timings and winner in timings
    if timed:
        assert timings["jnp.dia"] > 0 and marshal_s["jnp.dia"] > 0
        assert spans.totals()["lilac.dia_packed"]["count"] == 1
    else:
        assert spans.totals()["lilac.dia_refused"]["count"] == 1


def test_fill_is_a_stat_of_the_enclosing_marshal_span(tmp_path):
    from jax.profiler import ProfileData
    csr = stencil(5, 4, 3)
    b = _binding(csr, _x(csr.cols))
    with jax.profiler.trace(str(tmp_path)):
        M.DataPlane().ensure("csr_binding", "DIA",
                             (b["a"], b["colidx"], b["rowstr"]), b)
        spans.annotate("lilac.marshal", fill=-1.0)   # no span open: no-op
    path, = tmp_path.rglob("*.xplane.pb")
    fills = [dict(ev.stats).get("fill")
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name == "lilac.marshal"]
    # stored values (the padding of each slab to 1024 rows included)
    assert fills == [pytest.approx(27 * 1024 / csr.nnz)]
