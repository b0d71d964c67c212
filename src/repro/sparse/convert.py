"""Format conversions used as marshaled invariants (LiLAC-How INPUTs).

Each conversion is expensive relative to one SpMV — exactly the paper's
cudaMemcpy / SparseX-tuning situation — so the marshaling cache (core.marshal)
memoizes them keyed on the source arrays' fingerprints.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.sparse.formats import (
    BCSR, CSR, DIA, DIA_ROW_ALIGN, ELL, JDS, bcsr_from_csr, ell_from_csr,
    jds_from_csr,
)


class DIARefused(ValueError):
    """The matrix stores too few nonzeros per diagonal for DIA to pay."""


def infer_cols(col_ind, explicit_cols: int | None = None) -> int:
    """The paper's `cols = max(col_ind)+1` invariant (Fig. 7 lines 2-5 /
    Fig. 9 `Maximum` INPUT)."""
    if explicit_cols is not None:
        return int(explicit_cols)
    c = np.asarray(col_ind)
    return int(c.max()) + 1 if c.size else 0


def csr_to_ell(csr: CSR, **kw) -> ELL:
    return ell_from_csr(csr, **kw)


def csr_to_jds(csr: CSR) -> JDS:
    return jds_from_csr(csr)


def csr_to_bcsr(csr: CSR, block_shape=(8, 128)) -> BCSR:
    return bcsr_from_csr(csr, block_shape)


def csr_to_dia(csr: CSR) -> DIA:
    """Diagonal storage of a CSR matrix, built from the nonzeros in O(nnz)
    (duplicate entries add).

    A product over DIA reads ``ndiag * rows`` values and no indices; over
    CSR it reads ``nnz`` values and ``nnz`` column indices.  Where DIA would
    read more bytes (for float32 values and int32 indices: ``ndiag * rows >
    2 * nnz``) this raises :class:`DIARefused` after one histogram of the
    offsets, before any slab is allocated."""
    rows, cols = csr.shape
    row_ptr = np.asarray(csr.row_ptr)
    nnz = int(row_ptr[-1])
    val = np.asarray(csr.val)[:nnz]
    col = np.asarray(csr.col_ind)[:nnz]
    row = np.repeat(np.arange(rows, dtype=np.int64), np.diff(row_ptr))
    shifted = col.astype(np.int64) - row + (rows - 1)   # offset + rows - 1
    span = max(rows + cols - 1, 0)
    present = np.flatnonzero(np.bincount(shifted, minlength=span))
    ndiag = present.shape[0]
    dia_bytes = ndiag * rows * val.dtype.itemsize
    csr_bytes = nnz * (val.dtype.itemsize + col.dtype.itemsize)
    if dia_bytes > csr_bytes:
        raise DIARefused(f"DIA of this matrix stores {ndiag} diagonals of "
                         f"{rows} rows ({dia_bytes} bytes), more than the "
                         f"{csr_bytes} bytes of its CSR values and indices")
    slot = np.zeros(span, np.int64)
    slot[present] = np.arange(ndiag)
    padded = -(-rows // DIA_ROW_ALIGN) * DIA_ROW_ALIGN
    data = np.zeros((ndiag, padded), val.dtype)
    np.add.at(data.reshape(-1), slot[shifted] * padded + row, val)
    return DIA(data=jnp.asarray(data.reshape(ndiag, padded // 128, 128)),
               offsets=tuple(int(o) for o in present - (rows - 1)),
               shape=(rows, cols))


def csr_to_dense(csr: CSR):
    return csr.todense()


def pad_vector(vec, to: int):
    v = jnp.asarray(vec)
    if v.shape[0] < to:
        v = jnp.pad(v, (0, to - v.shape[0]))
    return v
