"""Format conversions used as marshaled invariants (LiLAC-How INPUTs).

Each conversion is expensive relative to one SpMV — exactly the paper's
cudaMemcpy / SparseX-tuning situation — so the marshaling cache (core.marshal)
memoizes them keyed on the source arrays' fingerprints.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.sparse.formats import (
    BCSR, CSR, ELL, JDS, bcsr_from_csr, ell_from_csr, jds_from_csr,
)


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


def csr_to_dense(csr: CSR):
    return csr.todense()


def pad_vector(vec, to: int):
    v = jnp.asarray(vec)
    if v.shape[0] < to:
        v = jnp.pad(v, (0, to - v.shape[0]))
    return v
