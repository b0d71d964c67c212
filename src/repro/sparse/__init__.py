"""Sparse matrix substrate: formats, conversions, reference ops.

Formats mirror the paper's §3.2 (CSR, JDS, COO) plus the TPU-native
adaptations (ELL row-slabs, BCSR 128x128 MXU tiles, DIA diagonal slabs).
"""
from repro.sparse.formats import (
    CSR,
    COO,
    ELL,
    JDS,
    BCSR,
    DIA,
    bcsr_from_dense,
    coo_from_dense,
    csr_from_dense,
    ell_from_csr,
    jds_from_csr,
)
from repro.sparse.ops import (
    spmv_csr_ref,
    spmv_coo_ref,
    spmv_ell_ref,
    spmv_jds_ref,
    bcsr_spmm_ref,
)
from repro.sparse.random import random_csr, random_bcsr

__all__ = [
    "CSR", "COO", "ELL", "JDS", "BCSR", "DIA",
    "csr_from_dense", "coo_from_dense", "ell_from_csr", "jds_from_csr",
    "bcsr_from_dense",
    "spmv_csr_ref", "spmv_coo_ref", "spmv_ell_ref", "spmv_jds_ref",
    "bcsr_spmm_ref",
    "random_csr", "random_bcsr",
]
