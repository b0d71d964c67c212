"""Deterministic random sparse matrix generators (host-side numpy).

Used by tests, benchmarks and the graph-analytics examples; stands in for
the UFlorida collection matrices of the paper's evaluation (§5).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from repro.sparse.formats import BCSR, CSR, bcsr_from_dense, csr_from_dense


def random_dense_sparse(rows: int, cols: int, density: float, seed: int = 0,
                        dtype=np.float32, skew: float = 0.0) -> np.ndarray:
    """Dense array with ~density nonzeros; ``skew`` > 0 gives power-law rows
    (graph-like degree distribution, the hard case for padded formats)."""
    rng = np.random.default_rng(seed)
    if skew > 0:
        # per-row density drawn from a Pareto-ish distribution
        row_density = density * (1.0 + rng.pareto(1.0 + 1.0 / skew, rows))
        row_density = np.minimum(row_density, 1.0)
        mask = rng.random((rows, cols)) < row_density[:, None]
    else:
        mask = rng.random((rows, cols)) < density
    vals = rng.standard_normal((rows, cols)).astype(dtype)
    return np.where(mask, vals, 0).astype(dtype)


def random_csr(rows: int, cols: int, density: float = 0.05, seed: int = 0,
               dtype=np.float32, skew: float = 0.0) -> CSR:
    return csr_from_dense(random_dense_sparse(rows, cols, density, seed, dtype, skew))


def random_bcsr(rows: int, cols: int, block_shape=(8, 128),
                block_density: float = 0.2, seed: int = 0,
                dtype=np.float32) -> BCSR:
    rng = np.random.default_rng(seed)
    bm, bn = block_shape
    mask = rng.random((rows // bm, cols // bn)) < block_density
    d = rng.standard_normal((rows, cols)).astype(dtype)
    d = d * np.kron(mask, np.ones((bm, bn))).astype(dtype)
    return bcsr_from_dense(d, block_shape)


def random_graph_edges(nodes: int, avg_degree: float = 8.0, seed: int = 0
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Erdos-Renyi G(n, m) edge list with m = nodes * avg_degree draws:
    (src, dst) int64 arrays sorted row-major, no self loops, no duplicate
    edges.  Built from the edges alone, never an (n, n) array, so graphs of
    millions of nodes cost memory in proportion to their edges."""
    rng = np.random.default_rng(seed)
    m = int(round(nodes * avg_degree))
    src = rng.integers(0, nodes, m, dtype=np.int64)
    dst = rng.integers(0, nodes, m, dtype=np.int64)
    key = np.unique(src[src != dst] * nodes + dst[src != dst])
    return key // nodes, key % nodes


def csr_from_edges(rows: int, cols: int, src: np.ndarray, dst: np.ndarray,
                   val: np.ndarray) -> CSR:
    """CSR of the entries ``(src[k], dst[k]) = val[k]``, given sorted
    row-major without duplicates."""
    counts = np.bincount(src, minlength=rows)
    row_ptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return CSR(val=jnp.asarray(val), col_ind=jnp.asarray(dst.astype(np.int32)),
               row_ptr=jnp.asarray(row_ptr), shape=(rows, cols))


def random_graph_csr(nodes: int, avg_degree: float = 8.0, seed: int = 0,
                     dtype=np.float32) -> CSR:
    """Erdos-Renyi adjacency in CSR with column-stochastic values
    (PageRank): entry (i, j) is 1 / in-degree(j)."""
    src, dst = random_graph_edges(nodes, avg_degree, seed)
    deg = np.bincount(dst, minlength=nodes).astype(dtype)
    return csr_from_edges(nodes, nodes, src, dst,
                          (1.0 / deg[dst]).astype(dtype))
