"""Sparse matrix containers (pytrees) and host-side constructors.

These mirror the storage formats of the paper (§3.2 Fig. 4/5):

* CSR  — val / col_ind / row_ptr (paper Fig. 4)
* COO  — val / row / col
* JDS  — perm / nzcnt / jd_ptr / val / col_ind (paper Fig. 5)
* ELL  — row-padded (TPU adaptation of JDS: after the nnz row sort, rows are
         padded to a lane-aligned width so slabs are dense VMEM tiles)
* BCSR — block compressed sparse row with dense (bm, bn) blocks sized for the
         MXU; the TPU-native format for the Pallas matmul kernels.

All containers are registered pytrees so they flow through jit/shard_map.
Static metadata (shape, block size) lives in aux_data.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _register(cls, data_fields, meta_fields):
    def flatten(obj):
        return (
            tuple(getattr(obj, f) for f in data_fields),
            tuple(getattr(obj, f) for f in meta_fields),
        )

    def unflatten(meta, data):
        kwargs = dict(zip(data_fields, data))
        kwargs.update(dict(zip(meta_fields, meta)))
        return cls(**kwargs)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


@dataclasses.dataclass(frozen=True)
class CSR:
    """Compressed sparse row. row_ptr has length rows+1."""

    val: jax.Array      # (nnz,)
    col_ind: jax.Array  # (nnz,) int32
    row_ptr: jax.Array  # (rows+1,) int32
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return self.val.shape[0]

    @property
    def rows(self) -> int:
        return self.shape[0]

    @property
    def cols(self) -> int:
        return self.shape[1]

    def todense(self) -> jax.Array:
        rows, cols = self.shape
        row_ids = jnp.repeat(
            jnp.arange(rows, dtype=jnp.int32),
            jnp.diff(self.row_ptr),
            total_repeat_length=self.nnz,
        )
        out = jnp.zeros((rows, cols), self.val.dtype)
        return out.at[row_ids, self.col_ind].add(self.val)


_register(CSR, ("val", "col_ind", "row_ptr"), ("shape",))


@dataclasses.dataclass(frozen=True)
class COO:
    val: jax.Array  # (nnz,)
    row: jax.Array  # (nnz,) int32
    col: jax.Array  # (nnz,) int32
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return self.val.shape[0]


_register(COO, ("val", "row", "col"), ("shape",))


@dataclasses.dataclass(frozen=True)
class JDS:
    """Jagged diagonal storage (paper Fig. 5).

    Rows sorted by decreasing nnz; jagged diagonal j holds the j-th nonzero
    of every row that has one. jd_ptr[j] offsets into val/col_ind.
    """

    perm: jax.Array     # (rows,) int32 — perm[i] = original row of sorted row i
    nzcnt: jax.Array    # (rows,) int32 — nnz of sorted row i
    jd_ptr: jax.Array   # (max_nnz+1,) int32
    val: jax.Array      # (nnz,)
    col_ind: jax.Array  # (nnz,) int32
    shape: Tuple[int, int]


_register(JDS, ("perm", "nzcnt", "jd_ptr", "val", "col_ind"), ("shape",))


@dataclasses.dataclass(frozen=True)
class ELL:
    """Row-padded format (TPU slab adaptation of JDS).

    val/col (rows, width); padding entries have val=0, col=0 (valid gather).
    ``perm`` is the JDS-style row sort (identity if unsorted) so that slabs
    of consecutive rows have similar nnz and padding waste is bounded.
    """

    val: jax.Array   # (rows, width)
    col: jax.Array   # (rows, width) int32
    perm: jax.Array  # (rows,) int32
    shape: Tuple[int, int]

    @property
    def width(self) -> int:
        return self.val.shape[1]


_register(ELL, ("val", "col", "perm"), ("shape",))


@dataclasses.dataclass(frozen=True)
class BCSR:
    """Block CSR with dense (bm, bn) blocks — the MXU-native sparse format.

    blocks:       (nblocks, bm, bn) dense tiles
    block_col:    (nblocks,) int32 — block-column index of each tile
    block_rowptr: (block_rows+1,) int32 — CSR structure over tile rows
    """

    blocks: jax.Array
    block_col: jax.Array
    block_rowptr: jax.Array
    shape: Tuple[int, int]
    block_shape: Tuple[int, int]

    @property
    def nblocks(self) -> int:
        return self.blocks.shape[0]

    @property
    def block_rows(self) -> int:
        return self.shape[0] // self.block_shape[0]

    @functools.cached_property
    def all_block_rows_nonempty(self) -> bool:
        """True when every block-row owns at least one stored tile.  Gates
        in-kernel epilogue fusion (the last-visit trigger fires per
        block-row); computed once per packed matrix — a host sync here
        instead of on every kernel call."""
        return bool(np.all(np.diff(np.asarray(self.block_rowptr)) > 0))

    def todense(self) -> jax.Array:
        bm, bn = self.block_shape
        rows, cols = self.shape
        out = np.zeros((rows, cols), dtype=np.asarray(self.blocks).dtype)
        bp = np.asarray(self.block_rowptr)
        bc = np.asarray(self.block_col)
        blk = np.asarray(self.blocks)
        for br in range(self.block_rows):
            for k in range(int(bp[br]), int(bp[br + 1])):
                out[br * bm:(br + 1) * bm, bc[k] * bn:(bc[k] + 1) * bn] = blk[k]
        return jnp.asarray(out)


_register(BCSR, ("blocks", "block_col", "block_rowptr"), ("shape", "block_shape"))


# ---------------------------------------------------------------------------
# Host-side constructors (numpy; used by data loading and tests).
# ---------------------------------------------------------------------------

def csr_from_dense(dense) -> CSR:
    d = np.asarray(dense)
    rows, cols = d.shape
    r, c = np.nonzero(d)           # row-major order == CSR order
    counts = np.bincount(r, minlength=rows)
    row_ptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return CSR(
        val=jnp.asarray(d[r, c]),
        col_ind=jnp.asarray(c.astype(np.int32)),
        row_ptr=jnp.asarray(row_ptr),
        shape=(rows, cols),
    )


def coo_from_dense(dense) -> COO:
    d = np.asarray(dense)
    r, c = np.nonzero(d)
    return COO(
        val=jnp.asarray(d[r, c]),
        row=jnp.asarray(r.astype(np.int32)),
        col=jnp.asarray(c.astype(np.int32)),
        shape=d.shape,
    )


def jds_from_csr(csr: CSR) -> JDS:
    """Paper Fig. 5: sort rows by decreasing nnz, store jagged diagonals."""
    row_ptr = np.asarray(csr.row_ptr)
    val = np.asarray(csr.val)
    col = np.asarray(csr.col_ind)
    rows = csr.rows
    nnz_per_row = np.diff(row_ptr)
    perm = np.argsort(-nnz_per_row, kind="stable").astype(np.int32)
    nzcnt = nnz_per_row[perm].astype(np.int32)
    max_nnz = int(nzcnt[0]) if rows else 0
    jd_val, jd_col, jd_ptr = [], [], [0]
    for j in range(max_nnz):
        for i in range(rows):
            if nzcnt[i] > j:
                p = row_ptr[perm[i]] + j
                jd_val.append(val[p])
                jd_col.append(col[p])
            else:
                break  # rows sorted by decreasing nnz
        jd_ptr.append(len(jd_val))
    return JDS(
        perm=jnp.asarray(perm),
        nzcnt=jnp.asarray(nzcnt),
        jd_ptr=jnp.asarray(np.array(jd_ptr, dtype=np.int32)),
        val=jnp.asarray(np.array(jd_val, dtype=val.dtype)),
        col_ind=jnp.asarray(np.array(jd_col, dtype=np.int32)),
        shape=csr.shape,
    )


def ell_from_csr(csr: CSR, width: int | None = None, sort_rows: bool = True,
                 lane: int = 8) -> ELL:
    """TPU slab format: pad each row to ``width`` (lane-aligned).

    ``sort_rows`` applies the JDS permutation so padding waste within a slab
    is bounded; the permutation is part of the format (a marshaled invariant).
    """
    row_ptr = np.asarray(csr.row_ptr)
    valv = np.asarray(csr.val)
    colv = np.asarray(csr.col_ind)
    rows = csr.rows
    nnz_per_row = np.diff(row_ptr)
    if sort_rows:
        perm = np.argsort(-nnz_per_row, kind="stable").astype(np.int32)
    else:
        perm = np.arange(rows, dtype=np.int32)
    w = int(nnz_per_row.max()) if rows and nnz_per_row.size else 0
    if width is not None:
        w = max(w, width)
    w = max(lane, ((w + lane - 1) // lane) * lane)
    val = np.zeros((rows, w), dtype=valv.dtype)
    col = np.zeros((rows, w), dtype=np.int32)
    # entry k of source row r lands in slab row inv[r], slot k - row_ptr[r]
    src_row = np.repeat(np.arange(rows), nnz_per_row)
    inv = np.empty(rows, np.int64)
    inv[perm] = np.arange(rows)
    slot = np.arange(src_row.shape[0]) - row_ptr[src_row]
    val[inv[src_row], slot] = valv[:src_row.shape[0]]
    col[inv[src_row], slot] = colv[:src_row.shape[0]]
    return ELL(val=jnp.asarray(val), col=jnp.asarray(col),
               perm=jnp.asarray(perm), shape=csr.shape)


def bcsr_from_csr(csr: CSR, block_shape=(8, 128)) -> BCSR:
    """Tile a CSR matrix without densifying it: the same BCSR as
    ``bcsr_from_dense(pad(csr.todense()), block_shape)`` — nonzero tiles in
    block-row-major order, one explicit zero tile at block-column 0 for
    every empty block-row — built from the nonzeros alone."""
    bm, bn = block_shape
    rows, cols = csr.shape
    R, C = -(-rows // bm) * bm, -(-cols // bn) * bn
    row_ptr = np.asarray(csr.row_ptr)
    nnz = int(row_ptr[-1])
    val = np.asarray(csr.val)[:nnz]
    col = np.asarray(csr.col_ind)[:nnz].astype(np.int64)
    row = np.repeat(np.arange(rows, dtype=np.int64), np.diff(row_ptr))
    keep = val != 0
    row, col, val = row[keep], col[keep], val[keep]
    nbc = C // bn
    tile_of = (row // bm) * nbc + col // bn
    tiles, where = np.unique(tile_of, return_inverse=True)
    need = tiles.shape[0] * bm * bn * val.dtype.itemsize
    host = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    if need > host:
        # a scattered matrix puts about one nonzero in each tile: refuse
        # before the allocation rather than let the OS kill the process
        raise MemoryError(f"BCSR{bm}x{bn} of this matrix holds "
                          f"{tiles.shape[0]} tiles ({need} bytes), more "
                          f"than the host's {host} bytes")
    blocks = np.zeros((tiles.shape[0], bm, bn), dtype=val.dtype)
    np.add.at(blocks, (where, row % bm, col % bn), val)
    # a tile whose entries cancel is zero in the dense form: drop it
    live = blocks.reshape(blocks.shape[0], -1).any(axis=1)
    tiles, blocks = tiles[live], blocks[live]
    empty = np.setdiff1d(np.arange(R // bm), tiles // nbc)
    order = np.argsort(np.concatenate([tiles, empty * nbc]), kind="stable")
    blocks = np.concatenate(
        [blocks, np.zeros((empty.shape[0], bm, bn), blocks.dtype)])[order]
    tiles = np.concatenate([tiles, empty * nbc])[order]
    counts = np.bincount(tiles // nbc, minlength=R // bm)
    return BCSR(
        blocks=jnp.asarray(blocks),
        block_col=jnp.asarray((tiles % nbc).astype(np.int32)),
        block_rowptr=jnp.asarray(
            np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)),
        shape=(R, C),
        block_shape=(bm, bn),
    )


def bcsr_from_dense(dense, block_shape=(8, 128)) -> BCSR:
    """Tile a dense matrix and keep only nonzero tiles (MXU-native)."""
    d = np.asarray(dense)
    bm, bn = block_shape
    rows, cols = d.shape
    assert rows % bm == 0 and cols % bn == 0, (d.shape, block_shape)
    blocks, block_col, block_rowptr = [], [], [0]
    for br in range(rows // bm):
        row_has_block = False
        for bc in range(cols // bn):
            tile = d[br * bm:(br + 1) * bm, bc * bn:(bc + 1) * bn]
            if np.any(tile != 0):
                blocks.append(tile)
                block_col.append(bc)
                row_has_block = True
        if not row_has_block:
            # keep one explicit zero block per empty block-row so the Pallas
            # kernel's revisiting accumulator always initializes the output
            blocks.append(np.zeros((bm, bn), dtype=d.dtype))
            block_col.append(0)
        block_rowptr.append(len(blocks))
    return BCSR(
        blocks=jnp.asarray(np.stack(blocks)),
        block_col=jnp.asarray(np.array(block_col, dtype=np.int32)),
        block_rowptr=jnp.asarray(np.array(block_rowptr, dtype=np.int32)),
        shape=(rows, cols),
        block_shape=(bm, bn),
    )
