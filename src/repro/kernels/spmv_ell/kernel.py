"""ELL/JDS row-slab SpMV on the VPU.

Hardware adaptation: the paper's JDS layout exists to give GPU warps
coalesced loads down jagged diagonals.  On TPU the analogous resource is
dense VMEM tiles: rows are sorted by nnz (the JDS permutation, kept as a
marshaled invariant), padded to a lane-aligned width (ELL slab), and
processed in (rows_per_slab, width) tiles.

The gather ``vec[col[i, j]]`` runs as an XLA gather in front of the
kernel, which then streams the gathered slab beside the value slab.
Mosaic lowers no gather from an arbitrary-length vector (only 2-D
in-register gathers), so a vector resident in VMEM cannot be indexed by
``col`` inside the kernel; with the gather outside there is also no
bound on the vector's length.

Schedule parameters (declared as ``tune`` clauses in the HARNESS block and
swept by the autotuner — no module constants):

  rows_per_slab        rows per grid step; trades grid overhead against
                       VMEM working set per step.
  dimension_semantics  Mosaic grid annotation ('parallel' row slabs: each
                       slab writes its own output block).

Fused epilogue: the kernel optionally applies ``(+bias) -> relu|silu``
in-register before the single output store, eliminating the full
output-size HBM round-trip an unfused activation pays.

Grid: (num_slabs,) over row slabs.  The output (and bias) are carried as
(rows, 1) columns: a row sum keeps the sublane layout of the slab, and a
(R, 1) block is legal for every R that is a multiple of 8.
VMEM per step: val + gathered slabs (2 x R x W x 4B) + out column.
For R=256, W=128: 256 KiB — double-buffer safe.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import apply_epilogue_inregister, compiler_params


def _spmv_ell_kernel(val_ref, xg_ref, *rest, epilogue=None, has_bias=False):
    bias_ref = rest[0] if has_bias else None
    out_ref = rest[-1]
    val = val_ref[...].astype(jnp.float32)       # (R, W)
    xg = xg_ref[...].astype(jnp.float32)         # (R, W) = vec[col]
    acc = jnp.sum(val * xg, axis=1, keepdims=True)
    bias = bias_ref[...].astype(jnp.float32) if has_bias else None
    out_ref[...] = apply_epilogue_inregister(acc, bias, epilogue)


@functools.partial(jax.jit, static_argnames=("rows_per_slab",
                                             "dimension_semantics",
                                             "epilogue", "interpret"))
def spmv_ell_pallas(val: jax.Array,   # (rows, width)
                    col: jax.Array,   # (rows, width) int32
                    vec: jax.Array,   # (V,)
                    rows_per_slab: int = 256,
                    dimension_semantics: Optional[Tuple[str, ...]] = None,
                    epilogue: Optional[str] = None,
                    bias: Optional[jax.Array] = None,   # (rows,)
                    interpret: bool = False) -> jax.Array:
    rows, width = val.shape
    assert rows % rows_per_slab == 0, (rows, rows_per_slab)
    grid = (rows // rows_per_slab,)
    has_bias = bias is not None
    slab = pl.BlockSpec((rows_per_slab, width), lambda i: (i, 0))
    column = pl.BlockSpec((rows_per_slab, 1), lambda i: (i, 0))
    in_specs = [slab, slab]
    args = [val, jnp.take(vec, col, axis=0)]
    if has_bias:
        in_specs.append(column)
        args.append(bias.reshape(rows, 1))
    fn = pl.pallas_call(
        functools.partial(_spmv_ell_kernel, epilogue=epilogue,
                          has_bias=has_bias),
        grid=grid,
        in_specs=in_specs,
        out_specs=column,
        out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        interpret=interpret,
        **compiler_params(dimension_semantics),
    )
    return fn(*args).reshape(rows)
