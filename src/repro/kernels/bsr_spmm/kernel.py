"""BCSR block-sparse x dense matmul on the MXU (TPU-native SpMM).

Hardware adaptation (DESIGN.md §2): a GPU csrmv assigns threads to rows and
gathers scalars — no TPU analogue.  Instead the sparse matrix is stored as
dense (bm, bk) tiles (BCSR) sized for the MXU; the kernel walks the stored
tiles in CSR order, streaming each tile and the matching rhs block through
VMEM and accumulating into the output block for the current block-row.

Grid: (n_tiles, nnzb) — the block index k iterates fastest, so all visits
to one output block-row are consecutive; the accumulator lives in the
output VMEM ref and is zeroed when a new block-row begins (is_first), the
standard Pallas revisiting-accumulator pattern.

Scalar prefetch: block_row (nnzb,) and block_col (nnzb,) arrive as SMEM
scalars *before* the grid runs, so the BlockSpec index_maps can use them to
steer the DMA of rhs/out tiles — this is the TPU-idiomatic equivalent of
indirect addressing.

Schedule parameters (``tune`` clauses in the HARNESS blocks, swept by the
autotuner): ``bn`` — the rhs/output block width, trading DMA granularity
against VMEM per step — and ``dimension_semantics`` for the n-tile grid
dimension (the nnzb dimension is always 'arbitrary': it revisits the
accumulator).

Fused epilogue: on the *last* visit to an output block-row (the next
stored tile belongs to a different row), the kernel applies
``(+bias) -> relu|silu`` in-register before the block leaves VMEM.  Bias
can be per-row ((rows, 1) tiles steered by block_row) or per-column
((1, bn) tiles steered by the n-tile index).

VMEM working set per grid step:
    blocks tile (bm, bk) + rhs tile (bk, bn) + out tile (bm, bn)
    = 128x128 f32 x 3 = 192 KiB  « 16 MiB VMEM -> double-buffering safe.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import apply_epilogue_inregister, compiler_params


def _bsr_spmm_kernel(block_row_ref, block_col_ref,   # scalar prefetch (SMEM)
                     *refs, epilogue=None, bias_kind=None):
    blocks_ref, rhs_ref = refs[0], refs[1]
    bias_ref = refs[2] if bias_kind else None
    out_ref = refs[-1]
    k = pl.program_id(1)
    nk = pl.num_programs(1)
    row = block_row_ref[k]
    is_first = jnp.logical_or(k == 0, block_row_ref[jnp.maximum(k - 1, 0)] != row)

    @pl.when(is_first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = blocks_ref[0]                                # (bm, bk)
    b = rhs_ref[...]                                 # (bk, bn)
    # f32 operands get f32 products (the MXU's default passes round them
    # to bf16), matching the elementwise naive program the pass replaces
    prec = (jax.lax.Precision.HIGHEST if jnp.float32 in (a.dtype, b.dtype)
            else None)
    out_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32,
                            precision=prec)

    if epilogue is not None or bias_kind:
        is_last = jnp.logical_or(
            k == nk - 1, block_row_ref[jnp.minimum(k + 1, nk - 1)] != row)

        @pl.when(is_last)
        def _():
            bias = bias_ref[...].astype(jnp.float32) if bias_kind else None
            out_ref[...] = apply_epilogue_inregister(out_ref[...], bias,
                                                     epilogue)


@functools.partial(jax.jit, static_argnames=("num_block_rows", "bn",
                                             "dimension_semantics",
                                             "epilogue", "bias_kind",
                                             "interpret"))
def bsr_spmm_pallas(blocks: jax.Array,      # (nnzb, bm, bk)
                    block_col: jax.Array,   # (nnzb,) int32
                    block_row: jax.Array,   # (nnzb,) int32, sorted
                    dense: jax.Array,       # (K, N)
                    num_block_rows: int,
                    bn: int = 128,
                    dimension_semantics: Optional[Tuple[str, ...]] = None,
                    epilogue: Optional[str] = None,
                    bias: Optional[jax.Array] = None,
                    bias_kind: Optional[str] = None,   # 'row' | 'col'
                    interpret: bool = False) -> jax.Array:
    nnzb, bm, bk = blocks.shape
    kdim, n = dense.shape
    assert kdim % bk == 0 and n % bn == 0, (dense.shape, (bk, bn))
    n_tiles = n // bn

    in_specs = [
        # one stored tile per step k
        pl.BlockSpec((1, bm, bk), lambda j, k, br, bc: (k, 0, 0)),
        # rhs block steered by the prefetched block-column index
        pl.BlockSpec((bk, bn), lambda j, k, br, bc: (bc[k], j)),
    ]
    args = [blocks, dense]
    if bias_kind == "row":
        # (rows, 1) column vector; tiles steered by the block-row index
        in_specs.append(pl.BlockSpec((bm, 1), lambda j, k, br, bc: (br[k], 0)))
        args.append(bias.reshape(-1, 1))
    elif bias_kind == "col":
        # (1, n) row vector; tiles steered by the output column tile
        in_specs.append(pl.BlockSpec((1, bn), lambda j, k, br, bc: (0, j)))
        args.append(bias.reshape(1, -1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, nnzb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda j, k, br, bc: (br[k], j)),
    )
    out_shape = jax.ShapeDtypeStruct((num_block_rows * bm, n), jnp.float32)
    fn = pl.pallas_call(
        functools.partial(_bsr_spmm_kernel, epilogue=epilogue,
                          bias_kind=bias_kind),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        **compiler_params(dimension_semantics),
    )
    return fn(block_row, block_col, *args)
