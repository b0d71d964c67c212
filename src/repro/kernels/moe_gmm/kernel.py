"""Group-aligned ragged grouped matmul (gmm) — the MoE expert hot loop.

This is the TPU-native replacement for the naive dense-dispatch MoE that
the LiLAC pass detects: tokens are sorted by expert, each expert's group is
padded to a row-tile multiple so every (tm, dk) x-tile belongs to exactly
one expert, and the per-tile expert id is scalar-prefetched so the
BlockSpec index_map can steer the weight DMA (indirect addressing on the
tile->expert table, the same mechanism as bsr_spmm's block indices).

FLOPs: sum_e ceil(c_e/tm)*tm * D * F  ~=  T*K*D*F  (vs naive E*T*D*F) —
exact results, no token drops (unlike capacity-factor dispatch).

Grid: (m_tiles, n_tiles, k_tiles), k fastest -> f32 accumulation in the
output VMEM block across k steps (revisiting pattern).  m_tiles may be
given at run time (the row tiles that hold rows), so tiles past the rows
held are never stepped.

Schedule parameters (``tune`` clauses in the HARNESS block): ``tm``
(token-tile rows — also the group-alignment quantum), ``fn``/``dk``
(output / contraction tile preferences), and ``dimension_semantics`` for
the m/n grid dimensions (k always 'arbitrary': it revisits the output
block).  A constraint bounds the per-step VMEM working set.

VMEM per step (tm=dk=fn=128, bf16 in / f32 acc):
    x (128x128x2) + w (128x128x2) + out (128x128x4) = 128 KiB.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import compiler_params


def _gmm_kernel(tile_expert_ref, xs_ref, w_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = xs_ref[...]                 # (tm, dk)
    w = w_ref[0]                    # (dk, fn)
    out_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tm", "fn", "dk",
                                             "dimension_semantics",
                                             "interpret"))
def gmm_pallas(xs: jax.Array,           # (Tp, D) group-aligned rows
               w: jax.Array,            # (E, D, F)
               tile_expert: jax.Array,  # (Tp//tm,) int32
               live_tiles: Optional[jax.Array] = None,   # (1,) int32
               tm: int = 128, fn: int = 128, dk: int = 128,
               dimension_semantics: Optional[Tuple[str, ...]] = None,
               interpret: bool = False) -> jax.Array:
    """Only the leading ``live_tiles`` row tiles (None: all of them) hold
    rows: the grid's row dimension is that count, read at run time, so the
    steps follow the rows held and not the buffer's worst-case size.  The
    output rows past them are left unwritten and must not be read."""
    Tp, D = xs.shape
    E, D2, F = w.shape
    assert D == D2 and Tp % tm == 0 and D % dk == 0 and F % fn == 0, \
        (xs.shape, w.shape, (tm, dk, fn))
    rows = (Tp // tm if live_tiles is None
            else live_tiles.astype(jnp.int32)[0])
    grid = (rows, F // fn, D // dk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, dk), lambda i, j, k, te: (i, k)),
            pl.BlockSpec((1, dk, fn), lambda i, j, k, te: (te[i], k, j)),
        ],
        out_specs=pl.BlockSpec((tm, fn), lambda i, j, k, te: (i, j)),
    )
    fn_call = pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, F), jnp.float32),
        interpret=interpret,
        **compiler_params(dimension_semantics),
    )
    return fn_call(tile_expert, xs, w)
