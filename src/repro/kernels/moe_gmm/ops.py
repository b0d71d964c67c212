"""jit'd wrapper: routing + sort + group alignment + three gmm calls.

``moe_ffn`` is numerically exact w.r.t. the naive dense-dispatch oracle
(no capacity drops) while doing ~E/K times less matmul work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.moe_gmm.kernel import gmm_pallas
from repro.kernels.moe_gmm.ref import moe_ffn_ref


def _route(idx: jax.Array, T: int, K: int, E: int, tm: int):
    """Sort (token, k) pairs by expert and compute group-aligned row slots.

    A pair whose expert id lies outside [0, E) (an expert another chip
    holds) takes no row: its ``dest`` is ``Tp``, one past the buffer, so
    a scatter with ``mode="drop"`` skips it and a gather must mask it.

    Returns (dest, tile_expert, live_tiles, Tp):
      dest:        (T*K,) destination row of each flat pair in the aligned
                   buffer (rows grouped by expert, groups padded to tm)
      tile_expert: (Tp//tm,) expert id of every row tile
      live_tiles:  (1,) how many leading tiles hold rows; the kernel's
                   grid covers these alone, so its steps follow the pairs
                   held, not T*K
    """
    TK = T * K
    Tp = int(np.ceil(TK / tm) * tm + (E - 1) * tm)  # worst-case alignment pad
    flat_e = idx.reshape(-1)
    held = (flat_e >= 0) & (flat_e < E)
    e = jnp.where(held, flat_e, 0)
    onehot = jax.nn.one_hot(e, E, dtype=jnp.int32) * held[:, None]  # (TK, E)
    counts = jnp.sum(onehot, axis=0)                              # (E,)
    aligned = ((counts + tm - 1) // tm) * tm
    bounds = jnp.cumsum(aligned).astype(jnp.int32)                # (E,)
    group_start = bounds - aligned
    # rank of each pair within its expert
    rank = (jnp.cumsum(onehot, axis=0) - onehot)[jnp.arange(TK), e]
    dest = jnp.where(held, group_start[e] + rank, Tp)             # (TK,)
    # expert of each row tile: search the group boundary table
    tile_rows = jnp.arange(Tp // tm, dtype=jnp.int32) * tm
    tile_expert = jnp.searchsorted(bounds, tile_rows, side="right").astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, E - 1)
    live_tiles = (bounds[-1] // tm).reshape(1)
    return dest, tile_expert, live_tiles, Tp


@functools.partial(jax.jit, static_argnames=("tm", "fn", "dk",
                                             "dimension_semantics",
                                             "interpret"))
def moe_ffn(x: jax.Array,      # (T, D)
            gate: jax.Array,   # (T, K)
            idx: jax.Array,    # (T, K) int32
            wg: jax.Array, wu: jax.Array,   # (E, D, F)
            wd: jax.Array,                  # (E, F, D)
            tm: int = 128,
            fn: int = 128, dk: int = 128,   # tile-size *preferences*
            dimension_semantics=None,
            interpret: bool = False) -> jax.Array:
    """``fn``/``dk`` are schedule preferences: each of the three grouped
    matmuls contracts/outputs over D or F, so the preference clamps to the
    largest aligned divisor of the actual dimension (`_tile`) — a swept
    schedule can therefore never produce an invalid tiling, only coincide
    with a neighbor (and be deduplicated by the sweep's argmin)."""
    T, D = x.shape
    K = idx.shape[1]
    E = wg.shape[0]
    F = wg.shape[2]
    dest, tile_expert, live, Tp = _route(idx, T, K, E, tm)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    xs = jnp.zeros((Tp, D), x.dtype).at[dest].set(x[flat_t], mode="drop")
    dims = ((dimension_semantics, dimension_semantics, "arbitrary")
            if dimension_semantics else None)
    dk_d, fn_f = _tile(D, dk), _tile(F, fn)   # contract D / output F (up)
    dk_f, fn_d = _tile(F, dk), _tile(D, fn)   # contract F / output D (down)
    # each matmul accumulates in f32 and rounds to the activation dtype,
    # the rounding points of the naive dense-dispatch program
    g = gmm_pallas(xs, wg, tile_expert, live, tm=tm, fn=fn_f, dk=dk_d,
                   dimension_semantics=dims, interpret=interpret)
    u = gmm_pallas(xs, wu, tile_expert, live, tm=tm, fn=fn_f, dk=dk_d,
                   dimension_semantics=dims, interpret=interpret)
    h = jax.nn.silu(g.astype(x.dtype)) * u.astype(x.dtype)
    y = gmm_pallas(h, wd, tile_expert, live, tm=tm, fn=fn_d, dk=dk_f,
                   dimension_semantics=dims, interpret=interpret)  # (Tp, D)
    flat_g = gate.reshape(-1).astype(x.dtype).astype(jnp.float32)
    # an absent pair reads no row: the rows past the live tiles are never
    # written, so a clamped gather of them is masked, not multiplied by 0
    rows = y.astype(x.dtype).astype(jnp.float32)[jnp.minimum(dest, Tp - 1)]
    contrib = jnp.where((dest < Tp)[:, None], rows, 0.0) * flat_g[:, None]
    out = jax.ops.segment_sum(contrib, flat_t, num_segments=T)
    return out.astype(x.dtype)


def _tile(n: int, pref: int = 128) -> int:
    """Largest hardware-aligned tile size dividing n (prefer ``pref``)."""
    if n % pref == 0:
        return pref
    for t in (128, 64, 32, 16, 8):
        if t < pref and n % t == 0:
            return t
    return n


def moe_ffn_oracle(x, gate, idx, wg, wu, wd):
    return moe_ffn_ref(x, gate, idx, wg, wu, wd)
