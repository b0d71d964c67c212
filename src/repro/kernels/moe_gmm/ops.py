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

    Returns (dest, tile_expert, Tp):
      dest:        (T*K,) destination row of each flat pair in the aligned
                   buffer (rows grouped by expert, groups padded to tm)
      tile_expert: (Tp//tm,) expert id of every row tile
    """
    TK = T * K
    Tp = int(np.ceil(TK / tm) * tm + (E - 1) * tm)  # worst-case alignment pad
    flat_e = idx.reshape(-1)
    counts = jnp.bincount(flat_e, length=E)                       # (E,)
    aligned = ((counts + tm - 1) // tm) * tm
    aligned = jnp.where(counts == 0, 0, aligned)
    group_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(aligned)[:-1].astype(jnp.int32)])
    # rank of each pair within its expert
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)           # (TK, E)
    rank = (jnp.cumsum(onehot, axis=0) - onehot)[jnp.arange(TK), flat_e]
    dest = group_start[flat_e] + rank                             # (TK,)
    # expert of each row tile: search the group boundary table
    bounds = jnp.cumsum(aligned)                                  # (E,)
    tile_rows = jnp.arange(Tp // tm, dtype=jnp.int32) * tm
    tile_expert = jnp.searchsorted(bounds, tile_rows, side="right").astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, E - 1)
    return dest, tile_expert, Tp


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
    dest, tile_expert, Tp = _route(idx, T, K, E, tm)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    xs = jnp.zeros((Tp, D), x.dtype).at[dest].set(x[flat_t])
    dims = ((dimension_semantics, dimension_semantics, "arbitrary")
            if dimension_semantics else None)
    dk_d, fn_f = _tile(D, dk), _tile(F, fn)   # contract D / output F (up)
    dk_f, fn_d = _tile(F, dk), _tile(D, fn)   # contract F / output D (down)
    # each matmul accumulates in f32 and rounds to the activation dtype,
    # the rounding points of the naive dense-dispatch program
    g = gmm_pallas(xs, wg, tile_expert, tm=tm, fn=fn_f, dk=dk_d,
                   dimension_semantics=dims, interpret=interpret)
    u = gmm_pallas(xs, wu, tile_expert, tm=tm, fn=fn_f, dk=dk_d,
                   dimension_semantics=dims, interpret=interpret)
    h = jax.nn.silu(g.astype(x.dtype)) * u.astype(x.dtype)
    y = gmm_pallas(h, wd, tile_expert, tm=tm, fn=fn_d, dk=dk_f,
                   dimension_semantics=dims, interpret=interpret)  # (Tp, D)
    flat_g = gate.reshape(-1).astype(x.dtype).astype(jnp.float32)
    contrib = y.astype(x.dtype).astype(jnp.float32)[dest] * flat_g[:, None]
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
