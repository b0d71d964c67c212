"""Plain reference for the HPCG problem: the 27-point stencil applied on
the grid (no matrix is stored) and unpreconditioned CG, in jax.numpy.

It shares nothing with the program under test: the operator is a sum of
the grid's shifted copies, not a sparse matrix, and every array it uses is
made here from the grid and the right-hand side.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _box3(a, axis):
    """Sum of each element and its two neighbours along ``axis`` of a
    zero-padded array (the padding shrinks by one on each side)."""
    n = a.shape[axis] - 2
    sl = lambda k: jax.lax.slice_in_dim(a, k, k + n, axis=axis)  # noqa: E731
    return sl(0) + sl(1) + sl(2)


def apply(cfg: dict, x, dtype=jnp.float32):
    """y = A x for HPCG's operator: ``diagonal`` times the point plus
    ``off_diagonal`` times each of its (up to 26) neighbours inside the
    grid, computed in ``dtype``."""
    g = x.astype(dtype).reshape(cfg["nz"], cfg["ny"], cfg["nx"])
    s = jnp.pad(g, 1)
    for axis in range(3):
        s = _box3(s, axis)
    d, o = (jnp.asarray(cfg["diagonal"], dtype),
            jnp.asarray(cfg["off_diagonal"], dtype))
    return (d * g + o * (s - g)).reshape(-1).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _cg(cfg_items, b, iters, dtype):
    cfg = dict(cfg_items)
    spmv = lambda v: apply(cfg, v, dtype)  # noqa: E731

    def body(k, carry):
        x, r, p, rs, hist, ap0 = carry
        ap = spmv(p)
        ap0 = jnp.where(k == 0, ap, ap0)
        alpha = rs / jnp.dot(p, ap)
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = jnp.dot(r, r)
        hist = hist.at[k].set(jnp.sqrt(rs_new))
        p = r + (rs_new / rs) * p
        return x, r, p, rs_new, hist, ap0

    zero = jnp.zeros_like(b)
    init = (zero, b, b, jnp.dot(b, b), jnp.zeros((iters,), jnp.float32),
            zero)
    x, _, _, _, hist, ap0 = jax.lax.fori_loop(0, iters, body, init)
    return ap0, x, hist


def cg(cfg: dict, b, iters: int, dtype=jnp.float32):
    """``iters`` CG iterations from x = 0: the first product A b, the
    final iterate and the residual norm after each iteration.  ``dtype``
    is the precision of the operator (float32 as the configuration
    states; the control takes the next lower, bfloat16)."""
    keys = ("nx", "ny", "nz", "diagonal", "off_diagonal")
    return _cg(tuple((k, cfg[k]) for k in keys), b, iters, dtype)
