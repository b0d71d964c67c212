"""Mamba-2 mixer (SSD, arXiv:2405.21060), as Granite 4.0-H runs it.

Structure: in_proj -> (z, xBC, dt); causal depthwise conv (k=4, with bias)
+ SiLU over xBC; x split into H heads of P channels, B and C into G groups
of N (each group shared by H/G heads); per head a scalar decay
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t ;   y_t = h_t C_t + D x_t
then the gated RMSNorm  norm(y * SiLU(z)) * w  and out_proj.

State: ssm (B, H, P, N) + conv tail (B, K-1, conv_dim), both float32 and
computed in float32; the projections run in the parameters' dtype.

Prefill runs the chunked SSD form (§6 of the paper): within a chunk the
output is masked matmuls, between chunks one state pass, so no per-token
state is ever materialized.  A tail shorter than a chunk is padded with
dt = 0, which neither decays nor feeds the state.  Decode runs the
one-step recurrence.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.mamba import CONV_K, _causal_conv
from repro.models.spec import ParamSpec

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def dims(cfg) -> Tuple[int, int, int, int, int]:
    """(heads, head_dim, d_state, groups, conv_dim) of ``cfg``."""
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_state, cfg.ssm_groups
    return H, P, N, G, H * P + 2 * G * N


def mamba2_spec(cfg) -> Dict[str, ParamSpec]:
    H, P, N, G, conv_dim = dims(cfg)
    d, di = cfg.d_model, H * P
    # the two projections are down-scaled as in mamba.py's spec: init
    # draws a stacked leaf with std scale/sqrt(#periods), and at unit scale
    # the block amplifies float reassociation noise into prediction flips
    return {
        "in_proj": ParamSpec((d, di + conv_dim + H), ("embed", "mlp"),
                             scale=0.125),
        "conv_w": ParamSpec((CONV_K, conv_dim), (None, "mlp"), dtype=F32),
        "conv_b": ParamSpec((conv_dim,), ("mlp",), init="zeros", dtype=F32),
        "dt_bias": ParamSpec((H,), (None,), init="dt_bias", scale=0.01,
                             dtype=F32),
        "a_log": ParamSpec((H,), (None,), init="arange_log", dtype=F32),
        "d_skip": ParamSpec((H,), (None,), init="ones", dtype=F32),
        "norm": ParamSpec((di,), ("mlp",), init="ones", dtype=F32),
        "out_proj": ParamSpec((di, d), ("mlp", "embed"), scale=0.125),
    }


def _inputs(p, x, conv_tail, cfg):
    """Projections and conv: (z, xs (B,S,H,P), B, C (B,S,G,N), dt (B,S,H),
    new conv tail), all but z in float32."""
    H, P, N, G, conv_dim = dims(cfg)
    di = H * P
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z = zxbcdt[..., :di]
    xbc, tail = _causal_conv(zxbcdt[..., di:di + conv_dim].astype(F32),
                             p["conv_w"], p["conv_b"], conv_tail)
    xbc = jax.nn.silu(xbc)
    Bs, S = x.shape[:2]
    xs = xbc[..., :di].reshape(Bs, S, H, P)
    Bm = xbc[..., di:di + G * N].reshape(Bs, S, G, N)
    Cm = xbc[..., di + G * N:].reshape(Bs, S, G, N)
    dt = jax.nn.softplus(zxbcdt[..., di + conv_dim:].astype(F32)
                         + p["dt_bias"])
    return z, xs, Bm, Cm, dt, tail


def _out(p, y, z, xs, cfg, dtype):
    """D skip, gated RMSNorm over the whole inner width, out_proj."""
    Bs, S, H, P = xs.shape
    y = (y + xs * p["d_skip"][:, None]).reshape(Bs, S, H * P)
    y = y * jax.nn.silu(z.astype(F32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
    y = (y * p["norm"]).astype(dtype)
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"])


def _segsum(a):
    """a: (..., T) -> (..., T, T) with [i, j] = sum(a[j+1..i]) for j <= i
    and -inf above the diagonal: exp of it is the decay from j to i."""
    T = a.shape[-1]
    cs = jnp.cumsum(a, -1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd(xs, dt, A, Bm, Cm, h0, chunk: int):
    """Chunked SSD scan.  xs (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm
    (B,S,G,N), h0 (B,H,P,N).  Returns (y (B,S,H,P), final state)."""
    Bs, S, H, P = xs.shape
    G = Bm.shape[2]
    rep = H // G
    l = min(chunk, S)
    pad = (-S) % l
    if pad:   # dt = 0 pads: no decay and no input, so the state is exact
        padw = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (a.ndim - 2))
        xs, dt, Bm, Cm = padw(xs), padw(dt), padw(Bm), padw(Cm)
    c = (S + pad) // l
    # heads share their group's B and C
    Bh = jnp.repeat(Bm, rep, axis=2).reshape(Bs, c, l, H, -1)
    Ch = jnp.repeat(Cm, rep, axis=2).reshape(Bs, c, l, H, -1)
    xdt = (xs * dt[..., None]).reshape(Bs, c, l, H, P)
    a = (dt * A).reshape(Bs, c, l, H).transpose(0, 3, 1, 2)   # (B,H,c,l)
    a_cs = jnp.cumsum(a, -1)
    # 1. within each chunk: (C_i . B_j) decay(j -> i) x_j dt_j, j <= i
    decay = jnp.exp(_segsum(a))                                 # (B,H,c,l,l)
    cb = jnp.einsum("bclhn,bcshn->bhcls", Ch, Bh, precision=HI)
    y = jnp.einsum("bhcls,bcshp->bclhp", cb * decay, xdt, precision=HI)
    # 2. each chunk's own contribution to the state at its end
    to_end = jnp.exp(a_cs[..., -1:] - a_cs)                     # (B,H,c,l)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", Bh, to_end, xdt,
                        precision=HI)
    # 3. the state pass between chunks, from h0
    states = jnp.concatenate([h0[:, None], states], 1)          # (B,c+1,...)
    chunk_decay = jnp.exp(_segsum(jnp.pad(a_cs[..., -1], ((0, 0), (0, 0),
                                                          (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states,
                        precision=HI)
    entering, final = states[:, :-1], states[:, -1]
    # 4. the state entering each chunk, read out at every position
    y = y + jnp.einsum("bclhn,bchpn,bhcl->bclhp", Ch, entering,
                       jnp.exp(a_cs), precision=HI)
    return y.reshape(Bs, c * l, H, P)[:, :S], final


def mamba2_prefill(p, x, state, cfg):
    """x (B,S,D); state = (ssm (B,H,P,N), conv tail (B,K-1,conv_dim)).
    Returns (out (B,S,D), new state)."""
    ssm, conv_tail = state
    z, xs, Bm, Cm, dt, tail = _inputs(p, x, conv_tail, cfg)
    A = -jnp.exp(p["a_log"])
    y, ssm = ssd(xs, dt, A, Bm, Cm, ssm, cfg.ssm_chunk)
    return _out(p, y, z, xs, cfg, x.dtype), (ssm, tail)


def mamba2_step(p, x, state, cfg):
    """One token: x (B,1,D); the recurrence on the state directly."""
    ssm, conv_tail = state
    z, xs, Bm, Cm, dt, tail = _inputs(p, x, conv_tail, cfg)
    H, G = xs.shape[2], Bm.shape[2]
    rep = H // G
    A = -jnp.exp(p["a_log"])
    xs, dt = xs[:, 0], dt[:, 0]                                 # (B,H,P), (B,H)
    Bh = jnp.repeat(Bm[:, 0], rep, axis=1)                      # (B,H,N)
    Ch = jnp.repeat(Cm[:, 0], rep, axis=1)
    ssm = (ssm * jnp.exp(dt * A)[..., None, None]
           + (xs * dt[..., None])[..., None] * Bh[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", ssm, Ch, precision=HI)
    return _out(p, y[:, None], z, xs[:, None], cfg, x.dtype), (ssm, tail)
