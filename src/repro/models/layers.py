"""Shared layers: norms, rotary, GQA attention, SwiGLU MLP, MoE variants.

All functions are pure; parameters arrive as dict subtrees built from the
matching *_spec functions (see spec.py).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.spec import ParamSpec

F32 = jnp.float32


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_spec(d: int) -> Dict[str, ParamSpec]:
    return {"scale": ParamSpec((d,), (None,), init="ones", dtype=jnp.float32)}


def rmsnorm(p, x, eps: float = 1e-6):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


def nonparam_layernorm(x, eps: float = 1e-5):
    """OLMo-style non-parametric LayerNorm (no scale, no bias)."""
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def chunked_scan(step, init, xs, chunk: int = 128):
    """lax.scan in rematerialized chunks: backward saves carries only at
    chunk boundaries and replays the chunk forward — O(S/chunk) state
    memory instead of O(S) (the Mamba 'don't materialize h' insight,
    realized with jax.checkpoint).  xs leaves: (S, ...)."""
    S = jax.tree.leaves(xs)[0].shape[0]
    if S <= chunk:
        return jax.lax.scan(step, init, xs)
    nch = S // chunk
    rem = S - nch * chunk
    xs_main = jax.tree.map(
        lambda a: a[:nch * chunk].reshape((nch, chunk) + a.shape[1:]), xs)

    @jax.checkpoint
    def inner(carry, xs_chunk):
        return jax.lax.scan(step, carry, xs_chunk)

    carry, ys = jax.lax.scan(inner, init, xs_main)
    ys = jax.tree.map(
        lambda a: a.reshape((nch * chunk,) + a.shape[2:]), ys)
    if rem:
        tail = jax.tree.map(lambda a: a[nch * chunk:], xs)
        carry, ys_tail = jax.lax.scan(step, carry, tail)
        ys = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), ys, ys_tail)
    return carry, ys


def make_norm(kind: str, d: int):
    if kind == "rmsnorm":
        return rmsnorm_spec(d), rmsnorm
    if kind == "layernorm_nonparam":
        return {}, lambda p, x: nonparam_layernorm(x)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float = 1e4) -> jax.Array:
    """x: (..., S, H, dh); positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    angles = positions[..., None].astype(F32) * freqs          # (..., S, half)
    angles = angles[..., None, :]                              # (..., S, 1, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half].astype(F32), x[..., half:2 * half].astype(F32)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    rest = x[..., 2 * half:]
    return jnp.concatenate(
        [out1.astype(x.dtype), out2.astype(x.dtype), rest], axis=-1)


# ---------------------------------------------------------------------------
# GQA attention (chunked online-softmax for long sequences)
# ---------------------------------------------------------------------------

def attention_spec(d_model: int, n_heads: int, n_kv: int,
                   head_dim: int) -> Dict[str, ParamSpec]:
    return {
        "wq": ParamSpec((d_model, n_heads, head_dim), ("embed", "heads", None)),
        "wk": ParamSpec((d_model, n_kv, head_dim), ("embed", "kv_heads", None)),
        "wv": ParamSpec((d_model, n_kv, head_dim), ("embed", "kv_heads", None)),
        "wo": ParamSpec((n_heads, head_dim, d_model), ("heads", None, "embed")),
    }


def _qkv(p, x, positions, theta, use_rope: bool = True):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if use_rope:
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
    return q, k, v


def chunked_attention(q, k, v, *, causal: bool, kv_chunk: int = 1024,
                      q_positions=None, kv_positions=None, scale=None):
    """Memory-efficient attention: scan over kv chunks with running
    (max, denom, acc) — O(S * kv_chunk) live logits instead of O(S^2).

    q: (B, Sq, H, dh); k, v: (B, Skv, KV, dh) with H % KV == 0.  ``scale``
    is the softmax scale (None: 1/sqrt(dh)).
    """
    B, Sq, H, dh = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / np.sqrt(dh) if scale is None else scale
    if q_positions is None:
        q_positions = jnp.arange(Sq)
    if kv_positions is None:
        kv_positions = jnp.arange(Skv)
    nchunks = (Skv + kv_chunk - 1) // kv_chunk
    pad = nchunks * kv_chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad), constant_values=-1)
    kc = k.reshape(B, nchunks, kv_chunk, KV, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nchunks, kv_chunk, KV, dh).transpose(1, 0, 2, 3, 4)
    pc = kv_positions.reshape(nchunks, kv_chunk)
    qg = q.reshape(B, Sq, KV, G, dh)

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, pb = inp                                 # (B,ck,KV,dh), (ck,)
        logits = jnp.einsum("bskgd,bckd->bskgc", qg.astype(F32),
                            kb.astype(F32)) * scale       # (B,Sq,KV,G,ck)
        mask = pb[None, None, None, None, :] >= 0
        if causal:
            mask = mask & (pb[None, :] <= q_positions[:, None]
                           )[None, :, None, None, :]
        logits = jnp.where(mask, logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + jnp.sum(probs, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bskgc,bckd->bskgd", probs, vb.astype(F32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, KV, G), -1e30, F32)
    l0 = jnp.zeros((B, Sq, KV, G), F32)
    acc0 = jnp.zeros((B, Sq, KV, G, dh), F32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kc, vc, pc))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, H, dh)


def attention_block(p, x, *, positions, causal: bool, theta: float,
                    kv_chunk: int = 1024):
    q, k, v = _qkv(p, x, positions, theta)
    out = chunked_attention(q, k, v, causal=causal, kv_chunk=kv_chunk,
                            q_positions=positions[0] if positions.ndim > 1 else positions,
                            kv_positions=positions[0] if positions.ndim > 1 else positions)
    return jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])


def attention_decode_stacked(p, x, k_cache, v_cache, pos, *,
                             theta: float, use_rope: bool = True,
                             scale=None):
    """One-token decode against a PER-LAYER (B, S, KV, dh) cache buffer.

    The new k/v token is written with a tiny dynamic_update_slice directly
    into the buffer; the read is the buffer itself (zero-copy).  Earlier
    designs that carried a stacked (periods, ...) cache through a scan and
    sliced periods in/out forced XLA to double-buffer the whole cache
    (measured: +0.5-1 TB of copies per step on granite-34b decode_32k —
    see EXPERIMENTS.md §Perf).

    ``pos`` is a scalar (whole batch at one position — the classic path)
    or a (B,) vector of per-row positions (continuous batching: every slot
    is at a different depth).  The scalar path is left byte-for-byte
    unchanged so existing baked plans keep matching.  ``use_rope`` False
    leaves positions out of q and k (NoPE); ``scale`` is the softmax scale
    (None: 1/sqrt(dh)).
    """
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    positions = pos[:, None] if per_slot else jnp.full((B, 1), pos, jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if use_rope:
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
    if per_slot:
        write = jax.vmap(
            lambda c, t, pp: jax.lax.dynamic_update_slice(c, t, (pp, 0, 0)))
        k_cache = write(k_cache, k.astype(k_cache.dtype), pos)
        v_cache = write(v_cache, v.astype(v_cache.dtype), pos)
    else:
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, pos, 0, 0))
    ck, cv = k_cache, v_cache
    Smax, KV = ck.shape[1], ck.shape[2]
    H = q.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, -1)
    logits = jnp.einsum("bskgd,bckd->bskgc", qg.astype(F32),
                        ck.astype(F32))
    logits = (logits / np.sqrt(q.shape[-1]) if scale is None
              else logits * scale)
    if per_slot:
        mask = (jnp.arange(Smax)[None, None, None, None, :]
                <= pos[:, None, None, None, None])
    else:
        mask = jnp.arange(Smax)[None, None, None, None, :] <= pos
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bskgc,bckd->bskgd", probs, cv.astype(F32))
    out = out.reshape(B, 1, H, -1).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, k_cache, v_cache


def attention_decode(p, x, cache, pos, *, theta: float):
    """One-token decode against a KV cache.

    x: (B, 1, D); cache: {"k","v"): (B, Smax, KV, dh)}; pos: scalar int32.
    """
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _qkv(p, x, positions, theta)
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, pos, 0, 0))
    Smax, KV = ck.shape[1], ck.shape[2]
    H = q.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, -1)
    logits = jnp.einsum("bskgd,bckd->bskgc", qg.astype(F32),
                        ck.astype(F32)) / np.sqrt(q.shape[-1])
    mask = jnp.arange(Smax)[None, None, None, None, :] <= pos
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bskgc,bckd->bskgd", probs, cv.astype(F32))
    out = out.reshape(B, 1, H, -1).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_spec(d_model: int, d_ff: int) -> Dict[str, ParamSpec]:
    return {
        "wg": ParamSpec((d_model, d_ff), ("embed", "mlp")),
        "wu": ParamSpec((d_model, d_ff), ("embed", "mlp")),
        "wd": ParamSpec((d_ff, d_model), ("mlp", "embed")),
    }


def mlp_block(p, x):
    g = jnp.einsum("bsd,df->bsf", x, p["wg"])
    u = jnp.einsum("bsd,df->bsf", x, p["wu"])
    h = jax.nn.silu(g.astype(F32)).astype(x.dtype) * u
    return jnp.einsum("bsf,fd->bsd", h, p["wd"])


# ---------------------------------------------------------------------------
# MoE: naive (dense dispatch), lilac (detected+rewritten), grouped (direct)
# ---------------------------------------------------------------------------

def moe_spec(d_model: int, d_ff: int, n_experts: int,
             n_held: int = 0) -> Dict[str, ParamSpec]:
    """The router scores all ``n_experts``; weights are held for
    ``n_held`` of them (0: all)."""
    e = n_held or n_experts
    return {
        "router": ParamSpec((d_model, n_experts), ("embed", "expert"),
                            dtype=jnp.float32),
        "wg": ParamSpec((e, d_model, d_ff), ("expert", "embed", "mlp")),
        "wu": ParamSpec((e, d_model, d_ff), ("expert", "embed", "mlp")),
        "wd": ParamSpec((e, d_ff, d_model), ("expert", "mlp", "embed")),
    }


def moe_router(p, x, topk: int, aux_loss: bool = True):
    """returns (gate (B,S,K) f32 normalized, idx (B,S,K) int32, aux_loss).
    Softmax over all experts, then the top-k renormalised: the same gates
    as a softmax over the top-k logits alone (Granite's, Mixtral's), since
    the softmax's denominator cancels.  ``aux_loss`` False (a decode
    step, which trains nothing) returns 0 for it and leaves its
    reduction out of the program."""
    logits = jnp.einsum("bsd,de->bse", x.astype(F32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, topk)
    gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)
    if not aux_loss:
        return gate, idx.astype(jnp.int32), jnp.zeros((), F32)
    # load-balancing auxiliary loss (Switch-style)
    E = p["router"].shape[-1]
    me = jnp.mean(probs, axis=(0, 1))                       # (E,)
    ce = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=F32), axis=(0, 1))
    aux = E * jnp.sum(me * ce)
    return gate, idx.astype(jnp.int32), aux


def _moe_naive_2d(x, gate, idx, wg, wu, wd):
    """The canonical naive formulation — EXACTLY the form the LiLAC
    detector's moe_ffn matcher targets (see core/detect.py MoeMatcher)."""
    E = wg.shape[0]
    onehot = jax.nn.one_hot(idx, E, dtype=x.dtype)
    combine = jnp.einsum("tke,tk->te", onehot, gate.astype(x.dtype))
    g = jnp.einsum("td,edf->etf", x, wg)
    u = jnp.einsum("td,edf->etf", x, wu)
    h = jax.nn.silu(g) * u
    y = jnp.einsum("etf,efd->etd", h, wd)
    return jnp.einsum("te,etd->td", combine, y)


def _moe_grouped_2d(x, gate, idx, wg, wu, wd, capacity_factor: float = 2.0):
    """Capacity-bucketed grouped dispatch over one token group (T, D):
    compute scales with top-k instead of E. Host/CPU path (the harness
    `jnp.capacity` uses the same algorithm); the distributed path is the
    batched `_moe_grouped_batched` below."""
    out = _moe_grouped_batched(x[None], gate[None], idx[None], wg, wu, wd,
                               capacity_factor=capacity_factor)
    return out[0]


def _wsc(v, pspec, enabled: bool):
    if not enabled or pspec is None:
        return v
    return jax.lax.with_sharding_constraint(v, pspec)


def _moe_grouped_batched(x, gate, idx, wg, wu, wd,
                         capacity_factor: float = 2.0,
                         shard: bool = False,
                         batch_axis="data", model_axis="model"):
    """Batched grouped dispatch: groups = leading dim (sequences or the
    whole decode batch).  Fully GSPMD-shardable: tokens stay on their
    group's shard until the explicitly-constrained (B, E@model, C, D)
    bucket tensor forces the EP dispatch collective; the combine gather
    routes results back.  No vmap, no segment_sum — scatter/gather with a
    leading batch dim plus a top-k reduction.

    x: (B, T, D); gate/idx: (B, T, K). Returns (B, T, D).
    """
    from jax.sharding import PartitionSpec as P

    B, T, D = x.shape
    K = idx.shape[-1]
    E = wg.shape[0]
    C = int(np.ceil(T * K / E * capacity_factor))
    C = max(4, min(C, T * K))
    TK = T * K
    flat_e = idx.reshape(B, TK)                                  # (B, TK)
    flat_g = gate.reshape(B, TK)
    # rank of each (token,k) within its expert queue, per group
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # (B, TK, E)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=1) - onehot,
                              flat_e[..., None], axis=2)[..., 0]  # (B, TK)
    keep = pos < C
    # Unique slots (dropped pairs get unique out-of-bounds slots) keep the
    # scatter a plain parallel scatter — duplicate indices would force XLA
    # into a sort-based distributed scatter (catastrophic collectives).
    oob = E * C + jnp.arange(TK, dtype=jnp.int32)[None, :]
    slot = jnp.where(keep, flat_e * C + pos, oob)                # (B, TK)
    xtok = jnp.repeat(x, K, axis=1)                              # (B, TK, D)
    bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
    xb = jnp.zeros((B, E * C, D), x.dtype).at[bidx, slot].set(
        xtok, mode="drop", unique_indices=True)
    xb = xb.reshape(B, E, C, D)
    xb = _wsc(xb, P(batch_axis, model_axis, None, None), shard)  # EP dispatch
    g = jnp.einsum("becd,edf->becf", xb, wg)
    u = jnp.einsum("becd,edf->becf", xb, wu)
    h = jax.nn.silu(g.astype(F32)).astype(x.dtype) * u
    y = jnp.einsum("becf,efd->becd", h, wd)
    y = _wsc(y, P(batch_axis, model_axis, None, None), shard)
    y = y.reshape(B, E * C, D)
    y = jnp.concatenate([y, jnp.zeros((B, 1, D), y.dtype)], axis=1)
    back = y[bidx, jnp.where(keep, slot, E * C)]                 # (B, TK, D)
    back = jnp.where(keep[..., None], back, 0)
    back = _wsc(back, P(batch_axis, None, None), shard)          # EP combine
    contrib = back.astype(F32) * flat_g[..., None]
    out = jnp.sum(contrib.reshape(B, T, K, D), axis=2)
    return out.astype(x.dtype)


_LILAC_MOE_CACHE: Dict[int, Any] = {}


def _lilac_moe_2d():
    """lilac.compile applied to the naive form — the paper's compiler pass
    running inside the LM framework. Cached module-level (detection runs
    once per shape signature)."""
    if 0 not in _LILAC_MOE_CACHE:
        from repro import lilac
        _LILAC_MOE_CACHE[0] = lilac.compile(_moe_naive_2d)
    return _LILAC_MOE_CACHE[0]


def _moe_grouped_shardmap(x, gate, idx, wg, wu, wd, *,
                          capacity_factor: float,
                          batch_axes=("data",), model_axis="model",
                          model_size: int = 1,
                          combine_bf16: bool = False):
    """Expert-parallel grouped MoE via shard_map (Megatron-style EP).

    Tokens are batch-sharded; experts are model-sharded.  Every model shard
    dispatches its (replicated-over-model) local tokens into buckets for
    ITS OWN E/m experts only — dispatch needs NO collective.  Expert FFNs
    run local; the combine is one psum over the model axis per layer (the
    same cost class as a Megatron TP all-reduce).  Expert counts that
    don't divide the model axis are zero-padded (granite-moe: 40 -> 48);
    padded experts are never routed to, their buckets stay empty.
    """
    from jax.sharding import PartitionSpec as P

    B, T, D = x.shape
    K = idx.shape[-1]
    E = wg.shape[0]
    E_pad = ((E + model_size - 1) // model_size) * model_size
    if E_pad != E:
        padw = ((0, E_pad - E), (0, 0), (0, 0))
        wg, wu, wd = (jnp.pad(w, padw) for w in (wg, wu, wd))
    E_loc = E_pad // model_size
    C = int(np.ceil(T * K / E * capacity_factor))
    C = max(4, min(C, T * K))

    def local_fn(x, gate, idx, wg, wu, wd):
        # x: (B_loc, T, D) — replicated over model; wg: (E_loc, D, F)
        Bl = x.shape[0]
        TK = T * K
        eix = jax.lax.axis_index(model_axis)
        e0 = eix * E_loc
        flat_e = idx.reshape(Bl, TK) - e0                 # local expert ids
        flat_g = gate.reshape(Bl, TK)
        valid = (flat_e >= 0) & (flat_e < E_loc)
        e_cl = jnp.clip(flat_e, 0, E_loc - 1)
        onehot = jax.nn.one_hot(e_cl, E_loc, dtype=jnp.int32) \
            * valid[..., None].astype(jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=1) - onehot,
                                  e_cl[..., None], axis=2)[..., 0]
        keep = valid & (pos < C)
        oob = E_loc * C + jnp.arange(TK, dtype=jnp.int32)[None, :]
        slot = jnp.where(keep, e_cl * C + pos, oob)
        xtok = jnp.repeat(x, K, axis=1)                   # (B_loc, TK, D)
        bidx = jnp.arange(Bl, dtype=jnp.int32)[:, None]
        xb = jnp.zeros((Bl, E_loc * C, D), x.dtype).at[bidx, slot].set(
            xtok, mode="drop", unique_indices=True)
        xb = xb.reshape(Bl, E_loc, C, D)
        g = jnp.einsum("becd,edf->becf", xb, wg)
        u = jnp.einsum("becd,edf->becf", xb, wu)
        h = jax.nn.silu(g.astype(F32)).astype(x.dtype) * u
        y = jnp.einsum("becf,efd->becd", h, wd).reshape(Bl, E_loc * C, D)
        y = jnp.concatenate([y, jnp.zeros((Bl, 1, D), y.dtype)], axis=1)
        back = y[bidx, jnp.where(keep, slot, E_loc * C)]  # (B_loc, TK, D)
        back = jnp.where(keep[..., None], back, 0)
        partial = jnp.sum((back.astype(F32)
                           * flat_g[..., None]).reshape(Bl, T, K, D), axis=2)
        if combine_bf16:
            partial = partial.astype(x.dtype)   # halve the EP psum bytes
        return jax.lax.psum(partial, model_axis).astype(x.dtype)

    bspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0],
              *([None] * 2))
    wspec = P(model_axis, None, None)
    return jax.shard_map(
        local_fn,
        in_specs=(bspec, bspec, bspec, wspec, wspec, wspec),
        out_specs=bspec,
    )(x, gate, idx, wg, wu, wd)


def moe_block(p, x, *, topk: int, impl: str = "grouped",
              capacity_factor: float = 2.0, shard_ctx=None,
              held_first: Optional[int] = None, aux_loss: bool = True):
    """x: (B, S, D). Groups = sequences (train/prefill) — dispatch is
    per-sequence so no cross-batch communication is needed to form buckets;
    decode callers pass S=1 groups of the whole batch instead.

    shard_ctx: None (single host) or dict(batch_axes, model_axis,
    model_size) — selects the shard_map EP path.

    held_first: the layer holds experts [held_first, held_first + E) of
    the router's experts (E = the weights' leading dim).  Routing is over
    all of them; ids are shifted into the held range, so an absent
    expert's id lies outside [0, E) and its pair adds nothing.  The result
    is this share's part of the layer (expert parallelism without its
    exchange)."""
    B, S, D = x.shape
    gate, idx, aux = moe_router(p, x, topk, aux_loss)
    wg, wu, wd = p["wg"], p["wu"], p["wd"]
    if held_first is not None:
        if impl not in ("naive", "naive_flat", "lilac"):
            raise ValueError(f"a held share of experts needs a dispatch "
                             f"that drops absent ids; {impl!r} does not")
        idx = idx - held_first
    if impl == "naive":
        fn = functools.partial(_moe_naive_2d, wg=wg, wu=wu, wd=wd)
        out = jax.vmap(lambda xx, gg, ii: fn(xx, gg, ii))(x, gate, idx)
    elif impl == "lilac":
        lf = _lilac_moe_2d()
        out = jax.vmap(lambda xx, gg, ii: lf(xx, gg, ii, wg, wu, wd))(
            x, gate, idx)
    elif impl == "grouped" and shard_ctx:
        out = _moe_grouped_shardmap(x, gate, idx, wg, wu, wd,
                                    capacity_factor=capacity_factor,
                                    **shard_ctx)
    elif impl == "grouped":
        out = _moe_grouped_batched(x, gate, idx, wg, wu, wd,
                                   capacity_factor=capacity_factor,
                                   shard=False)
    elif impl == "grouped_flat":
        # one global group (decode): flatten groups into a single bucket set
        out = _moe_grouped_batched(x.reshape(1, B * S, D),
                                   gate.reshape(1, B * S, -1),
                                   idx.reshape(1, B * S, -1), wg, wu, wd,
                                   capacity_factor=capacity_factor,
                                   shard=False)
        out = out.reshape(B, S, D)
    elif impl == "naive_flat":
        # one flat naive call over all B*S tokens — the exact 2-D dense
        # dispatch the LiLAC detector matches, so compiling a decode step
        # that uses this impl exposes the MoE to detect/tune/bake
        out = _moe_naive_2d(x.reshape(B * S, D), gate.reshape(B * S, -1),
                            idx.reshape(B * S, -1), wg, wu, wd)
        out = out.reshape(B, S, D)
    else:
        raise ValueError(impl)
    return out, aux
