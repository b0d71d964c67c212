"""Block assembly and the generic LM: spec building, scan-over-layers
forward, chunked LM loss, prefill and decode.

Layer stacking: the model is a scan over "periods".  A period is the
repeating unit of the architecture — 1 block for homogeneous stacks, 8
blocks for Jamba (1 attention + 7 mamba, MoE on odd indices).  Period
parameters are stacked on a leading "layers" axis, so the HLO contains one
period body regardless of depth (compile time and code size stay flat from
olmo-1b to mistral-large-123b).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import mamba as M
from repro.models import rwkv as R
from repro.models.spec import ParamSpec

F32 = jnp.float32


# ---------------------------------------------------------------------------
# Pattern: which blocks make up one period
# ---------------------------------------------------------------------------

def arch_pattern(cfg) -> List[Tuple[str, str]]:
    """[(mixer_kind, ffn_kind)] per layer within one period."""
    if cfg.family == "ssm":                       # rwkv6
        return [("rwkv", "channelmix")]
    if cfg.family == "hybrid":                    # jamba: attn @ idx 4 of 8
        period = cfg.attn_layer_period or 8
        out = []
        for i in range(period):
            mixer = "attn" if i == (cfg.attn_layer_offset or 4) else "mamba"
            ffn = "moe" if (cfg.moe_experts and i % (cfg.moe_layer_period or 2)
                            == 1) else "mlp"
            out.append((mixer, ffn))
        return out
    ffn = "moe" if cfg.moe_experts else "mlp"
    return [("attn", ffn)]


def n_periods(cfg) -> int:
    period = len(arch_pattern(cfg))
    assert cfg.n_layers % period == 0, (cfg.n_layers, period)
    return cfg.n_layers // period


# ---------------------------------------------------------------------------
# Spec building
# ---------------------------------------------------------------------------

def _norm_spec(cfg):
    s, _ = L.make_norm(cfg.norm, cfg.d_model)
    return s


def block_spec(cfg, mixer: str, ffn: str) -> Dict[str, Any]:
    d = cfg.d_model
    hd = cfg.head_dim or d // cfg.n_heads
    spec: Dict[str, Any] = {"ln1": _norm_spec(cfg)}
    if mixer == "attn":
        spec["attn"] = L.attention_spec(d, cfg.n_heads, cfg.n_kv_heads, hd)
    elif mixer == "mamba":
        spec["mamba"] = M.mamba_spec(d, d_state=cfg.d_state)
    elif mixer == "rwkv":
        spec["tm"] = R.timemix_spec(d, cfg.n_heads)
    else:
        raise ValueError(mixer)
    spec["ln2"] = _norm_spec(cfg)
    if ffn == "mlp":
        spec["mlp"] = L.mlp_spec(d, cfg.d_ff)
    elif ffn == "moe":
        spec["moe"] = L.moe_spec(d, cfg.d_ff, cfg.moe_experts)
    elif ffn == "channelmix":
        spec["cm"] = R.channelmix_spec(d, cfg.d_ff)
    else:
        raise ValueError(ffn)
    return spec


def _stack_spec(spec_tree, n: int):
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes,
                            dtype=s.dtype, init=s.init, scale=s.scale),
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def model_spec(cfg) -> Dict[str, Any]:
    d = cfg.d_model
    pattern = arch_pattern(cfg)
    period_spec = {f"b{i}": block_spec(cfg, mx, ff)
                   for i, (mx, ff) in enumerate(pattern)}
    spec: Dict[str, Any] = {
        "blocks": _stack_spec(period_spec, n_periods(cfg)),
        "final_norm": _norm_spec(cfg),
        "unembed": ParamSpec((d, cfg.vocab), ("embed", "vocab")),
    }
    if cfg.frontend == "none":
        spec["embed"] = ParamSpec((cfg.vocab, d), ("vocab", "embed"))
    # stub frontends feed precomputed embeddings; no embed table needed for
    # the fwd path, but decode still consumes tokens -> keep a table for vlm
    elif cfg.family == "vlm":
        spec["embed"] = ParamSpec((cfg.vocab, d), ("vocab", "embed"))
    return spec


# ---------------------------------------------------------------------------
# Block application (train / prefill share code; decode is separate)
# ---------------------------------------------------------------------------

def _norm_apply(cfg, p, x):
    _, fn = L.make_norm(cfg.norm, cfg.d_model)
    return fn(p, x)


def _axis_sizes(cfg) -> Dict[str, int]:
    return dict(cfg.mesh_axis_sizes)


def _constrain(cfg, spec_tree, params):
    """Compute-time weight resolution (no-op unless cfg.spmd_constraints).

    Weights whose storage sharding uses the FSDP ("data") axis are gathered
    with an explicit shard_map all_gather — its transpose is a
    psum_scatter, so each layer's weight gradient is reduce-scattered over
    the data axis (exact ZeRO-3 semantics, in the weight dtype).  Leaving
    this to with_sharding_constraint lets the scan-backward accumulator
    round-trip full f32 gradients through all-gathers instead.
    """
    if not cfg.spmd_constraints:
        return params
    from jax.sharding import PartitionSpec as P
    from repro.models import spec as S
    sizes = _axis_sizes(cfg)
    storage_rules = S.MULTI_POD_RULES if "pod" in sizes else S.SINGLE_POD_RULES

    def resolve(spec_leaf, value):
        storage = S.spec_to_pspec_sizes(spec_leaf, sizes, storage_rules)
        compute = S.spec_to_pspec_sizes(spec_leaf, sizes, S.COMPUTE_RULES)
        fsdp_axes = [i for i, (s, c) in enumerate(zip(storage, compute))
                     if s == "data" and c is None]
        if not fsdp_axes or sizes.get("data", 1) == 1:
            return jax.lax.with_sharding_constraint(value, compute)
        ax = fsdp_axes[0]

        def local(w):
            return jax.lax.all_gather(w, "data", axis=ax, tiled=True)

        return jax.shard_map(local, in_specs=storage, out_specs=compute,
                             check_vma=False)(value)

    return jax.tree.map(
        resolve, spec_tree, params,
        is_leaf=lambda x: isinstance(x, S.ParamSpec))


def _constrain_leaf(cfg, spec_leaf, value):
    if not cfg.spmd_constraints:
        return value
    return _constrain(cfg, spec_leaf, value)


def _moe_shard_ctx(cfg):
    """shard_map EP context for MoE layers (None on single host)."""
    if not cfg.spmd_constraints:
        return None
    sizes = _axis_sizes(cfg)
    batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
    return {"batch_axes": batch_axes, "model_axis": "model",
            "model_size": sizes.get("model", 1),
            "combine_bf16": cfg.moe_combine_bf16}


def _use_sp(cfg) -> bool:
    """Sequence-parallel activation carries: shard the (B, S, D) residual
    stream over the model axis between layers.  Essential for deep/wide
    models (88 x 1.6 GB carries would blow HBM on mistral-large) and it
    turns TP all-reduces into all-gather/reduce-scatter pairs.  Disabled
    for recurrent mixers (rwkv/mamba scan over a sharded time axis would
    force per-step collectives)."""
    return (cfg.spmd_constraints
            and cfg.seq_parallel
            and cfg.family not in ("ssm", "hybrid")
            and dict(cfg.mesh_axis_sizes).get("model", 1) > 1)


def _sp_constrain(cfg, x, batch_ok: bool = True):
    if not _use_sp(cfg):
        return x
    sizes = _axis_sizes(cfg)
    batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
    b = (batch_axes if len(batch_axes) > 1 else batch_axes[0]) \
        if (batch_ok and batch_axes) else None
    if x.shape[1] % sizes.get("model", 1) != 0:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(b, "model", None))


def apply_block(cfg, bp, x, *, mixer: str, ffn: str, positions,
                moe_impl: Optional[str] = None):
    """Full-sequence block application. Returns (x, aux_loss, cache_entry)."""
    cache_entry = {}
    h = _norm_apply(cfg, bp["ln1"], x)
    if mixer == "attn":
        q, k, v = L._qkv(bp["attn"], h, positions, cfg.rope_theta)
        out = L.chunked_attention(
            q, k, v, causal=cfg.causal, kv_chunk=cfg.kv_chunk,
            q_positions=positions[0] if positions.ndim > 1 else positions,
            kv_positions=positions[0] if positions.ndim > 1 else positions)
        out = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype),
                         bp["attn"]["wo"])
        cache_entry = {"k": k.astype(x.dtype), "v": v.astype(x.dtype)}
    elif mixer == "mamba":
        B = x.shape[0]
        di = bp["mamba"]["in_proj"].shape[1] // 2
        state = (jnp.zeros((B, di, cfg.d_state), F32),
                 jnp.zeros((B, M.CONV_K - 1, di), F32))
        out, state = M.mamba_block(bp["mamba"], h, state, cfg.d_state)
        cache_entry = {"ssm": state[0], "conv": state[1]}
    elif mixer == "rwkv":
        B = x.shape[0]
        hd = cfg.d_model // cfg.n_heads
        state = jnp.zeros((B, cfg.n_heads, hd, hd), F32)
        out, state, last_x = R.timemix(bp["tm"], h, state, cfg.n_heads)
        cache_entry = {"s": state, "last_tm": last_x}
    else:
        raise ValueError(mixer)
    x = x + out
    aux = jnp.zeros((), F32)
    h = _norm_apply(cfg, bp["ln2"], x)
    if ffn == "mlp":
        x = x + L.mlp_block(bp["mlp"], h)
    elif ffn == "moe":
        out, aux = L.moe_block(bp["moe"], h, topk=cfg.moe_topk,
                               impl=moe_impl or cfg.moe_impl,
                               capacity_factor=cfg.capacity_factor,
                               shard_ctx=_moe_shard_ctx(cfg))
        x = x + out
    elif ffn == "channelmix":
        out, last_cm = R.channelmix(bp["cm"], h)
        x = x + out
        cache_entry["last_cm"] = last_cm
    return x, aux, cache_entry


def forward(cfg, params, inputs: Dict[str, Any], *, collect_cache: bool = False):
    """Full-sequence forward (training / prefill).

    inputs: {"tokens": (B,S) int32} or {"embeds": (B,S,D)} for stub
    frontends; optional "positions" (B,S).
    Returns (x_final (B,S,D), aux_loss, cache or None).
    """
    pattern = arch_pattern(cfg)
    if "embeds" in inputs:
        x = inputs["embeds"].astype(cfg.param_dtype)
    else:
        embed = _constrain_leaf(
            cfg, ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "embed")),
            params["embed"])
        x = embed[inputs["tokens"]]
    B, S = x.shape[0], x.shape[1]
    positions = inputs.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    period_specs = {f"b{i}": block_spec(cfg, mx, ff)
                    for i, (mx, ff) in enumerate(pattern)}

    batch_ok = inputs.get("_batch_shardable", True)

    def period_fn(carry, period_params):
        x, aux = carry
        caches = {}
        x = _sp_constrain(cfg, x, batch_ok)
        for i, (mx, ff) in enumerate(pattern):
            bp = _constrain(cfg, period_specs[f"b{i}"], period_params[f"b{i}"])
            x, a, ce = apply_block(cfg, bp, x,
                                   mixer=mx, ffn=ff, positions=positions)
            aux = aux + a
            if collect_cache:
                caches[f"b{i}"] = ce
        x = _sp_constrain(cfg, x, batch_ok)
        return (x, aux), caches if collect_cache else None

    body = period_fn
    if cfg.remat:
        body = jax.checkpoint(
            period_fn,
            policy=jax.checkpoint_policies.save_only_these_names())
    (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), F32)),
                                    params["blocks"])
    x = _norm_apply(cfg, params["final_norm"], x)
    return x, aux, caches


# ---------------------------------------------------------------------------
# Chunked LM loss (vocab logits never fully materialized)
# ---------------------------------------------------------------------------

def lm_loss(cfg, params, x_final, labels, *, chunk: int = 512):
    """Cross-entropy over the vocab, computed in sequence chunks so the
    (B, S, V) logits tensor never exists; mask = labels >= 0."""
    B, S, D = x_final.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x_final = jnp.pad(x_final, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nch = x_final.shape[1] // chunk
    xc = x_final.reshape(B, nch, chunk, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nch, chunk).transpose(1, 0, 2)
    unembed = _constrain_leaf(
        cfg, ParamSpec((D, cfg.vocab), ("embed", "vocab")), params["unembed"])

    def body(carry, inp):
        tot, cnt = carry
        xck, lck = inp
        logits = jnp.einsum("bsd,dv->bsv", xck.astype(F32),
                            unembed.astype(F32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(lck, 0)[..., None], axis=-1)[..., 0]
        mask = (lck >= 0).astype(F32)
        tot = tot + jnp.sum((lse - picked) * mask)
        cnt = cnt + jnp.sum(mask)
        return (tot, cnt), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), F32), jnp.zeros((), F32)),
                                 (xc, lc))
    return tot / jnp.maximum(cnt, 1.0)


def lm_logits_last(cfg, params, x_final):
    """Logits of the last position only (prefill -> first generated token)."""
    xl = x_final[:, -1, :]
    return jnp.einsum("bd,dv->bv", xl.astype(F32),
                      params["unembed"].astype(F32))


# ---------------------------------------------------------------------------
# Decode (single new token, cache carried)
# ---------------------------------------------------------------------------

def init_cache(cfg, B: int, max_seq: int) -> Dict[str, Any]:
    """Per-layer-instance cache: {"p{j}": {"b{i}": entries}} with NO
    stacked periods dim — separate buffers alias cleanly under donation
    (stacked scan-carried caches get double-buffered; §Perf)."""
    pattern = arch_pattern(cfg)
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    di = 2 * cfg.d_model
    cache: Dict[str, Any] = {}
    for j in range(n_periods(cfg)):
        period_cache = {}
        for i, (mx, ff) in enumerate(pattern):
            ce: Dict[str, Any] = {}
            if mx == "attn":
                ce = {"k": jnp.zeros((B, max_seq, cfg.n_kv_heads, hd),
                                     cfg.cache_dtype),
                      "v": jnp.zeros((B, max_seq, cfg.n_kv_heads, hd),
                                     cfg.cache_dtype)}
            elif mx == "mamba":
                ce = {"ssm": jnp.zeros((B, di, cfg.d_state), F32),
                      "conv": jnp.zeros((B, M.CONV_K - 1, di), F32)}
            elif mx == "rwkv":
                ce = {"s": jnp.zeros((B, cfg.n_heads, hd, hd), F32),
                      "last_tm": jnp.zeros((B, cfg.d_model),
                                           cfg.param_dtype)}
            if ff == "channelmix":
                ce["last_cm"] = jnp.zeros((B, cfg.d_model), cfg.param_dtype)
            period_cache[f"b{i}"] = ce
        cache[f"p{j}"] = period_cache
    return cache


def decode_block(cfg, bp, x, ce, pos, *, mixer: str, ffn: str):
    """One decode block against its own per-layer cache entry."""
    h = _norm_apply(cfg, bp["ln1"], x)
    new_ce = dict(ce)
    if mixer == "attn":
        out, kc, vc = L.attention_decode_stacked(
            bp["attn"], h, ce["k"], ce["v"], pos, theta=cfg.rope_theta)
        new_ce["k"], new_ce["v"] = kc, vc
    elif mixer == "mamba":
        out, (ssm, conv) = M.mamba_block(
            bp["mamba"], h, (ce["ssm"], ce["conv"]), cfg.d_state)
        new_ce["ssm"], new_ce["conv"] = ssm, conv
    elif mixer == "rwkv":
        out, s, last = R.timemix(bp["tm"], h, ce["s"], cfg.n_heads,
                                 x_prev=ce["last_tm"])
        new_ce["s"], new_ce["last_tm"] = s, last.astype(ce["last_tm"].dtype)
    x = x + out
    h = _norm_apply(cfg, bp["ln2"], x)
    if ffn == "mlp":
        x = x + L.mlp_block(bp["mlp"], h)
    elif ffn == "moe":
        out, _ = L.moe_block(bp["moe"], h, topk=cfg.moe_topk,
                             impl=cfg.moe_decode_impl,
                             capacity_factor=cfg.capacity_factor)
        x = x + out
    elif ffn == "channelmix":
        out, last = R.channelmix(bp["cm"], h, x_prev=ce["last_cm"])
        x = x + out
        new_ce["last_cm"] = last.astype(ce["last_cm"].dtype)
    return x, new_ce


def decode_step(cfg, params, cache, tokens, pos):
    """tokens: (B, 1) int32; pos: scalar int32 (whole batch at one write
    position) or (B,) int32 (per-slot positions — continuous batching).
    Returns (logits (B, V), new_cache).

    The period loop is UNROLLED (static Python loop over statically-sliced
    stacked params): each per-layer cache buffer gets exactly one tiny
    in-place dynamic_update_slice, which XLA aliases with the donated
    input.  Scanning with the cache as carry instead double-buffers the
    whole cache each step (§Perf: ~1 TB/step of copies on 32k decode)."""
    pattern = arch_pattern(cfg)
    embed = _constrain_leaf(
        cfg, ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "embed")),
        params["embed"])
    x = embed[tokens]
    period_specs = {f"b{i}": block_spec(cfg, mx, ff)
                    for i, (mx, ff) in enumerate(pattern)}

    new_cache: Dict[str, Any] = {}
    for j in range(n_periods(cfg)):
        period_params = jax.tree.map(lambda a: a[j], params["blocks"])
        new_period = {}
        for i, (mx, ff) in enumerate(pattern):
            bp = _constrain(cfg, period_specs[f"b{i}"], period_params[f"b{i}"])
            x, new_period[f"b{i}"] = decode_block(
                cfg, bp, x, cache[f"p{j}"][f"b{i}"], pos, mixer=mx, ffn=ff)
        new_cache[f"p{j}"] = new_period
    x = _norm_apply(cfg, params["final_norm"], x)
    unembed = _constrain_leaf(
        cfg, ParamSpec((cfg.d_model, cfg.vocab), ("embed", "vocab")),
        params["unembed"])
    logits = jnp.einsum("bd,dv->bv", x[:, 0].astype(F32),
                        unembed.astype(F32))
    return logits, new_cache
