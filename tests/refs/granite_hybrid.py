"""Plain reference for Granite 4.0-H (GraniteMoeHybrid) as the program runs
it, one chip's share of each layer, for the CPU tests.  The benchmark keeps
its own copy beside its configuration (``bench/configs/``), which must not
import the tests.

``init_params`` draws every weight from the seed on the device in one
jitted program, in the layout and types of the program's parameter tree:
N(0, 1/fan_in) for every matrix (its true fan-in: the contracted width;
for the embedding, which is also the tied head, the hidden size), ones
for norm scales and ``D``, zeros for the
conv bias, and Mamba-2's own draws for ``A_log`` (A ~ U[1, 16]) and
``dt_bias`` (softplus^-1 of dt, log-uniform over [1e-3, 1e-1]).

``forward`` is the whole-sequence forward pass in float32 at the highest
matmul precision, one layer at a time:

* embeddings x ``embedding_multiplier``;
* per layer ``x += r * mixer(rms(x))`` then
  ``x += r * (moe(rms(x)) + shared(rms(x)))``, r the residual multiplier;
* Mamba-2 mixer by its sequential recurrence, token by token (not the
  chunked form): in_proj -> (z, xBC, dt), causal depthwise conv (k=4,
  with bias) and SiLU over xBC, dt = softplus(dt + dt_bias), per head
  h = exp(dt A) h + dt x (x) B, y = h C + D x, then the gated RMSNorm
  rms(y * SiLU(z)) * w and out_proj;
* attention without positions (NoPE), grouped key/value heads, causal,
  softmax scale ``attention_multiplier``;
* a softmax router over all ``router_experts`` experts, top-k, gates
  renormalised; only the held experts [first, first + count) are
  computed (every one, weighted by its gate, zero when not chosen), and
  one shared SwiGLU expert;
* final RMSNorm, logits through the tied embedding / ``logits_scaling``.

RMSNorm eps is ``rms_norm_eps`` everywhere.  It imports nothing of the
program.

``forward(..., dtype=t)`` computes the same in type ``t``: every weight
and every input of every matmul, and of the recurrence, rounded to ``t``,
accumulation in float32 (the control takes float8_e4m3fn, the next
precision below the served bfloat16).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONV_K = 4


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    N, G = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    dep = cfg["deployment"]
    return {"L": cfg["num_hidden_layers"], "d": d, "h": h,
            "kv": cfg["num_key_value_heads"], "dh": d // h,
            "H": H, "P": P, "N": N, "G": G, "di": H * P,
            "conv": H * P + 2 * G * N,
            "e": cfg["num_local_experts"], "k": cfg["num_experts_per_tok"],
            "E": dep["router_experts"], "first": dep["experts_held_first"],
            "f": cfg["intermediate_size"],
            "sf": cfg["shared_intermediate_size"], "v": cfg["vocab_size"],
            "eps": float(cfg["rms_norm_eps"]),
            "emb": float(cfg["embedding_multiplier"]),
            "res": float(cfg["residual_multiplier"]),
            "logit": float(cfg["logits_scaling"]),
            "att": float(cfg["attention_multiplier"])}


def period(cfg: dict) -> list:
    """The layer kinds of one period of the program's stack."""
    kinds = cfg["layer_types"]
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and kinds == kinds[:p] * (n // p):
            return kinds[:p]
    return kinds


def param_shapes(cfg: dict) -> dict:
    """(shape, dtype, init) of every leaf: init is a fan-in (normal),
    or "ones", "zeros", "a_log", "dt_bias"."""
    m = dims(cfg)
    kinds = period(cfg)
    L = m["L"] // len(kinds)
    d, h, kv, dh = m["d"], m["h"], m["kv"], m["dh"]
    bf = jnp.bfloat16
    blocks = {}
    for i, kind in enumerate(kinds):
        b = {"ln1": {"scale": ((L, d), F32, "ones")},
             "ln2": {"scale": ((L, d), F32, "ones")}}
        if kind == "attention":
            b["attn"] = {"wq": ((L, d, h, dh), bf, d),
                         "wk": ((L, d, kv, dh), bf, d),
                         "wv": ((L, d, kv, dh), bf, d),
                         "wo": ((L, h, dh, d), bf, h * dh)}
        else:
            H, di, conv = m["H"], m["di"], m["conv"]
            b["mamba2"] = {
                "in_proj": ((L, d, di + conv + H), bf, d),
                "conv_w": ((L, CONV_K, conv), F32, CONV_K),
                "conv_b": ((L, conv), F32, "zeros"),
                "dt_bias": ((L, H), F32, "dt_bias"),
                "a_log": ((L, H), F32, "a_log"),
                "d_skip": ((L, H), F32, "ones"),
                "norm": ((L, di), F32, "ones"),
                "out_proj": ((L, di, d), bf, di)}
        e, f, sf = m["e"], m["f"], m["sf"]
        b["moe"] = {"router": ((L, d, m["E"]), F32, d),
                    "wg": ((L, e, d, f), bf, d), "wu": ((L, e, d, f), bf, d),
                    "wd": ((L, e, f, d), bf, f)}
        b["shared"] = {"wg": ((L, d, sf), bf, d), "wu": ((L, d, sf), bf, d),
                       "wd": ((L, sf, d), bf, sf)}
        blocks[f"b{i}"] = b
    return {"blocks": blocks,
            "final_norm": {"scale": ((d,), F32, "ones")},
            "embed": ((m["v"], d), bf, d)}


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def _draw(key, shape, dtype, init):
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    u = jax.random.uniform(key, shape, F32)
    if init == "a_log":                     # A ~ U[1, 16]
        return jnp.log(1.0 + 15.0 * u).astype(dtype)
    if init == "dt_bias":                   # softplus(dt_bias) = dt
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    z = jax.random.normal(key, shape, dtype)
    return z * jnp.asarray(1.0 / math.sqrt(init), dtype)


@functools.partial(jax.jit, static_argnums=(0,))
def _init(spec_items, key):
    spec = jax.tree.unflatten(spec_items[0], spec_items[1])
    leaves, tree = jax.tree.flatten(spec, is_leaf=_is_leaf)
    out = [_draw(jax.random.fold_in(key, i), *leaf)
           for i, leaf in enumerate(leaves)]
    return jax.tree.unflatten(tree, out)


def init_params(cfg: dict, seed: int):
    """Every weight, from ``seed``, in one jitted program on the device."""
    leaves, tree = jax.tree.flatten(param_shapes(cfg), is_leaf=_is_leaf)
    return _init((tree, tuple(leaves)), jax.random.PRNGKey(seed))


def _w(a, dtype):
    """``a`` in float32, rounded to ``dtype`` first where one is given."""
    a = a.astype(dtype) if dtype is not None else a
    return a.astype(F32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mamba(x, p, m_items, dtype):
    """x (B,S,d) normed input -> the mixer's output (B,S,d)."""
    m = dict(m_items)
    r = functools.partial(_w, dtype=dtype)
    B, S, _ = x.shape
    H, P, N, G, di, conv = (m[k] for k in ("H", "P", "N", "G", "di", "conv"))
    zxbcdt = r(x) @ r(p["in_proj"])
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + conv],
                  zxbcdt[..., di + conv:])
    xp = jnp.pad(xbc, ((0, 0), (CONV_K - 1, 0), (0, 0)))
    w = r(p["conv_w"])
    xbc = sum(xp[:, i:i + S] * w[i] for i in range(CONV_K)) + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :di].reshape(B, S, H, P)
    Bm = xbc[..., di:di + G * N].reshape(B, S, G, N)
    Cm = xbc[..., di + G * N:].reshape(B, S, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # (B,S,H)
    A = -jnp.exp(p["a_log"])

    def step(h, inp):
        x_t, b_t, c_t, dt_t = inp            # (B,H,P), (B,G,N) x2, (B,H)
        b_t, c_t = (jnp.repeat(a, H // G, 1) for a in (b_t, c_t))
        h = (h * jnp.exp(dt_t * A)[..., None, None]
             + r(x_t * dt_t[..., None])[..., None] * r(b_t)[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", r(h), r(c_t))

    t_major = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), F32),
                        (t_major(xs), t_major(Bm), t_major(Cm), t_major(dt)))
    y = jnp.moveaxis(y, 0, 1) + xs * p["d_skip"][:, None]
    y = y.reshape(B, S, di) * jax.nn.silu(z)
    y = _rms(y, p["norm"], m["eps"])
    return r(y) @ r(p["out_proj"])


@functools.partial(jax.jit, static_argnums=(2, 3))
def _attention(x, p, m_items, dtype):
    """x (B,S,d) normed input -> causal NoPE GQA attention's output, one
    sequence at a time."""
    m = dict(m_items)
    r = functools.partial(_w, dtype=dtype)
    g = m["h"] // m["kv"]

    def one(xb):                              # (S, d)
        S = xb.shape[0]
        q = jnp.einsum("sd,dhk->shk", r(xb), r(p["wq"]))
        k = jnp.repeat(r(jnp.einsum("sd,dhk->shk", r(xb), r(p["wk"]))), g, 1)
        v = jnp.repeat(r(jnp.einsum("sd,dhk->shk", r(xb), r(p["wv"]))), g, 1)
        s = jnp.einsum("qhk,chk->hqc", r(q), k) * m["att"]
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum("hqc,chk->qhk", r(jax.nn.softmax(s, -1)), v)
        return jnp.einsum("qhk,hkd->qd", r(a), r(p["wo"]))

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _ffn(x, p, m_items, dtype):
    """x (B,S,d) normed input -> held experts' part + shared expert."""
    m = dict(m_items)
    r = functools.partial(_w, dtype=dtype)
    B, S, d = x.shape
    hn = r(x.reshape(B * S, d))
    probs = jax.nn.softmax(hn @ r(p["router"]), -1)
    top, idx = jax.lax.top_k(probs, m["k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(B * S)[:, None], idx].set(top)
    gate = gate[:, m["first"]:m["first"] + m["e"]]

    def expert(acc, i):
        h = jax.nn.silu(hn @ r(p["wg"][i])) * (hn @ r(p["wu"][i]))
        g_i = jax.lax.dynamic_slice_in_dim(gate, i, 1, axis=1)
        return acc + g_i * (r(h) @ r(p["wd"][i])), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(hn), jnp.arange(m["e"]))
    sh = jax.nn.silu(hn @ r(p["swg"])) * (hn @ r(p["swu"]))
    y = y + r(sh) @ r(p["swd"])
    return y.reshape(B, S, d)


@functools.partial(jax.jit, static_argnums=(2,))
def _head(x, p, m_items):
    m = dict(m_items)
    return (_rms(x, p["final_norm"], m["eps"]) @ p["embed"].T) / m["logit"]


def forward(cfg: dict, params, tokens, dtype=None):
    """Logits (B, S, vocab) in float32 of each position of ``tokens``;
    with ``dtype``, computed in that type (see above).  One sequence at a
    time, so that a layer's float32 activations of a long one fit."""
    return jnp.concatenate([_forward(cfg, params, tokens[i:i + 1], dtype)
                            for i in range(tokens.shape[0])])


def _forward(cfg: dict, params, tokens, dtype):
    m = dims(cfg)
    m_items = tuple(sorted(m.items()))
    kinds = period(cfg)
    with jax.default_matmul_precision("highest"):
        x = _w(params["embed"], dtype)[tokens] * m["emb"]
        for layer in range(m["L"]):
            i, j = layer % len(kinds), layer // len(kinds)
            blk = jax.tree.map(lambda a: a[j], params["blocks"][f"b{i}"])
            h = _rms(x, blk["ln1"]["scale"], m["eps"])
            if kinds[i] == "attention":
                out = _attention(h, blk["attn"], m_items, dtype)
            else:
                out = _mamba(h, blk["mamba2"], m_items, dtype)
            x = x + m["res"] * out
            h = _rms(x, blk["ln2"]["scale"], m["eps"])
            ffn = {**blk["moe"], **{"s" + k: v
                                    for k, v in blk["shared"].items()}}
            x = x + m["res"] * _ffn(h, ffn, m_items, dtype)
        return _head(x, {"final_norm": params["final_norm"]["scale"],
                         "embed": _w(params["embed"], dtype)}, m_items)
