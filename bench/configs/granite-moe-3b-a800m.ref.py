"""Plain reference for the Granite-MoE decoder as the program runs it, and
the weights the benchmark serves it with.

``init_params`` draws every weight from the seed on the device in one
jitted program, in the layout and types the program's parameter tree
has.  ``forward`` is the whole-sequence forward pass in float32 at the
highest matmul precision, one layer at a time: RMSNorm (eps 1e-6), rotary
attention with grouped key/value heads and a causal mask, a softmax
router whose top-k gates are renormalised, SwiGLU experts (every expert
computed, weighted by its gate, zero when not chosen), a final RMSNorm
and the unembedding.  It imports nothing of the program.

``forward(..., dtype=t)`` computes the same in type ``t``: every weight
and every input of every matmul rounded to ``t``, accumulation in float32
(the control takes float8_e4m3fn, the next precision below the served
bfloat16).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
EPS = 1e-6


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "h": h,
            "kv": cfg["num_key_value_heads"], "dh": d // h,
            "e": cfg["num_local_experts"], "k": cfg["num_experts_per_tok"],
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "theta": float(cfg["rope_theta"])}


def param_shapes(cfg: dict) -> dict:
    """(shape, dtype, fan_in) of every leaf; fan_in None: ones."""
    m = dims(cfg)
    L, d, h, kv, dh, e, f, v = (m[x] for x in "L d h kv dh e f v".split())
    bf = jnp.bfloat16
    block = {
        "ln1": {"scale": ((L, d), F32, None)},
        "attn": {"wq": ((L, d, h, dh), bf, d), "wk": ((L, d, kv, dh), bf, d),
                 "wv": ((L, d, kv, dh), bf, d),
                 "wo": ((L, h, dh, d), bf, h * dh)},
        "ln2": {"scale": ((L, d), F32, None)},
        "moe": {"router": ((L, d, e), F32, d), "wg": ((L, e, d, f), bf, d),
                "wu": ((L, e, d, f), bf, d), "wd": ((L, e, f, d), bf, f)},
    }
    return {"blocks": {"b0": block},
            "final_norm": {"scale": ((d,), F32, None)},
            "unembed": ((d, v), bf, d),
            "embed": ((v, d), bf, 1)}


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


@functools.partial(jax.jit, static_argnums=(0,))
def _init(spec_items, key):
    spec = jax.tree.unflatten(spec_items[0], spec_items[1])
    leaves, tree = jax.tree.flatten(spec, is_leaf=_is_leaf)
    out = []
    for i, (shape, dtype, fan_in) in enumerate(leaves):
        if fan_in is None:
            out.append(jnp.ones(shape, dtype))
            continue
        z = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
        out.append(z * jnp.asarray(1.0 / math.sqrt(fan_in), dtype))
    return jax.tree.unflatten(tree, out)


def init_params(cfg: dict, seed: int):
    """Every weight, from ``seed``, in one jitted program on the device."""
    leaves, tree = jax.tree.flatten(param_shapes(cfg), is_leaf=_is_leaf)
    return _init((tree, tuple(leaves)), jax.random.PRNGKey(seed))


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x, theta):
    """x: (B, S, H, dh), rotating the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _w(a, dtype):
    """``a`` in float32, rounded to ``dtype`` first where one is given."""
    a = a.astype(dtype) if dtype is not None else a
    return a.astype(F32)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, p, m_items, dtype):
    m = dict(m_items)
    B, S, _ = x.shape
    w = {k: _w(v, dtype) for k, v in p.items()}
    r = functools.partial(_w, dtype=dtype)    # a matmul input, rounded
    hn = r(_rms(x, p["ln1"]))
    q = _rope(jnp.einsum("bsd,dhk->bshk", hn, w["wq"]), m["theta"])
    k = _rope(jnp.einsum("bsd,dhk->bshk", hn, w["wk"]), m["theta"])
    v = jnp.einsum("bsd,dhk->bshk", hn, w["wv"])
    g = m["h"] // m["kv"]
    k, v = jnp.repeat(r(k), g, axis=2), jnp.repeat(r(v), g, axis=2)
    s = jnp.einsum("bqhk,bchk->bhqc", r(q), k) / math.sqrt(m["dh"])
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqc,bchk->bqhk", r(jax.nn.softmax(s, -1)), v)
    x = x + jnp.einsum("bqhk,hkd->bqd", r(a), w["wo"])
    hn = r(_rms(x, p["ln2"]).reshape(B * S, -1))
    probs = jax.nn.softmax(hn @ w["router"], -1)
    top, idx = jax.lax.top_k(probs, m["k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(B * S)[:, None], idx].set(top)

    def expert(acc, i):
        h = jax.nn.silu(hn @ w["wg"][i]) * (hn @ w["wu"][i])
        g_i = jax.lax.dynamic_slice_in_dim(gate, i, 1, axis=1)
        return acc + g_i * (r(h) @ w["wd"][i]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(hn), jnp.arange(m["e"]))
    return x + y.reshape(B, S, -1)


@functools.partial(jax.jit, static_argnums=(2,))
def _head(x, p, dtype):
    return _w(_rms(x, p["final_norm"]), dtype) @ _w(p["unembed"], dtype)


def forward(cfg: dict, params, tokens, dtype=None):
    """Logits (B, S, vocab) in float32 of each position of ``tokens``;
    with ``dtype``, computed in that type (see above)."""
    m = dims(cfg)
    m_items = tuple(sorted(m.items()))
    with jax.default_matmul_precision("highest"):
        x = _w(params["embed"], dtype)[tokens]
        blk = params["blocks"]["b0"]
        for layer in range(m["L"]):
            p = {"ln1": blk["ln1"]["scale"][layer],
                 "ln2": blk["ln2"]["scale"][layer],
                 **{n: blk["attn"][n][layer] for n in ("wq", "wk", "wv", "wo")},
                 **{n: blk["moe"][n][layer]
                    for n in ("router", "wg", "wu", "wd")}}
            x = _layer(x, p, m_items, dtype)
        return _head(x, {"final_norm": params["final_norm"]["scale"],
                         "unembed": params["unembed"]}, dtype)
