"""granite-4.0-h-small (Mamba-2 + attention + held-share MoE) against a plain
float32 reference at a small size: the chunked SSD scan, prefill then
decode through the serving engine, the held share of the experts, the
expert-parallel routing of the grouped-matmul kernel, slot plumbing of the
Mamba-2 cache and cache donation."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, smoke_config
from repro.models import build_model
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.serve import BucketPolicy, Engine, Request, ServeConfig

_spec = importlib.util.spec_from_file_location(
    "granite_hybrid_ref", Path(__file__).parent / "refs" / "granite_hybrid.py")
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)

HELD_FIRST, HELD, ROUTER = 4, 4, 16


def small_cfg(**kw):
    """One period at smoke widths, this chip holding 4 of 16 experts;
    float32 weights and cache unless asked otherwise."""
    cfg = smoke_config(get_arch("granite-4.0-h-small")).replace(
        moe_experts=ROUTER, moe_topk=4, experts_held=(HELD_FIRST, HELD),
        vocab=384, moe_impl="naive", moe_decode_impl="naive_flat")
    return cfg.replace(**kw)


def ref_cfg(cfg) -> dict:
    """The reference's configuration (Hugging Face's keys) of ``cfg``."""
    return {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
        "layer_types": ["attention" if k == "attn" else "mamba"
                        for k in cfg.layer_types],
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "mamba_n_heads": cfg.ssm_heads, "mamba_d_head": cfg.ssm_head_dim,
        "mamba_d_state": cfg.d_state, "mamba_n_groups": cfg.ssm_groups,
        "num_local_experts": (cfg.experts_held or (0, cfg.moe_experts))[1],
        "num_experts_per_tok": cfg.moe_topk,
        "intermediate_size": cfg.d_ff,
        "shared_intermediate_size": cfg.shared_d_ff,
        "vocab_size": cfg.vocab, "rms_norm_eps": cfg.norm_eps,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "logits_scaling": cfg.logits_scaling,
        "attention_multiplier": cfg.attention_multiplier,
        "deployment": {"router_experts": cfg.moe_experts,
                       "experts_held_first": (cfg.experts_held or (0,))[0]},
    }


def params_for(cfg, seed=3, dtype=None):
    """The reference's weights (true fan-in), in the program's dtypes or,
    with ``dtype``, all in that one."""
    params = REF.init_params(ref_cfg(cfg), seed)
    want = build_model(cfg).abstract_params()
    assert jax.tree.structure(want) == jax.tree.structure(params)
    return jax.tree.map(lambda a, w: a.astype(dtype or w.dtype), params,
                        want)


# -- the chunked SSD scan ----------------------------------------------------

CHUNK = 8


@pytest.mark.parametrize("S", [1, CHUNK - 1, CHUNK, CHUNK + 1,
                               3 * CHUNK + 5])
def test_ssd_matches_sequential_recurrence(S):
    """Chunked SSD (masked matmuls in a chunk, a state pass between
    chunks, dt = 0 padding of the tail) equals the token-by-token
    recurrence from a nonzero initial state, in float32: the two orders of
    summation differ by rounding only (tolerance ~100 ulp of the
    outputs)."""
    rng = np.random.default_rng(S)
    B, H, P, N, G = 2, 4, 3, 5, 2
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    xs, Bm, Cm, h0 = f(B, S, H, P), f(B, S, G, N), f(B, S, G, N), \
        f(B, H, P, N)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, (B, S, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 4, (H,)), jnp.float32)
    y, h = M2.ssd(xs, dt, A, Bm, Cm, h0, CHUNK)
    hs, ys = np.asarray(h0, np.float64), []
    Bh = np.repeat(np.asarray(Bm, np.float64), H // G, 2)
    Ch = np.repeat(np.asarray(Cm, np.float64), H // G, 2)
    for t in range(S):
        dt_t = np.asarray(dt[:, t], np.float64)
        hs = (hs * np.exp(dt_t * np.asarray(A))[..., None, None]
              + (np.asarray(xs[:, t]) * dt_t[..., None])[..., None]
              * Bh[:, t, :, None, :])
        ys.append(np.einsum("bhpn,bhn->bhp", hs, Ch[:, t]))
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), hs, rtol=1e-5, atol=1e-5)


# -- prefill then decode through the engine, against the reference ----------

def _serve_logits(cfg, params, prompts, new_tokens, state_dtype):
    """Serve ``prompts`` through the engine (lilac decode, the MoE baked
    to pallas.gmm, interpreted here), recording the logits of every token
    it produced: {rid: [logits row of each token]}."""
    model = build_model(cfg)
    eng = Engine(model, params, ServeConfig(
        buckets=BucketPolicy(batch=(2,), seq=(32,)),
        policy="pallas.gmm", prewarm_on_start=False))
    rows: dict = {}
    prefill, decode = eng._prefill, eng._decode

    def rounded(cache):         # the state kept in another type per step
        return {j: {b: {n: (a.astype(state_dtype).astype(a.dtype)
                            if n == "ssm" else a) for n, a in ce.items()}
                    for b, ce in per.items()} for j, per in cache.items()}

    def rec_prefill(p, toks):
        logits, caches = prefill(p, toks)
        rows.setdefault("prefill", []).append(np.asarray(logits[0]))
        return logits, caches

    def rec_decode(p, cache, tokens, pos):
        logits, cache = decode(p, cache, tokens, pos)
        rows.setdefault("decode", []).append(np.asarray(logits))
        return logits, rounded(cache)

    eng._prefill, eng._decode = rec_prefill, rec_decode
    reqs = [Request(prompt=np.asarray(p, np.int32), max_new_tokens=new_tokens)
            for p in prompts]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_idle()
    assert {n for _, n in decode.last_selections} == {"pallas.gmm"}
    return reqs, rows


def _logit_error(cfg, state_dtype):
    """Largest |served - reference| logit over every position served,
    relative to the reference logits' largest magnitude."""
    params = params_for(cfg, dtype=jnp.float32)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab, n) for n in (9, 13)]
    reqs, rows = _serve_logits(cfg, params, prompts, 8, state_dtype)
    err, scale = 0.0, 0.0
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        with jax.default_matmul_precision("highest"):
            want = np.asarray(REF.forward(ref_cfg(cfg), params,
                                          jnp.asarray(seq[None]))[0])
        got = [rows["prefill"][i]] + [rows["decode"][s][i]
                                     for s in range(len(r.tokens) - 1)]
        pos = np.arange(r.prompt_len - 1, len(seq))
        err = max(err, float(np.abs(np.stack(got) - want[pos]).max()))
        scale = max(scale, float(np.abs(want).max()))
    return err / scale


def test_engine_decode_matches_reference():
    """With float32 weights (so that activations are float32 and the
    state's precision is what the comparison sees): prefill, install, then
    7 decode steps through the cache agree with the reference's full
    forward to 2e-6 of the logits' scale: float32 rounding through 10
    layers and the SSD's order of summation (measured 2.1e-7).  Keeping
    the Mamba-2 state in bfloat16 between steps reads 1.5e-5, so the
    limit holds the float32 state to account."""
    assert _logit_error(small_cfg(), jnp.float32) < 2e-6
    assert _logit_error(small_cfg(), jnp.bfloat16) > 2e-6


# -- the held share ----------------------------------------------------------

def test_held_shares_add_up_to_the_uncut_layer():
    """Each chip routes over all 16 experts and computes its 4 held ones;
    the four shares' MoE outputs plus the shared expert (counted once) are
    the uncut layer, as the reference computes it."""
    cfg = small_cfg()
    rng = np.random.default_rng(0)
    d, f, E = cfg.d_model, cfg.d_ff, ROUTER
    w = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]),
                               jnp.float32)
    full = {"router": w(d, E), "wg": w(E, d, f), "wu": w(E, d, f),
            "wd": w(E, f, d)}
    shared = {"wg": w(d, 32), "wu": w(d, 32), "wd": w(32, d)}
    x = jnp.asarray(rng.standard_normal((2, 5, d)), jnp.float32)
    total = L.mlp_block(shared, x)
    for first in range(0, E, HELD):
        share = {"router": full["router"],
                 **{k: full[k][first:first + HELD]
                    for k in ("wg", "wu", "wd")}}
        out, _ = L.moe_block(share, x, topk=cfg.moe_topk, impl="naive",
                             held_first=first)
        total = total + out
    uncut, _ = L.moe_block(full, x, topk=cfg.moe_topk, impl="naive")
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(uncut + L.mlp_block(shared, x)),
                               rtol=1e-5, atol=1e-5)
    m = REF.dims({**ref_cfg(cfg), "num_local_experts": E,
                  "deployment": {"router_experts": E,
                                 "experts_held_first": 0}})
    p = {**full, **{"s" + k: v for k, v in shared.items()}}
    with jax.default_matmul_precision("highest"):
        want = REF._ffn(x, p, tuple(sorted(m.items())), None)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_held_share_decode_is_detected_and_baked_to_gmm():
    """The decode's held-share MoE (ids shifted out of [0, E) for absent
    experts) is matched by the detector in every layer and baked into the
    decode plan through pallas.gmm."""
    cfg = small_cfg()
    eng = Engine(build_model(cfg), params_for(cfg), ServeConfig(
        buckets=BucketPolicy(batch=(2,), seq=(16,)), policy="pallas.gmm"))
    assert eng.metrics.prewarm["baked"] == 1
    sel = [(m.computation, n) for m, n in eng._decode.last_selections]
    assert sel.count(("moe_ffn", "pallas.gmm")) == cfg.n_layers
    plan = eng._decode._last_plan
    assert plan is not None and ("pallas.gmm" in
                                 [n for _, n in plan.selections])


# -- expert-parallel routing in every MoE harness ----------------------------

def _moe_operands(seed=0):
    rng = np.random.default_rng(seed)
    T, D, F, E, K = 12, 16, 32, 4, 3
    f = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
    idx = jnp.asarray(rng.integers(-4, E + 4, (T, K)), jnp.int32)
    return {"x": f(T, D), "gate": jnp.asarray(rng.random((T, K)),
                                              jnp.float32),
            "idx": idx, "wg": f(E, D, F), "wu": f(E, D, F), "wd": f(E, F, D),
            "experts": E}


def _held_oracle(b):
    """Pairs outside [0, E) dropped from the dense one-hot oracle."""
    from repro.kernels.moe_gmm.ref import moe_ffn_ref
    held = (b["idx"] >= 0) & (b["idx"] < b["experts"])
    return moe_ffn_ref(b["x"], jnp.where(held, b["gate"], 0),
                       jnp.clip(b["idx"], 0, b["experts"] - 1),
                       b["wg"], b["wu"], b["wd"])


def test_route_takes_no_rows_for_absent_experts():
    from repro.kernels.moe_gmm import ops
    b = _moe_operands()
    T, K = b["idx"].shape
    E, tm = b["experts"], 8
    dest, tile_expert, live, Tp = ops._route(b["idx"], T, K, E, tm)
    flat = np.asarray(b["idx"]).reshape(-1)
    held = (flat >= 0) & (flat < E)
    dest = np.asarray(dest)
    assert (dest[~held] == Tp).all()
    assert len(set(dest[held])) == held.sum() and (dest[held] < Tp).all()
    counts = np.bincount(flat[held], minlength=E)
    assert int(live[0]) == sum(-(-c // tm) for c in counts)
    for j in np.flatnonzero(held):  # every held pair in its expert's tile
        assert int(tile_expert[dest[j] // tm]) == flat[j]


@pytest.mark.parametrize("name", ["pallas.gmm", "jnp.capacity", "dense"])
def test_moe_harnesses_drop_absent_experts(name):
    """Every harness the tuner may race gives the held share's part: a
    pair routed outside [0, E) adds nothing and takes no slot."""
    from repro.core.harness import REGISTRY, CallCtx
    b = _moe_operands()
    h = REGISTRY.get("moe_ffn", name)
    out = h(b, CallCtx(mode="trace", cache=None, format="MOE",
                       platform="cpu"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(_held_oracle(b)),
                               rtol=1e-5, atol=1e-5)


def test_gmm_with_no_held_pair_is_zero():
    from repro.kernels.moe_gmm import ops
    b = _moe_operands()
    idx = jnp.full_like(b["idx"], b["experts"] + 2)
    out = ops.moe_ffn(b["x"], b["gate"], idx, b["wg"], b["wu"], b["wd"],
                      tm=8, fn=32, dk=16, interpret=True)
    assert not np.asarray(out).any()


@pytest.mark.parametrize("live", [0, 2, 4])
def test_gmm_grid_runs_over_the_live_tiles_alone(live):
    """pallas.gmm's row grid is the live tile count, read at run time:
    the tiles past it are never visited (the interpreter leaves them
    NaN), so its steps follow the pairs held, not the buffer's size."""
    from repro.kernels.moe_gmm.kernel import gmm_pallas
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 16, 8)), jnp.float32)
    te = jnp.array([0, 1, 1, 0], jnp.int32)
    out = np.asarray(gmm_pallas(xs, w, te, jnp.array([live], jnp.int32),
                                tm=8, fn=8, dk=16, interpret=True))
    for t in range(4):
        rows = slice(8 * t, 8 * t + 8)
        if t < live:
            np.testing.assert_allclose(
                out[rows], np.asarray(xs[rows] @ w[int(te[t])]),
                rtol=1e-5, atol=1e-5)
        else:
            assert np.isnan(out[rows]).all()


# -- slots of the Mamba-2 cache ----------------------------------------------

def test_mamba2_slot_install_move_and_resize():
    """The ssm and conv leaves travel with their row through install
    (prefill -> batched cache), move (eviction compaction) and resize;
    only the attention leaves carry a sequence axis."""
    cfg = small_cfg()
    model = build_model(cfg)
    params = params_for(cfg)
    toks = jnp.asarray(np.random.default_rng(1).integers(1, 300, (1, 11)),
                       jnp.int32)
    _, caches = model.prefill(params, {"tokens": toks})
    row = model.cache_from_prefill(caches, 11, 16)
    b0 = row["p0"]["b0"]
    H, P, N, _, conv = M2.dims(cfg)
    assert b0["ssm"].shape == (1, H, P, N) and b0["ssm"].dtype == jnp.float32
    assert b0["conv"].shape == (1, 3, conv)
    assert row["p0"]["b5"]["k"].shape[1] == 16
    cache = model.cache_set_slot(model.init_cache(4, 16), 2, row)
    moved = model.cache_move_slot(cache, 2, 0)
    grown = model.cache_resize(moved, B=8, max_seq=32)
    for name in ("ssm", "conv"):
        want = np.asarray(b0[name][0])
        np.testing.assert_array_equal(np.asarray(cache["p0"]["b0"][name][2]),
                                      want)
        np.testing.assert_array_equal(np.asarray(moved["p0"]["b0"][name][0]),
                                      want)
        assert grown["p0"]["b0"][name].shape == (8,) + want.shape
        np.testing.assert_array_equal(np.asarray(grown["p0"]["b0"][name][0]),
                                      want)
    assert grown["p0"]["b5"]["k"].shape[:2] == (8, 32)
    eng = Engine(model, params, ServeConfig(
        buckets=BucketPolicy(batch=(4,), seq=(16,)), use_lilac=False,
        prewarm_on_start=False))
    jitted = eng._install(model.init_cache(4, 16), caches, 3, 11, 16)
    np.testing.assert_array_equal(np.asarray(jitted["p0"]["b0"]["ssm"][3]),
                                  np.asarray(b0["ssm"][0]))
    jitted = eng._move(jitted, 3, 1)
    np.testing.assert_array_equal(np.asarray(jitted["p0"]["b0"]["conv"][1]),
                                  np.asarray(b0["conv"][0]))


def test_install_span_and_state_counters():
    """An admission runs under ``serve.install`` and adds its row's
    recurrent-state bytes (every cache leaf but k and v, one row) to
    ``serve.state_bytes_installed``; an eviction move adds them to
    ``serve.state_bytes_moved``."""
    from repro.core import spans
    cfg = small_cfg()
    model = build_model(cfg)
    eng = Engine(model, params_for(cfg), ServeConfig(
        buckets=BucketPolicy(batch=(2,), seq=(32,)), use_lilac=False,
        prewarm_on_start=False))
    H, P, N, _, conv = M2.dims(cfg)
    row = 9 * (H * P * N + 3 * conv) * 4
    spans.reset()
    reqs = [Request(prompt=np.arange(1, 6, dtype=np.int32),
                    max_new_tokens=n) for n in (2, 6)]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_idle()
    got = spans.totals()
    assert got["serve.install"]["count"] == 2
    assert got["serve.state_bytes_installed"]["count"] == 2 * row
    # the short request finishes first from slot 0; the long one moves
    assert got["serve.state_bytes_moved"]["count"] == row
    assert eng.metrics.snapshot()["spans"]["serve.state_bytes_moved"] \
        == {"count": row}


# -- donation ----------------------------------------------------------------

def test_donated_decode_is_bit_identical():
    """The granite decode, baked with its cache donated, gives the same
    logits bit for bit as the same plan without donation, and the donated
    cache is consumed (updated in place) from the first call on: that call
    is recorded abstractly and served by the baked plan."""
    from repro import lilac
    cfg = smoke_config(get_arch("granite-moe-3b-a800m")).replace(
        moe_decode_impl="naive_flat", param_dtype=jnp.bfloat16,
        cache_dtype=jnp.bfloat16)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    n_p = len(jax.tree.leaves(params))
    n_c = len(jax.tree.leaves(model.init_cache(1, 1)))
    plain = lilac.compile(model.decode, mode="host", plan_cache=False)
    donor = lilac.compile(model.decode, mode="host", plan_cache=False,
                          donate_args=tuple(range(n_p, n_p + n_c)))
    rng = np.random.default_rng(0)
    caches = [model.init_cache(4, 16), model.init_cache(4, 16)]
    # the plain function's first call is interpreted; resolve it first so
    # both sides run their baked plans at every step
    plain(params, model.init_cache(4, 16), jnp.zeros((4, 1), jnp.int32),
          jnp.zeros((4,), jnp.int32))
    for step in range(4):
        toks = jnp.asarray(rng.integers(1, cfg.vocab, (4, 1)), jnp.int32)
        pos = jnp.full((4,), step, jnp.int32)
        a, caches[0] = plain(params, caches[0], toks, pos)
        old = caches[1]
        b, caches[1] = donor(params, old, toks, pos)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert all(x.is_deleted() for x in jax.tree.leaves(old))
    assert donor._last_plan is not None
