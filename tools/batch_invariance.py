"""Batch invariance of the served decode of granite-moe-3b-a800m: where a
request decoded in a batch of 8 first leaves the same request decoded
alone, whether a replay at the served batch shape is exact, and how far
the lilac decode and a planted fault sit from the un-rewritten decode.

    PYTHONPATH=src:. python3 tools/batch_invariance.py [--seed N] [--smoke]

Run it on the chip, one process.  ``--smoke`` takes the arch's smoke
config, for a rehearsal on the CPU.  Weights are bf16 from the seed; 8
prompts of 64/128 tokens are prefilled into one batch cache, as the serve
phase of ``chip_smoke.py`` does.  Sections:

taps     one decode step through a copy of the decode that also returns
         each layer's intermediates (ln1, attn, x after attention, ln2,
         router gate and idx, moe, x after the block), under ``jax.jit``
         (the un-rewritten program) and under ``lilac.compile`` (the MoE
         on the platform's harness), at batch 8 and with row 0 alone at
         batch 1.  Per layer: how far row 0 of the two steps is apart at
         each tap, in units in the last place (ulp) of the tap's dtype,
         and the relative L2 distance of the residual stream.
padded   the same at batch 8 with rows 1..7 empty, against the full
         batch: does a row's result depend on what its neighbours hold?
f32      the tap search on the first F32_LAYERS layers with f32 weights
         and cache.
streams  8 requests x 32 new tokens served on the engine; per request,
         the tokens that agree with ``Engine.replay_solo`` (the request
         alone at its served shapes) and with a replay at batch 1.
logits   one decode step of the model cut to 1, 2 and 4 layers: relative
         L2 distance per row of the lilac decode, and of planted faults
         (each token's heaviest, second and lightest expert skipped), from
         the un-rewritten decode.
ops      each op of layer 0 alone on identical inputs, batch 8 against
         batch 1.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
F32_LAYERS = 4
TAPS = ("ln1", "attn", "x_attn", "ln2", "gate", "idx", "moe", "x")


def ulps(got, want) -> float:
    """Largest distance of ``got`` from ``want`` in units in the last place
    of ``want``'s dtype at ``want``'s magnitude (integers: count of
    differing elements)."""
    import jax.numpy as jnp
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    if not jnp.issubdtype(want.dtype, jnp.floating):
        return float(np.sum(got != want))
    nmant = jnp.finfo(want.dtype).nmant
    g, w = got.astype(np.float64), want.astype(np.float64)
    tiny = float(jnp.finfo(want.dtype).tiny)
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(w), tiny))) - nmant)
    return float(np.max(np.abs(g - w) / ulp))


def rel_l2(got, want) -> float:
    import numpy as np
    g = np.asarray(got, np.float64).ravel()
    w = np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-300))


def tapped_decode(cfg):
    """``transformer.decode_step`` for an attention + MoE stack (the MoE in
    its ``naive_flat`` decode form), returning each layer's intermediates
    next to the logits."""
    import jax
    import jax.numpy as jnp

    from repro.models import layers as L
    from repro.models import transformer as T

    assert T.arch_pattern(cfg) == [("attn", "moe")], T.arch_pattern(cfg)

    def fn(params, cache, tokens, pos):
        x = params["embed"][tokens]
        taps = []
        for j in range(T.n_periods(cfg)):
            bp = jax.tree.map(lambda a: a[j], params["blocks"])["b0"]
            ce = cache[f"p{j}"]["b0"]
            t = {"ln1": T._norm_apply(cfg, bp["ln1"], x)}
            t["attn"], _, _ = L.attention_decode_stacked(
                bp["attn"], t["ln1"], ce["k"], ce["v"], pos,
                theta=cfg.rope_theta)
            x = t["x_attn"] = x + t["attn"]
            h = t["ln2"] = T._norm_apply(cfg, bp["ln2"], x)
            B, S, D = h.shape
            gate, idx, _ = L.moe_router(bp["moe"], h, cfg.moe_topk)
            t["gate"], t["idx"] = gate, idx
            moe = bp["moe"]
            t["moe"] = L._moe_naive_2d(
                h.reshape(B * S, D), gate.reshape(B * S, -1),
                idx.reshape(B * S, -1), moe["wg"], moe["wu"],
                moe["wd"]).reshape(B, S, D)
            x = t["x"] = x + t["moe"]
            taps.append(t)
        x = T._norm_apply(cfg, params["final_norm"], x)
        logits = jnp.einsum("bd,dv->bv", x[:, 0].astype(jnp.float32),
                            params["unembed"].astype(jnp.float32))
        return logits, taps
    return fn


def batch_cache(eng, prompts, S: int):
    """Prefill each prompt on ``eng`` and stack the rows into one cache;
    also returns the first tokens and the decode positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rows, first = [], []
    for p in prompts:
        logits, caches = eng._prefill(eng.params, p[None, :])
        first.append(int(np.argmax(np.asarray(logits)[0])))
        rows.append(eng.model.cache_from_prefill(caches, len(p), S))
    cache = jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *rows)
    tokens = np.array(first, np.int32)[:, None]
    pos = np.array([len(p) for p in prompts], np.int32)
    return cache, tokens, pos


def rows(tree, n: int, keep: int = 1):
    """The first ``keep`` rows of every leaf, zero-padded back to ``n``
    rows (``n == keep``: just the slice)."""
    import jax
    import jax.numpy as jnp

    def one(a):
        a = a[:keep]
        if n > keep:
            a = jnp.concatenate(
                [a, jnp.zeros((n - keep,) + a.shape[1:], a.dtype)])
        return a
    return jax.tree.map(one, tree)


def compare_taps(label, fn, params, cache, tokens, pos, *, against_rows):
    """Run ``fn`` on the full batch and on row 0 alone (``against_rows``
    rows, the rest empty); print row 0's distance per layer and tap."""
    import jax
    import numpy as np
    import jax.numpy as jnp
    B, n = tokens.shape[0], against_rows
    small = (rows(cache, n), rows(jnp.asarray(tokens), n),
             rows(jnp.asarray(pos), n))
    # a lilac function interprets the first call at a new shape and
    # serves its baked plan from the second on
    for _ in range(2):
        out_b = jax.block_until_ready(fn(params, cache, tokens, pos))
        out_1 = jax.block_until_ready(fn(params, *small))
    (lg_b, taps_b), (lg_1, taps_1) = out_b, out_1
    print(f"-- {label}: row 0 at batch {B} vs row 0 at batch {n}"
          + (" (other rows empty)" if n > 1 else ""))
    first = None
    for layer, (tb, t1) in enumerate(zip(taps_b, taps_1)):
        cells = []
        for name in TAPS:
            d = ulps(np.asarray(tb[name])[:1], np.asarray(t1[name])[:1])
            cells.append(f"{name} {d:g}")
            if d and first is None:
                first = (layer, name, d)
        xr = rel_l2(np.asarray(tb["x"])[:1], np.asarray(t1["x"])[:1])
        print(f"   layer {layer:2d}: " + ", ".join(cells)
              + f"; x rel L2 {xr:.3e}")
    lr = rel_l2(np.asarray(lg_b)[:1], np.asarray(lg_1)[:1])
    am = (int(np.argmax(np.asarray(lg_b)[0])),
          int(np.argmax(np.asarray(lg_1)[0])))
    print(f"   logits rel L2 {lr:.3e}, argmax {am[0]} / {am[1]}")
    print(f"   first difference: {first if first else 'none (bit-equal)'}")
    return out_b, first


def op_checks(cfg, params, cache, tokens, pos, moe_harness):
    """Each op of layer 0 on identical inputs at batch B and batch 1."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import layers as L
    from repro.models import transformer as T

    F32 = jnp.float32
    bp = jax.tree.map(lambda a: a[0], params["blocks"])["b0"]
    ce = cache["p0"]["b0"]
    a, m = bp["attn"], bp["moe"]
    x = params["embed"][tokens]
    h = T._norm_apply(cfg, bp["ln1"], x)
    positions = jnp.asarray(pos)[:, None]

    def qkv(h):
        q = jnp.einsum("bsd,dhk->bshk", h, a["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, a["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, a["wv"])
        return q, k, v

    q, k, v = qkv(h)
    qr = L.rope(q, positions, cfg.rope_theta)
    kr = L.rope(k, positions, cfg.rope_theta)
    write = jax.vmap(
        lambda c, t, pp: jax.lax.dynamic_update_slice(c, t, (pp, 0, 0)))
    ck = write(ce["k"], kr.astype(ce["k"].dtype), jnp.asarray(pos))
    cv = write(ce["v"], v.astype(ce["v"].dtype), jnp.asarray(pos))
    B, KV, dh = q.shape[0], ck.shape[2], q.shape[-1]
    G = q.shape[2] // KV
    S = ck.shape[1]

    def scores(qr, ck):
        qg = qr.reshape(B if qr.shape[0] == B else 1, 1, KV, G, -1)
        return jnp.einsum("bskgd,bckd->bskgc", qg.astype(F32),
                          ck.astype(F32)) / np.sqrt(dh)

    lg = scores(qr, ck)
    mask = jnp.arange(S)[None, None, None, None, :] \
        <= jnp.asarray(pos)[:, None, None, None, None]

    def softmax(lg, mask):
        return jax.nn.softmax(jnp.where(mask, lg, -1e30), axis=-1)

    probs = softmax(lg, mask)

    def pv(probs, cv):
        return jnp.einsum("bskgc,bckd->bskgd", probs, cv.astype(F32))

    o = pv(probs, cv).reshape(B, 1, -1, dh).astype(x.dtype)

    def wo(o):
        return jnp.einsum("bshk,hkd->bsd", o, a["wo"])

    attn = wo(o)
    x2 = x + attn
    h2 = T._norm_apply(cfg, bp["ln2"], x2)

    def router_logits(h2):
        return jnp.einsum("bsd,de->bse", h2.astype(F32), m["router"])

    gate, idx, _ = L.moe_router(m, h2, cfg.moe_topk)
    t2 = (h2.reshape(B, -1), gate.reshape(B, -1), idx.reshape(B, -1))

    def moe_naive(hh, g, i):
        return L._moe_naive_2d(hh, g, i, m["wg"], m["wu"], m["wd"])

    def moe_kernel(hh, g, i):
        from repro.kernels.moe_gmm import ops as gmm_ops
        return gmm_ops.moe_ffn(hh, g.astype(hh.dtype), i, m["wg"], m["wu"],
                               m["wd"],
                               interpret=jax.default_backend() != "tpu")

    def unembed(xf):
        return jnp.einsum("bd,dv->bv", xf[:, 0].astype(F32),
                          params["unembed"].astype(F32))

    xf = T._norm_apply(cfg, params["final_norm"], x2)
    checks = [
        ("embed gather", lambda t: params["embed"][t], (tokens,)),
        ("ln1 rmsnorm", lambda x: T._norm_apply(cfg, bp["ln1"], x), (x,)),
        ("q/k/v einsums", qkv, (h,)),
        ("scores einsum", scores, (qr, ck)),
        ("masked softmax", softmax, (lg, mask)),
        ("probs @ v einsum", pv, (probs, cv)),
        ("wo einsum", wo, (o,)),
        ("attention_decode_stacked",
         lambda h, k, v, p: L.attention_decode_stacked(
             a, h, k, v, p, theta=cfg.rope_theta)[0],
         (h, ce["k"], ce["v"], jnp.asarray(pos))),
        ("ln2 rmsnorm", lambda x: T._norm_apply(cfg, bp["ln2"], x), (x2,)),
        ("residual add + ln2 rmsnorm",
         lambda x, y: T._norm_apply(cfg, bp["ln2"], x + y), (x, attn)),
        ("rmsnorm mean of squares (f32)",
         lambda x: jnp.mean(jnp.square(x.astype(F32)), -1), (x2,)),
        ("router einsum", router_logits, (h2,)),
        ("router top-k", lambda h: L.moe_router(m, h, cfg.moe_topk)[:2],
         (h2,)),
        ("moe naive dispatch", moe_naive, t2),
        (f"moe kernel ({moe_harness})", moe_kernel, t2),
        ("final rmsnorm + unembed einsum",
         lambda x: unembed(T._norm_apply(cfg, params["final_norm"], x)),
         (x2,)),
        ("unembed einsum", unembed, (xf,)),
    ]
    print(f"-- ops of layer 0 on identical inputs: row 0 at batch {B} vs "
          "batch 1 (max ulp; idx: differing elements)")
    for name, fn, args in checks:
        f = jax.jit(fn)
        got = jax.tree.leaves(f(*args))
        want = jax.tree.leaves(f(*[a_[:1] for a_ in args]))
        d = [ulps(np.asarray(g)[:1], np.asarray(w)) for g, w in
             zip(got, want)]
        print(f"   {name}: " + ", ".join(f"{v:g}" for v in d))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="the arch's smoke config (CPU rehearsal)")
    args = ap.parse_args()
    store = ROOT / "build" / "batch_invariance"
    store.mkdir(parents=True, exist_ok=True)
    for var, name in (("LILAC_AUTOTUNE_CACHE", "autotune.json"),
                      ("LILAC_PLAN_CACHE", "plans.json"),
                      ("LILAC_QUARANTINE_CACHE", "quarantine.json")):
        os.environ[var] = str(store / name)
        (store / name).unlink(missing_ok=True)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chip_smoke import cut_engine, decode_step_logits, row_rel_l2
    from repro import lilac
    from repro.serve import BucketPolicy, Request, ServeConfig, build_engine

    t_start = time.perf_counter()
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}")
    lens = (8, 16) if args.smoke else (64, 128)
    S = 64 if args.smoke else 256
    new_tokens = 8 if args.smoke else 32
    cfg = ServeConfig(buckets=BucketPolicy(batch=(1, 8), seq=(S,)),
                      prefill_lengths=lens, prewarm_on_start=False)
    eng = build_engine("granite-moe-3b-a800m", smoke=args.smoke,
                       seed=args.seed, config=cfg)
    eng.prewarm()
    mcfg, params = eng.model.cfg, eng.params
    moe_harness = lilac.REGISTRY.default_name("moe_ffn", dev.platform)
    print(f"engine built and prewarmed: {time.perf_counter() - t_start:.1f}"
          f" s; {mcfg.n_layers} layers, moe harness {moe_harness}")

    rng = np.random.default_rng(args.seed + 3)
    prompts = [rng.integers(1, mcfg.vocab, lens[i % 2]).astype(np.int32)
               for i in range(8)]
    cache, tokens, pos = batch_cache(eng, prompts, S)

    # taps: plain and lilac, batch 8 vs batch 1, and batch 8 vs padded
    fn = tapped_decode(mcfg)
    plain = jax.jit(fn)
    (lg8, _), _ = compare_taps("taps, jax.jit", plain, params, cache,
                               tokens, pos, against_rows=1)
    served, _ = eng._decode(params, cache, tokens, pos)
    print(f"   tapped jit logits vs the engine's lilac decode, batch 8: "
          f"rel L2 {rel_l2(lg8, served):.3e}")
    compare_taps("padded, jax.jit", plain, params, cache, tokens, pos,
                 against_rows=8)
    lil = lilac.compile(fn, mode="host")
    lil(params, cache, tokens, pos)               # detect, bake
    compare_taps("taps, lilac.compile", lil, params, cache, tokens, pos,
                 against_rows=1)
    compare_taps("padded, lilac.compile", lil, params, cache, tokens, pos,
                 against_rows=8)
    print("   lilac selections: " + str(sorted(
        {n for m, n in lil.last_selections})))
    del lil
    one, _ = eng._decode(params, rows(cache, 1), tokens[:1], pos[:1])
    padded, _ = eng._decode(params, rows(cache, 8),
                            rows(jnp.asarray(tokens), 8),
                            rows(jnp.asarray(pos), 8))
    print("-- the engine's own decode plans, row 0 logits at batch 8: "
          f"rel L2 {rel_l2(served[:1], one):.3e} from batch 1, "
          f"{rel_l2(served[:1], padded[:1]):.3e} from batch 8 padded")
    print(f"[taps done {time.perf_counter() - t_start:.1f} s]", flush=True)

    # f32 weights and cache on the first F32_LAYERS layers
    n32 = min(F32_LAYERS, mcfg.n_layers)
    cfg32 = mcfg.replace(n_layers=n32, param_dtype=jnp.float32,
                         cache_dtype=jnp.float32)

    def f32(tree):
        return jax.tree.map(lambda a: a.astype(jnp.float32)
                            if jnp.issubdtype(a.dtype, jnp.floating) else a,
                            tree)
    p32 = dict(params)
    p32["blocks"] = jax.tree.map(lambda a: a[:n32], params["blocks"])
    p32 = f32(p32)
    c32 = f32({k: cache[k] for k in [f"p{j}" for j in range(n32)]})
    compare_taps(f"f32, first {n32} layers, jax.jit",
                 jax.jit(tapped_decode(cfg32)), p32, c32,
                 tokens, pos, against_rows=1)
    del p32, c32
    print(f"[f32 done {time.perf_counter() - t_start:.1f} s]", flush=True)

    # streams
    reqs = [Request(prompt=p, max_new_tokens=new_tokens) for p in prompts]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_idle()
    print(f"-- streams: {len(reqs)} requests x {new_tokens} tokens, "
          "tokens before the first disagreement")
    for r in reqs:
        solo = eng.replay_solo(r)
        alone = Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens)
        alone.tokens = list(r.tokens)
        alone.decode_shapes = [(1, S)] * len(r.decode_shapes)
        b1 = eng.replay_solo(alone)

        def agree(other):
            return ([a == b for a, b in zip(other, r.tokens)]
                    + [False]).index(False)
        print(f"   request {r.rid}: served at {sorted(set(r.decode_shapes))}"
              f"; replay at served shapes {agree(solo)}/{len(r.tokens)}, "
              f"replay at batch 1 {agree(b1)}/{len(r.tokens)}")
    print(f"[streams done {time.perf_counter() - t_start:.1f} s]", flush=True)

    # logits: lilac and a planted fault against the un-rewritten decode
    print("-- logits: relative L2 per row against the un-rewritten decode; "
          "fault k: each token's k-th heaviest expert skipped")
    drops = tuple(sorted({0, 1, mcfg.moe_topk - 1}))
    for n in (1, 2, 4):
        if n > mcfg.n_layers:
            break
        cut = cut_engine(eng, n)
        lg = decode_step_logits(cut, reqs, S, drops=drops)
        for name in ["lilac"] + [f"fault {k}" for k in drops]:
            e = row_rel_l2(lg[name], lg["un-rewritten"])
            print(f"   {n} layer(s), {name}: "
                  + " ".join(f"{v:.3e}" for v in e)
                  + f" (max {e.max():.3e})")
        del cut, lg
    # last: a compiler failure on one of these small programs aborts
    # the process
    op_checks(mcfg, params, cache, tokens, pos, moe_harness)
    print(f"[ops done {time.perf_counter() - t_start:.1f} s]", flush=True)

    print(f"DONE {time.perf_counter() - t_start:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
