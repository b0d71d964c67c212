#!/usr/bin/env python3
"""Where the served model's logits leave the plain reference, layer by
layer of the serving path, on the chip.

    python3 bench/diagnose_prefill.py

Prints, against the float32 reference of ``granite-moe-3b-a800m.ref.py``
on seeded prompts: the reference itself computed in bfloat16 and in
float8 (what rounding alone gives); the program's prefill with its default
MoE (grouped dispatch, capacity factor 2) and with its dropless one
(``moe_impl="naive"``); one batch-32 decode step through the engine's
lilac decode and through the un-rewritten decode, from caches of each
prefill.  Each line: relative L2 distance of the logits (max and mean
over rows), the gap of the picked token below the reference's best, and
the share of rows whose top token differs.  Not part of any cell.
"""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main() -> int:
    sys.path.insert(0, str(HERE))
    import harness
    root = HERE.parent
    harness.prepare_env(root)
    harness.configure_jax()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer
    from repro.models.factory import build_model
    from repro.serve import BucketPolicy, Engine, ServeConfig

    cell = harness.find_cell(root, "serve.granite.decode")
    ref, drv, cfg = cell.reference(), cell.driver(), cell.config
    vocab = cfg["vocab_size"]
    params = ref.init_params(cfg, 12345)
    rng = np.random.default_rng(0)

    def stats(name, got, want):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        rel = (np.linalg.norm(got - want, axis=-1)
               / np.linalg.norm(want, axis=-1))
        pick = np.take_along_axis(want, got.argmax(-1)[..., None], -1)[..., 0]
        gap = want.max(-1) - pick
        print(f"{name:46s} rel max {rel.max():.4e} mean {rel.mean():.4e}  "
              f"gap max {gap.max():.4f} mean {gap.mean():.5f}  not-first "
              f"{np.mean(got.argmax(-1) != want.argmax(-1)):.3f}", flush=True)

    for L in (32, 128):
        prompts = jnp.asarray(rng.integers(1, vocab, (8, L)), jnp.int32)
        want = np.asarray(ref.forward(cfg, params, prompts))
        for dt in (jnp.bfloat16, jnp.float8_e4m3fn):
            stats(f"L={L} reference in {dt.__name__}, all positions",
                  ref.forward(cfg, params, prompts, dtype=dt), want)
        for impl in ("grouped", "naive"):
            model = build_model(drv.arch_config(cfg).replace(moe_impl=impl))
            pre = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}))
            rows = [pre(params, prompts[i:i + 1])[0] for i in range(8)]
            stats(f"L={L} program prefill moe={impl}, last position",
                  jnp.concatenate(rows), want[:, -1])
            fwd = jax.jit(lambda p, t: transformer.forward(
                model.cfg, p, {"tokens": t})[0])
            full = jnp.einsum("bsd,dv->bsv",
                              fwd(params, prompts).astype(jnp.float32),
                              params["unembed"].astype(jnp.float32))
            stats(f"L={L} program forward moe={impl}, all positions",
                  full, want)

    L, B, S = 64, 32, 256
    prompts = jnp.asarray(rng.integers(1, vocab, (B, L)), jnp.int32)
    nxt = jnp.asarray(rng.integers(1, vocab, (B, 1)), jnp.int32)
    want = np.asarray(ref.forward(
        cfg, params, jnp.concatenate([prompts, nxt], 1)))[:, -1]
    pos = jnp.full((B,), L, jnp.int32)
    for impl in ("grouped", "naive"):
        model = build_model(drv.arch_config(cfg).replace(moe_impl=impl))
        eng = Engine(model, params, ServeConfig(
            buckets=BucketPolicy(batch=(B,), seq=(S,)),
            prewarm_on_start=False, use_lilac=True))
        rows = []
        for i in range(B):
            _, caches = eng._prefill(params, prompts[i:i + 1])
            rows.append(model.cache_from_prefill(caches, L, S))
        cache = jax.tree.map(lambda *a: jnp.concatenate(a, 0), *rows)
        for _ in range(2):    # the second call runs the baked plan
            logits, _ = eng._decode(params, cache, np.asarray(nxt),
                                    np.asarray(pos))
        stats(f"decode B=32 lilac, prefill moe={impl}", logits, want)
        logits, _ = jax.jit(model.decode)(params, cache, nxt, pos)
        stats(f"decode B=32 un-rewritten, prefill moe={impl}", logits, want)
        print("decode harnesses:",
              sorted({n for _, n in eng._decode.last_selections}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
