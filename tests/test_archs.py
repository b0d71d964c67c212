"""Per-architecture smoke tests: reduced same-family config, one forward +
one train step on CPU, asserting shapes and finiteness. Decode steps for
causal archs. (Full configs are exercised only via the dry-run.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, all_archs, get_arch, shape_skips, smoke_config
from repro.models import build_model
from repro.train.optim import AdamWConfig, adamw_init, adamw_update

ARCHS = sorted(all_archs())


def _batch(cfg, B=2, S=16, seed=0):
    rng = np.random.default_rng(seed)
    if cfg.frontend == "stub":
        return {
            "embeds": jnp.asarray(rng.standard_normal(
                (B, S, cfg.d_model)).astype(np.float32)),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S))
                                  .astype(np.int32)),
        }
    return {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S))
                              .astype(np.int32)),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S))
                              .astype(np.int32)),
    }


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = smoke_config(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = _batch(cfg)
    loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
    assert jnp.isfinite(loss), arch
    gn = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree.leaves(grads))
    assert jnp.isfinite(gn) and gn > 0, arch
    # one optimizer step moves the loss
    ocfg = AdamWConfig(lr=1e-2, total_steps=10, warmup_steps=0)
    state = adamw_init(ocfg, params)
    new_params, state, _ = adamw_update(ocfg, grads, state, params)
    loss2 = model.loss_fn(new_params, batch)
    assert jnp.isfinite(loss2), arch
    assert float(loss2) != float(loss), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode(arch):
    cfg = smoke_config(get_arch(arch))
    if not cfg.causal:
        pytest.skip("encoder-only: no decode step")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    B, cache_len = 2, 24
    cache = model.init_cache(B, cache_len)
    logits = None
    for pos in range(3):
        tok = jnp.full((B, 1), pos + 1, jnp.int32)
        logits, cache = model.decode(params, cache, tok, jnp.int32(pos))
    assert logits.shape == (B, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits))), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_matches_decode(arch):
    """Teacher-forced decode must agree with a full prefill forward —
    the KV-cache/state path is consistent with the parallel path."""
    cfg = smoke_config(get_arch(arch))
    if not cfg.causal:
        pytest.skip("encoder-only")
    if cfg.frontend == "stub":
        pytest.skip("stub frontends feed embeddings; decode consumes tokens")
    if arch == "jamba-v0.1-52b":
        # Diagnosed (see ROADMAP open items): with the Jamba dt/B/C
        # RMSNorms and reference-style mamba init the ssm states are
        # bounded (~1e2, was ~1e7) and the per-layer paths agree
        # bit-exactly when applied eagerly, but this toolchain's XLA-CPU
        # *fused* elementwise kernels evaluate the logistic with a fast
        # approximation (silu(16.75) -> 16.6875, rel ~4e-3, independent of
        # --xla_cpu_enable_fast_math).  Prefill (one fused scan program)
        # and decode (many small programs) therefore disagree by ~4e-3
        # per silu site, which 16 recurrent layers amplify past tol with
        # occasional argmax flips.  Not a cache/position logic bug.
        # Re-checked on jax 0.9.0: still a max logit difference of ~1.1
        # with argmax flips, so the xfail stays.
        pytest.xfail("XLA-CPU fused-kernel logistic approximation; "
                     "prefill/decode program shapes differ")
    if cfg.moe_experts:
        # capacity drops depend on the dispatch group (sequence in prefill,
        # batch in decode); equality holds when nothing is dropped
        cfg = cfg.replace(capacity_factor=float(cfg.moe_experts))
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    B, S = 2, 8
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, (B, S)).astype(np.int32))
    logits_pre, _ = model.prefill(params, {"tokens": tokens})
    cache = model.init_cache(B, S)
    logits_dec = None
    for pos in range(S):
        logits_dec, cache = model.decode(
            params, cache, tokens[:, pos:pos + 1], jnp.int32(pos))
    # fp reassociation differs between the fused prefill path and the
    # unrolled per-token decode path; recurrent state and discrete top-k
    # routing (tie flips) amplify it. A logic bug (wrong position, stale
    # cache) produces O(1..10) differences and disagreeing predictions.
    if cfg.family in ("ssm", "hybrid") or cfg.moe_experts:
        tol = 3e-1
    else:
        tol = 5e-2
    np.testing.assert_allclose(np.asarray(logits_dec), np.asarray(logits_pre),
                               atol=tol, rtol=tol)
    # prediction agreement is exact regardless of family
    np.testing.assert_array_equal(np.argmax(np.asarray(logits_dec), -1),
                                  np.argmax(np.asarray(logits_pre), -1))


def test_shape_skip_table():
    """The skip matrix matches DESIGN.md §Arch-applicability."""
    skips = {(a, s): shape_skips(get_arch(a), SHAPES[s])
             for a in ARCHS for s in SHAPES}
    n_skipped = sum(1 for v in skips.values() if v)
    assert n_skipped == 9
    assert skips[("rwkv6-1.6b", "long_500k")] is None
    assert skips[("jamba-v0.1-52b", "long_500k")] is None
    assert skips[("granite-8b", "long_500k")] is not None
    assert skips[("hubert-xlarge", "decode_32k")] is not None


def test_param_counts_match_advertised_sizes():
    expected = {
        "rwkv6-1.6b": (1.5e9, 1.9e9),
        "internvl2-2b": (1.7e9, 2.2e9),
        "granite-moe-3b-a800m": (3.0e9, 3.7e9),
        "olmoe-1b-7b": (6.4e9, 7.4e9),
        "granite-8b": (7.5e9, 9.0e9),
        "mistral-large-123b": (118e9, 128e9),
        "granite-34b": (33e9, 50e9),
        "olmo-1b": (1.1e9, 1.5e9),
        "jamba-v0.1-52b": (48e9, 56e9),
        "hubert-xlarge": (0.9e9, 1.4e9),
    }
    for arch, (lo, hi) in expected.items():
        n = build_model(get_arch(arch)).param_count()
        assert lo <= n <= hi, (arch, n)
