"""Serving-tier tests: bucket policy, scheduler invariants, continuous
batching bit-identity vs solo decode (hypothesis-driven over a mock
model), ragged MoE packing, and a real-model parity smoke.

The mock model's decode is a per-slot integer rolling hash over
``(token, position)`` — the next token depends ONLY on that request's own
history, so any slot mix-up (wrong install row, bad eviction move, stale
position) changes the stream and fails the bit-identity property.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:          # property tests skip; seeded sweeps still run
    HAS_HYPOTHESIS = False
needs_hypothesis = pytest.mark.skipif(
    not HAS_HYPOTHESIS, reason="hypothesis not installed")

from repro.serve import (  # noqa: E402
    BucketError, BucketPolicy, Engine, Request, Scheduler, SchedulerFull,
    ServeConfig, SyntheticWorkload, default_buckets, moe_ffn_padded,
    moe_ffn_ragged, pack, padding_waste, parse_buckets, unpack,
)

VOCAB = 10007
_MOD = 9973


# ---------------------------------------------------------------------------
# mock model: integer rolling-hash decode, numpy-only (no accelerator)
# ---------------------------------------------------------------------------

def _fold(h: int, tok: int, pos: int) -> int:
    return (h * 1000003 + int(tok) * 31 + int(pos) + 7) % _MOD


class MockModel:
    """Model-surface stub for engine/scheduler tests.  The cache is
    ``{"state": (B,) int64, "cap": int}``; decode advances each row's
    hash with its (token, pos) pair and emits the hash as the next
    token."""

    def init_cache(self, B, S):
        return {"state": np.zeros((B,), np.int64), "cap": int(S)}

    def prefill(self, params, batch):
        toks = np.asarray(batch["tokens"])
        B, L = toks.shape
        h = np.zeros((B,), np.int64)
        for b in range(B):
            acc = 0
            for p in range(L):
                acc = _fold(acc, toks[b, p], p)
            h[b] = acc
        logits = np.zeros((B, VOCAB), np.float32)
        logits[np.arange(B), h] = 1.0
        return logits, {"state": h}

    def cache_from_prefill(self, caches, L, S):
        return {"state": np.asarray(caches["state"]).copy(),
                "cap": int(S)}

    def cache_set_slot(self, cache, slot, row):
        out = {"state": cache["state"].copy(), "cap": cache["cap"]}
        out["state"][slot] = row["state"][0]
        return out

    def cache_move_slot(self, cache, src, dst):
        out = {"state": cache["state"].copy(), "cap": cache["cap"]}
        out["state"][dst] = out["state"][src]
        return out

    def cache_resize(self, cache, B=None, max_seq=None):
        old = cache["state"]
        B = B if B is not None else old.shape[0]
        state = np.zeros((B,), np.int64)
        state[: min(B, old.shape[0])] = old[: min(B, old.shape[0])]
        return {"state": state,
                "cap": int(max_seq) if max_seq else cache["cap"]}

    def decode(self, params, cache, tokens, pos):
        tokens = np.asarray(tokens)
        pos = np.asarray(pos)
        B = tokens.shape[0]
        state = cache["state"].copy()
        for b in range(B):
            state[b] = _fold(int(state[b]), tokens[b, 0], int(pos[b]))
        logits = np.zeros((B, VOCAB), np.float32)
        logits[np.arange(B), state] = 1.0
        return logits, {"state": state, "cap": cache["cap"]}


def _mock_engine(mode="continuous", batch=(1, 2, 4), seq=(16, 32, 64),
                 **kw):
    cfg = ServeConfig(buckets=BucketPolicy(batch=batch, seq=seq),
                      mode=mode, use_lilac=False, jit_prefill=False, **kw)
    return Engine(MockModel(), params=None, config=cfg)


def _solo_stream(prompt, max_new):
    """Reference stream computed directly from the hash recurrence."""
    h = 0
    for p, t in enumerate(prompt):
        h = _fold(h, t, p)
    out = [h]
    L = len(prompt)
    while len(out) < max_new:
        h = _fold(h, out[-1], L + len(out) - 1)
        out.append(h)
    return out


# ---------------------------------------------------------------------------
# buckets
# ---------------------------------------------------------------------------

def test_bucket_smallest_fit_and_overflow():
    p = BucketPolicy(batch=(1, 2, 4), seq=(128, 512))
    assert p.batch_bucket(1) == 1
    assert p.batch_bucket(3) == 4
    assert p.seq_bucket(128) == 128
    assert p.seq_bucket(129) == 512
    with pytest.raises(BucketError):
        p.batch_bucket(5)
    with pytest.raises(BucketError):
        p.seq_bucket(513)
    assert p.max_batch == 4 and p.max_seq == 512
    assert len(p.grid()) == 6


def test_parse_buckets_and_env(monkeypatch):
    p = parse_buckets("1,2,4x128,256")
    assert p.batch == (1, 2, 4) and p.seq == (128, 256)
    monkeypatch.setenv("LILAC_SERVE_BUCKETS", "2x64")
    assert default_buckets().spec() == "2x64"
    monkeypatch.setenv("LILAC_SERVE_BUCKETS", "nonsense")
    with pytest.raises(BucketError):
        default_buckets()


def test_bucket_policy_sorted_deduped():
    p = BucketPolicy(batch=(4, 1, 4), seq=(256, 64))
    assert p.batch == (1, 4) and p.seq == (64, 256)


# ---------------------------------------------------------------------------
# scheduler invariants + edge cases (ISSUE: empty batch, all-finish-
# same-step, over-capacity queue)
# ---------------------------------------------------------------------------

def _req(plen=4, new=3, **kw):
    return Request(prompt=np.arange(1, plen + 1, dtype=np.int32),
                   max_new_tokens=new, **kw)


def test_scheduler_empty_batch_step():
    s = Scheduler(max_batch=4)
    assert s.idle
    assert s.admissions() == []
    assert s.evict_finished() == ([], [])


def test_scheduler_over_capacity_queue():
    s = Scheduler(max_batch=1, queue_capacity=2)
    s.submit(_req())
    s.submit(_req())
    with pytest.raises(SchedulerFull):
        s.submit(_req())
    assert s.queue_depth == 2


def test_scheduler_all_finish_same_step():
    s = Scheduler(max_batch=4)
    reqs = [_req(new=1) for _ in range(4)]
    for r in reqs:
        s.submit(r)
    assert s.admissions() == reqs
    for r in reqs:
        r.tokens.append(1)          # every request done at once
    finished, moves = s.evict_finished()
    assert finished == reqs and moves == [] and s.idle


def test_scheduler_static_waits_for_drain():
    s = Scheduler(max_batch=2, mode="static")
    a, b, c = _req(new=1), _req(new=2), _req(new=1)
    for r in (a, b, c):
        s.submit(r)
    assert s.admissions() == [a, b]
    a.tokens.append(1)
    s.evict_finished()
    assert s.admissions() == []     # b still running: no refill
    b.tokens += [1, 2]
    s.evict_finished()
    assert s.admissions() == [c]    # batch drained: next wave


def test_scheduler_compaction_moves_preserve_prefix():
    s = Scheduler(max_batch=6)
    reqs = [_req(new=5) for _ in range(6)]
    for r in reqs:
        s.submit(r)
    s.admissions()
    for i in (0, 2, 5):             # finish a head, a middle, and the tail
        reqs[i].tokens += [1] * 5
    finished, moves = s.evict_finished()
    assert {r.rid for r in finished} == {reqs[i].rid for i in (0, 2, 5)}
    # moves fill low holes from tail survivors, src >= n_new > dst
    n_new = 3
    assert all(src >= n_new > dst for src, dst in moves)
    assert s.active == [reqs[4], reqs[1], reqs[3]] or \
        {r.rid for r in s.active} == {reqs[i].rid for i in (1, 3, 4)}
    assert len(s.active) == n_new


def _drive_random_evictions(new_counts, rng):
    """Whatever subset finishes each step, survivors always end up in
    slots [0, n) and no move overwrites another move's source."""
    s = Scheduler(max_batch=8)
    reqs = [_req(new=n) for n in new_counts]
    for r in reqs:
        s.submit(r)
    while not s.idle:
        s.admissions()
        n = len(s.active)
        done = [i for i in range(n) if rng.random() < 0.4]
        before = {r.rid for r in s.active}
        for i in done:
            s.active[i].tokens += [1] * s.active[i].max_new_tokens
        survivors = [r.rid for r in s.active if not r.done]
        _, moves = s.evict_finished()
        seen_src = set()
        for src, dst in moves:
            assert src not in seen_src and dst < len(s.active)
            seen_src.add(src)
        assert sorted(r.rid for r in s.active) == sorted(survivors)
        assert all(r.rid in before for r in s.active)
        for r in s.active:          # undone requests must still make progress
            if not r.done:
                r.tokens.append(1)


def test_scheduler_random_evictions_seeded_sweep():
    for seed in range(25):
        rng = np.random.default_rng(seed)
        counts = list(rng.integers(1, 7, size=rng.integers(1, 11)))
        _drive_random_evictions(counts, rng)


if HAS_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(st.lists(st.integers(1, 6), min_size=1, max_size=10),
           st.integers(0, 2**16))
    def test_scheduler_random_evictions_keep_invariant(new_counts, seed):
        _drive_random_evictions(new_counts, np.random.default_rng(seed))


# ---------------------------------------------------------------------------
# engine bit-identity: batched continuous == solo, over random workloads
# ---------------------------------------------------------------------------

def _make_requests(spec):
    out = []
    for plen, new, seed in spec:
        prompt = np.random.default_rng(seed).integers(
            1, VOCAB - 1, size=plen).astype(np.int32)
        out.append(Request(prompt=prompt, max_new_tokens=new))
    return out


def _check_bit_identity(spec, mode):
    eng = _mock_engine(mode=mode)
    reqs = _make_requests(spec)
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_idle()
    for (plen, new, _), r in zip(spec, reqs):
        assert len(r.tokens) == new
        assert r.tokens == _solo_stream(list(r.prompt), new), \
            f"stream diverged for rid={r.rid} mode={mode}"


@pytest.mark.parametrize("mode", ["continuous", "static"])
def test_batched_streams_bit_identical_seeded_sweep(mode):
    for seed in range(12):
        rng = np.random.default_rng((77, seed))
        spec = [(int(rng.integers(1, 11)), int(rng.integers(1, 7)),
                 int(rng.integers(0, 2**16)))
                for _ in range(int(rng.integers(1, 9)))]
        _check_bit_identity(spec, mode)


if HAS_HYPOTHESIS:
    @st.composite
    def request_set(draw):
        n = draw(st.integers(1, 8))
        return [(draw(st.integers(1, 10)), draw(st.integers(1, 6)),
                 draw(st.integers(0, 2**16))) for _ in range(n)]

    @settings(max_examples=30, deadline=None)
    @given(request_set(), st.sampled_from(["continuous", "static"]))
    def test_batched_streams_bit_identical_to_solo(spec, mode):
        _check_bit_identity(spec, mode)


def test_engine_eviction_midstream_does_not_corrupt_neighbors():
    """A short request finishing early triggers a compaction move; the
    surviving long request's stream must be unaffected."""
    eng = _mock_engine(batch=(2,), seq=(32,))
    short = _req(plen=3, new=1)
    long = _req(plen=5, new=8)
    late = _req(plen=4, new=2)      # admitted into the freed slot
    for r in (short, long, late):
        assert eng.submit(r)
    eng.run_until_idle()
    assert long.tokens == _solo_stream(list(long.prompt), 8)
    assert late.tokens == _solo_stream(list(late.prompt), 2)


class ShapeRoundingModel(MockModel):
    """A decode whose rows depend on the batch bucket, as a TPU program's
    rounding does: the hash also folds in the batch size."""

    def decode(self, params, cache, tokens, pos):
        B = np.asarray(tokens).shape[0]
        return super().decode(params, cache, tokens, np.asarray(pos) + B)


def test_replay_solo_decodes_at_served_shapes():
    """A request served in a batch of 4, then of 2, replays exactly only
    at those shapes; at batch 1 it would differ from the second token on.
    Request shadowing therefore stays clean."""
    cfg = ServeConfig(buckets=BucketPolicy(batch=(1, 2, 4), seq=(32,)),
                      use_lilac=False, jit_prefill=False,
                      request_shadow_rate=1.0)
    eng = Engine(ShapeRoundingModel(), params=None, config=cfg)
    reqs = [_req(plen=3, new=n) for n in (2, 3, 6, 6)]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_idle()
    long = reqs[-1]
    assert long.decode_shapes == [(4, 32), (4, 32), (2, 32), (2, 32),
                                  (2, 32)]
    for r in reqs:
        assert eng.replay_solo(r) == r.tokens
        assert len(r.decode_shapes) == len(r.tokens) - 1
    alone = _req(plen=3, new=6)
    alone.prompt = long.prompt
    alone.tokens = list(long.tokens)
    alone.decode_shapes = [(1, 32)] * 5
    at_batch_1 = eng.replay_solo(alone)
    assert at_batch_1[0] == long.tokens[0]
    assert at_batch_1[1] != long.tokens[1]
    assert eng.metrics.request_shadow_checks == 4
    assert eng.metrics.request_shadow_divergences == 0


def test_engine_rejects_unbucketable_and_full_queue():
    eng = _mock_engine(batch=(1,), seq=(16,), queue_capacity=1)
    assert not eng.submit(_req(plen=20, new=4))      # 24 > max seq 16
    assert eng.metrics.snapshot()["requests"]["rejected"] == 1
    assert eng.submit(_req(plen=2, new=2))           # fills the 1-deep queue
    assert not eng.submit(_req(plen=2, new=2))       # queue full
    assert eng.metrics.snapshot()["requests"]["rejected"] == 2
    eng.step()                                       # admits, queue drains
    assert eng.submit(_req(plen=2, new=2))
    eng.run_until_idle()


def test_engine_eos_stops_stream():
    eng = _mock_engine()
    r = _req(plen=4, new=50)
    stream = _solo_stream(list(r.prompt), 50)
    r.eos_id = stream[2]            # third token is "eos"
    assert eng.submit(r)
    eng.run_until_idle()
    assert r.tokens == stream[:3]


def test_engine_run_with_workload_snapshot():
    wl = SyntheticWorkload(n_requests=5, vocab=VOCAB, prompt_len=(2, 6),
                           new_tokens=(1, 4), seed=3)
    eng = _mock_engine()
    snap = eng.run(wl)
    assert snap["requests"]["finished"] == 5
    assert snap["requests"]["rejected"] == 0
    assert snap["steps"] >= 1
    assert 0.0 < snap["batch_occupancy"] <= 1.0
    assert np.isfinite(snap["ttft_s"]["p99"])


def test_workload_deterministic_replay():
    wl = SyntheticWorkload(n_requests=4, vocab=100, seed=9)
    a, b = wl.requests(), wl.requests()
    for (ta, ra), (tb, rb) in zip(a, b):
        assert ta == tb
        assert np.array_equal(ra.prompt, rb.prompt)
        assert ra.max_new_tokens == rb.max_new_tokens


# ---------------------------------------------------------------------------
# ragged packing
# ---------------------------------------------------------------------------

def _check_pack_roundtrip(parts):
    arrs = [np.asarray(p, np.float32).reshape(-1, 1) for p in parts]
    flat, offsets = pack(arrs)
    assert offsets[0] == 0 and offsets[-1] == sum(len(p) for p in parts)
    back = unpack(flat, offsets)
    assert len(back) == len(parts)
    for a, b in zip(arrs, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pack_unpack_roundtrip_seeded_sweep():
    for seed in range(20):
        rng = np.random.default_rng(seed)
        parts = [list(rng.integers(-5, 6, size=rng.integers(0, 8)))
                 for _ in range(rng.integers(1, 7))]
        _check_pack_roundtrip(parts)


if HAS_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(st.lists(st.lists(st.integers(-5, 5), min_size=0, max_size=7),
                    min_size=1, max_size=6))
    def test_pack_unpack_roundtrip(parts):
        _check_pack_roundtrip(parts)


def test_padding_waste():
    assert padding_waste([4, 4]) == 0.0
    assert padding_waste([1, 3], pad_to=4) == pytest.approx(0.5)


def test_ragged_moe_matches_padded():
    rng = np.random.default_rng(0)
    E, D, F, K = 4, 8, 16, 2
    lengths = [3, 7, 1, 5]
    xs = [rng.standard_normal((t, D)).astype(np.float32) for t in lengths]
    gates = [rng.random((t, K)).astype(np.float32) for t in lengths]
    idxs = [rng.integers(0, E, (t, K)).astype(np.int32) for t in lengths]
    wg, wu = (rng.standard_normal((E, D, F)).astype(np.float32) * 0.1
              for _ in range(2))
    wd = rng.standard_normal((E, F, D)).astype(np.float32) * 0.1
    ragged = moe_ffn_ragged(xs, gates, idxs, wg, wu, wd, backend="naive")
    padded = moe_ffn_padded(xs, gates, idxs, wg, wu, wd)
    for a, b in zip(ragged, padded):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# real model: engine vs solo parity + prewarm
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_lm():
    import jax
    from repro.configs.base import get_arch, smoke_config
    from repro.models.factory import build_model
    cfg = smoke_config(get_arch("olmoe-1b-7b")).replace(
        moe_decode_impl="naive_flat")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_real_model_engine_matches_solo(small_lm):
    cfg, model, params = small_lm
    policy = BucketPolicy(batch=(1, 2), seq=(16,))
    eng = Engine(model, params,
                 ServeConfig(buckets=policy, use_lilac=False,
                             prewarm_on_start=False))
    rng = np.random.default_rng(1)
    reqs = [Request(prompt=rng.integers(1, cfg.vocab, size=p)
                    .astype(np.int32), max_new_tokens=n)
            for p, n in ((5, 4), (3, 6), (7, 3))]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_idle()
    for r in reqs:
        solo = eng.generate_solo(r.prompt, r.max_new_tokens)
        assert r.tokens == solo, f"batched != solo for rid={r.rid}"


def test_real_model_prewarm_bakes_grid(small_lm):
    cfg, model, params = small_lm
    policy = BucketPolicy(batch=(1, 2), seq=(16,))
    eng = Engine(model, params,
                 ServeConfig(buckets=policy, prefill_lengths=(4,)))
    pw = eng.metrics.prewarm
    assert pw["n_signatures"] == len(policy.grid())
    assert pw["baked"] == len(policy.grid())
    r = Request(prompt=np.arange(1, 5, dtype=np.int32), max_new_tokens=3)
    assert eng.submit(r)
    eng.run_until_idle()
    assert len(r.tokens) == 3
    snap = eng.metrics.snapshot()
    assert snap["buckets"]["misses"] == 0    # every decode on a warm bucket


def test_vector_pos_decode_matches_scalar(small_lm):
    """attention_decode_stacked with a (B,)-vector of equal positions is
    byte-identical to the scalar-pos path."""
    import jax.numpy as jnp
    cfg, model, params = small_lm
    B, L, S = 2, 5, 16
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(1, cfg.vocab, (B, L)).astype(np.int32))
    _, caches = model.prefill(params, {"tokens": toks})
    cache = model.cache_from_prefill(caches, L, S)
    step = jnp.asarray(rng.integers(1, cfg.vocab, (B, 1)).astype(np.int32))
    lo_s, c_s = model.decode(params, cache, step, jnp.int32(L))
    lo_v, c_v = model.decode(params, cache, step,
                             jnp.full((B,), L, jnp.int32))
    np.testing.assert_array_equal(np.asarray(lo_s), np.asarray(lo_v))
    import jax
    for a, b in zip(jax.tree.leaves(c_s), jax.tree.leaves(c_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_moe_decode_matches_moe_ffn():
    """The decode step of a bf16 MoE model carries the dense-dispatch MoE
    as the model builds it: ``x`` a reshape of the activations, each
    expert weight a slice of the layer-stacked parameters, and the gate
    and expert indices computed by the router.  The detector must match
    it there, not only in a hand-written layer."""
    import jax
    from repro.serve import build_engine
    eng = build_engine("granite-moe-3b-a800m", smoke=True, config=ServeConfig(
        buckets=BucketPolicy(batch=(2,), seq=(16,)), prewarm_on_start=False))
    assert jax.tree.leaves(eng.params)[0].dtype == np.dtype("bfloat16")
    report = eng.prewarm()
    assert report["baked"] == 1
    comps = [m.computation for m, _ in eng._decode.last_selections]
    assert comps.count("moe_ffn") == eng.model.cfg.n_layers, comps
