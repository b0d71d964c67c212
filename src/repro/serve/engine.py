"""The serving engine: continuous batching over a lilac-compiled decode.

One :class:`Engine` owns one replica's state — the batched KV cache, the
:class:`~repro.serve.scheduler.Scheduler`, the lilac-compiled decode step
and a :class:`~repro.serve.metrics.ServeMetrics` sink — and advances it
one decode step at a time:

1. **admit** — pop waiting requests into free slots (continuous mode:
   any step with a free slot; static mode: only when the batch drained).
   Each admission runs an exact-length jitted prefill, converts the
   collected caches into one batched-cache row, and takes its first token
   from the prefill logits (greedy).
2. **re-bucket** — resize the batched cache to the smallest
   ``(batch, seq-capacity)`` bucket that holds the active set (see
   :mod:`repro.serve.buckets`).  Every bucket pair was prewarmed at
   startup, so the resized shape dispatches onto an already-baked
   :class:`~repro.core.plan.ExecutablePlan` — never detect/tune/bake.
3. **decode** — one batched step with *per-slot* positions (each row of
   the cache is at its own depth); greedy next token per active row.
4. **evict** — finished requests leave; tail survivors compact into the
   holes via ``(src, dst)`` cache-row moves so the active prefix invariant
   holds for the next step.

``prewarm()`` walks the bucket grid through
:meth:`~repro.core.pass_manager.LilacFunction.prewarm` before any traffic,
so steady-state decode is plan dispatch only; with a persistent plan
cache shared across replicas, even the *first* replica boot after a fleet
has run pays zero detection (the serving benchmark gates on this).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.spans import count, span
from repro.serve.buckets import BucketPolicy, default_buckets
from repro.serve.metrics import ServeMetrics
from repro.serve.scheduler import Request, Scheduler

DEFAULT_MAX_STEPS = 200_000


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine configuration (model-independent knobs)."""
    buckets: Optional[BucketPolicy] = None   # None -> LILAC_SERVE_BUCKETS/env
    mode: str = "continuous"                 # continuous | static
    queue_capacity: int = 1024
    eos_id: Optional[int] = None             # default eos for submitted text
    use_lilac: bool = True                   # lilac-compile the decode step
    lilac_mode: str = "host"
    policy: str = "default"
    plan_cache: Any = None                   # forwarded to lilac.compile
    # jit the admission/eviction tensor plumbing (prefill, cache-row
    # install, slot moves).  True requires a jax-traceable model; mock
    # models in tests turn it off and the engine calls the model's cache
    # hooks directly.
    jit_prefill: bool = True
    prewarm_on_start: bool = True
    # prompt lengths whose prefill XLA executables are compiled during
    # prewarm — requests at other lengths still work, they just pay a
    # first-occurrence jit compile on the request path
    prefill_lengths: Tuple[int, ...] = ()
    # default per-request deadline (seconds from arrival): a request past
    # it is evicted with failed="deadline" instead of holding a slot;
    # None = no deadline unless the Request carries its own
    deadline_s: Optional[float] = None
    # when set, submit() admits via Scheduler.try_admit(deadline=...)
    # (bounded retry-with-backoff on a full queue) instead of a single
    # SchedulerFull-raising attempt
    admit_deadline_s: Optional[float] = None
    # request-level shadow verification: the floor fraction of finished
    # requests re-decoded solo on this engine and compared token-for-token
    # against the batched stream (catches slot mix-ups / compaction bugs
    # the per-dispatch shadow cannot see).  None -> the
    # LILAC_REQUEST_SHADOW_RATE env var (default 0 = off); the effective
    # rate is adaptive — divergences spike it, clean checks decay it
    # (see repro.core.resilience.AdaptiveShadowRate)
    request_shadow_rate: Optional[float] = None

    def replace(self, **kw) -> "ServeConfig":
        return dataclasses.replace(self, **kw)


class Engine:
    """One serving replica.  ``model`` is anything with the
    :class:`repro.models.factory.Model` surface (prefill / decode /
    init_cache / cache_from_prefill / cache_set_slot / cache_move_slot /
    cache_resize); tests drive the scheduler logic with an integer mock.
    """

    def __init__(self, model, params, config: Optional[ServeConfig] = None,
                 *, clock=time.perf_counter):
        self.model = model
        self.params = params
        self.config = config or ServeConfig()
        self.buckets = self.config.buckets or default_buckets()
        self.clock = clock
        self.scheduler = Scheduler(self.buckets.max_batch,
                                   queue_capacity=self.config.queue_capacity,
                                   mode=self.config.mode)
        self.metrics = ServeMetrics(clock=clock)
        self._cache = None
        self._shape: Optional[Tuple[int, int]] = None    # (batch, seq) bucket
        self._prewarmed: set = set()
        from repro.core.resilience import AdaptiveShadowRate
        self._request_shadow = AdaptiveShadowRate(
            "LILAC_REQUEST_SHADOW_RATE",
            floor=self.config.request_shadow_rate)
        self._req_shadow_ctr = 0
        self.metrics.set_request_shadow_provider(self._request_shadow.snapshot)
        # a jax-traceable model's cache is donated to every program that
        # rewrites it (decode, slot install, slot move), so each updates
        # the cache in place instead of holding a second copy of it
        donate = ()
        self._state_row_bytes = 0
        if self.config.jit_prefill:
            import jax
            n_params = len(jax.tree.leaves(params))
            # one row and one position: the leaves' count and names do not
            # depend on the bucket
            cache_sds = jax.eval_shape(lambda: model.init_cache(1, 1))
            donate = tuple(range(n_params,
                                 n_params + len(jax.tree.leaves(cache_sds))))
            self._state_row_bytes = _state_row_bytes(cache_sds)
        if self.config.use_lilac:
            from repro import lilac
            self._decode = lilac.compile(
                model.decode, mode=self.config.lilac_mode,
                policy=self.config.policy,
                plan_cache=self.config.plan_cache, donate_args=donate)
            info = getattr(self._decode, "resilience_info", None)
            if info is not None:
                self.metrics.set_resilience_provider(info)
        else:
            self._decode = model.decode
        if self.config.jit_prefill:
            import jax

            def _prefill(p, toks):
                return model.prefill(p, {"tokens": toks})

            self._prefill = jax.jit(_prefill)
            # admission install and eviction compaction as single jitted
            # programs with a *dynamic* slot index: one XLA executable per
            # (prompt-length, bucket) combination, reused for every slot —
            # the eager tree-op spelling pays per-op dispatch/compile on
            # every admission instead

            def _install(cache, caches, slot, L, S):
                row = model.cache_from_prefill(caches, L, S)
                return jax.tree.map(
                    lambda full, one: jax.lax.dynamic_update_index_in_dim(
                        full, one[0].astype(full.dtype), slot, 0),
                    cache, row)

            def _move(cache, src, dst):
                return jax.tree.map(
                    lambda a: jax.lax.dynamic_update_index_in_dim(
                        a, jax.lax.dynamic_index_in_dim(
                            a, src, 0, keepdims=False), dst, 0),
                    cache)

            self._install = jax.jit(_install, static_argnums=(3, 4),
                                    donate_argnums=(0,))
            self._move = jax.jit(_move, donate_argnums=(0,))
        else:
            self._prefill = lambda p, toks: model.prefill(
                p, {"tokens": toks})

            def _install(cache, caches, slot, L, S):
                row = model.cache_from_prefill(caches, L, S)
                return model.cache_set_slot(cache, slot, row)

            self._install = _install
            self._move = model.cache_move_slot
        if self.config.prewarm_on_start and self.config.use_lilac:
            self.prewarm()

    # -- startup ---------------------------------------------------------

    def prewarm(self) -> Dict[str, Any]:
        """Bake one decode plan per bucket-grid point before traffic.

        Builds each ``(batch, seq)`` signature from shape specs (zero
        allocation for the caller) and funnels them through
        ``LilacFunction.prewarm``; the returned report carries per-bucket
        ``{baked, detect_calls, from_plan_cache}``.  With a warm
        persistent plan cache, ``detect_calls`` is 0 across the board.
        """
        import jax
        import jax.numpy as jnp
        sigs = []
        for (b, s) in self.buckets.grid():
            cache_sds = jax.eval_shape(lambda: self.model.init_cache(b, s))
            sigs.append((self.params, cache_sds,
                         jax.ShapeDtypeStruct((b, 1), jnp.int32),
                         jax.ShapeDtypeStruct((b,), jnp.int32)))
        report = self._decode.prewarm(*sigs)
        report["grid"] = [list(g) for g in self.buckets.grid()]
        self._prewarmed = set(self.buckets.grid())
        # prefill/admission warmup: trigger the per-(length, bucket) XLA
        # compiles of the prefill step, the cache-row install and the
        # slot-move compaction now, so admission and eviction at any
        # prewarmed shape are pure execution
        lengths = [L for L in self.config.prefill_lengths]
        prefills = {}
        for L in lengths:
            prefills[L] = self._prefill(self.params,
                                        jnp.zeros((1, L), jnp.int32))
            jax.block_until_ready(prefills[L])
        if lengths and self.config.jit_prefill:
            for (b, s) in self.buckets.grid():
                cache = self.model.init_cache(b, s)
                for L in lengths:
                    if L <= s:
                        _, caches = prefills[L]
                        cache = self._install(cache, caches, 0, L, s)
                jax.block_until_ready(self._move(cache, 0, b - 1))
        report["prefill_warmed"] = lengths
        self.metrics.record_prewarm(report)
        return report

    # -- request intake --------------------------------------------------

    def submit(self, req: Request) -> bool:
        """Enqueue a request; False (and a rejection metric) when the
        queue is full or the request cannot fit any bucket.  With
        ``config.admit_deadline_s`` set, a full queue is retried with
        bounded backoff (``Scheduler.try_admit``) before rejecting."""
        from repro.serve.buckets import BucketError
        from repro.serve.scheduler import SchedulerFull
        if req.eos_id is None:
            req.eos_id = self.config.eos_id
        if req.deadline_s is None:
            req.deadline_s = self.config.deadline_s
        try:
            self.buckets.seq_bucket(req.prompt_len + req.max_new_tokens)
        except BucketError:
            self.metrics.record_rejected()
            return False
        if self.config.admit_deadline_s is not None:
            retries = 0

            def _sleep(dt, _sleep=time.sleep):
                nonlocal retries
                retries += 1
                _sleep(dt)

            ok = self.scheduler.try_admit(
                req, deadline=self.config.admit_deadline_s, sleep=_sleep)
            if retries:
                self.metrics.record_admission_retries(retries)
            if not ok:
                self.metrics.record_admission_timeout()
                self.metrics.record_rejected()
                return False
        else:
            try:
                self.scheduler.submit(req)
            except SchedulerFull:
                self.metrics.record_rejected()
                return False
        req.arrival_t = self.clock()
        self.metrics.record_submit(req.rid, req.arrival_t, req.prompt_len)
        return True

    # -- one engine step --------------------------------------------------

    def step(self) -> List[Request]:
        """Admit -> re-bucket -> prefill admissions -> decode -> evict.
        Returns the requests that finished during this step.  Each phase
        runs under its ``serve.*`` span (docs/serving.md)."""
        with span("serve.step"):
            finished: List[Request] = []
            with span("serve.schedule"):
                self._expire_deadlines()
                admitted = self.scheduler.admissions()
                if self.scheduler.active:
                    self._fit_buckets()
            if admitted:
                self._admit(admitted)
                finished += self._evict()
            if self.scheduler.active:
                self._decode_once()
                finished += self._evict()
            return finished

    def run_until_idle(self, max_steps: int = DEFAULT_MAX_STEPS
                       ) -> List[Request]:
        out: List[Request] = []
        steps = 0
        while not self.scheduler.idle:
            out += self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"engine did not drain in {max_steps} "
                                   f"steps (livelock?)")
        return out

    def run(self, workload=None, max_steps: int = DEFAULT_MAX_STEPS
            ) -> Dict[str, Any]:
        """Drive a workload (iterable of ``(arrival_offset_s, Request)``)
        plus anything already submitted until drained; returns the metrics
        snapshot."""
        pending = deque(sorted(workload, key=lambda ar: ar[0])
                        if workload is not None else [])
        start = self.clock()
        steps = 0
        while pending or not self.scheduler.idle:
            now = self.clock() - start
            while pending and pending[0][0] <= now:
                _, req = pending.popleft()
                self.submit(req)
            if self.scheduler.idle:
                if pending:
                    wait = pending[0][0] - (self.clock() - start)
                    if wait > 0:
                        time.sleep(min(wait, 0.05))
                continue
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"workload did not drain in {max_steps} "
                                   f"steps")
        return self.metrics.snapshot()

    def drain(self) -> List[Request]:
        """Remove and return every in-flight request (active in slot
        order, then waiting in arrival order), resetting the replica's
        batch state.  The front door calls this on a failed replica; the
        caller discards partial generation before resubmitting — greedy
        decode is deterministic, so a re-run on a survivor regenerates
        the identical token stream."""
        out = self.scheduler.drain()
        self._cache = None
        self._shape = None
        return out

    def replay_solo(self, req: Request) -> List[int]:
        """Re-decode a finished request's stream alone ON THIS ENGINE,
        through the same compiled prefill/install/decode the batched path
        used: the request sits in slot 0 and each decode step runs at the
        ``(batch, seq)`` bucket it was served at (``req.decode_shapes``),
        the other rows empty.  Returns exactly ``len(req.tokens)`` greedy
        tokens — the reference the request-level shadow compares against.

        The served shapes matter.  Each bucket is its own XLA program, and
        on TPU the batch-8 and batch-1 programs round a row differently in
        the last bits, which greedy decoding through a deep model turns
        into different tokens.  What the *other* rows hold does not change
        a row's result, so a replay at the served shapes is exact and any
        difference is the batched path's fault (slot map, compaction,
        cache moves)."""
        shapes = list(req.decode_shapes[:len(req.tokens) - 1])
        shape = shapes[0] if shapes else (
            self.buckets.batch_bucket(1),
            self.buckets.seq_bucket(req.prompt_len + req.max_new_tokens))
        cache = self.model.init_cache(*shape)
        logits, caches = self._prefill(self.params, req.prompt[None, :])
        cache = self._install(cache, caches, 0, req.prompt_len, shape[1])
        with span("serve.readback"):
            logits_np = np.asarray(logits)
        toks = [int(np.argmax(logits_np[0]))]
        for B, S in shapes:
            if (B, S) != shape:
                cache = self.model.cache_resize(cache, B=B, max_seq=S)
                shape = (B, S)
            tokens = np.zeros((B, 1), np.int32)
            pos = np.zeros((B,), np.int32)
            tokens[0, 0] = toks[-1]
            pos[0] = req.prompt_len + len(toks) - 1
            logits, cache = self._decode(self.params, cache, tokens, pos)
            with span("serve.readback"):
                logits_np = np.asarray(logits)
            toks.append(int(np.argmax(logits_np[0])))
        return toks

    def generate_solo(self, prompt, max_new_tokens: int, *,
                      eos_id: Optional[int] = None) -> List[int]:
        """Run one request on a FRESH engine (same model/params/buckets,
        no prewarm) — the per-request reference stream the batching
        property tests compare against."""
        eng = Engine(self.model, self.params,
                     self.config.replace(prewarm_on_start=False,
                                         request_shadow_rate=0.0),
                     clock=self.clock)
        req = Request(prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens, eos_id=eos_id)
        if not eng.submit(req):
            raise ValueError("request does not fit any bucket")
        eng.run_until_idle()
        return list(req.tokens)

    # -- internals --------------------------------------------------------

    def _fit_buckets(self):
        active = self.scheduler.active
        need_s = max(r.prompt_len + r.max_new_tokens for r in active)
        target = (self.buckets.batch_bucket(len(active)),
                  self.buckets.seq_bucket(need_s))
        if target == self._shape:
            return
        if self._cache is None:
            self._cache = self.model.init_cache(*target)
        else:
            self._cache = self.model.cache_resize(
                self._cache, B=target[0], max_seq=target[1])
            self.metrics.record_resize()
        self._shape = target

    def _admit(self, admitted: Sequence[Request]):
        for req in admitted:
            with span("serve.prefill", rid=req.rid):
                slot = self.scheduler.active.index(req)
                t0 = self.clock()
                logits, caches = self._prefill(self.params,
                                               req.prompt[None, :])
                with span("serve.install", rid=req.rid,
                          state_bytes=self._state_row_bytes):
                    self._cache = self._install(self._cache, caches, slot,
                                                req.prompt_len,
                                                self._shape[1])
                count("serve.state_bytes_installed", self._state_row_bytes)
                with span("serve.readback"):
                    logits_np = np.asarray(logits)
                tok = int(np.argmax(logits_np[0]))
                req.tokens.append(tok)
                req.token_logits.append(float(logits_np[0, tok]))
                req.prefill_s = self.clock() - t0
                req.ttft_s = self.clock() - req.arrival_t
                self.metrics.record_admit(req.rid, req.prefill_s, req.ttft_s)

    def _decode_once(self):
        from repro.core import faults
        tb, ts = self._shape
        active = self.scheduler.active
        with span("serve.decode"):
            tokens = np.zeros((tb, 1), np.int32)
            pos = np.zeros((tb,), np.int32)
            for i, r in enumerate(active):
                tokens[i, 0] = r.tokens[-1]
                # the new token is written at the row's current depth
                pos[i] = r.prompt_len + len(r.tokens) - 1
            t0 = self.clock()
            try:
                if faults.ACTIVE is not None:
                    # attribute the injected fault to a rotating batch slot
                    # so chaos runs exercise eviction at every position
                    slot = faults.ACTIVE.attempts(
                        "decode_raise", "decode") % len(active)
                    faults.fail("decode_raise", "decode", slot=slot)
                logits, self._cache = self._decode(self.params, self._cache,
                                                   tokens, pos)
            except Exception as e:   # containment boundary: poison one slot
                slot = getattr(e, "slot", None)
                if not isinstance(slot, int) or not 0 <= slot < len(active):
                    slot = len(active) - 1
                active[slot].failed = \
                    f"decode: {type(e).__name__}: {e}"[:200]
                self.metrics.record_decode_fault()
                # the cache was NOT reassigned, so this step is a no-op for
                # the survivors: they redo the identical decode next step
                # and their streams stay bit-identical to a fault-free run
                # (a fault raised before the donated call consumed it)
                return
            dt = self.clock() - t0
        with span("serve.readback"):
            logits_np = np.asarray(logits)
        with span("serve.sample"):
            if faults.ACTIVE is not None and np.issubdtype(
                    logits_np.dtype, np.floating):
                if faults.check("decode_nan", "decode"):
                    slot = faults.ACTIVE.attempts(
                        "decode_nan", "decode") % len(active)
                    logits_np = np.array(logits_np, copy=True)
                    logits_np[slot] = np.nan
            # per-row finite check: a NaN/Inf row fails only that request;
            # the cache row itself is overwritten or compacted away at
            # eviction
            finite = np.isfinite(
                logits_np.reshape(logits_np.shape[0], -1)).all(axis=1)
            nxt = np.argmax(logits_np, axis=-1)
            picked = np.take_along_axis(logits_np, nxt[:, None], -1)[:, 0]
            for i, r in enumerate(active):
                if not finite[i]:
                    r.failed = "non-finite decode logits"
                    self.metrics.record_decode_fault()
                    continue
                r.tokens.append(int(nxt[i]))
                r.token_logits.append(float(picked[i]))
                r.decode_shapes.append((tb, ts))
            self.metrics.record_step(
                dt, batch=tb, active=len(active),
                queue_depth=self.scheduler.queue_depth,
                bucket_hit=(tb, ts) in self._prewarmed)

    def _expire_deadlines(self):
        """Evict requests past their per-request deadline.  Active ones
        are marked failed and leave through the ordinary compaction;
        waiting ones are dropped from the queue directly (they hold no
        cache slot, so no moves are needed)."""
        now = self.clock()

        def _past(r: Request) -> bool:
            return (r.deadline_s is not None and r.failed is None
                    and r.arrival_t and now - r.arrival_t > r.deadline_s)

        for r in self.scheduler.active:
            if _past(r):
                r.failed = "deadline"
        expired = [r for r in self.scheduler.waiting if _past(r)]
        if expired:
            self.scheduler.waiting = deque(
                r for r in self.scheduler.waiting if r not in expired)
            for r in expired:
                r.failed = "deadline"
                r.finish_t = now
                self.metrics.record_fault_eviction("deadline")
                self.metrics.record_finish(r.rid, len(r.tokens),
                                           now - r.arrival_t)

    def _evict(self) -> List[Request]:
        with span("serve.evict"):
            finished, moves = self.scheduler.evict_finished()
            for src, dst in moves:
                self._cache = self._move(self._cache, src, dst)
                count("serve.state_bytes_moved", self._state_row_bytes)
            now = self.clock()
            for r in finished:
                r.finish_t = now
                if r.failed is not None:
                    self.metrics.record_fault_eviction(r.failed)
                self.metrics.record_finish(r.rid, len(r.tokens),
                                           now - r.arrival_t)
                if r.failed is None and r.tokens:
                    self._maybe_shadow_request(r)
            return finished

    def _maybe_shadow_request(self, req: Request):
        """Request-level shadow verification on a deterministic stratified
        sample of finished requests (same scheme as the dispatch-level
        shadow: rate r checks finish n iff the integer part of n*r
        advances).  The batched stream is compared token-for-token with a
        solo replay on this same engine — any difference means the
        *batched path* (slot map, compaction, cache moves) corrupted the
        request, which per-dispatch shadowing of the decode fn cannot
        see.  Divergence feeds the compiled decode's quarantine→re-tune
        path and spikes both adaptive rates."""
        from repro.core import faults
        r = self._request_shadow.effective()
        if r <= 0.0:
            return
        self._req_shadow_ctr = n = self._req_shadow_ctr + 1
        if int(n * r) == int((n - 1) * r):
            return
        try:
            solo = self.replay_solo(req)
        except Exception:
            return      # the replay itself failed; never punish the served path
        diverged = (solo != list(req.tokens)
                    or faults.check("shadow_diverge", "request"))
        self.metrics.record_request_shadow(diverged)
        if not diverged:
            self._request_shadow.clean()
            return
        self._request_shadow.spike("request shadow divergence")
        report = getattr(self._decode, "report_divergence", None)
        if report is not None:
            report(reason=f"request-shadow divergence (rid {req.rid})")


def _state_row_bytes(cache_shapes) -> int:
    """Bytes of one cache row's recurrent state: every leaf but the
    attention keys and values, whose length grows with the sequence."""
    import jax
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache_shapes):
        if getattr(path[-1], "key", None) not in ("k", "v"):
            total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    return total


def build_engine(arch: str = "olmoe-1b-7b", *, smoke: bool = True,
                 seed: int = 0, config: Optional[ServeConfig] = None,
                 moe_decode_impl: Optional[str] = "naive_flat") -> Engine:
    """Convenience constructor: registry arch -> (smoke-sized) model ->
    initialized params -> Engine.  ``moe_decode_impl="naive_flat"`` makes
    the decode jaxpr carry the canonical dense-dispatch MoE form so the
    LiLAC detector can target it; None keeps the arch default."""
    import jax
    from repro.configs.base import get_arch, smoke_config
    from repro.models.factory import build_model
    cfg = get_arch(arch)
    if smoke:
        cfg = smoke_config(cfg)
    if moe_decode_impl is not None and cfg.moe_experts:
        cfg = cfg.replace(moe_decode_impl=moe_decode_impl)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    return Engine(model, params, config)
