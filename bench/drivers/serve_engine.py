"""Drives the continuous-batching server (``serve.Engine``) with its decode
step compiled by the LiLAC pass, under closed-loop clients.

Set-up builds the model from the configuration's sizes, draws the weights
from the seed (the reference's ``init_params``), builds the engine on the
traffic's buckets, bakes its decode plan and prefill programs, and serves
one short warm-up burst through the same calls the window makes.  In the
window each client sends its next request when its last one finishes;
every output token is delivered when the engine step that produced it
returns.
"""
from __future__ import annotations

import gc
import heapq
import time
from typing import Any, Dict, List

import numpy as np


def arch_config(cfg: Dict[str, Any]):
    """The program's model configuration at the file's sizes."""
    import jax.numpy as jnp
    from repro.configs.base import get_arch
    return get_arch(cfg["arch"]).replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        moe_experts=cfg["num_local_experts"],
        moe_topk=cfg["num_experts_per_tok"],
        rope_theta=float(cfg["rope_theta"]), head_dim=None,
        param_dtype=getattr(jnp, cfg["torch_dtype"]),
        cache_dtype=getattr(jnp, cfg["torch_dtype"]),
        moe_impl=cfg["moe_impl"], moe_decode_impl="naive_flat")


def request_sizes(traffic: Dict[str, Any], rng: np.random.Generator,
                  blocks: int) -> List[tuple]:
    """(prompt length, new tokens, think seconds) of each request in
    sending order.  Every block of requests holds the same sizes (each
    prompt length with each new-token count on an even grid of the range)
    and the same think times (an even grid of theirs), shuffled by the
    seed, so seeds change the order and never the amount of work."""
    lo, hi = traffic["new_tokens"]
    levels = traffic["new_token_levels"]
    news = [lo + round(i * (hi - lo) / (levels - 1)) for i in range(levels)]
    block = [(p, t) for p in traffic["prompt_lens"] for t in news]
    t0, t1 = traffic["think_s"]
    thinks = [t0 + (t1 - t0) * (i + 0.5) / len(block)
              for i in range(len(block))]
    out = []
    for _ in range(blocks):
        order, think = rng.permutation(len(block)), rng.permutation(thinks)
        out += [block[i] + (float(w),) for i, w in zip(order, think)]
    return out


class _CompileCounter:
    """Counts XLA compilations while ``active``."""

    def __init__(self):
        import jax
        self.active = False
        self.count = 0

        def on_event(event, duration, **kw):
            if self.active and event.endswith("backend_compile_duration"):
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)


def _steal_s() -> float:
    """Seconds the host's hypervisor has held this machine's CPUs, summed
    over them (``steal`` in ``/proc/stat``); 0 where it is not kept."""
    import os
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        return int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return 0.0


class Run:
    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, log=print, reference=None):
        self.cfg, self.traffic, self.log, self.ref = (config, traffic, log,
                                                      reference)
        self.rng = np.random.default_rng(seed)
        self.seed = int(self.rng.integers(2**31 - 1))
        self.notes: List[str] = []
        self.done: List[Any] = []

    # -- set-up ------------------------------------------------------------

    def setup(self):
        import jax
        from repro.models.factory import build_model
        from repro.serve import BucketPolicy, Engine, ServeConfig
        t0 = time.perf_counter()
        self.model = build_model(arch_config(self.cfg))
        params = self.ref.init_params(self.cfg, self.seed)
        want = jax.tree.map(lambda a: (a.shape, a.dtype),
                            self.model.abstract_params())
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if want != got:
            raise ValueError("the reference's weights do not match the "
                             "program's parameter tree")
        jax.block_until_ready(params)
        self.log(f"weights drawn: {time.perf_counter() - t0:.3f} s")
        t = self.traffic
        self.engine = Engine(self.model, params, ServeConfig(
            buckets=BucketPolicy(batch=tuple(t["batch_buckets"]),
                                 seq=(t["seq_bucket"],)),
            prefill_lengths=tuple(t["prompt_lens"]),
            prewarm_on_start=False, use_lilac=True))
        t0 = time.perf_counter()
        report = self.engine.prewarm()
        self.log(f"prewarm: {report.get('baked')} decode plan(s) baked, "
                 f"prefill {report.get('prefill_warmed')}: "
                 f"{time.perf_counter() - t0:.3f} s")
        t0 = time.perf_counter()
        self._warm_burst()
        self.log(f"warm-up burst: {time.perf_counter() - t0:.3f} s")
        self.compiles = _CompileCounter()
        sel = sorted({n for _, n in self.engine._decode.last_selections})
        self.notes.append(f"decode harnesses: {sel}")
        self.sizes = request_sizes(t, self.rng, blocks=256)
        self._ramp()

    def _request(self, prompt_len: int, new_tokens: int):
        from repro.serve import Request
        prompt = self.rng.integers(1, self.cfg["vocab_size"], prompt_len)
        return Request(prompt=prompt.astype(np.int32),
                       max_new_tokens=int(new_tokens))

    def _warm_burst(self):
        """A full batch of short requests at every prompt length, ending
        on different steps, so admission, decode and eviction compaction
        at the traffic's shapes have all run before the window."""
        lens = self.traffic["prompt_lens"]
        batch = max(self.traffic["batch_buckets"])
        for i in range(batch):
            assert self.engine.submit(self._request(lens[i % len(lens)],
                                                    1 + i % 3))
        self.engine.run_until_idle()

    def _ramp(self):
        """Every client's first request, admitted before the window: their
        new-token counts spread evenly over the traffic's range, so they
        finish on different steps and the window opens on a batch already
        in its steady mix, not on one burst of admissions."""
        clients = int(self.traffic["clients"])
        lens = self.traffic["prompt_lens"]
        hi = self.traffic["new_tokens"][1]
        self.ramp = [self._request(lens[i % len(lens)],
                                   1 + (i * (hi - 1)) // max(clients - 1, 1))
                     for i in range(clients)]
        for r in self.ramp:
            assert self.engine.submit(r)
        self.done += [r for r in self.engine.step() if r.failed is None]
        self.ramp_t = time.perf_counter()

    # -- window ------------------------------------------------------------

    def measure(self, seconds: float, span) -> Dict[str, Any]:
        """Closed loop with think time: when a client's request finishes,
        its next one is due ``think`` seconds later; due requests are
        submitted between engine steps and timed from when they were due."""
        eng = self.engine
        sizes = iter(self.sizes)
        due: List[tuple] = []           # heap of (due time, n, size)
        sent: List[Any] = []            # (request, due time), in the window
        live = list(self.ramp)          # every request whose tokens count
        seen = {r.rid: len(r.tokens) for r in live}
        last_t = {r.rid: self.ramp_t for r in live}
        first_token: Dict[int, float] = {}
        gaps: List[float] = []
        decode_rows: List[int] = []
        context: List[int] = []
        step_wall: List[float] = []
        step_cpu: List[float] = []
        refused = 0
        steps0 = len(eng.metrics.decode_step_s)

        def think(now):
            size = next(sizes)
            heapq.heappush(due, (now + size[2], len(sent) + len(due), size))

        def send(until):
            nonlocal refused
            while due and due[0][0] <= until:
                t, _, size = heapq.heappop(due)
                r = self._request(*size[:2])
                if eng.submit(r):
                    sent.append((r, t))
                    live.append(r)
                else:
                    refused += 1

        gc.collect()
        gc.disable()
        self.compiles.active = True
        steal0 = _steal_s()
        with span("bench.window"):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            for _ in self.done:
                think(t0)
            tokens = 0
            while True:
                with span("bench.serve.send"):
                    send(time.perf_counter())
                if eng.scheduler.idle:
                    time.sleep(max(0.0, min(due[0][0] if due else deadline,
                                            deadline) - time.perf_counter()))
                    now = time.perf_counter()
                    if now >= deadline:
                        break
                    continue
                with span("bench.serve.step"):
                    t_step, c_step = time.perf_counter(), time.process_time()
                    finished = eng.step()
                now = time.perf_counter()
                step_wall.append(now - t_step)
                step_cpu.append(time.process_time() - c_step)
                rows = ctx = 0
                for r in live:
                    n_old, n_new = seen.get(r.rid, 0), len(r.tokens)
                    if n_new == n_old:
                        continue
                    if n_old == 0:
                        first_token[r.rid] = now
                    else:
                        gaps.append(now - last_t[r.rid])
                    gaps.extend([0.0] * (n_new - n_old - 1))
                    tokens += n_new - n_old
                    seen[r.rid], last_t[r.rid] = n_new, now
                    decoded = n_new - max(n_old, 1)
                    rows += decoded > 0
                    ctx += (r.prompt_len + n_new - 1) * (decoded > 0)
                decode_rows.append(rows)
                context.append(ctx)
                self.done += [r for r in finished if r.failed is None]
                live = [r for r in live if not r.done]
                for _ in finished:
                    think(now)
                if now >= deadline:
                    break
            window = now - t0
            self.compiles.active = False
            steal = _steal_s() - steal0
            # requests due in the window but not yet sent go in now; every
            # request due in the window gets its first token
            send(deadline)
            while any(r.rid not in first_token for r, _ in sent
                      if r.failed is None):
                eng.step()
                now = time.perf_counter()
                for r, _ in sent:
                    if r.tokens and r.rid not in first_token:
                        first_token[r.rid] = now
        gc.enable()
        step_s = eng.metrics.decode_step_s[steps0:]
        ttft = [first_token[r.rid] - t for r, t in sent
                if r.rid in first_token]
        failed = refused + sum(1 for r, _ in sent if r.failed is not None)
        self.log(f"window: {window:.3f} s, {len(sent)} requests sent, "
                 f"{len(self.done)} finished, {tokens} tokens, "
                 f"{len(decode_rows)} steps, compiles in window "
                 f"{self.compiles.count}")
        self._steadiness(window, step_wall, step_cpu, decode_rows, step_s,
                         steal)
        eng.drain()
        return {"metrics": {
                    "decode_tok_s": tokens / window,
                    "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3,
                    "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3},
                "counters": {"steps": len(decode_rows), "window_s": window,
                             "decode_rows": decode_rows, "context": context,
                             "engine_decode_step_s": step_s,
                             "tokens": tokens, "requests": len(sent)},
                "attempted": len(sent) + refused, "failed": failed}

    def _steadiness(self, window, step_wall, step_cpu, rows, dispatch,
                    steal):
        """Notes on how the window's time was spent, to tell a run slowed
        throughout from one held up by a few long steps, and a step the
        process spent computing from one it spent waiting."""
        w = np.asarray(step_wall) * 1e3
        full = w[np.asarray(rows) == max(rows, default=0)]
        med = float(np.median(w)) if w.size else 0.0
        long = w[w > 1.5 * med]
        self.notes.append(
            f"steps: {w.size}, step ms median {med:.3f} (full batch "
            f"{float(np.median(full)) if full.size else 0.0:.3f}) p90 "
            f"{float(np.percentile(w, 90)) if w.size else 0.0:.3f} max "
            f"{float(w.max()) if w.size else 0.0:.3f}; {long.size} steps "
            f"over 1.5x median, {float(long.sum()) / 1e3:.3f} s in them; "
            f"outside steps {window - float(w.sum()) / 1e3:.3f} s; "
            f"engine dispatch ms median "
            f"{float(np.median(dispatch)) * 1e3 if len(dispatch) else 0.0:.3f}")
        cpu = np.asarray(step_cpu) * 1e3
        top = np.argsort(-w)[:3]
        self.notes.append(
            "longest steps (index, wall ms, process cpu ms): "
            + ", ".join(f"({i}, {w[i]:.1f}, {cpu[i]:.1f})" for i in top)
            + f"; host steal in window {steal:.3f} s; process cpu in steps "
            f"{float(cpu.sum()) / 1e3:.3f} s")

    def release(self):
        self.engine = self.model = None
        gc.collect()

    # -- correctness -------------------------------------------------------

    def _sample(self) -> List[Any]:
        """A sample of the finished requests drawn from the seed, the
        longest among them."""
        done = sorted(self.done, key=lambda r: -(r.prompt_len + len(r.tokens)))
        n = min(int(self.traffic["check_requests"]), len(done))
        if n == 0:
            return []
        rest = np.random.default_rng(self.seed).permutation(len(done) - 1)
        return [done[0]] + [done[1 + i] for i in sorted(rest[: n - 1])]

    def _gaps(self, dtype=None):
        """Per sampled request, the logit gap of each served token below
        the reference's best at its position; with ``dtype`` the gap,
        under the float32 reference, of the token that the reference
        computed in that type puts first (the control)."""
        import jax.numpy as jnp
        sample = self._sample()
        if not sample:
            return None
        S = int(self.traffic["seq_bucket"])
        seqs = np.zeros((len(sample), S), np.int32)
        for i, r in enumerate(sample):
            full = np.concatenate([r.prompt, np.asarray(r.tokens[:-1],
                                                        np.int32)])
            seqs[i, :len(full)] = full
        params = self.ref.init_params(self.cfg, self.seed)
        logits = self.ref.forward(self.cfg, params, jnp.asarray(seqs))
        best = jnp.max(logits, -1)
        if dtype is not None:
            low = self.ref.forward(self.cfg, params, jnp.asarray(seqs),
                                   dtype=dtype)
            picked = jnp.take_along_axis(
                logits, jnp.argmax(low, -1)[..., None], -1)[..., 0]
        gaps = {}
        for i, r in enumerate(sample):
            pos = np.arange(r.prompt_len - 1, r.prompt_len - 1 + len(r.tokens))
            got = (logits[i, pos, np.asarray(r.tokens)] if dtype is None
                   else picked[i, pos])
            gaps.setdefault(r.prompt_len, []).append(
                np.asarray(best[i, pos] - got, np.float64))
        for n, g in sorted(gaps.items()):
            g = np.concatenate(g)
            self.log(f"{'control' if dtype else 'program'} prompts of {n}: "
                     f"{g.size} tokens, gap max {g.max():.4f} mean "
                     f"{g.mean():.5f}, not the reference's first "
                     f"{np.mean(g > 0):.4f}")
        return np.concatenate([x for g in gaps.values() for x in g])

    def _numbers(self, gaps) -> Dict[str, float]:
        return {"token_gap_mean": float(gaps.mean())}

    def check(self, ref):
        gaps = self._gaps()
        if gaps is None:
            return [("requests_compared", 0, -1)]
        lim = self.cfg["limits"]
        got = self._numbers(gaps)
        return [(name, got[name], lim[name]) for name in lim]

    def control(self, ref):
        import jax.numpy as jnp
        return sorted(self._numbers(
            self._gaps(dtype=jnp.float8_e4m3fn)).items())
