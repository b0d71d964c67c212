"""Drives the continuous-batching server over a hybrid Mamba-2 / attention /
MoE model (Granite 4.0-H), one chip's share of each layer, under
closed-loop clients.

Everything but the model and the check is ``serve_engine.py``'s, loaded
from its file into a copy of its own: set-up, warm-up, the closed loop
with think time, the window's metrics and the sample of finished requests
compared.  This driver changes how the model is configured (its layer
kinds, Mamba-2 sizes, Granite's scalars, the held share of the experts),
counts what the window prefilled and how many bytes of recurrent state
its slot installs and moves wrote, for the per-layer metrics, and compares
logits, not tokens.

The comparison: for every token a sampled request was served, the logit
the engine computed for it (prefill for the first, then each decode step
through the cache) against the reference's full forward's logit of the
same token at the same position, as a share of the spread (standard
deviation) of the reference's logits there; ``logit_err_mean`` is the
mean over the tokens.  Tokens alone cannot tell: with random weights the
tied embedding, scaled by 12, makes the input token the argmax nearly
everywhere, in the program, the reference and the float8 control alike.
The same embedding dominates the residual stream, so a state lost
between steps moves the logits little more than bfloat16 rounding does;
hence the second comparison, of the state itself: for a few requests
still decoding when the window closes, each Mamba-2 layer's state in the
engine's cache against the reference's recurrence over the same tokens
(``state_err_max``, the largest relative L2 error over requests, layers
and the two parts of the state).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Any, Dict

import numpy as np

_path = Path(__file__).resolve().parent / "serve_engine.py"
_spec = importlib.util.spec_from_file_location("bench_serve_engine_hybrid",
                                               _path)
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

_KINDS = {"mamba": "mamba2", "attention": "attn"}


def arch_config(cfg: Dict[str, Any]):
    """The program's model configuration at the file's sizes: one period
    of the file's layer kinds, the held experts of the router's."""
    import jax.numpy as jnp
    from repro.configs.base import get_arch
    kinds = [_KINDS[k] for k in cfg["layer_types"]]
    dep = cfg["deployment"]
    return get_arch(cfg["arch"]).replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        moe_experts=dep["router_experts"],
        moe_topk=cfg["num_experts_per_tok"],
        experts_held=(dep["experts_held_first"], cfg["num_local_experts"]),
        layer_types=tuple(kinds[:_period(kinds)]),
        d_state=cfg["mamba_d_state"], ssm_heads=cfg["mamba_n_heads"],
        ssm_head_dim=cfg["mamba_d_head"], ssm_groups=cfg["mamba_n_groups"],
        ssm_chunk=cfg["mamba_chunk_size"],
        shared_d_ff=cfg["shared_intermediate_size"],
        rope=cfg["position_embedding_type"] != "nope",
        norm_eps=float(cfg["rms_norm_eps"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), head_dim=None,
        param_dtype=getattr(jnp, cfg["torch_dtype"]),
        cache_dtype=getattr(jnp, cfg["torch_dtype"]),
        moe_impl=cfg["moe_impl"], moe_decode_impl="naive_flat")


def _period(kinds) -> int:
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


# this driver's own copy of serve_engine builds the hybrid model
base.arch_config = arch_config

COUNTERS = ("serve.state_bytes_installed", "serve.state_bytes_moved")


def _counters() -> Dict[str, int]:
    """The program's slot counters; empty for a program without them."""
    try:
        from repro.core import spans
    except ImportError:
        return {}
    got = spans.totals()
    return {n: got[n]["count"] for n in COUNTERS if n in got}


class Run(base.Run):
    def _request(self, prompt_len: int, new_tokens: int):
        r = super()._request(prompt_len, new_tokens)
        self.made.append(r)
        return r

    def setup(self):
        self.made = []
        super().setup()

    def measure(self, seconds: float, span) -> Dict[str, Any]:
        """The closed loop of ``serve_engine``; besides, the lengths of the
        prompts prefilled in the window and the recurrent-state bytes
        that slot installs and moves wrote in it, and the states of a few
        requests still decoding when the loop drains the engine."""
        eng = self.engine
        drain = eng.drain

        def keep_then_drain():
            self.kept = self._keep_states()
            return drain()

        n0, before = len(self.made), _counters()
        eng.drain = keep_then_drain
        try:
            out = super().measure(seconds, span)
        finally:
            del eng.drain
        after = _counters()
        out["counters"]["prefill_lens"] = [
            r.prompt_len for r in self.made[n0:] if r.tokens]
        for name in after:
            key = name.split(".", 1)[1]
            out["counters"][key] = after[name] - before.get(name, 0)
        return out

    def _keep_states(self):
        """For a sample of the requests in flight (drawn from the seed,
        the longest among them): the tokens each has consumed and each
        Mamba-2 layer's state in its row of the engine's cache, by layer
        index, on the host."""
        eng = self.engine
        rows = [(slot, r) for slot, r in enumerate(eng.scheduler.active)
                if r.failed is None and r.tokens]
        if eng._cache is None or not rows:
            return []
        rows.sort(key=lambda sr: -(sr[1].prompt_len + len(sr[1].tokens)))
        n = min(int(self.traffic["state_requests"]), len(rows))
        rest = np.random.default_rng(self.seed).permutation(len(rows) - 1)
        pick = [rows[0]] + [rows[1 + i] for i in sorted(rest[: n - 1])]
        period = len(eng._cache["p0"])
        layers = {j * period + int(b[1:]): ce
                  for p, per in eng._cache.items() for b, ce in per.items()
                  for j in [int(p[1:])] if "ssm" in ce}
        kept = []
        for slot, r in pick:
            seq = np.concatenate([r.prompt,
                                  np.asarray(r.tokens[:-1], np.int32)])
            kept.append((seq, {k: (np.asarray(ce["ssm"][slot]),
                                   np.asarray(ce["conv"][slot]))
                               for k, ce in layers.items()}))
        return kept

    # -- correctness -------------------------------------------------------

    def _errors(self, dtype=None):
        """|served logit - reference logit| / spread of the reference's
        logits, per token of the sampled requests; with ``dtype`` the
        reference computed in that type stands in for the program (the
        control)."""
        import jax.numpy as jnp
        sample = self._sample()
        if not sample:
            return None
        seqs = np.zeros((len(sample), int(self.traffic["seq_bucket"])),
                        np.int32)
        for i, r in enumerate(sample):
            full = np.concatenate([r.prompt, np.asarray(r.tokens[:-1],
                                                        np.int32)])
            seqs[i, :len(full)] = full
        params = self.ref.init_params(self.cfg, self.seed)
        want = self.ref.forward(self.cfg, params, jnp.asarray(seqs))
        low = (self.ref.forward(self.cfg, params, jnp.asarray(seqs),
                                dtype=dtype) if dtype is not None else None)
        errs = {}
        for i, r in enumerate(sample):
            pos = np.arange(r.prompt_len - 1, r.prompt_len - 1 + len(r.tokens))
            toks = np.asarray(r.tokens)
            ref = np.asarray(want[i, pos, toks], np.float64)
            got = (np.asarray(low[i, pos, toks], np.float64) if low is not None
                   else np.asarray(r.token_logits, np.float64))
            spread = np.asarray(jnp.std(want[i, pos], -1), np.float64)
            errs.setdefault(r.prompt_len, []).append(np.abs(got - ref)
                                                     / spread)
        for n, e in sorted(errs.items()):
            e = np.concatenate(e)
            self.log(f"{'control' if dtype else 'program'} prompts of {n}: "
                     f"{e.size} tokens, logit error / spread max "
                     f"{e.max():.5f} mean {e.mean():.6f}")
        return np.concatenate([x for e in errs.values() for x in e])

    def _state_errors(self, dtype=None):
        """|state - reference's state| / |reference's state| (L2, float32)
        of each kept request, Mamba-2 layer and part (the recurrence's
        state, the conv's tail); with ``dtype`` the reference computed in
        that type stands in for the program."""
        import jax.numpy as jnp
        kept = getattr(self, "kept", None)
        if not kept:
            return None
        seqs = np.zeros((len(kept), int(self.traffic["seq_bucket"])),
                        np.int32)
        for i, (seq, _) in enumerate(kept):
            seqs[i, :len(seq)] = seq
        stop = np.asarray([len(seq) - 1 for seq, _ in kept], np.int32)
        params = self.ref.init_params(self.cfg, self.seed)
        want = self.ref.states(self.cfg, params, jnp.asarray(seqs), stop)
        low = (self.ref.states(self.cfg, params, jnp.asarray(seqs), stop,
                               dtype=dtype) if dtype is not None else None)
        errs = []
        for k, (ssm, conv) in sorted(want.items()):
            for part, w in ((0, ssm), (1, conv)):
                w = np.asarray(w, np.float64)
                got = (np.asarray(low[k][part], np.float64) if low is not None
                       else np.stack([st[k][part] for _, st in kept]))
                for i in range(len(kept)):
                    errs.append(np.linalg.norm(got[i] - w[i])
                                / max(np.linalg.norm(w[i]), 1e-30))
        errs = np.asarray(errs)
        self.log(f"{'control' if dtype else 'program'} states of "
                 f"{len(kept)} requests in flight, {len(want)} layers: "
                 f"relative error max {errs.max():.6f} "
                 f"mean {errs.mean():.6f}")
        return errs

    def check(self, ref):
        errs = self._errors()
        if errs is None:
            return [("requests_compared", 0, -1)]
        got = {"logit_err_mean": float(errs.mean())}
        st = self._state_errors()
        if st is not None:
            got["state_err_max"] = float(st.max())
        elif "state_err_max" in self.cfg["limits"]:
            return [("states_compared", 0, -1)]
        return [(name, got[name], lim)
                for name, lim in self.cfg["limits"].items()]

    def control(self, ref):
        import jax.numpy as jnp
        errs = self._errors(dtype=jnp.float8_e4m3fn)
        out = [("logit_err_mean", float(errs.mean()))]
        st = self._state_errors(dtype=jnp.float8_e4m3fn)
        if st is not None:
            out.append(("state_err_max", float(st.max())))
        return out
