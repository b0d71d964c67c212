"""The work of a hybrid Mamba-2 / attention / MoE model (Granite 4.0-H), one
chip's share of each layer, counted from the configuration's sizes.

As in ``work.py`` (whose ``Work`` and peaks this reuses), every count is
of the computation, not of whatever implements it: weights are read once
per step or per prompt, each row's recurrent state (Mamba-2's ``ssm`` and
conv tail) read and written once per decode step, the attention cache read
at each row's own depth, and the routed experts counted for the pairs
that land on the experts held here.  Which pairs those are depends on the
router, so the counts take the expectation under even routing:
``tokens x top-k x held / router_experts`` pairs, and as many distinct
held experts as those pairs can reach.
"""
from __future__ import annotations

import math

from work import BF16, F32, Work


def sizes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    N, G = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    kinds = cfg["layer_types"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"], "dh": d // h,
            "H": H, "P": P, "N": N, "di": H * P, "conv": H * P + 2 * G * N,
            "mamba": sum(k == "mamba" for k in kinds),
            "attn": sum(k == "attention" for k in kinds),
            "layers": cfg["num_hidden_layers"],
            "e": cfg["num_local_experts"], "k": cfg["num_experts_per_tok"],
            "E": cfg["deployment"]["router_experts"],
            "f": cfg["intermediate_size"],
            "sf": cfg["shared_intermediate_size"], "v": cfg["vocab_size"],
            "K": cfg["mamba_d_conv"]}


def held_pairs(cfg: dict, tokens: float) -> float:
    """(token, expert) pairs of ``tokens`` tokens routed to the experts
    held here, expected under even routing."""
    m = sizes(cfg)
    return tokens * m["k"] * m["e"] / m["E"]


def held_experts(cfg: dict, tokens: float) -> int:
    """Held experts whose weights one layer reads: at most all of them,
    at most one per pair (the pairs rounded up)."""
    return min(sizes(cfg)["e"], math.ceil(held_pairs(cfg, tokens)))


def routed_experts(cfg: dict, tokens: float,
                   weight_bytes: int = BF16) -> Work:
    """One layer's held experts (SwiGLU: gate, up, down) for ``tokens``
    tokens: three d x f matmuls per held pair, the weights of the experts
    hit read once, the tokens' input and output moved once."""
    m = sizes(cfg)
    d, f = m["d"], m["f"]
    return Work(held_pairs(cfg, tokens) * 3 * 2.0 * d * f,
                float(held_experts(cfg, tokens) * 3 * d * f * weight_bytes
                      + 2 * tokens * d * weight_bytes))


def _layer_weights(cfg: dict) -> dict:
    """Elements of the weights each kind of layer reads for every token,
    apart from the routed experts."""
    m = sizes(cfg)
    d = m["d"]
    return {
        "mamba": d * (m["di"] + m["conv"] + m["H"]) + m["di"] * d,
        "attn": d * (m["h"] * m["dh"] + 2 * m["kv"] * m["dh"])
        + m["h"] * m["dh"] * d,
        "router": d * m["E"],
        "shared": 3 * d * m["sf"],
    }


def _dense_work(cfg: dict, tokens: float, weight_bytes: int) -> Work:
    """Every layer's projections, router, shared expert and held experts
    for ``tokens`` tokens, weights read once."""
    m = sizes(cfg)
    w = _layer_weights(cfg)
    mats = m["mamba"] * w["mamba"] + m["attn"] * w["attn"] \
        + m["layers"] * w["shared"]
    small = m["mamba"] * (m["K"] * m["conv"] + m["conv"] + 3 * m["H"]
                          + m["di"]) + 2 * m["layers"] * m["d"] + m["d"]
    return (Work(2.0 * tokens * (mats + m["layers"] * w["router"]),
                 float(mats * weight_bytes
                       + (m["layers"] * w["router"] + small) * F32))
            + m["layers"] * routed_experts(cfg, tokens, weight_bytes))


def state_row_bytes(cfg: dict, state_bytes: int = F32) -> int:
    """One row's recurrent state over the Mamba-2 layers: ssm and conv."""
    m = sizes(cfg)
    return m["mamba"] * (m["H"] * m["P"] * m["N"]
                         + (m["K"] - 1) * m["conv"]) * state_bytes


def decode_step(cfg: dict, tokens: int, context: int,
                weight_bytes: int = BF16, cache_bytes: int = BF16,
                state_bytes: int = F32) -> Work:
    """One decode step for ``tokens`` active rows whose attended positions
    sum to ``context``: all of the layers' weights once, each row's state
    read and written once, the recurrence (state update and read-out),
    attention over the cache at each row's depth, and the tied head."""
    m = sizes(cfg)
    recur = Work(4.0 * tokens * m["mamba"] * m["H"] * m["P"] * m["N"],
                 float(2 * tokens * state_row_bytes(cfg, state_bytes)))
    kv = 2 * m["kv"] * m["dh"]
    attn = Work(2 * 2.0 * context * m["h"] * m["dh"] * m["attn"],
                float(m["attn"] * (context + tokens) * kv * cache_bytes))
    head = Work(2.0 * tokens * m["d"] * m["v"],
                float(m["v"] * m["d"] * weight_bytes
                      + tokens * m["v"] * F32))
    return _dense_work(cfg, tokens, weight_bytes) + recur + attn + head


def prefill(cfg: dict, length: int, weight_bytes: int = BF16,
            cache_bytes: int = BF16, state_bytes: int = F32) -> Work:
    """One prompt of ``length`` tokens: all of the layers' weights once,
    the recurrence for every token, causal attention, the final state
    and the keys and values written once, and the head for the last
    position only."""
    m = sizes(cfg)
    recur = Work(4.0 * length * m["mamba"] * m["H"] * m["P"] * m["N"],
                 float(state_row_bytes(cfg, state_bytes)))
    kv = 2 * m["kv"] * m["dh"]
    attn = Work(2 * 2.0 * length * (length + 1) / 2 * m["h"] * m["dh"]
                * m["attn"], float(m["attn"] * length * kv * cache_bytes))
    head = Work(2.0 * m["d"] * m["v"], float(m["v"] * m["d"] * weight_bytes))
    return _dense_work(cfg, length, weight_bytes) + recur + attn + head


def slot_writes(state_bytes: float) -> Work:
    """Installs and moves of rows whose recurrent state totals
    ``state_bytes``: each byte read once and written once."""
    return Work(0.0, 2.0 * state_bytes)
