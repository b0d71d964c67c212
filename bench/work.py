"""The work a computation needs, counted from the configuration's sizes.

Every count here is of the computation, not of whatever implements it: a
sparse matrix-vector product moves its nonzeros and its two vectors once,
whatever format, block size, padding or tiling the harness that runs it
uses.  So a later change of format or kernel changes the time, and never
the count.  A least time is the larger of the FLOP bound and the byte
bound at the chip's published peaks (``peaks.json``).
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

F32 = 4
BF16 = 2


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)

    __rmul__ = __mul__

    def least_s(self, peak: dict) -> float:
        """Least seconds at the chip's peaks: the larger bound."""
        return max(self.flops / peak["flops_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])


def peak_for(device_kind: str, path: Path | None = None) -> dict:
    """The published peaks of ``device_kind``; a kind that is not in the
    table is an error, never a default."""
    path = path or Path(__file__).resolve().parent / "peaks.json"
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(table)}")
    return table[device_kind]


# -- stencil CG ---------------------------------------------------------------

def stencil27_size(nx: int, ny: int, nz: int) -> tuple[int, int]:
    """Rows and nonzeros of the 27-point stencil on an nx x ny x nz grid
    (HPCG's GenerateProblem): a row holds each neighbour inside the grid,
    so each axis contributes 3n - 2 (offset, index) pairs."""
    return nx * ny * nz, (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)


def spmv(n: int, nnz: int, value_bytes: int = F32) -> Work:
    """y = A x: a multiply and an add per nonzero; each nonzero value read
    once, x read once and y written once.  Indices are not counted."""
    return Work(2.0 * nnz, float(nnz * value_bytes + 2 * n * value_bytes))


def cg_iteration(n: int, nnz: int, value_bytes: int = F32) -> Work:
    """One unpreconditioned CG iteration: the SpMV, two dot products
    (p.Ap reads two vectors, r.r one) and three axpys (x, r and p: two
    vectors read and one written each)."""
    dots = Work(2.0 * 2 * n, float(3 * n * value_bytes))
    axpys = Work(3 * 2.0 * n, float(3 * 3 * n * value_bytes))
    return spmv(n, nnz, value_bytes) + dots + axpys


# -- MoE decode -----------------------------------------------------------------

def experts_hit(tokens: int, topk: int, n_experts: int) -> int:
    """The most experts ``tokens`` tokens can route to; an upper bound on
    the experts whose weights one layer has to read."""
    return min(n_experts, tokens * topk)


def routed_experts(tokens: int, *, d_model: int, d_expert: int, topk: int,
                   n_experts: int, weight_bytes: int = BF16) -> Work:
    """One layer's routed expert FFN (SwiGLU: gate, up, down) for
    ``tokens`` tokens: three d_model x d_expert matmuls per (token, expert)
    pair; the weights of the experts hit read once, the tokens' input and
    output written and read once."""
    pairs = tokens * topk
    flops = pairs * 3 * 2.0 * d_model * d_expert
    w = experts_hit(tokens, topk, n_experts) * 3 * d_model * d_expert
    return Work(flops, float(w * weight_bytes
                             + 2 * tokens * d_model * weight_bytes))


def decode_step(cfg: dict, tokens: int, context: int,
                weight_bytes: int = BF16, cache_bytes: int = BF16) -> Work:
    """One decode step of the MoE transformer for ``tokens`` active rows
    whose attended positions sum to ``context``: every layer's attention
    projections, attention over the cache, router and routed experts, and
    the unembedding.  Weights are read once per step; the cache is read at
    each row's own depth (padding beyond it is not counted)."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    e, k, f = (cfg["num_local_experts"], cfg["num_experts_per_tok"],
               cfg["intermediate_size"])
    v = cfg["vocab_size"]
    proj = d * (h * dh + 2 * kv * dh) + h * dh * d          # q, k, v, o
    attn = Work(2.0 * tokens * proj + 2 * 2.0 * context * h * dh,
                float(proj * weight_bytes
                      + context * 2 * kv * dh * cache_bytes
                      + tokens * 2 * kv * dh * cache_bytes))
    router = Work(2.0 * tokens * d * e, float(d * e * F32))
    norms = Work(0.0, float(2 * d * F32))
    experts = routed_experts(tokens, d_model=d, d_expert=f, topk=k,
                             n_experts=e, weight_bytes=weight_bytes)
    head = Work(2.0 * tokens * d * v,
                float(d * v * weight_bytes + d * F32 + tokens * v * F32))
    return L * (attn + router + norms + experts) + head
