"""One-chip bring-up smoke: the LiLAC pass, its three Pallas kernels and
the MoE server, driven through their normal entry points at real size on
one TPU.

    python3 chip_smoke.py [--seed N]

Run it from anywhere inside a checkout; it finds ``src/`` next to itself.
Each phase runs in a process of its own, one after the other, and this
parent never imports jax, so exactly one process holds the chip at a time:

  device  prints ``jax.devices()``; a platform other than ``tpu`` fails
  sparse  naive CSR SpMV (the segment_sum form) on a 2^20-row symmetric,
          diagonally dominant random graph of average degree 16, through
          ``lilac.compile(mode="host")`` with policy ``pallas.ell`` and with
          ``autotune``, against ``jax.jit`` of the same function; then 25
          iterations of the CG loop of ``examples/cg_solver.py`` through
          the compiled SpMV against the same loop under ``jax.jit``
  spmm    naive CSR SpMM, 65,536 rows of three 128x128 block diagonals
          times a (65,536 x 512) f32 right-hand side, through the
          ``spmm_csr`` default on TPU (``pallas.bcsr``)
  serve   granite-moe-3b-a800m at its published widths with bf16 weights
          from the seed: 8 requests on the continuous-batching engine,
          whose decode step runs ``pallas.gmm``, and two requests served
          alone, all under request shadowing: every stream equals
          ``Engine.replay_solo`` (the request alone at the buckets it was
          served at) and nothing is quarantined; one decode step of the
          model cut to its first layer against an un-rewritten engine,
          and a planted fault (each token's heaviest expert skipped)
          that this check must catch

Sizes are module constants.  To rehearse a phase on the CPU at a tiny
size, import this module with ``PYTHONPATH=src:.``, lower the sizes, set
``SPMM_HARNESS`` and ``MOE_HARNESS`` to the CPU defaults (``jnp.segment``,
``jnp.capacity``) and ``SERVE_SMOKE_CONFIG = True``, point the three
``LILAC_*_CACHE`` variables at a scratch directory and call a ``phase_*``
function.

After each phase the run checks that nothing fell back in silence: each
match selected the expected Pallas harness, the plan baked, containment
counted no exception, quarantine or fallback, the quarantine store is
empty and (serve) the engine counted no decode fault.  Any failed check
exits nonzero.  The autotune, plan and quarantine stores live in a fresh
``.smoke_cache/`` of the checkout; XLA's compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jax_cache``.

The last line of standard output is one JSON object, printed only when
every phase passed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Timings and peak memory are printed on earlier lines; they are not
metrics.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PHASES = ("device", "sparse", "spmm", "serve")
# seconds the whole run may take; a phase still running then is killed
RUN_TIMEOUT = 1150
RESULT = "PHASE_RESULT "

# sizes of the phases
SPARSE_N = 1 << 20            # rows of the SpMV matrix
AVG_DEGREE = 16.0
CG_ITERS = 25
SPMM_BLOCK_ROWS = 512         # 128-row block rows of the SpMM matrix
SPMM_RHS = 512                # columns of its dense right-hand side
SPMM_PANELS = 16              # row panels of the jax.jit reference
SERVE_ARCH = "granite-moe-3b-a800m"
SERVE_SMOKE_CONFIG = False    # True: the arch's smoke config
SERVE_BATCH = (1, 4, 8)
SERVE_SEQ = (256,)
PROMPT_LENS = (64, 128)
N_REQUESTS = 8
NEW_TOKENS = 32
# the harness each phase must select: sparse pins it by name, spmm and
# serve take the platform default
SPMV_HARNESS = "pallas.ell"
SPMM_HARNESS = "pallas.bcsr"
MOE_HARNESS = "pallas.gmm"

# tolerances, relative to the largest magnitude of the reference
SPMV_RTOL = 1e-5     # f32 sums of ~34 products, reassociated
SPMM_RTOL = 1e-5     # f32 sums of 384 products, reassociated
CG_RTOL = 1e-4       # 25 CG iterations of the above
# Decode logits, on the model cut to CUT_LAYERS layers: the lilac decode
# against the un-rewritten decode of the same bf16 weights and cache, as
# the relative L2 distance of each batch row.  The two programs round at
# different points (XLA fuses each its own way) and sit a few 1e-3 apart
# at one layer.  Each extra layer of the random-weight model multiplies
# such a difference (tools/batch_invariance.py measures both), so the
# comparison runs at one layer, and every row must be within the limit.
# A planted fault must fail that check, so it is shown to catch a fault
# of that size: the expert FFN is handed a gate with each token's
# FAULT_DROP-th heaviest expert zeroed (0: the heaviest), as a kernel
# that skipped that expert would compute.  (The random model's router is
# so peaked that the lighter experts barely move a row.)
LOGIT_RTOL = 1e-2
CUT_LAYERS = 1
FAULT_DROP = 0


# ---------------------------------------------------------------------------
# phases (each runs in its own child process)
# ---------------------------------------------------------------------------

def phase_device():
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"devices: {devs}")
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    print(f"platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}")
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: jax reports platform {d.platform!r}")
    return info


def _check_no_fallback(fn, comp: str, expect: str):
    """The phase's compiled function served a baked plan in which every
    ``comp`` match runs the expected harness, and containment never
    stepped in."""
    from repro.core.resilience import shared_quarantine
    names = sorted({name for m, name in fn.last_selections
                    if m.computation == comp})
    assert names == [expect], f"{comp} selected {names}, expected {expect}"
    info = fn.plan_info()
    assert info["baked"] >= 1 and not info["bake_errors"], info
    cont = fn.resilience_info()["containment"]
    bad = {k: cont[k] for k in ("contained_exceptions", "quarantines",
                                "fallbacks") if cont[k]}
    assert not bad, f"containment stepped in: {bad}"
    active = shared_quarantine().active()
    assert not active, f"quarantine store not empty: {sorted(active)}"


def _rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all(), "non-finite output"
    return float(np.abs(got - want).max() / np.abs(want).max())


def _mem_stat(key: str = "peak_bytes_in_use"):
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get(key)


def _timed(label, fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    print(f"{label}: {time.perf_counter() - t0:.3f} s")
    return out


def spd_graph_csr(n: int, avg_degree: float, seed: int):
    """``A + A^T + D`` of a random graph A, with D the diagonal that makes
    every row strictly dominant (so the matrix is SPD), built from the
    edges alone."""
    import numpy as np
    from repro.sparse.random import csr_from_edges, random_graph_csr
    g = random_graph_csr(n, avg_degree, seed)
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(np.asarray(g.row_ptr)))
    dst = np.asarray(g.col_ind).astype(np.int64)
    val = np.asarray(g.val)
    key = np.concatenate([src * n + dst, dst * n + src])
    key, inv = np.unique(key, return_inverse=True)
    sym = np.bincount(inv, weights=np.concatenate([val, val]))
    rows, cols = key // n, key % n
    diag = np.bincount(rows, weights=np.abs(sym), minlength=n) + 1.0
    key = np.concatenate([key, np.arange(n, dtype=np.int64) * (n + 1)])
    vals = np.concatenate([sym, diag])
    order = np.argsort(key, kind="stable")
    key = key[order]
    return csr_from_edges(n, n, key // n, key % n,
                          vals[order].astype(np.float32))


def phase_sparse(seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import naive_spmv_fn
    from repro import lilac
    sys.path.insert(0, str(ROOT / "examples"))
    from cg_solver import cg

    n, harness = SPARSE_N, SPMV_HARNESS
    t0 = time.perf_counter()
    csr = spd_graph_csr(n, AVG_DEGREE, seed)
    print(f"matrix: {n} rows, {csr.nnz} nonzeros, built in "
          f"{time.perf_counter() - t0:.1f} s")
    args = (csr.val, csr.col_ind, csr.row_ptr)
    x = jnp.asarray(np.random.default_rng(seed + 1).standard_normal(n)
                    .astype(np.float32))
    naive = naive_spmv_fn(n, csr.nnz)
    ref = jax.jit(naive)
    y_ref = _timed("jit naive spmv (first call)", ref, *args, x)
    _timed("jit naive spmv", ref, *args, x)

    fast = lilac.compile(naive, mode="host", policy=harness)
    _timed(f"lilac {harness} (detect, marshal, bake)", fast, *args, x)
    y = _timed(f"lilac {harness} (baked plan)", fast, *args, x)
    err = _rel_err(y, y_ref)
    print(f"spmv {harness}: max rel err {err:.3e} (limit {SPMV_RTOL:g})")
    assert err <= SPMV_RTOL
    _check_no_fallback(fast, "spmv_csr", harness)

    # CG through the compiled SpMV, against the same loop under jax.jit
    b = jnp.asarray(np.random.default_rng(seed + 2).standard_normal(n)
                    .astype(np.float32))
    t0 = time.perf_counter()
    x_jit, _ = cg(ref, csr, b, iters=CG_ITERS, tol=0.0)
    x_jit = jax.block_until_ready(x_jit)
    print(f"cg {CG_ITERS} iterations, jax.jit: "
          f"{time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    x_fast, _ = cg(fast, csr, b, iters=CG_ITERS, tol=0.0)
    x_fast = jax.block_until_ready(x_fast)
    print(f"cg {CG_ITERS} iterations, lilac {harness}: "
          f"{time.perf_counter() - t0:.3f} s")
    bnorm = float(jnp.linalg.norm(b))
    res_jit = float(jnp.linalg.norm(ref(*args, x_jit) - b)) / bnorm
    res_fast = float(jnp.linalg.norm(ref(*args, x_fast) - b)) / bnorm
    x_err = _rel_err(x_fast, x_jit)
    print(f"cg relative residual: jit {res_jit:.3e}, lilac {res_fast:.3e}; "
          f"solution max rel diff {x_err:.3e} (limit {CG_RTOL:g})")
    assert res_jit < 1e-2 and res_fast < 1e-2, "CG made no progress"
    assert abs(res_fast - res_jit) <= CG_RTOL and x_err <= CG_RTOL
    _check_no_fallback(fast, "spmv_csr", harness)
    del fast

    tuned = lilac.compile(naive, mode="host", policy="autotune")
    _timed("lilac autotune (detect, tune, marshal, bake)", tuned, *args, x)
    y = _timed("lilac autotune (baked plan)", tuned, *args, x)
    err = _rel_err(y, y_ref)
    winner = tuned.last_selections[0][1]
    decision = lilac.REGISTRY.autotuner.last_decision
    rec = lilac.REGISTRY.autotuner.cache.get(decision.sig, "host") or {}
    timings = rec.get("timings", {})
    print(f"autotune winner {winner} {decision.schedule}; best seconds per "
          f"harness: {json.dumps(timings, sort_keys=True)}")
    print(f"spmv autotune: max rel err {err:.3e} (limit {SPMV_RTOL:g})")
    assert err <= SPMV_RTOL
    assert harness in timings, f"{harness} was not measured: {sorted(timings)}"
    _check_no_fallback(tuned, "spmv_csr", winner)
    return {"harness": harness, "autotune_winner": winner}


def block_banded_csr(block_rows: int, bs: int, seed: int):
    """Three ``bs`` x ``bs`` block diagonals (the first and last block-row
    hold two blocks), dense within each block, N(0, 1) values."""
    import numpy as np
    from repro.sparse.random import csr_from_edges
    n = block_rows * bs
    rng = np.random.default_rng(seed)
    br = np.arange(n, dtype=np.int64) // bs
    lo = np.maximum(br - 1, 0) * bs
    hi = np.minimum(br + 2, block_rows) * bs
    counts = hi - lo
    rows = np.repeat(np.arange(n, dtype=np.int64), counts)
    start = np.repeat(np.cumsum(counts) - counts, counts)
    cols = np.repeat(lo, counts) + np.arange(rows.shape[0]) - start
    vals = rng.standard_normal(rows.shape[0]).astype(np.float32)
    return csr_from_edges(n, n, rows, cols, vals)


def phase_spmm(seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import lilac

    t0 = time.perf_counter()
    csr = block_banded_csr(SPMM_BLOCK_ROWS, 128, seed)
    rows = csr.rows
    print(f"matrix: {rows} rows, {csr.nnz} nonzeros, built in "
          f"{time.perf_counter() - t0:.1f} s")
    dense = jnp.asarray(np.random.default_rng(seed + 1).standard_normal(
        (rows, SPMM_RHS)).astype(np.float32))

    def naive(val, col, row_ptr, rhs):
        r = val.shape[0]
        m = row_ptr.shape[0] - 1
        row = jnp.repeat(jnp.arange(m, dtype=jnp.int32), jnp.diff(row_ptr),
                         total_repeat_length=r)
        return jax.ops.segment_sum(val[:, None] * rhs[col], row,
                                   num_segments=m)

    fast = lilac.compile(naive, mode="host")
    args = (csr.val, csr.col_ind, csr.row_ptr, dense)
    _timed("lilac spmm (detect, marshal, bake)", fast, *args)
    y = _timed("lilac spmm (baked plan)", fast, *args)

    # The reference is jax.jit of the same naive function.  Its (nnz, 512)
    # product alone is 51 GB, so it runs on row panels of the matrix.
    ref = jax.jit(naive)
    ptr = np.asarray(csr.row_ptr)
    step = rows // SPMM_PANELS
    t0 = time.perf_counter()
    parts = []
    for p in range(SPMM_PANELS):
        a, b = int(ptr[p * step]), int(ptr[(p + 1) * step])
        parts.append(ref(csr.val[a:b], csr.col_ind[a:b],
                         csr.row_ptr[p * step:(p + 1) * step + 1] - a, dense))
    y_ref = jax.block_until_ready(jnp.concatenate(parts))
    print(f"jit naive spmm ({SPMM_PANELS} row panels): "
          f"{time.perf_counter() - t0:.3f} s")
    err = _rel_err(y, y_ref)
    print(f"spmm: max rel err {err:.3e} (limit {SPMM_RTOL:g})")
    assert err <= SPMM_RTOL
    _check_no_fallback(fast, "spmm_csr", SPMM_HARNESS)
    return {"harness": SPMM_HARNESS}


def phase_serve(seed: int):
    import jax
    import numpy as np

    from repro.serve import BucketPolicy, Request, ServeConfig, build_engine

    harness = MOE_HARNESS
    # every finished request is shadowed: replay_solo re-decodes it alone
    # at the buckets it was served at, and a different stream would be
    # reported as a divergence and quarantine the decode's harness
    cfg = ServeConfig(buckets=BucketPolicy(batch=SERVE_BATCH, seq=SERVE_SEQ),
                      prefill_lengths=PROMPT_LENS, prewarm_on_start=False,
                      request_shadow_rate=1.0)
    t0 = time.perf_counter()
    eng = build_engine(SERVE_ARCH, smoke=SERVE_SMOKE_CONFIG, seed=seed,
                       config=cfg)
    jax.block_until_ready(eng.params)
    print(f"engine built, {eng.model.param_count()} parameters "
          f"({jax.tree.leaves(eng.params)[0].dtype}): "
          f"{time.perf_counter() - t0:.1f} s, peak_bytes_in_use so far "
          f"{_mem_stat()}")
    t0 = time.perf_counter()
    report = eng.prewarm()
    print(f"prewarm: {report['baked']}/{report['n_signatures']} decode "
          f"plans baked, prefill lengths {report['prefill_warmed']}: "
          f"{time.perf_counter() - t0:.1f} s")

    rng = np.random.default_rng(seed + 3)
    vocab = eng.model.cfg.vocab
    reqs = [Request(prompt=rng.integers(1, vocab, PROMPT_LENS[i % 2])
                    .astype(np.int32), max_new_tokens=NEW_TOKENS)
            for i in range(N_REQUESTS)]
    t0 = time.perf_counter()
    for r in reqs:
        assert eng.submit(r), "request rejected"
    eng.run_until_idle()
    print(f"served {N_REQUESTS} requests x {NEW_TOKENS} new tokens, each "
          f"replayed by the request shadow: {time.perf_counter() - t0:.1f} "
          f"s, bytes_in_use {_mem_stat('bytes_in_use')}")
    print("ttft s: " + " ".join(f"{r.ttft_s:.3f}" for r in reqs))
    alone = [Request(prompt=rng.integers(1, vocab, n).astype(np.int32),
                     max_new_tokens=NEW_TOKENS) for n in PROMPT_LENS]
    for r in alone:
        assert eng.submit(r), "request rejected"
        eng.run_until_idle()
    snap = eng.metrics.snapshot()
    print(f"metrics: {json.dumps(snap, default=str, sort_keys=True)}")
    done = reqs + alone
    failed = [(r.rid, r.failed) for r in done if r.failed is not None]
    assert not failed, f"requests failed: {failed}"
    assert all(len(r.tokens) == NEW_TOKENS for r in done)
    res = snap["resilience"]
    assert res["decode_faults"] == 0, res
    assert res["request_shadow_checks"] == len(done), res
    assert res["request_shadow_divergences"] == 0, res
    print(f"{len(reqs)} requests served in a batch and {len(alone)} served "
          "alone identical to their solo replays at the served shapes "
          f"{sorted({s for r in done for s in r.decode_shapes})}")
    _check_no_fallback(eng._decode, "moe_ffn", harness)

    cut = cut_engine(eng, CUT_LAYERS)
    logits = decode_step_logits(cut, reqs[:SERVE_BATCH[-1]], SERVE_SEQ[-1])
    err = {name: row_rel_l2(got, logits["un-rewritten"])
           for name, got in logits.items() if name != "un-rewritten"}
    for name, e in err.items():
        print(f"decode logits, first {CUT_LAYERS} layer(s), {name} vs "
              "un-rewritten, relative L2 per row: "
              + " ".join(f"{v:.3e}" for v in e))
    fault = err[f"fault {FAULT_DROP}"]
    print(f"largest row: lilac {err['lilac'].max():.3e}, planted fault "
          f"{fault.max():.3e} (limit {LOGIT_RTOL:g})")
    assert err["lilac"].max() <= LOGIT_RTOL
    assert fault.max() > LOGIT_RTOL, \
        "the logits check cannot tell the planted fault from rounding"
    _check_no_fallback(cut._decode, "moe_ffn", harness)
    return {"harness": harness}


def row_rel_l2(got, want):
    """Relative L2 distance of each row of ``got`` from ``want``."""
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all() and np.isfinite(want).all(), \
        "non-finite decode logits"
    return np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)


def cut_engine(eng, n_layers: int):
    """An engine on the first ``n_layers`` layers of ``eng``'s model and
    params, with the same serving config."""
    import jax

    from repro.models import build_model
    from repro.serve import Engine

    params = dict(eng.params)
    params["blocks"] = jax.tree.map(lambda a: a[:n_layers], params["blocks"])
    cfg = eng.model.cfg.replace(n_layers=n_layers)
    return Engine(build_model(cfg), params, eng.config)


@contextlib.contextmanager
def planted_fault(drop: int):
    """Within the block, the MoE router hands the expert FFN a gate with
    each token's ``drop``-th heaviest expert zeroed."""
    import jax.numpy as jnp

    from repro.models import layers as L
    real = L.moe_router

    def faulty(p, x, topk):
        gate, idx, aux = real(p, x, topk)
        return gate * (jnp.arange(topk) != drop), idx, aux
    L.moe_router = faulty
    try:
        yield
    finally:
        L.moe_router = real


def decode_step_logits(eng, reqs, S: int, drops=(FAULT_DROP,)) -> dict:
    """Logits of one decode step of a batch holding each request's
    prefill cache in its own slot: through the engine's lilac decode,
    through the decode of an un-rewritten engine on the same model and
    params, and, for each ``drops`` entry k, through the lilac decode of
    an engine traced under ``planted_fault(k)`` ("fault k")."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import Engine, ServeConfig

    model, params = eng.model, eng.params
    rows = []
    for r in reqs:
        _, caches = eng._prefill(params, r.prompt[None, :])
        rows.append(model.cache_from_prefill(caches, r.prompt_len, S))
    cache = jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *rows)
    tokens = np.array([[r.tokens[0]] for r in reqs], np.int32)
    pos = np.array([r.prompt_len for r in reqs], np.int32)
    plain = Engine(model, params, ServeConfig(
        buckets=eng.buckets, use_lilac=False, jit_prefill=False))

    def step(decode):
        # the second call runs the baked plan, the program serving runs;
        # the first may still come from the eager interpreter
        for _ in range(2):
            logits, _ = decode(params, cache, tokens, pos)
        return np.asarray(logits, np.float64)

    out = {"lilac": step(eng._decode),
           "un-rewritten": step(jax.jit(plain._decode))}
    for k in drops:
        with planted_fault(k):
            out[f"fault {k}"] = step(cut_engine(eng, model.cfg.n_layers)
                                     ._decode)
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_phase(name: str, seed: int) -> int:
    """Child side: run one phase, print its result line, exit 0."""
    t0 = time.perf_counter()
    if name == "device":
        result = phase_device()
    else:
        import jax
        result = {"sparse": phase_sparse, "spmm": phase_spmm,
                  "serve": phase_serve}[name](seed)
        result["peak_bytes_in_use"] = _mem_stat()
        print(f"peak_bytes_in_use: {result['peak_bytes_in_use']} "
              f"({jax.devices()[0].device_kind})")
    result["seconds"] = round(time.perf_counter() - t0, 1)
    print(RESULT + json.dumps(result), flush=True)
    return 0


def _child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    stores = ROOT / ".smoke_cache"
    shutil.rmtree(stores, ignore_errors=True)
    stores.mkdir()
    env["LILAC_AUTOTUNE_CACHE"] = str(stores / "autotune.json")
    env["LILAC_PLAN_CACHE"] = str(stores / "plans.json")
    env["LILAC_QUARANTINE_CACHE"] = str(stores / "quarantine.json")
    return env


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=PHASES, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:
        return run_phase(args.phase, args.seed)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro next to {__file__}", file=sys.stderr)
        return 2
    env = _child_env()
    device = None
    deadline = time.monotonic() + RUN_TIMEOUT
    for name in PHASES:
        cmd = [sys.executable, str(Path(__file__).resolve()),
               "--phase", name, "--seed", str(args.seed)]
        print(f"== phase {name}", flush=True)
        try:
            proc = subprocess.run(cmd, env=env, cwd=ROOT, text=True,
                                  stdout=subprocess.PIPE,
                                  timeout=deadline - time.monotonic())
        except subprocess.TimeoutExpired as e:
            sys.stdout.write(e.stdout or "")
            print(f"chip_smoke: phase {name} still running after "
                  f"{RUN_TIMEOUT} s of the run", file=sys.stderr)
            return 1
        result = None
        for line in proc.stdout.splitlines():
            if line.startswith(RESULT):
                result = json.loads(line[len(RESULT):])
            else:
                print(f"  {line}")
        if proc.returncode != 0 or result is None:
            print(f"chip_smoke: phase {name} failed (exit "
                  f"{proc.returncode})", file=sys.stderr)
            return 1
        print(f"phase {name} passed in {result['seconds']} s: "
              f"{json.dumps(result, sort_keys=True)}", flush=True)
        if name == "device":
            device = {k: result[k] for k in ("platform", "kind", "count")}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
