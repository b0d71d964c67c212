"""Each cell's check, driven through a whole run with the chip check
skipped, passes the program as it is and fails it with its timed path
broken underneath."""
from __future__ import annotations

import pytest

from conftest import run


def altered_product(r):
    """One entry of the SpMV's answer altered where it is produced."""
    real = r.spmv

    def broken(*args):
        return real(*args).at[0].add(1.0)
    r.spmv = broken


def unchanged_state(r):
    """The step returns its state unchanged: the SpMV hands back its
    input."""
    r.spmv = lambda val, col, row_ptr, v: v


@pytest.mark.parametrize("fault", [None, altered_product, unchanged_state])
def test_cg(checkout, fault):
    out = run(checkout, "cg.hpcg104", after_setup=fault)
    assert out.result["correct"] is (fault is None), out.result["checks"]


def _wrap_decode(r, change):
    eng = r.engine
    real = eng._decode

    def broken(params, cache, tokens, pos):
        logits, cache = real(params, cache, tokens, pos)
        return change(logits), cache
    eng._decode = broken


def altered_token(r):
    """Row 0's token replaced where the decode produces it."""
    import jax.numpy as jnp

    def change(logits):
        wrong = (jnp.argmax(logits[0]) + 1) % logits.shape[1]
        return logits.at[0, wrong].set(jnp.max(logits) + 1.0)
    _wrap_decode(r, change)


def half_the_batch(r):
    """The second half of the batch left out: its rows' logits never
    computed."""
    def change(logits):
        return logits.at[logits.shape[0] // 2:].set(0.0)
    _wrap_decode(r, change)


@pytest.mark.parametrize("fault", [None, altered_token, half_the_batch])
def test_serve(checkout, fault):
    out = run(checkout, "serve.granite.decode", seconds=2.0,
              after_setup=fault)
    assert out.result["correct"] is (fault is None), out.result["checks"]
