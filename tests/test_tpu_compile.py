"""Ahead-of-time compiles of the three Pallas kernels for a TPU v5e.

Interpret mode (the rest of the suite) checks results, not what Mosaic
accepts: block shapes, in-kernel gathers and the VMEM budget only fail
when the kernel is lowered for a real chip.  These tests lower and compile
each kernel against a *described* ``v5e:2x2`` topology, at the shapes the
one-chip smoke (``chip_smoke.py``) runs, without a chip attached.  Each
asserts that the compiled program holds the Mosaic kernel
(``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library, and every test worker imports
this file.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.bsr_spmm.kernel import bsr_spmm_pallas
from repro.kernels.moe_gmm.kernel import gmm_pallas
from repro.kernels.spmv_ell.kernel import spmv_ell_pallas

DIMSEMS = ("arbitrary", "parallel")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile_for_chip(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("dimsem", DIMSEMS)
def test_spmv_ell_compiles(one_chip, dimsem):
    """2^20 rows of width 128 against a 2^20 vector, 256-row slabs."""
    rows, width = 1 << 20, 128
    fn = functools.partial(spmv_ell_pallas, rows_per_slab=256,
                           dimension_semantics=(dimsem,))
    _compile_for_chip(fn, one_chip,
                      ((rows, width), jnp.float32),
                      ((rows, width), jnp.int32),
                      ((rows,), jnp.float32))


@pytest.mark.parametrize("epilogue", ["relu", "silu"])
def test_spmv_ell_fused_epilogue_compiles(one_chip, epilogue):
    rows, width = 1 << 16, 128
    fn = functools.partial(spmv_ell_pallas, rows_per_slab=256,
                           dimension_semantics=("parallel",),
                           epilogue=epilogue)
    _compile_for_chip(lambda v, c, x, b: fn(v, c, x, bias=b), one_chip,
                      ((rows, width), jnp.float32),
                      ((rows, width), jnp.int32),
                      ((rows,), jnp.float32),
                      ((rows,), jnp.float32))


@pytest.mark.parametrize("bn", [128, 512])
@pytest.mark.parametrize("dimsem", DIMSEMS)
def test_bsr_spmm_compiles(one_chip, bn, dimsem):
    """65,536 rows of three 128x128 block diagonals times (65,536 x 512)."""
    block_rows, bs, n = 512, 128, 512
    nnzb = 3 * block_rows - 2
    fn = functools.partial(bsr_spmm_pallas, num_block_rows=block_rows,
                           bn=bn, dimension_semantics=(dimsem, "arbitrary"))
    _compile_for_chip(fn, one_chip,
                      ((nnzb, bs, bs), jnp.float32),
                      ((nnzb,), jnp.int32),
                      ((nnzb,), jnp.int32),
                      ((block_rows * bs, n), jnp.float32))


@pytest.mark.parametrize("dimsem", DIMSEMS)
@pytest.mark.parametrize("D,F", [(1536, 512), (512, 1536)],
                         ids=["up", "down"])
def test_gmm_compiles(one_chip, dimsem, D, F):
    """granite-moe-3b-a800m widths (d_model 1536, expert d_ff 512, 40
    experts): the up/gate projection contracts D, the down one F."""
    E, tm = 40, 128
    TK = 8 * 8                               # decode batch 8, top-8
    Tp = -(-TK // tm) * tm + (E - 1) * tm    # moe_ffn's aligned row count
    fn = functools.partial(gmm_pallas, tm=tm, fn=128, dk=128,
                           dimension_semantics=(dimsem, dimsem, "arbitrary"))
    _compile_for_chip(fn, one_chip,
                      ((Tp, D), jnp.bfloat16),
                      ((E, D, F), jnp.bfloat16),
                      ((Tp // tm,), jnp.int32))
