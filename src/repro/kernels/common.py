"""Helpers shared by the Pallas kernel packages.

Kept outside any one kernel package so siblings don't reach into each
other's internals: every kernel builds its Mosaic compiler params and its
in-register epilogue from here.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def apply_epilogue_inregister(acc, bias, epilogue: Optional[str]):
    """The in-register epilogue: bias add then activation, applied to a
    value that is still in VMEM/registers.  Must match
    ``repro.core.rewrite.apply_epilogue`` bit-for-bit."""
    if bias is not None:
        acc = acc + bias
    if epilogue == "relu":
        acc = jnp.maximum(acc, jnp.zeros_like(acc))
    elif epilogue == "silu":
        acc = acc * jax.nn.sigmoid(acc)
    return acc


def compiler_params(dimension_semantics: Optional[Tuple[str, ...]]):
    """``pallas_call`` kwargs for a tuned ``dimension_semantics`` tuple
    (empty when None, so untuned calls stay byte-identical)."""
    if dimension_semantics is None:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics))}
