"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax use.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    return _auto_mesh((data, model), ("data", "model"))


def mesh_rules(multi_pod: bool = False):
    from repro.models.spec import MULTI_POD_RULES, SINGLE_POD_RULES
    return MULTI_POD_RULES if multi_pod else SINGLE_POD_RULES
