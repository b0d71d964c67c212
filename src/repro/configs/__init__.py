"""Assigned architecture configs — one module per --arch id."""
from repro.configs.base import (  # noqa: F401
    SHAPES, ArchConfig, ShapeConfig, all_archs, get_arch, register,
    shape_skips, smoke_config,
)
# importing each module registers its config
from repro.configs import (  # noqa: F401
    rwkv6_1p6b,
    internvl2_2b,
    granite_moe_3b_a800m,
    olmoe_1b_7b,
    granite_8b,
    mistral_large_123b,
    granite_34b,
    olmo_1b,
    jamba_v0_1_52b,
    hubert_xlarge,
    granite_4_0_h_small,
)
