"""Pallas TPU kernels for the compute hot-spots LiLAC routes to.

Each kernel package has:
    kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
    ops.py    — jit'd wrapper with layout/padding marshaling
    ref.py    — pure-jnp oracle used by the allclose test sweeps

Kernels (TPU-native adaptations of the paper's GPU library calls, §2 of
DESIGN.md):
    bsr_spmm — block-sparse (BCSR) x dense on the MXU, scalar-prefetched
               block indices (the cuSPARSE csrmv analogue, re-blocked for
               the systolic array)
    spmv_ell — ELL/JDS row-slab SpMV on the VPU over the gathered vector
    moe_gmm  — group-aligned ragged grouped matmul (megablocks-style), the
               MoE expert FFN hot loop
"""
