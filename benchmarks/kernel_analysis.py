"""Kernel-level analysis of the Pallas TPU kernels: VMEM working set,
arithmetic intensity, and the roofline regime each kernel lands in on v5e.

The 40-cell dry-run lowers jnp harnesses (DESIGN.md §7.1); this is the
structural analysis of the hand-tiled kernels themselves, from their
BlockSpecs (no hardware needed — the numbers are exact functions of the
tiling)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit

PEAK_FLOPS = 197e12
HBM_BW = 819e9
VMEM = 16 * 2 ** 20     # ~16 MiB usable (half of 32 for double buffering)


def _analyze(name, *, flops_per_step, hbm_bytes_per_step, vmem_bytes,
             notes=""):
    intensity = flops_per_step / max(hbm_bytes_per_step, 1)
    ridge = PEAK_FLOPS / HBM_BW   # ~240 flops/byte on v5e
    regime = "compute-bound" if intensity >= ridge else "memory-bound"
    attainable = min(PEAK_FLOPS, intensity * HBM_BW)
    emit(f"kernels.{name}", 0.0,
         f"vmem={vmem_bytes/2**10:.0f}KiB({'OK' if vmem_bytes < VMEM else 'OVER'}) "
         f"intensity={intensity:.1f}flop/B ridge={ridge:.0f} {regime} "
         f"attainable={attainable/1e12:.1f}TF/s "
         f"({attainable/PEAK_FLOPS*100:.0f}% of peak) {notes}")


def run() -> None:
    # bsr_spmm: (bm,bk)x(bk,bn) f32 tiles, block density d
    bm = bk = bn = 128
    _analyze(
        "bsr_spmm.128x128",
        flops_per_step=2 * bm * bk * bn,
        # per step: one stored tile + one rhs tile stream in; out revisited
        hbm_bytes_per_step=(bm * bk + bk * bn) * 4,
        vmem_bytes=(bm * bk + bk * bn + bm * bn) * 4,
        notes="MXU-aligned; out-block reuse across k amortizes the write",
    )
    # bf16 variant doubles intensity
    _analyze(
        "bsr_spmm.128x128.bf16",
        flops_per_step=2 * bm * bk * bn,
        hbm_bytes_per_step=(bm * bk + bk * bn) * 2,
        vmem_bytes=(bm * bk + bk * bn) * 2 + bm * bn * 4,
    )
    # spmv_ell: R x W value slab + gathered-vector slab; SpMV is
    # memory-bound by nature
    R, W = 256, 128
    _analyze(
        "spmv_ell.256x128",
        flops_per_step=2 * R * W,
        hbm_bytes_per_step=(R * W) * (4 + 4),   # val + gathered vec stream
        vmem_bytes=(R * W) * 8 + R * 4,
        notes="vec[col] gathered by XLA in front of the kernel",
    )
    # moe_gmm: (tm,dk)x(dk,fn) bf16, weight tile revisited per m-tile
    tm = dk = fn = 128
    _analyze(
        "moe_gmm.128",
        flops_per_step=2 * tm * dk * fn,
        hbm_bytes_per_step=(tm * dk + dk * fn) * 2,
        vmem_bytes=(tm * dk + dk * fn) * 2 + tm * fn * 4,
        notes="group-aligned; expert weight DMA steered by scalar prefetch",
    )
    # decode-regime gmm (tm=8 tokens): weight-streaming bound
    tm2 = 8
    _analyze(
        "moe_gmm.decode_tm8",
        flops_per_step=2 * tm2 * dk * fn,
        hbm_bytes_per_step=(tm2 * dk + dk * fn) * 2,
        vmem_bytes=(tm2 * dk + dk * fn) * 2 + tm2 * fn * 4,
        notes="decode: weight stream dominates -> memory-bound as expected",
    )


if __name__ == "__main__":
    run()
