"""LiLAC HARNESS declarations for the ELL/JDS row-slab Pallas kernel.

The paper's "add a backend" story: a HARNESS block (the How-descriptor)
plus a kernel body, nothing else.  Marshaling for the CSR/COO entry point
is generated from the declared ``ell_pack128`` repack clause — this module
never touches the MarshalingCache directly.

Kernel schedules are first-class: the ``tune`` clauses declare the
parameter space (the first value of each is the previously hard-coded
constant, so the default schedule is bit-identical to the old kernel), the
autotuner sweeps the cross-product, and the winning schedule arrives at
the body as keyword arguments.  ``fuse epilogue`` declares that the body
applies detected ``(+bias) -> relu|silu`` chains itself — in-register for
the direct ELL path, post-permutation for JDS/CSR (the permuted output
must exist before the bias indexes it).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.rewrite import apply_epilogue
from repro.core.spec import harness


@harness("""
HARNESS pallas.ell implements spmv_ell, spmv_jds
  formats ELL, JDS;
  tune rows_per_slab in {256, 64, 128, 512};
  tune dimsem in {arbitrary, parallel};
  fuse epilogue;
  vjp spmv_ell_bwd(val, vector);
""")
def spmv_ell_pallas(b, ctx, *, rows_per_slab=256, dimsem="arbitrary"):
    """Direct ELL/JDS match -> VPU row-slab kernel."""
    from repro.kernels.spmv_ell import ops as ell_ops
    perm = b.get("perm")
    interpret = ctx.platform != "tpu"
    epilogue = getattr(ctx, "epilogue", None)
    bias = b.get("bias")
    if perm is None:
        # pure ELL: the epilogue fuses in-register before the only store
        return ell_ops.spmv_ell(b["val"], b["col_ind"], b["vector"],
                                rows_per_slab=rows_per_slab,
                                dimension_semantics=dimsem,
                                epilogue=epilogue, bias=bias,
                                interpret=interpret)
    acc = ell_ops.spmv_ell(b["val"], b["col_ind"], b["vector"],
                           rows_per_slab=rows_per_slab,
                           dimension_semantics=dimsem,
                           interpret=interpret)
    out = jnp.zeros((b["rows"],), acc.dtype)
    out = out.at[perm].set(acc)
    if epilogue is not None:
        # JDS: the detected bias lives in output (post-perm) space
        out = apply_epilogue(out, bias, epilogue)
    return out


# pallas harnesses are TPU-targeted: on CPU they run the kernel
# interpreter (correctness only, far too slow for autotune); they
# stay selectable by explicit policy name.
@harness("""
HARNESS pallas.ell implements spmv_csr, spmv_coo
  platforms tpu;
  formats CSR, COO;
  host_only;
  marshal ell = ell_pack128(a, colidx, rowstr|rowidx)
      from csr_binding to ELL128;
  tune rows_per_slab in {256, 64, 128, 512};
  tune dimsem in {arbitrary, parallel};
  fuse epilogue;
  vjp spmv_csr_bwd(a, iv);
""")
def spmv_ell_pallas_host(b, ctx, *, ell, rows_per_slab=256,
                         dimsem="arbitrary"):
    """CSR/COO match -> marshaled ELL repack -> Pallas slab kernel."""
    from repro.kernels.spmv_ell import ops as ell_ops
    interpret = ctx.platform != "tpu"
    acc = ell_ops.spmv_ell(ell.val, ell.col, b["iv"],
                           rows_per_slab=rows_per_slab,
                           dimension_semantics=dimsem,
                           interpret=interpret)
    out = jnp.zeros((b["rows"],), acc.dtype)
    out = out.at[ell.perm].set(acc)
    epilogue = getattr(ctx, "epilogue", None)
    if epilogue is not None:
        out = apply_epilogue(out, b.get("bias"), epilogue)
    return out
