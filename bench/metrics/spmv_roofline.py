"""Share of the SpMV's roofline, in %: the least time of one product
A x of the configuration's matrix (its nonzeros read once, x read and
y written once; ``work.spmv``) over the device time per call of the baked
plan's program (every operation under ``jit_baked``), whatever harness,
format or schedule the tuner picked."""


def read(ctx):
    if not ctx.peak or ctx.summary is None:
        return None
    device_s = ctx.summary.module_s.get("jit_baked", 0.0)
    calls = ctx.counters.get("spmv_calls", 0)
    if device_s <= 0 or not calls:
        return None
    least = ctx.work.spmv(ctx.counters["n"], ctx.counters["nnz"]).least_s(
        ctx.peak)
    return least / (device_s / calls) * 100.0
