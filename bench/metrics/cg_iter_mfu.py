"""Share of the chip's peak that a whole CG iteration reaches, in %: the
least time of one iteration (the SpMV, two dot products and three axpys
over n-vectors; ``work.cg_iteration``) over ``cg_iter_ms`` of the traced
run.  It bounds every kernel roofline of the iteration."""


def read(ctx):
    if not ctx.peak:
        return None
    least = ctx.work.cg_iteration(ctx.counters["n"],
                                  ctx.counters["nnz"]).least_s(ctx.peak)
    return least / (ctx.end_to_end["cg_iter_ms"] / 1e3) * 100.0
