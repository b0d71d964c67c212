"""Share of the chip's peak that the whole decode step reaches, in %: the
least time of each step of the window at its batch (every layer's
attention over the cache at each row's depth, router, routed experts and
the unembedding; ``work.decode_step``) summed, over the traced window.
It bounds every kernel roofline of the step."""


def read(ctx):
    if not ctx.peak:
        return None
    least = sum(ctx.work.decode_step(ctx.config, rows, context)
                .least_s(ctx.peak)
                for rows, context in zip(ctx.counters["decode_rows"],
                                         ctx.counters["context"]) if rows)
    return least / ctx.counters["window_s"] * 100.0
