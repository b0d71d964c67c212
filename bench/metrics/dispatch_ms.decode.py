"""Host time of the engine's decode dispatch, in ms: the mean over the
window's steps of ``ServeMetrics.decode_step_s`` (``serve/metrics.py``),
which the engine takes around the call to the compiled decode and which
ends before the logits reach the host."""


def read(ctx):
    steps = ctx.counters.get("engine_decode_step_s") or []
    return sum(steps) / len(steps) * 1e3 if steps else None
