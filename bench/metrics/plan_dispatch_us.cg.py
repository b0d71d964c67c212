"""Host time to enqueue one call of the baked LiLAC plan (the SpMV), in
microseconds: the mean over the window's calls of the host clock around
the call, which returns before the device finishes (``core/plan.py``)."""


def read(ctx):
    calls = ctx.counters.get("dispatch_s") or []
    return sum(calls) / len(calls) * 1e6 if calls else None
