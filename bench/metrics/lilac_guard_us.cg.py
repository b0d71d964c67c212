"""Host time of the LiLAC pass's own guard per call of the baked SpMV
plan, in microseconds: over the calls made in the traced window, the
seconds of the program's ``lilac.dispatch`` spans (all of
``LilacFunction.__call__``) less those of its ``lilac.enqueue`` spans (the
jitted plan's call), over the number of ``lilac.dispatch`` spans
(``core/pass_manager.py``).  Read from the program's table of the spans a
profiler recorded (``repro.core.spans``)."""


def read(ctx):
    try:
        from repro.core import spans
    except ImportError:             # a program without spans
        return None
    got = spans.totals(traced=True)
    if "lilac.dispatch" not in got or "lilac.enqueue" not in got:
        return None
    return (got["lilac.dispatch"]["total_s"] - got["lilac.enqueue"]["total_s"]
            ) / got["lilac.dispatch"]["count"] * 1e6
