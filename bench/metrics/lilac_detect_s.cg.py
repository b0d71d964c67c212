"""Set-up seconds of the LiLAC pass's detection, in s: the total of the
program's ``lilac.detect`` spans (``make_jaxpr``, normalisation, then
detection or the plan cache's rehydration; ``core/pass_manager.py``) in
this process, read from its span table (``repro.core.spans``).  Set-up
runs before the tracer starts, so the table, not the trace, holds it."""


def read(ctx):
    try:
        from repro.core import spans
    except ImportError:             # a program without spans
        return None
    got = spans.totals().get("lilac.detect")
    return got["total_s"] if got else None
