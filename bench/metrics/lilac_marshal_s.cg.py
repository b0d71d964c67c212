"""Set-up seconds of the LiLAC data plane's conversions, in s: the total
of the program's ``lilac.marshal`` spans (each conversion computed, a
marshaling-cache miss or a conversion path run; ``core/marshal.py``) in
this process, read from its span table (``repro.core.spans``).  Set-up
runs before the tracer starts, so the table, not the trace, holds it.
Conversions run inside the tuner's timing and the bake as well as on
their own, so this total overlaps ``lilac_tune_s.cg`` and
``lilac_bake_s.cg``."""


def read(ctx):
    try:
        from repro.core import spans
    except ImportError:             # a program without spans
        return None
    got = spans.totals().get("lilac.marshal")
    return got["total_s"] if got else None
