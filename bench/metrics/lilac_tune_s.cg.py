"""Set-up seconds of the LiLAC tuner, in s: the total of the program's
``lilac.tune`` spans (``Autotuner.select``, which compiles and times the
candidates, and the joint plan search; ``core/autotune.py``) in this
process, read from its span table (``repro.core.spans``).  Set-up runs
before the tracer starts, so the table, not the trace, holds it."""


def read(ctx):
    try:
        from repro.core import spans
    except ImportError:             # a program without spans
        return None
    got = spans.totals().get("lilac.tune")
    return got["total_s"] if got else None
