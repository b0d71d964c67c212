"""Set-up seconds of baking the LiLAC plan, in s: the total of the
program's ``lilac.bake`` spans (``plan.bake_plan``: trace, compile and
warm-up of the one jitted program; ``core/plan.py``) in this process,
read from its span table (``repro.core.spans``).  Set-up runs before the
tracer starts, so the table, not the trace, holds it."""


def read(ctx):
    try:
        from repro.core import spans
    except ImportError:             # a program without spans
        return None
    got = spans.totals().get("lilac.bake")
    return got["total_s"] if got else None
