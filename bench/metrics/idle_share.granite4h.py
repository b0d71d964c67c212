"""Share of the hybrid model's serving window in which the device ran no
operation, in %: 1 - (union of the device's operation intervals) /
(traced window)."""


def read(ctx):
    if not ctx.peak or ctx.summary is None:
        return None
    return ctx.summary.idle_share * 100.0
