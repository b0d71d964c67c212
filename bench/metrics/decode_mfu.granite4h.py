"""Share of the chip's peak that the whole decode step of the hybrid model
reaches, in %: the least time of each step of the window at its rows and
their depths (every layer's weights once, each row's Mamba-2 state read
and written once, attention over the cache, the held experts and the tied
head; ``work_hybrid.decode_step``) summed, over the traced window."""
from pathlib import Path

from harness import load_module


def read(ctx):
    if not ctx.peak:
        return None
    work = load_module(Path(__file__).resolve().parents[1] / "work_hybrid.py")
    least = sum(work.decode_step(ctx.config, rows, context).least_s(ctx.peak)
                for rows, context in zip(ctx.counters["decode_rows"],
                                         ctx.counters["context"]) if rows)
    return least / ctx.counters["window_s"] * 100.0
