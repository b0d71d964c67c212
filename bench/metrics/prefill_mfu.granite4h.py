"""Share of the chip's peak that prefill reaches, in %: the least time of
the prompts prefilled in the window (each prompt's weights once, the
recurrence and causal attention over its tokens, the head for its last
position; ``work_hybrid.prefill``) summed, over the device time of the
engine's prefill program (module ``jit__prefill``).  Silent for a program
that does not count its prefills or names the program otherwise."""
from pathlib import Path

from harness import load_module


def read(ctx):
    if not ctx.peak or ctx.summary is None:
        return None
    lens = ctx.counters.get("prefill_lens")
    device_s = ctx.summary.module_s.get("jit__prefill", 0.0)
    if not lens or device_s <= 0:
        return None
    work = load_module(Path(__file__).resolve().parents[1] / "work_hybrid.py")
    least = sum(work.prefill(ctx.config, n).least_s(ctx.peak) for n in lens)
    return least / device_s * 100.0
