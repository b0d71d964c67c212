"""Share of the roofline that the engine's slot writes reach, in %: the
least time of the recurrent-state bytes that the window's slot installs
and eviction moves wrote (the program's ``serve.state_bytes_installed``
and ``serve.state_bytes_moved`` counters, each byte read and written
once; ``work_hybrid.slot_writes``), over the device time of the install
and move programs (modules ``jit__install`` and ``jit__move``).  A slot
write that copies the whole cache instead of its row reads far below
100.  Silent for a program without those counters."""
from pathlib import Path

from harness import load_module


def read(ctx):
    if not ctx.peak or ctx.summary is None:
        return None
    c = ctx.counters
    if "state_bytes_installed" not in c and "state_bytes_moved" not in c:
        return None
    device_s = sum(ctx.summary.module_s.get(m, 0.0)
                   for m in ("jit__install", "jit__move"))
    if device_s <= 0:
        return None
    work = load_module(Path(__file__).resolve().parents[1] / "work_hybrid.py")
    state = c.get("state_bytes_installed", 0) + c.get("state_bytes_moved", 0)
    return work.slot_writes(state).least_s(ctx.peak) / device_s * 100.0
