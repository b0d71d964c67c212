"""The profiler trace of a measured window, reduced to what metrics read.

``Tracer`` records the window with JAX's profiler (the Python tracer off,
so only the benchmark's own ``bench.*`` spans and the device's operations
are in it).  ``read_xplane`` turns the written ``.xplane.pb`` into plain
tuples, and ``reduce`` (pure, tested on a small recorded trace) computes
from those tuples the device's busy time, the idle gaps named by the host
span open in each, and the time of each device operation.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."

# (device, module, op name, start ns, end ns)
Op = Tuple[str, str, str, float, float]
# (span name, start ns, end ns)
Span = Tuple[str, float, float]


class Tracer:
    """Profiles the block it wraps into ``log_dir`` (emptied first)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def __enter__(self):
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False

    @staticmethod
    def span(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def xplane(self) -> str:
        found = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no profile written under {self.log_dir}")
        return found[-1]


def _module_name(raw: str) -> str:
    """``jit_baked(123)`` -> ``jit_baked``."""
    return raw.split("(", 1)[0]


def _op_name(raw: str) -> str:
    """An HLO instruction's name without its numeric suffix:
    ``%bsr_spmm_pallas.1 = f32[...] custom-call(...)`` ->
    ``bsr_spmm_pallas``, so one kind of operation sums across the
    program's copies of it."""
    name = raw.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name)


def read_xplane(path: str, host_ops: bool = False
                ) -> Tuple[List[Op], List[Span]]:
    """Device operations and ``bench.*`` host spans of one trace file.
    ``host_ops``: the XLA operations of the host's own lines count as the
    device's (a CPU run, where the host is the device)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            op_line = lines.get("XLA Ops")
            if op_line is None:
                continue
            modules = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns,
                 _module_name(ev.name))
                for ev in (lines["XLA Modules"].events
                           if "XLA Modules" in lines else ()))
            j = 0
            for ev in sorted(op_line.events, key=lambda e: e.start_ns):
                start, end = ev.start_ns, ev.start_ns + ev.duration_ns
                while j < len(modules) and modules[j][1] < start:
                    j += 1
                module = ""
                if j < len(modules) and modules[j][0] <= start:
                    module = modules[j][2]
                if not module:
                    stats = dict(ev.stats)
                    module = _module_name(str(stats.get("hlo_module", "")))
                ops.append((plane.name, module, _op_name(ev.name), start,
                            end))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                    elif host_ops and line.name.startswith("tf_XLA"):
                        stats = dict(ev.stats)
                        if "hlo_module" in stats:
                            ops.append((plane.name, _module_name(
                                str(stats["hlo_module"])), _op_name(ev.name),
                                ev.start_ns, ev.start_ns + ev.duration_ns))
    return ops, spans


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


@dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over the devices used
    devices: int
    op_s: Dict[str, float] = field(default_factory=dict)   # "module/op"
    module_s: Dict[str, float] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def ops_matching(self, text: str) -> float:
        """Device seconds of every operation whose name holds ``text``."""
        text = text.lower()
        return sum(s for name, s in self.op_s.items()
                   if text in name.split("/", 1)[-1].lower())

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]]}


def _open_span(spans: List[Span], t: float) -> str:
    """The innermost ``bench.*`` span (other than the window) open at t."""
    best: Optional[Span] = None
    for sp in spans:
        if sp[0] != WINDOW_SPAN and sp[1] <= t <= sp[2]:
            if best is None or sp[1] >= best[1]:
                best = sp
    return best[0] if best else "none"


def reduce(ops: List[Op], spans: List[Span], top_gaps: int = 10) -> Summary:
    """Reduce one traced window to a ``Summary``.  The window is the
    ``bench.window`` span; operations are clipped to it.  Busy time is the
    union of each device's operation intervals, averaged over the devices
    that ran any; an idle gap is a stretch of the window in which the
    first device used ran nothing, named by the host span open at its
    middle."""
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    w0, w1 = win[0][1], win[0][2]
    inside = [(d, m, n, max(s, w0), min(e, w1)) for d, m, n, s, e in ops
              if e > w0 and s < w1]
    devices = sorted({o[0] for o in inside})
    if not devices:
        raise ValueError("no device operation ran inside the window")
    busy = {d: _union((s, e) for dd, _, _, s, e in inside if dd == d)
            for d in devices}
    busy_s = sum(sum(e - s for s, e in busy[d]) for d in devices) \
        / len(devices) / 1e9
    op_s: Dict[str, float] = {}
    module_s: Dict[str, float] = {}
    for _, m, n, s, e in inside:
        key = f"{m}/{n}" if m else n
        op_s[key] = op_s.get(key, 0.0) + (e - s) / 1e9
        module_s[m] = module_s.get(m, 0.0) + (e - s) / 1e9
    inner = [s for s in spans if s[0] != WINDOW_SPAN
             and s[2] > w0 and s[1] < w1]
    edges = [w0] + [t for iv in busy[devices[0]] for t in iv] + [w1]
    longest = sorted(((b - a, a) for a, b in zip(edges[0::2], edges[1::2])
                      if b > a), reverse=True)[:top_gaps]
    gaps = [(_open_span(inner, a + d / 2), d / 1e9) for d, a in longest]
    return Summary(window_s=(w1 - w0) / 1e9, busy_s=busy_s,
                   devices=len(devices), op_s=op_s, module_s=module_s,
                   idle_gaps=gaps)
