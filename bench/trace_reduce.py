"""From the profiler's trace (``.xplane.pb``) to device busy time, device
time by operation and by program, and the longest idle gaps labelled with
the host span that covers them.

Read through ``jax.profiler.ProfileData`` and nothing else.  Device planes
are named ``/device:<KIND>:<n>``; their ``XLA Ops`` line holds one event per
operation run (a fusion, a copy, a Pallas kernel's custom call) and their
``XLA Modules`` line one event per program run (``jit_<function>``).  Host
spans are the benchmark's ``TraceAnnotation``\\ s on the host plane, on the
same clock.  The traced window is the host span named ``bench.window``.
Where no device plane exists (the CPU backend, in tests), the operations are
the host events that name their program (``hlo_module``).
"""

from __future__ import annotations

import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float            # ns
    dur: float              # ns
    stats: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: list[Event]                 # device operations, all chips
    modules: list[Event]             # device program runs, all chips
    host: list[Event]                # host spans (TraceAnnotation)
    window: tuple[float, float]      # ns
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line, keep_stats: bool) -> list[Event]:
    out = []
    for e in line.events:
        stats = {}
        if keep_stats:
            for k, v in e.stats:
                stats[k] = v
        out.append(Event(e.name, float(e.start_ns), float(e.duration_ns),
                         stats))
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host, cpu_ops = [], [], [], []
    n_dev = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            n_dev += 1
            ops += _events(lines[OPS_LINE], keep_stats=False)
            if MODULES_LINE in lines:
                modules += _events(lines[MODULES_LINE], keep_stats=False)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = _events(ln, keep_stats=True)
                host += [e for e in evs
                         if e.name.startswith(("bench.", "engine."))]
                cpu_ops += [e for e in evs if "hlo_module" in e.stats]
    if not n_dev:
        ops = cpu_ops
    win = [e for e in host if e.name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w = max(win, key=lambda e: e.dur)
    return Trace(ops=ops, modules=modules, host=host,
                 window=(w.start, w.end), n_devices=max(n_dev, 1))


def clip(events, window) -> list[tuple[float, float]]:
    lo, hi = window
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def merge(intervals) -> list[tuple[float, float]]:
    """Union of intervals as disjoint sorted intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(trace: Trace) -> float:
    """Seconds in the window in which some operation ran on a device,
    averaged over the devices in the trace."""
    total = sum(b - a for a, b in merge(clip(trace.ops, trace.window)))
    return total * 1e-9 / trace.n_devices


def op_seconds(trace: Trace, key=lambda e: e.name) -> dict[str, float]:
    """Device seconds in the window by ``key(event)``."""
    out: dict[str, float] = {}
    lo, hi = trace.window
    for e in trace.ops:
        if e.end <= lo or e.start >= hi:
            continue
        k = key(e)
        out[k] = out.get(k, 0.0) + (min(e.end, hi) - max(e.start, lo)) * 1e-9
    return out


def idle_gaps(trace: Trace, top: int = 10) -> list[tuple[str, float]]:
    """The longest stretches of the window with no operation on the device,
    each labelled with the innermost host span covering its middle."""
    busy = merge(clip(trace.ops, trace.window))
    lo, hi = trace.window
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        cover = [e for e in trace.host if e.start <= mid <= e.end
                 and e.name != WINDOW_SPAN]
        label = min(cover, key=lambda e: e.dur).name if cover else "host:none"
        out.append((label, (b - a) * 1e-9))
    out.sort(key=lambda x: -x[1])
    return out[:top]


def top_ops(trace: Trace, top: int = 10) -> list[tuple[str, float]]:
    secs = op_seconds(trace, key=op_label)
    return sorted(secs.items(), key=lambda x: -x[1])[:top]


def op_label(e: Event, width: int = 160) -> str:
    """The operation's HLO instruction (name, output shapes, operation),
    cut to ``width`` characters; on the CPU, with its program's name."""
    mod = e.stats.get("hlo_module", "")
    return (f"{mod}/{e.name}" if mod else e.name)[:width]


def program_seconds(trace: Trace, program: str) -> float:
    """Device seconds in the window of the runs of every program whose name
    starts with ``program`` (``jit_<function>(<fingerprint>)``)."""
    runs = [e for e in trace.modules if e.name.startswith(program)]
    return sum(b - a for a, b in clip(runs, trace.window)) * 1e-9


def kernel_events(trace: Trace, *needles: str) -> list[Event]:
    """Device operations in the window whose text holds every needle.  An
    operation's name in the trace is its HLO instruction: the op's name,
    its output and operand shapes and its custom-call target."""
    lo, hi = trace.window
    return [e for e in trace.ops if e.start >= lo and e.end <= hi
            and all(n in e.name for n in needles)]
