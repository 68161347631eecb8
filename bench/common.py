"""Pieces every cell shares: files found by name, spans and counters, the
aligned measuring window, the compile clock and the checks
that decide ``correct``."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a benchmark file by its path (names may hold '-' and '.')."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    name = "bench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class WindowClosed(Exception):
    """Raised from inside the system's own loop when the window has closed."""


class Probe:
    """Host spans and counters of one run, and the window's bookkeeping.

    Spans are kept in memory as ``(name, t0, t1, cpu)``: wall times on
    ``time.perf_counter`` and the CPU seconds the thread spent in between
    (``time.thread_time``: waiting for the device costs none), and are
    mirrored into the profiler's trace (``jax.profiler.TraceAnnotation``)
    so that idle gaps on the device can be labelled with the host's work.
    """

    def __init__(self):
        self.spans: list[tuple[str, float, float, float]] = []
        self.counters: dict[str, float] = {}
        self.t_open: float | None = None
        self.t_close: float | None = None
        self.on_open = []        # callbacks run when the window opens
        self.on_close = []       # ... and when it closes

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0, c0 = time.perf_counter(), time.thread_time()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, t0, time.perf_counter(),
                           time.thread_time() - c0))

    def count(self, name: str, n: float = 1):
        self.counters[name] = self.counters.get(name, 0) + n

    def open(self, t: float | None = None):
        for f in self.on_open:
            f()
        self.t_open = time.perf_counter() if t is None else t

    def close(self, t: float | None = None):
        self.t_close = time.perf_counter() if t is None else t
        for f in self.on_close:
            f()

    def window_spans(self, name: str, cpu: bool = False) -> list[float]:
        """Wall (or CPU) seconds of the spans called ``name`` that ended in
        the window."""
        return [c if cpu else t1 - t0 for n, t0, t1, c in self.spans
                if n == name and self.in_window(t1)]

    def in_window(self, t: float) -> bool:
        return (self.t_open is not None and t > self.t_open
                and (self.t_close is None or t <= self.t_close))


class LaunchWindow:
    """A window aligned on the launches of engine cycles, for throughput.

    It opens at a launch made once warm-up is over and closes at the first
    launch at or after ``open + seconds``, which is not made.  The engine
    launches a cycle only once the cycle before it has ended on the device,
    so every cycle launched in the window has finished when it closes; the
    rate is their work, added as each is finalized, over the time between
    the two launches.  A stall inside the window lowers the rate.
    """

    def __init__(self, probe: Probe, seconds: float):
        self.probe = probe
        self.seconds = seconds
        self.work = 0.0

    def launch(self, t: float | None = None) -> bool:
        """Before a launch: True when this launch closes the window."""
        t = time.perf_counter() if t is None else t
        if self.probe.t_open is None:
            self.probe.open(t)
            return False
        if t >= self.probe.t_open + self.seconds:
            self.probe.close(t)
            return True
        return False

    @property
    def elapsed(self) -> float:
        return self.probe.t_close - self.probe.t_open

    def rate(self) -> float:
        return self.work / self.elapsed


class CompileClock:
    """Seconds and times of backend compilations in this process, and the
    times of programs loaded from the persistent cache (which compile
    nothing), from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.times: list[float] = []
        self.loads: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_load)

    def _on_event(self, event, secs, **_):
        if event == self.EVENT:
            self.seconds += secs
            self.times.append(time.perf_counter())

    def _on_load(self, event, **_):
        if event == self.LOAD:
            self.loads.append(time.perf_counter())


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``: it passes at or under limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return (self.limit is not None and math.isfinite(self.value)
                and self.value <= self.limit)


@dataclasses.dataclass
class RunReport:
    """What a system hands back after its window."""
    e2e: dict[str, float]
    attempted: int
    failed: int
