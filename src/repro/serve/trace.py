"""The serving engine's own trace: host spans and counters, and the map from
a compiled cycle program's instructions to the named scopes of its layers.

:class:`EngineTrace` keeps the last ``maxlen`` spans in memory, on
``time.perf_counter``, and writes each one into the profiler's trace as a
``jax.profiler.TraceAnnotation`` of the same name, so that a profile taken
around the engine shows the host's work on the device trace's clock.  Out of
a profile the annotation costs next to nothing, so recording is always on.

Every span name starts with ``engine.``.  A span records its start, end,
enclosing span and the scheduling cycle it ran in, plus what was counted at
that boundary (``attrs``).  The spans of one run loop nest as::

    engine.cycle                      rids: the requests the cycle serves
      engine.admit
      engine.build                    container right-align, teacher rows
      engine.launch.step | .prefill   program dispatch
      engine.finalize.cycle           symbols, decoded, probes, hits
        engine.finalize.wait          a blocking read of device outputs
        engine.finalize.encode        one slot's chunk-encode dispatch
        engine.retire.pack            rid: container pack / token concat

``engine.submit.parse`` (container parse) nests wherever
``submit_decompress`` is called from.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import re
import time

SPAN_RING = 1024        # spans kept; a 30 s benchmark window makes < 200


@dataclasses.dataclass
class Span:
    name: str
    start: float                    # time.perf_counter seconds
    end: float
    sid: int                        # serial number, unique per trace
    parent: int | None              # sid of the enclosing span
    cycle: int                      # engine scheduling cycle
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


class EngineTrace:
    """Bounded host spans, counters and admission records of one engine."""

    def __init__(self, maxlen: int = SPAN_RING):
        self.spans: collections.deque[Span] = collections.deque(maxlen=maxlen)
        # (rid, slot, cycle) of each admission, oldest dropped first
        self.admissions: collections.deque[tuple[int, int, int]] = \
            collections.deque(maxlen=maxlen)
        self.counters: collections.Counter = collections.Counter()
        self.cycle = 0          # the run loop's current cycle
        self.t0 = 0.0           # perf_counter at the start of the last run
        self._open: list[Span] = []
        self._sid = 0

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Record ``name`` around the body; yields the span, whose ``attrs``
        the body may fill.  It is kept once the body exits, raising or
        not."""
        import jax
        sp = Span(name, time.perf_counter(), 0.0, self._sid,
                  self._open[-1].sid if self._open else None, self.cycle,
                  attrs)
        self._sid += 1
        self._open.append(sp)
        try:
            with jax.profiler.TraceAnnotation(name):
                yield sp
        finally:
            sp.end = time.perf_counter()
            self._open.pop()
            self.spans.append(sp)

    def count(self, name: str, n: int = 1):
        self.counters[name] += n


# -- compiled programs: instruction -> named scope ---------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def hlo_scopes(hlo_text: str, scopes) -> dict[str, str | None]:
    """``{instruction name: scope}`` for every instruction of a compiled HLO
    module (``compiled.as_text()``).

    An instruction's scope is the innermost component of its
    ``metadata={op_name="jit(f)/while/body/<scope>/..."}`` path that is one
    of ``scopes`` (``jax.named_scope`` names).  A fusion whose own metadata
    names none takes the scope of its fused computation: its root's, else
    the one most of its instructions carry.  Instructions the compiler
    inserted (loop-carry copies, the ``while`` itself) map to None.
    """
    scopes = frozenset(scopes)
    own: dict[str, str | None] = {}
    calls: dict[str, str] = {}
    comp_scopes: dict[str, list[str]] = {}
    comp_root: dict[str, str | None] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            comp_scopes[comp] = []
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        scope = None
        if op:
            inner = [p for p in op.group(1).split("/") if p in scopes]
            scope = inner[-1] if inner else None
        own[name] = scope
        if scope:
            comp_scopes[comp].append(scope)
        if m.group(1):
            comp_root[comp] = scope
        c = _CALLS.search(line)
        if c and " fusion(" in line:
            calls[name] = c.group(1)
    out = {}
    for name, scope in own.items():
        if scope is None and name in calls:
            fused = calls[name]
            scope = comp_root.get(fused)
            if scope is None and comp_scopes.get(fused):
                scope = collections.Counter(
                    comp_scopes[fused]).most_common(1)[0][0]
        out[name] = scope
    return out
