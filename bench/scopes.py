"""The engine's cycle programs split by the layers the program names, and
the engine's own host spans, for the per-layer metrics that read them.

Device side.  The program wraps each layer of its cycle programs in a
``jax.named_scope`` (``model``, ``spc``, ``topk``, ``decode_kernel``), and
``BatchEngine.scope_maps()`` maps every instruction of each compiled cycle
program to its scope.  An operation's name in the trace starts with its
instruction's name (``%fusion.247 = ...`` on a chip, ``fusion.247`` on the
CPU), so the two join on it.  Only the cycle programs' runs are read
(``jit__unknown``: on a device the operations inside those module runs, on
the CPU the operations whose ``hlo_module`` names it).  Operations nest in
the trace (a ``while`` covers its body's operations), so each nanosecond is
counted once, at the innermost operation covering it: its self time.  The
sum over scopes, unscoped time included, is the programs' busy time.  The
whole split, with the scopes no metric reads and the longest unscoped
operations, is also left in the run's counters (``scope.<program>.*``).

Host side.  ``BatchEngine.trace`` keeps the engine's spans on
``time.perf_counter``, the clock of the benchmark's ``Probe``, so a span
belongs to the window when it ends in it.

A program without these (one older than them) gives nothing to read: every
function here then returns None and raises nothing.
"""

from __future__ import annotations

import bisect
import heapq

PROGRAM = "jit__unknown"
UNMAPPED = "unmapped"        # operations whose instruction the map lacks
TOP_UNSCOPED = 5


def instruction(op_name: str) -> str:
    """The HLO instruction name an operation's trace name starts with."""
    head = op_name.split(" = ", 1)[0].split()
    return head[0].lstrip("%") if head else ""


def self_times(intervals) -> list[float]:
    """Self time of each ``(start, end)`` interval: the length of the part
    of it in which no interval that started later (an operation nested in
    it) runs.  Every instant covered by some interval is counted once, so
    the self times add up to the length of the intervals' union."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    out = [0.0] * len(intervals)
    active: list[tuple[float, float, int]] = []    # (-start, end, index)
    t = None

    def advance(to):
        nonlocal t
        while active and t < to:
            while active and active[0][1] <= t:
                heapq.heappop(active)
            if not active:
                break
            _, end, i = active[0]
            stop = min(to, end)
            out[i] += stop - t
            t = stop
        t = max(t, to)

    for i in order:
        a, b = intervals[i]
        if b <= a:
            continue
        if t is None:
            t = a
        advance(a)
        heapq.heappush(active, (-a, b, i))
    if active:
        advance(max(end for _, end, _ in active))
    return out


def program_ops(trace, program: str = PROGRAM) -> list:
    """Operations of the runs of ``program`` (a module-name prefix)."""
    cpu = [e for e in trace.ops if "hlo_module" in e.stats]
    if cpu:
        return [e for e in cpu
                if str(e.stats["hlo_module"]).startswith(program)]
    runs = sorted((m.start, m.end) for m in trace.modules
                  if m.name.startswith(program))
    starts = [a for a, _ in runs]
    out = []
    for e in trace.ops:
        k = bisect.bisect_right(starts, e.start) - 1
        if k >= 0 and e.end <= runs[k][1]:
            out.append(e)
    return out


def scope_seconds(trace, scope_map: dict, program: str = PROGRAM) -> dict:
    """Seconds of self time in the trace's window by scope (None: no
    scope; ``UNMAPPED``: instruction not in ``scope_map``), and by
    instruction for the unscoped ones (second value)."""
    lo, hi = trace.window
    ops = [e for e in program_ops(trace, program)
           if e.end > lo and e.start < hi]
    selfs = self_times([(max(e.start, lo), min(e.end, hi)) for e in ops])
    by_scope: dict = {}
    unscoped: dict[str, float] = {}
    for e, ns in zip(ops, selfs):
        name = instruction(e.name)
        scope = scope_map[name] if name in scope_map else UNMAPPED
        by_scope[scope] = by_scope.get(scope, 0.0) + ns * 1e-9
        if scope is None:
            unscoped[name] = unscoped.get(name, 0.0) + ns * 1e-9
    return by_scope, unscoped


def _scope_map(ctx, program: str):
    maps = getattr(getattr(ctx.system, "eng", None), "scope_maps", None)
    return maps().get(program) if maps is not None else None


def cycle_split_ms(ctx, program: str) -> dict | None:
    """Device milliseconds per cycle of the ``program`` (``"step"`` or
    ``"prefill"``) cycle program by scope, None for time no scope names.

    None where the program has no scope map, where the window also ran
    the other cycle program (both share one module name), and where more
    than 1% of the time falls on instructions the map lacks (then the map
    does not describe what ran)."""
    cache = vars(ctx).setdefault("_cycle_split_ms", {})
    if program in cache:
        return cache[program]
    cache[program] = None
    cycles = ctx.counters.get("window.cycles", 0)
    other = "prefill" if program == "step" else "step"
    if not cycles or ctx.counters.get(f"window.{other}_launches", 0):
        return None
    smap = _scope_map(ctx, program)
    if not smap:
        return None
    secs, unscoped = scope_seconds(ctx.trace, smap)
    total = sum(secs.values())
    if not total or secs.get(UNMAPPED, 0.0) > 0.01 * total:
        return None
    split = {k: v / cycles * 1e3 for k, v in secs.items()}
    for k, v in split.items():
        ctx.counters[f"scope.{program}.{k or 'unscoped'}_ms"] = v
    top = sorted(unscoped.items(), key=lambda x: -x[1])[:TOP_UNSCOPED]
    for name, s in top:
        ctx.counters[f"scope.{program}.unscoped_top.{name}_ms"] = \
            s / cycles * 1e3
    cache[program] = split
    return split


def scope_ms(ctx, program: str, scope: str | None):
    split = cycle_split_ms(ctx, program)
    return None if split is None else split.get(scope, 0.0)


def window_spans(ctx) -> list | None:
    """The engine's spans that ended in the window, or None where the
    engine keeps none or its ring has dropped some of the window's."""
    tr = getattr(getattr(ctx.system, "eng", None), "trace", None)
    spans = getattr(tr, "spans", None)
    if not spans:
        return None
    if len(spans) == spans.maxlen and spans[0].end > ctx.probe.t_open:
        return None
    return [s for s in spans if ctx.probe.in_window(s.end)]


def host_self_ms(ctx) -> float | None:
    """Wall milliseconds per cycle of the engine's outermost host spans
    ending in the window, less what their ``engine.finalize.wait``
    descendants cover (the host waiting for the device)."""
    spans = window_spans(ctx)
    cycles = ctx.counters.get("window.cycles", 0)
    if not spans or not cycles:
        return None
    by_sid = {s.sid: s for s in ctx.system.eng.trace.spans}
    roots = {s.sid for s in spans if s.parent is None}
    if not roots:
        return None
    wall = sum(by_sid[r].dur for r in roots)
    for s in by_sid.values():
        if s.name != "engine.finalize.wait":
            continue
        up = s
        while up.parent is not None and up.parent in by_sid:
            up = by_sid[up.parent]
        if up.sid in roots:
            wall -= s.dur
    return wall / cycles * 1e3


def spec_hit_share(ctx) -> float | None:
    """Decoded symbols found among the model's top-k candidates, over the
    decoded symbols, of the ``engine.finalize.cycle`` spans ending in the
    window, in percent."""
    spans = window_spans(ctx)
    if not spans:
        return None
    fin = [s for s in spans if s.name == "engine.finalize.cycle"]
    decoded = sum(s.attrs.get("decoded", 0) for s in fin)
    if not decoded:
        return None
    return sum(s.attrs.get("hits", 0) for s in fin) / decoded * 100
