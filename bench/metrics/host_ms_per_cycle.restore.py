"""Host CPU milliseconds per cycle in the engine's host half: building the
cycle's inputs (``_build_cycle``: container windows, teacher-forced rows),
dispatching it (``_launch``) and finalizing the previous one
(``_finalize``: chunk encode dispatch, syncs, pack), from the benchmark's
spans around them.  CPU time of the thread, not wall time: ``_finalize``
reads its outputs through device slices that queue behind the cycle
already in flight, so its wall time is mostly waiting for the device."""


def read(ctx):
    cycles = ctx.counters.get("window.cycles", 0)
    cpu = sum(sum(ctx.probe.window_spans(n, cpu=True)) for n in (
        "engine.build_cycle", "engine.launch", "engine.finalize"))
    if not cycles or not cpu:
        return None
    return cpu / cycles * 1e3
