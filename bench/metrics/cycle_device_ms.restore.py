"""Device milliseconds per cycle of the engine's step program
(``serve/engine._chunk_body``: model step, SPC tables, model top-k and the
step kernel, ``chunk_size`` steps in one ``lax.scan``), from the trace.

The engine jits its cycle bodies through ``functools.partial``, which JAX
names ``_unknown``: the step and the prefill program both run as
``jit__unknown(<fingerprint>)``.  In this cell every cycle in the window is
a step cycle (no prefill cycle was launched in it), so those runs are the
step program's; where a prefill cycle did run, the reader stays silent.
"""

PROGRAM = "jit__unknown("


def read(ctx):
    cycles = ctx.counters.get("window.cycles", 0)
    if not cycles or ctx.counters.get("window.prefill_launches", 0):
        return None
    secs = ctx.tr.program_seconds(ctx.trace, PROGRAM)
    return secs / cycles * 1e3 if secs else None
