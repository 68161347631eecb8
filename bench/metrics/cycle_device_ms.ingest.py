"""Device milliseconds per cycle of the engine's prefill program
(``serve/engine._prefill_body``: block prefill of the model and the
per-position SPC tables under ``lax.map``), from the trace.

The engine jits its cycle bodies through ``functools.partial``, which JAX
names ``_unknown``: the step and the prefill program both run as
``jit__unknown(<fingerprint>)``.  In this cell every cycle in the window
is a prefill cycle (no step cycle was launched in it), so those runs are
the prefill program's; otherwise the reader stays silent.
"""

PROGRAM = "jit__unknown("


def read(ctx):
    cycles = ctx.counters.get("window.cycles", 0)
    if not cycles or ctx.counters.get("window.step_launches", 0):
        return None
    secs = ctx.tr.program_seconds(ctx.trace, PROGRAM)
    return secs / cycles * 1e3 if secs else None
