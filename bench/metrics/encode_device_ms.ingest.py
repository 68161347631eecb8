"""Device milliseconds per cycle of the engine's chunk encode
(``serve/engine._encode_rows`` -> ``core/coder.encode``, one call per slot),
from the trace."""

PROGRAM = "jit__encode_rows("


def read(ctx):
    cycles = ctx.counters.get("window.cycles", 0)
    secs = ctx.tr.program_seconds(ctx.trace, PROGRAM)
    if not cycles or not secs:
        return None
    return secs / cycles * 1e3
