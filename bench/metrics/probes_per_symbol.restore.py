"""CDF probes per decoded symbol in the window: the engine's own probe
count (``RequestResult.probes``, summed per chunk) over the symbols, with
model top-k candidates tried first (the paper's Fig. 4(b) count)."""


def read(ctx):
    syms = ctx.counters.get("window.symbols", 0)
    if not syms:
        return None
    return ctx.counters["window.probes"] / syms
