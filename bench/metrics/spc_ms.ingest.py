"""Device milliseconds per cycle of the engine's prefill program
(``serve/engine._prefill_body``) under the ``spc`` scope: the SPC's
quantized tables (``serve/compress.step_tables``), self time of each
operation (``scopes.cycle_split_ms``), from the trace."""

import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "prefill", "spc")
