"""Device milliseconds per cycle of the engine's prefill program
(``serve/engine._prefill_body``) that no named scope covers: the scan's
own loop, loop-carry copies the compiler inserted, the per-row state
selects; self time of each operation (``scopes.cycle_split_ms``), from the
trace."""

import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "prefill", None)
