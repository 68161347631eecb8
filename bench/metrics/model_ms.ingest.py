"""Device milliseconds per cycle of the engine's prefill program
(``serve/engine._prefill_body``) under the ``model`` scope: the model's
row reset and forward, self time of each operation
(``scopes.cycle_split_ms``), from the trace."""

import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "prefill", "model")
