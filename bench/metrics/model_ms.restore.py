"""Device milliseconds per cycle of the engine's step program
(``serve/engine._chunk_body``) under the ``model`` scope: the model's row
reset and forward, self time of each operation
(``scopes.cycle_split_ms``), from the trace."""

import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "step", "model")
