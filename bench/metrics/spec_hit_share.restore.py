"""Share of decoded symbols that the model's top-k candidates resolve (the
CDF search's useful speculation over its attempts), from the hits and
decoded symbols the engine counts in its ``engine.finalize.cycle`` spans
(``scopes.spec_hit_share``)."""

import scopes


def read(ctx):
    return scopes.spec_hit_share(ctx)
