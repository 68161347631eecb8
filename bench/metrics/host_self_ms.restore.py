"""Wall milliseconds per cycle of the engine's host half, from the engine's
own spans (``BatchEngine.trace``): its outermost spans that ended in the
window (``engine.cycle``: admit, build, launch, finalize with chunk encode
dispatch and pack), less the time their ``engine.finalize.wait`` spans
spent blocked on the device (``scopes.host_self_ms``)."""

import scopes


def read(ctx):
    return scopes.host_self_ms(ctx)
