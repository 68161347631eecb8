"""Model FLOPs of every symbol finished in the window (``costs.model_flops``:
two per multiply-add of the matrix products plus causal attention at each
position's context) per second, over the chip's bf16 peak.  The model is
float32, but the chip runs float32 matrix products on its bf16 units at
default precision, so bf16 is the peak it can reach."""


def read(ctx):
    flops = ctx.counters.get("window.model_flops", 0)
    secs = ctx.counters.get("window.seconds", 0)
    if not flops or not secs:
        return None
    return flops / secs / ctx.peaks["bf16_flops_s"] * 100
