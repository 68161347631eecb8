"""The step kernel (``kernels/rans_decode.rans_decode_step``, one call per
step over all rows) against its memory roofline: the bytes each call needs
(``costs.decode_step_bytes``: the (cap, rows) window, per-row freq and cdf
tables, candidates, states in and out) at peak bandwidth, over the summed
kernel time in the trace.  Bound by bytes: the call does no matrix work.

The kernel has no stable name in the trace (its custom call is named
``closed_call.<n>``); it is the one TPU custom call of the step program
that reads the ``(cap, rows)`` byte window.
"""


def read(ctx):
    e = ctx.cfg["engine"]
    rows = e["slots"] * e["lanes"]
    cap = ctx.costs.default_cap(e["chunk_size"])
    ev = ctx.tr.kernel_events(ctx.trace, "tpu_custom_call",
                              f"u8[{cap},{rows}]")
    if not ev:
        return None
    per_call = ctx.costs.decode_step_bytes(
        rows, ctx.cfg["model"]["vocab_size"], cap, e["topk"])
    secs = sum(x.dur for x in ev) * 1e-9
    return len(ev) * per_call / ctx.peaks["hbm_bytes_s"] / secs * 100
