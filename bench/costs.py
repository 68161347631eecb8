"""Operations and bytes of the work the benchmark measures, computed from
the configuration and the shapes of each call, whatever implements it.

A kernel's roofline share is the least time its bytes need at the chip's
peak bandwidth over the time the trace gives it.  The rANS kernels do no
matrix work, and their integer operations are a small fraction of what the
vector units retire, so the byte bound is the one that binds; each function
below gives the bytes the call cannot avoid moving: every input read once,
every output written once.
"""

from __future__ import annotations

U32 = 4


def model_matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product for every symbol."""
    m = cfg["model"]
    d, ff, dh = m["d_model"], m["d_ff"], m["head_dim"]
    h, kv, v = m["n_heads"], m["n_kv_heads"], m["vocab_size"]
    attn = d * h * dh + 2 * d * kv * dh + h * dh * d
    mlp = 3 * d * ff
    return m["n_layers"] * (attn + mlp) + d * v


def model_flops(cfg: dict, pos0: int, n: int) -> float:
    """FLOPs of one lane's symbols at positions ``[pos0, pos0 + n)``: two per
    multiply-add of the matrix products, plus the causal attention at each
    position's context (``pos + 1`` keys: scores and values, two FLOPs per
    multiply-add each)."""
    m = cfg["model"]
    width = m["n_heads"] * m["head_dim"]
    ctx = n * pos0 + n * (n + 1) // 2          # sum of (pos + 1)
    return 2.0 * model_matmul_params(cfg) * n \
        + 4.0 * m["n_layers"] * width * ctx


def decode_step_bytes(rows: int, k: int, cap: int, topk: int) -> int:
    """One step kernel call over ``rows`` rows: the (cap, rows) byte window,
    states and cursors in and out, per-row tables (freq (rows, K), cdf
    (rows, K+1)), the (rows, topk) candidates, and symbols, probes and
    under-read flags out."""
    ins = cap * rows + 2 * rows * U32 + rows * k * U32 \
        + rows * (k + 1) * U32 + rows * topk * U32
    outs = 5 * rows * U32
    return ins + outs


def default_cap(chunk: int) -> int:
    """The coder's per-(chunk, lane) byte budget: two bytes a symbol and the
    state header, padded."""
    return 2 * chunk + 8
