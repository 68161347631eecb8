"""Plain reference of the coder's integer datapath, in numpy.

It imports nothing of the program.  It is what ``correct`` compares the
program's tables, streams and containers against:

* :func:`quantize` — probabilities -> integer frequencies summing to
  ``2**prob_bits``: bfloat16 storage, ``f = max(1, round(p * 2**n))``, then
  one largest-remainder correction (stable: on equal residuals the lower
  symbol index comes first).
* :func:`encode_streams` — textbook rANS over many independent streams at
  once: 32-bit state in ``[2**23, 2**31)``, byte renormalisation, exact
  integer division (no reciprocals), a 4-byte big-endian final state at the
  head of each stream.
* :func:`pack_v2` — the chunked container (``RAS2``): 24-byte header, one
  ``(offset u64, length u32, crc32 u32)`` index cell per (chunk, lane),
  chunk-major, then the concatenated payload.
"""

from __future__ import annotations

import struct
import zlib

import ml_dtypes
import numpy as np

RANS_L = 1 << 23


def quantize(probs: np.ndarray, prob_bits: int) -> np.ndarray:
    """``(..., K)`` float probabilities -> ``(..., K)`` int64 frequencies."""
    total = 1 << prob_bits
    p = np.asarray(probs, np.float32).astype(ml_dtypes.bfloat16)
    p = p.astype(np.float32)
    p = np.where(np.isfinite(p) & (p > 0), p, np.float32(0))
    scaled = p * np.float32(total)
    f0 = np.maximum(1, np.round(scaled)).astype(np.int64)
    resid = scaled - f0.astype(np.float32)
    k = p.shape[-1]
    delta = total - f0.sum(-1, keepdims=True)

    # delta >= 0: every symbol gets delta // K, the (delta % K) largest
    # residuals one more
    desc = np.argsort(-resid, axis=-1, kind="stable")
    rank_desc = np.empty_like(desc)
    np.put_along_axis(rank_desc, desc,
                      np.broadcast_to(np.arange(k), desc.shape), axis=-1)
    f_pos = f0 + delta // k + (rank_desc < delta % k)

    # delta < 0: take units back, smallest residual first, none below 1
    asc = np.argsort(resid, axis=-1, kind="stable")
    cap = np.take_along_axis(f0 - 1, asc, axis=-1)
    before = np.cumsum(cap, axis=-1) - cap
    take_sorted = np.clip(-delta - before, 0, cap)
    take = np.empty_like(take_sorted)
    np.put_along_axis(take, asc, take_sorted, axis=-1)
    f_neg = f0 - take
    return np.where(delta >= 0, f_pos, f_neg)


def cdf_of(freq: np.ndarray) -> np.ndarray:
    """Exclusive prefix sums ``(..., K+1)``."""
    freq = np.asarray(freq, np.int64)
    zero = np.zeros(freq.shape[:-1] + (1,), np.int64)
    return np.concatenate([zero, np.cumsum(freq, -1)], -1)


def softmax(logits: np.ndarray) -> np.ndarray:
    x = np.asarray(logits, np.float64)
    e = np.exp(x - x.max(-1, keepdims=True))
    return (e / e.sum(-1, keepdims=True)).astype(np.float32)


def encode_streams(symbols: np.ndarray, freq: np.ndarray,
                   prob_bits: int) -> list[bytes]:
    """rANS-encode ``N`` independent streams of ``T`` symbols each.

    ``symbols`` is ``(N, T)``; ``freq`` is ``(T, N, K)``, one table per
    position and stream.  Returns the ``N`` byte streams.
    """
    x = np.asarray(symbols, np.int64)
    n, t_len = x.shape
    freq = np.asarray(freq, np.int64)
    cdf = cdf_of(freq)
    rows = np.arange(n)
    cap = 2 * t_len + 8
    out = np.zeros((n, cap), np.uint8)
    ptr = np.full(n, cap, np.int64)
    s = np.full(n, RANS_L, np.int64)
    x_unit = (RANS_L >> prob_bits) << 8
    for t in range(t_len - 1, -1, -1):
        sym = x[:, t]
        f, c = freq[t, rows, sym], cdf[t, rows, sym]
        x_max = x_unit * f
        for _ in range(2):
            emit = s >= x_max
            ptr -= emit
            out[rows[emit], ptr[emit]] = (s[emit] & 0xFF).astype(np.uint8)
            s = np.where(emit, s >> 8, s)
        s = ((s // f) << prob_bits) + (s % f) + c
    for shift in (0, 8, 16, 24):
        ptr -= 1
        out[rows, ptr] = ((s >> shift) & 0xFF).astype(np.uint8)
    return [out[i, ptr[i]:].tobytes() for i in range(n)]


def pack_v2(cells: list[list[bytes]], *, lanes: int, n_symbols: int,
            chunk_size: int, prob_bits: int) -> bytes:
    """``cells[c][l]`` is the stream of chunk ``c``, lane ``l``."""
    n_chunks = len(cells)
    head = struct.pack("<4sBBHIIII", b"RAS2", 2, prob_bits, 1, lanes,
                       n_symbols, chunk_size, n_chunks)
    index, payload, off = [], [], 0
    for chunk in cells:
        for cell in chunk:
            index.append(struct.pack("<QII", off, len(cell),
                                     zlib.crc32(cell)))
            payload.append(cell)
            off += len(cell)
    return head + b"".join(index) + b"".join(payload)


def byte_gap(a: bytes, b: bytes) -> int:
    """How many bytes differ, a length difference counted in full."""
    n = min(len(a), len(b))
    diff = np.frombuffer(a[:n], np.uint8) != np.frombuffer(b[:n], np.uint8)
    return int(diff.sum()) + abs(len(a) - len(b))
