"""Streaming Prefetch Converter (SPC): the paper's mixed-precision probability module.

Implements Sec. IV-A of the RAS paper:

  * distributions are *stored* in BF16 ("half the table storage of fp32");
  * a **single** BF16 -> fixed-point conversion produces integer frequencies
        f(x) = max(1, round(p_x * 2**n))
    followed by a deterministic **mass-correction** pass enforcing
        sum_x f(x) == 2**n
    and a strictly monotone CDF  C(x) = sum_{y<x} f(y);
  * all subsequent division / modulo work happens purely in the fixed-point
    domain — here we go one step further than the RTL and fold the divider
    into the table: the SPC also emits per-symbol Barrett reciprocals
    (rcp, rshift, bias, cmpl) so the hot path needs no integer division at
    all (see DESIGN.md §2, "Barrett/Alverson reciprocal division").

Everything is pure jnp and jit-compatible, so the conversion can run inside
the compression graph (the "streams shared CDF/frequency tables" role).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import constants as C

_U32 = jnp.uint32
_I32 = jnp.int32


class TableSet(NamedTuple):
    """Fixed-point coding tables for one distribution (or a batch ``(..., K)``).

    All integer fields are uint32.  ``cdf`` has one more entry than the others
    (``cdf[..., K] == 2**prob_bits``).
    """

    freq: jax.Array      # (..., K)   quantized frequencies, >= 1
    cdf: jax.Array       # (..., K+1) exclusive prefix sums, cdf[...,0] == 0
    rcp: jax.Array       # (..., K)   Barrett reciprocal
    rshift: jax.Array    # (..., K)   post-mulhi shift
    bias: jax.Array      # (..., K)   additive bias (folds CDF + f==1 case)
    cmpl: jax.Array      # (..., K)   2**n - f   (ryg "complement frequency")
    x_max: jax.Array     # (..., K)   encoder renorm threshold  = scale * f

    @property
    def alphabet_size(self) -> int:
        return self.freq.shape[-1]


# ---------------------------------------------------------------------------
# BF16 storage + quantization + mass correction
# ---------------------------------------------------------------------------

def store_bf16(probs: jax.Array) -> jax.Array:
    """Simulate the paper's BF16 global-memory storage of distributions."""
    return probs.astype(jnp.bfloat16)


def quantize_probs(probs: jax.Array, prob_bits: int = C.PROB_BITS) -> jax.Array:
    """BF16/float probabilities -> integer frequencies with exact mass 2**n.

    Faithful to the paper: ``f0 = max(1, round(p * 2**n))`` then one
    deterministic largest-remainder correction pass so ``sum(f) == 2**n`` and
    the CDF is strictly monotone (every symbol keeps f >= 1).

    Works on a single distribution ``(K,)`` or a batch ``(..., K)``.

    §Perf: the correction runs on ONE stable ascending sort (XLA's CPU
    sort is a scalar loop — it was 80% of the serve profile at four sorts
    per call).  In sorted order the ascending rank is the position itself;
    the descending stable rank follows exactly from tie-run bookkeeping
    (``rank_desc = K - runlen + 2*pos_in_run - rank_asc`` — stable sorts
    keep equal keys in index order, so a run member's position within its
    run is its tie-break count for BOTH directions); inverse permutations
    are scatters, not second sorts.  All integer identities — bit-identical
    to the four-argsort form, pinned in tests/test_core_rans.py.
    """
    C.check_prob_bits(prob_bits)
    total = 1 << prob_bits
    k = probs.shape[-1]
    if k > total:
        raise ValueError(
            f"alphabet size {k} exceeds 2**prob_bits={total}; raise prob_bits")

    # Single BF16 -> fixed-point conversion (mass correction keeps it exact).
    p = probs.astype(jnp.bfloat16).astype(jnp.float32)
    p = jnp.where(jnp.isfinite(p) & (p > 0), p, 0.0)
    scaled = p * jnp.float32(total)

    f0 = jnp.maximum(1, jnp.round(scaled)).astype(_I32)
    delta = total - jnp.sum(f0, axis=-1, keepdims=True)  # (..., 1)
    resid = scaled - f0.astype(jnp.float32)

    order_asc = jnp.argsort(resid, axis=-1, stable=True)
    sortd = jnp.take_along_axis(resid, order_asc, axis=-1)
    pidx = jnp.broadcast_to(jnp.arange(k, dtype=_I32), resid.shape)
    edge = jnp.ones(resid.shape[:-1] + (1,), bool)
    first = jnp.concatenate([edge, sortd[..., 1:] != sortd[..., :-1]], -1)
    last = jnp.concatenate([first[..., 1:], edge], -1)
    ax = resid.ndim - 1
    start = jax.lax.cummax(jnp.where(first, pidx, 0), axis=ax)
    end = jax.lax.cummin(jnp.where(last, pidx, k - 1), axis=ax, reverse=True)
    runlen = end - start + 1                  # tie-run extent at each slot
    rank_desc_sorted = k - runlen + 2 * (pidx - start) - pidx

    # --- delta > 0: distribute delta units; BF16 storage error can make
    # delta exceed K, so give floor(delta/K) to every symbol and the
    # remainder to the largest residuals (stable largest-remainder rule).
    rank_desc = jnp.put_along_axis(jnp.zeros_like(pidx), order_asc,
                                   rank_desc_sorted, axis=-1, inplace=False)
    f_pos = f0 + delta // k + (rank_desc < delta % k).astype(_I32)

    # --- delta < 0: remove `-delta` units, smallest residual first, never
    # below 1.  capacity = f0 - 1; waterfill along ascending residual.
    need = (-delta).astype(_I32)                              # (..., 1)
    cap_sorted = jnp.take_along_axis(f0 - 1, order_asc, axis=-1)
    cum_excl = jnp.cumsum(cap_sorted, axis=-1) - cap_sorted
    take_sorted = jnp.clip(need - cum_excl, 0, cap_sorted)
    take = jnp.put_along_axis(jnp.zeros_like(pidx), order_asc, take_sorted,
                              axis=-1, inplace=False)
    f_neg = f0 - take

    f = jnp.where(delta >= 0, f_pos, f_neg)
    return f.astype(_U32)


# ---------------------------------------------------------------------------
# Barrett reciprocal construction (exact uint32 long division, no x64 needed)
# ---------------------------------------------------------------------------

def _ceil_div_pow2_u32(shift_amt: jax.Array, f: jax.Array) -> jax.Array:
    """ceil(2**(31 + shift_amt) / f) computed exactly in uint32.

    Uses  2**(31+s) // f = (2**31 // f) << s  +  ((2**31 % f) << s) // f
    (all pieces < 2**32 because f >= 2 and s = ceil(log2 f) <= 16).
    """
    two31 = _U32(1 << 31)
    a = two31 // f                       # <= 2**30
    r = two31 - a * f                    # < f <= 2**16
    hi = a << shift_amt                  # < 2**32 (since 2**s < 2f)
    num = r << shift_amt                 # < 2**32
    q2 = num // f
    rem = num - q2 * f
    rcp = hi + q2 + (rem > 0).astype(_U32)
    return rcp


def barrett_planes(freq: jax.Array, start: jax.Array, prob_bits: int):
    """``(freq, start)`` -> the five encoder planes ``(rcp, rshift, bias,
    cmpl, x_max)``.

    This is the *single source* of the Barrett reciprocal construction:
    :func:`build_tables` maps it over whole alphabets, and the stack codecs
    (``core.stack``) call it per-symbol on gathered ``(start, freq)`` pairs —
    structurally the same math, so push/pop over statfuns is bit-identical
    to the table path by construction.
    """
    total = _U32(1 << prob_bits)
    f = freq.astype(_U32)
    start = start.astype(_U32)

    is_one = f == 1
    # shift = ceil(log2 f) = bit_length(f - 1) for f >= 2.
    fm1 = jnp.maximum(f, 2) - 1
    shift = (_U32(32) - jax.lax.clz(fm1)).astype(_U32)
    rcp_ge2 = _ceil_div_pow2_u32(shift, jnp.maximum(f, 2))

    rcp = jnp.where(is_one, _U32(0xFFFFFFFF), rcp_ge2)
    rshift = jnp.where(is_one, _U32(0), shift - 1)
    bias = jnp.where(is_one, start + total - 1, start)
    cmpl = total - f
    x_max = _U32(C.x_max_scale(prob_bits)) * f
    return rcp, rshift, bias, cmpl, x_max


def build_tables(freq: jax.Array, prob_bits: int = C.PROB_BITS) -> TableSet:
    """Quantized frequencies -> full fixed-point TableSet (batched OK)."""
    C.check_prob_bits(prob_bits)
    f = freq.astype(_U32)

    cdf_hi = jnp.cumsum(f.astype(_I32), axis=-1).astype(_U32)
    zeros = jnp.zeros(f.shape[:-1] + (1,), _U32)
    cdf = jnp.concatenate([zeros, cdf_hi], axis=-1)          # (..., K+1)
    start = cdf[..., :-1]

    rcp, rshift, bias, cmpl, x_max = barrett_planes(f, start, prob_bits)
    return TableSet(freq=f, cdf=cdf, rcp=rcp, rshift=rshift,
                    bias=bias, cmpl=cmpl, x_max=x_max)


def freq_cdf_from_probs(probs: jax.Array, prob_bits: int = C.PROB_BITS):
    """Decode-only SPC fast path: probabilities -> ``(freq, cdf)``.

    The decoder's hot loop touches only the frequencies and the exclusive
    CDF — the Barrett reciprocal planes (rcp/rshift/bias/cmpl/x_max) are
    encoder-side machinery.  This helper runs the identical
    :func:`quantize_probs` mass correction and the *verbatim* CDF
    construction of :func:`build_tables`, so
    ``freq_cdf_from_probs(p) == (t.freq, t.cdf)`` for
    ``t = tables_from_probs(p)`` bit-for-bit, at ~2/7 the table FLOPs/bytes.
    The fused serve decode (serve.compress, DESIGN.md §9) quantizes each
    model step through this path just-in-time.
    """
    f = quantize_probs(probs, prob_bits)
    cdf_hi = jnp.cumsum(f.astype(_I32), axis=-1).astype(_U32)
    zeros = jnp.zeros(f.shape[:-1] + (1,), _U32)
    return f, jnp.concatenate([zeros, cdf_hi], axis=-1)


def tables_from_probs(probs: jax.Array,
                      prob_bits: int = C.PROB_BITS) -> TableSet:
    """One-shot SPC: BF16 probabilities -> coding tables (the paper's path)."""
    return build_tables(quantize_probs(probs, prob_bits), prob_bits)


def tables_from_logits(logits: jax.Array,
                       prob_bits: int = C.PROB_BITS) -> TableSet:
    """Model logits -> coding tables (softmax in f32, stored via BF16)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return tables_from_probs(store_bf16(probs), prob_bits)


def decode_lut(tables: TableSet, prob_bits: int = C.PROB_BITS) -> jax.Array:
    """Optional O(1) slot->symbol lookup table (static-table fast path).

    Beyond-paper optimization: for a *static* table the 2**n-entry inverse LUT
    replaces the binary search entirely (one gather per symbol).  Memory is
    2**n entries so this is only built for shared/static tables.
    """
    slots = jnp.arange(1 << prob_bits, dtype=_U32)
    # symbol = number of cdf entries <= slot, minus one.
    return (jnp.searchsorted(tables.cdf, slots, side="right") - 1).astype(_U32)


# ---------------------------------------------------------------------------
# numpy convenience (host-side table prep, container tooling)
# ---------------------------------------------------------------------------

def _host_device():
    """The CPU device for host-side table prep, or None (the default
    device) where the CPU backend is not initialised.  Either gives the
    same tables: the quantizer is a bf16 cast, a power-of-two scale, a
    round and integer bookkeeping, all exact on every backend."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def tables_from_counts_np(counts: np.ndarray,
                          prob_bits: int = C.PROB_BITS) -> TableSet:
    """Host-side helper: raw symbol counts -> TableSet (adds +1 smoothing)."""
    counts = np.asarray(counts, np.float64)
    probs = (counts + 1.0) / (counts + 1.0).sum(-1, keepdims=True)
    with jax.default_device(_host_device()):
        return jax.tree.map(np.asarray,
                            tables_from_probs(jnp.asarray(probs, jnp.float32),
                                              prob_bits))
