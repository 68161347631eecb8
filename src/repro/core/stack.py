"""Craystack-style push/pop stack interface over the multi-lane rANS coder.

The lane coder (:mod:`repro.core.coder`) is a *batch* codec: encode a whole
``(lanes, T)`` block, flush, decode it back.  Latent-variable compression
(bits-back / Bit-Swap, BB-ANS) needs the coder as a **stack**: interleaved
pushes and pops against one live state, where a *pop against the posterior*
recovers bits a *push against the prior* later pays back (the bits-back
identity).  This module is that stack:

  * :class:`StackState` — the live coder state: per-lane rANS states, the
    shared backward byte buffer, per-lane cursors and the per-lane
    ``underflow`` flag (a pop that reads past the stream end injects 0 and
    flags, exactly like :class:`repro.core.coder.DecState` — DESIGN.md §12);
  * **push/pop are inverses by construction**: push lands the single-source
    :func:`repro.core.update.encode_step` records backward, pop runs the
    single-source :func:`repro.core.search.find_symbol` inversion + the
    decoder's guarded forward refill.  Pop-then-push (and push-then-pop)
    restore the state bit-exactly because both directions share the same
    integer cores as the batch coder and the Pallas kernels;
  * **codecs** are ``(push, pop)`` pairs over symbol ↦ ``(start, freq)``
    statfuns in the fixed-point domain: :func:`NonUniform` (craystack's
    primitive), :func:`Uniform`, :func:`Categorical` /
    :func:`from_tableset` (tables from :mod:`repro.core.spc`, with a
    ``backend="kernel"`` pop through ``kernels.rans_decode_step``),
    :func:`DiagGaussian` and :func:`DiscretizedLogistic` (the observation
    codecs of the bits-back VAE), composed with :func:`serial` and
    :func:`substack`;
  * **initial bits** are explicit: :func:`stack_init` starts empty (a pop
    immediately *flags* — stream exhaustion is detectable, never silent),
    :func:`stack_init_bits` seeds the stack with random initial bits so
    posterior pops have entropy to draw from (the BB-ANS initial-bits
    protocol).

Every push gathers its ``(start, freq)`` pair and runs it through
:func:`repro.core.spc.barrett_planes` — the *same* single source
:func:`repro.core.spc.build_tables` maps over whole alphabets — so statfun
codecs and TableSet codecs are bit-identical by construction, not by test.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import constants as C
from repro.core import search, spc, update
from repro.core.bitstream import EncodedLanes
from repro.core.coder import (StreamExhaustedError, _check_exhausted,  # noqa: F401
                              _emit_backward, _read_byte)
from repro.core.search import take_gather as _gather

_U32 = jnp.uint32
_U8 = jnp.uint8
_I32 = jnp.int32


class StackState(NamedTuple):
    """Live stack state: bytes in ``buf[lane, ptr[lane]:]`` are the stream
    (pushed backward, popped forward — rANS is LIFO, so the byte at
    ``ptr`` is always the most recently pushed unconsumed byte)."""

    s: jax.Array          # (lanes,) uint32 rANS states
    buf: jax.Array        # (lanes, cap) uint8 backward byte stack
    ptr: jax.Array        # (lanes,) int32: next pop reads buf[lane, ptr]
    underflow: jax.Array  # (lanes,) bool: a pop read past the stream end


class Codec(NamedTuple):
    """A craystack codec: ``push(state, symbol) -> state`` and
    ``pop(state) -> (state, symbol)`` — exact inverses of each other."""

    push: Callable[[StackState, Any], StackState]
    pop: Callable[[StackState], tuple[StackState, Any]]


# ---------------------------------------------------------------------------
# stack lifecycle: init / initial bits / flush / open
# ---------------------------------------------------------------------------

def stack_init(lanes: int, cap: int) -> StackState:
    """Empty stack at the rANS normalization floor.

    A pop from this state has no entropy to draw on: the refill reads past
    the (empty) stream and raises the lane's ``underflow`` flag — exhaustion
    is *detectable* (satellite bugfix semantics), unlike the pre-fix coder
    which silently re-read its last byte.
    """
    return StackState(s=jnp.full((lanes,), C.RANS_L, _U32),
                      buf=jnp.zeros((lanes, cap), _U8),
                      ptr=jnp.full((lanes,), cap, _I32),
                      underflow=jnp.zeros((lanes,), bool))


def stack_init_bits(lanes: int, cap: int, n_bytes: int = 64,
                    seed: int = 0) -> StackState:
    """Stack seeded with ``n_bytes`` random initial bytes per lane plus a
    random in-range state — the BB-ANS "initial bits" a bits-back pop
    consumes and the matching decode-side push provably restores.

    The state is drawn from ``[RANS_L, 2**31)`` (any valid mid-stream rANS
    state); the bytes are uniform.  Deterministic in ``seed``.
    """
    if n_bytes > cap:
        raise ValueError(f"n_bytes={n_bytes} exceeds stack cap={cap}")
    rng = np.random.default_rng(seed)
    buf = np.zeros((lanes, cap), np.uint8)
    if n_bytes:
        buf[:, cap - n_bytes:] = rng.integers(0, 256, (lanes, n_bytes),
                                              dtype=np.uint8)
    s = rng.integers(C.RANS_L, 1 << 31, (lanes,), dtype=np.uint32)
    return StackState(s=jnp.asarray(s), buf=jnp.asarray(buf),
                      ptr=jnp.full((lanes,), cap - n_bytes, _I32),
                      underflow=jnp.zeros((lanes,), bool))


def stack_bytes(st: StackState) -> jax.Array:
    """Per-lane live stack size in bytes: stream bytes plus the 4-byte
    state header a :func:`stack_flush` would emit.  The bits-back ratio
    accounting unit: net cost of a message = ``stack_bytes`` after minus
    before (the initial bits are capital, not cost)."""
    cap = st.buf.shape[1]
    return (cap - st.ptr) + 4


def stack_flush(st: StackState) -> EncodedLanes:
    """Serialize the live stack: emit the 4-byte big-endian state header
    (read back first by :func:`stack_open`) and package the streams as
    :class:`EncodedLanes` — byte-compatible with ``coder.encode`` output,
    so flushed stacks ride the existing container/bitstream tooling."""
    s, buf, ptr = st.s, st.buf, st.ptr
    true = jnp.ones_like(s, bool)
    for shift in (0, 8, 16, 24):
        buf, ptr = _emit_backward(
            buf, ptr, ((s >> shift) & _U32(0xFF)).astype(_U8), true)
    cap = buf.shape[1]
    return EncodedLanes(buf=buf, start=jnp.maximum(ptr, 0),
                        length=jnp.asarray(cap, _I32) - ptr,
                        overflow=ptr < 0)


def stack_open(enc: EncodedLanes) -> StackState:
    """Inverse of :func:`stack_flush`: read the state header back off the
    stream and resume the live stack.  A header read past the stream end
    flags ``underflow`` (truncated container)."""
    lanes, cap = enc.buf.shape
    lane_idx = jnp.arange(lanes)
    s = jnp.zeros((lanes,), _U32)
    ptr = enc.start
    under = jnp.zeros((lanes,), bool)
    for _ in range(4):
        byte, oob = _read_byte(enc.buf, lane_idx, ptr, cap)
        under = under | oob
        s = (s << 8) | byte
        ptr = ptr + 1
    return StackState(s=s, buf=enc.buf, ptr=ptr, underflow=under)


# ---------------------------------------------------------------------------
# primitive push / pop over (start, freq) in the fixed-point domain
# ---------------------------------------------------------------------------

def push_with(st: StackState, start: jax.Array, freq: jax.Array,
              prob_bits: int = C.PROB_BITS) -> StackState:
    """Push one symbol per lane given its gathered ``(start, freq)`` pair.

    The encoder planes come from :func:`repro.core.spc.barrett_planes` —
    the single source ``build_tables`` maps over alphabets — then the
    single-source :func:`repro.core.update.encode_step` runs and its renorm
    records land backward, exactly like ``coder.encode_put``.
    """
    rcp, rshift, bias, cmpl, x_max = spc.barrett_planes(freq, start,
                                                        prob_bits)
    e = update.EncEntry(rcp=rcp, rshift=rshift, bias=bias, cmpl=cmpl,
                        x_max=x_max)
    s, recs = update.encode_step(st.s, e)
    buf, ptr = st.buf, st.ptr
    for byte, cond in recs:
        buf, ptr = _emit_backward(buf, ptr, byte, cond)
    return StackState(s, buf, ptr, st.underflow)


def pop_update(st: StackState, slot: jax.Array, start: jax.Array,
               freq: jax.Array, prob_bits: int = C.PROB_BITS) -> StackState:
    """Finish a pop once the symbol is known: the decoder state update plus
    the guarded forward refill (reads past the stream end inject 0 and flag
    ``underflow`` — shared semantics with ``coder.decode_get`` and the
    kernels' ``masked_refill``)."""
    lanes, cap = st.buf.shape
    lane_idx = jnp.arange(lanes)
    s = (freq.astype(_U32) * (st.s >> prob_bits)
         + slot - start.astype(_U32))
    ptr, under = st.ptr, st.underflow
    for _ in range(C.MAX_RENORM_STEPS):
        cond = s < _U32(C.RANS_L)
        byte, oob = _read_byte(st.buf, lane_idx, ptr, cap)
        under = under | (cond & oob)
        s = jnp.where(cond, (s << C.RENORM_SHIFT) | byte, s)
        ptr = ptr + cond.astype(_I32)
    return StackState(s, st.buf, ptr, under)


def stack_slot(st: StackState, prob_bits: int = C.PROB_BITS) -> jax.Array:
    """The per-lane low-bits slot the next pop inverts."""
    return st.s & _U32((1 << prob_bits) - 1)


# ---------------------------------------------------------------------------
# codec combinators
# ---------------------------------------------------------------------------

def NonUniform(enc_statfun, dec_statfun,
               prob_bits: int = C.PROB_BITS) -> Codec:
    """Craystack's primitive codec over statfuns in the fixed-point domain.

    ``enc_statfun(x) -> (start, freq)`` maps per-lane symbols to their CDF
    interval (uint32, mass ``2**prob_bits``); ``dec_statfun(slot) -> x``
    inverts a slot to the symbol whose interval contains it.  The pop
    re-derives ``(start, freq)`` through ``enc_statfun`` so both directions
    consume one statfun — push/pop inverse-ness reduces to the interval
    identity ``start <= slot < start + freq``.
    """
    def push(st: StackState, x) -> StackState:
        start, freq = enc_statfun(x)
        return push_with(st, start, freq, prob_bits)

    def pop(st: StackState):
        slot = stack_slot(st, prob_bits)
        x = dec_statfun(slot)
        start, freq = enc_statfun(x)
        return pop_update(st, slot, start, freq, prob_bits), x

    return Codec(push=push, pop=pop)


def Uniform(bits: int, prob_bits: int = C.PROB_BITS) -> Codec:
    """Table-free uniform codec over ``2**bits`` symbols: every symbol owns
    an equal ``2**(prob_bits - bits)`` slice of the slot space.  The exact
    codec for equal-mass prior bins (a standard-normal prior over its own
    equal-mass quantile bins IS uniform — DESIGN.md §12)."""
    if not 0 < bits <= prob_bits:
        raise ValueError(f"Uniform bits must be in (0, {prob_bits}], "
                         f"got {bits}")
    shift = prob_bits - bits

    def enc_statfun(x):
        x = x.astype(_U32)
        return x << shift, jnp.full_like(x, _U32(1 << shift))

    def dec_statfun(slot):
        return (slot >> shift).astype(_I32)

    return NonUniform(enc_statfun, dec_statfun, prob_bits)


def Categorical(freq: jax.Array, cdf: jax.Array,
                prob_bits: int = C.PROB_BITS,
                backend: str = "coder") -> Codec:
    """Codec over quantized ``(freq, cdf)`` planes (``spc.quantize_probs``
    / ``spc.freq_cdf_from_probs`` output), shared ``(K,)`` or per-lane
    ``(lanes, K)``.

    ``backend="coder"`` inverts slots with the single-source
    ``core.search.find_symbol``; ``backend="kernel"`` pops through the
    Pallas per-step decode kernel (``kernels.rans_decode_step``) — the
    same kernel the fused serve path scans, so stack pops are available on
    the accelerated path too.  Both are bit-identical (shared search and
    refill cores) and both flag stream exhaustion.
    """
    if backend not in ("coder", "kernel"):
        raise ValueError(f"unknown Categorical backend {backend!r}")
    k = freq.shape[-1]

    def enc_statfun(x):
        return _gather(cdf[..., :-1], x), _gather(freq, x)

    def push(st: StackState, x) -> StackState:
        start, f = enc_statfun(x)
        return push_with(st, start, f, prob_bits)

    if backend == "kernel":
        from repro.kernels.rans_decode import rans_decode_step

        def pop(st: StackState):
            s, ptr, x, _, u = rans_decode_step(
                st.buf.T, st.s, st.ptr, freq, cdf, prob_bits=prob_bits)
            under = st.underflow | (u > 0)
            return StackState(s, st.buf, ptr, under), x

        return Codec(push=push, pop=pop)

    def pop(st: StackState):
        slot = stack_slot(st, prob_bits)
        x, _ = search.find_symbol(cdf, k, slot)
        start, f = enc_statfun(x)
        return pop_update(st, slot, start, f, prob_bits), x

    return Codec(push=push, pop=pop)


def from_tableset(tbl: spc.TableSet, prob_bits: int = C.PROB_BITS,
                  backend: str = "coder") -> Codec:
    """Codec over a full :class:`repro.core.spc.TableSet` — the batch
    coder's table object, reused as a stack codec."""
    return Categorical(tbl.freq, tbl.cdf, prob_bits, backend=backend)


def serial(codecs) -> Codec:
    """Compose codecs sequentially: ``pop`` yields symbols in list order,
    so ``push`` runs in *reverse* order (LIFO stack discipline — craystack's
    ``serial``).  Symbols travel as a tuple matching ``codecs``."""
    codecs = list(codecs)

    def push(st: StackState, xs) -> StackState:
        if len(xs) != len(codecs):
            raise ValueError(f"serial push got {len(xs)} symbols for "
                             f"{len(codecs)} codecs")
        for codec, x in reversed(list(zip(codecs, xs))):
            st = codec.push(st, x)
        return st

    def pop(st: StackState):
        xs = []
        for codec in codecs:
            st, x = codec.pop(st)
            xs.append(x)
        return st, tuple(xs)

    return Codec(push=push, pop=pop)


def substack(codec: Codec, idx) -> Codec:
    """Run ``codec`` on the lane subset ``idx`` only (shape-splitting: each
    lane owns an independent state/stream row, so a lane-slice of the stack
    is itself a stack).  Other lanes are untouched bit-for-bit."""
    idx = jnp.asarray(idx, _I32)

    def view(st: StackState) -> StackState:
        return StackState(st.s[idx], st.buf[idx], st.ptr[idx],
                          st.underflow[idx])

    def merge(st: StackState, sub: StackState) -> StackState:
        return StackState(st.s.at[idx].set(sub.s),
                          st.buf.at[idx].set(sub.buf),
                          st.ptr.at[idx].set(sub.ptr),
                          st.underflow.at[idx].set(sub.underflow))

    def push(st: StackState, x) -> StackState:
        return merge(st, codec.push(view(st), x))

    def pop(st: StackState):
        sub, x = codec.pop(view(st))
        return merge(st, sub), x

    return Codec(push=push, pop=pop)


# ---------------------------------------------------------------------------
# array codecs: scan a (lanes, T) symbol block through per-position tables
# ---------------------------------------------------------------------------

def _position_tables(freq: jax.Array, cdf: jax.Array, t_len: int) -> bool:
    # leading-T contract, same as coder.is_per_position: a (T, K) /
    # (T, lanes, K) layout is per-position exactly when its leading dim
    # matches the block length (cdf carries the matching K+1 trailing dim)
    del cdf
    return freq.ndim >= 2 and freq.shape[0] == t_len


def push_symbols(st: StackState, x: jax.Array, freq: jax.Array,
                 cdf: jax.Array,
                 prob_bits: int = C.PROB_BITS) -> StackState:
    """Push a ``(lanes, T)`` symbol block; position tables are shared
    ``(K,)``, per-position ``(T, K)`` or per-position-per-lane
    ``(T, lanes, K)``.  Pushed in reverse position order (one reverse
    ``lax.scan``) so :func:`pop_symbols` pops positions forward — the array
    analogue of ``coder.encode`` against the live stack."""
    t_len = x.shape[1]
    per_position = _position_tables(freq, cdf, t_len)

    def step(carry, xs):
        if per_position:
            x_t, f_t, c_t = xs
        else:
            x_t, f_t, c_t = xs, freq, cdf
        start = _gather(c_t[..., :-1], x_t)
        f = _gather(f_t, x_t)
        return push_with(carry, start, f, prob_bits), None

    xs = (x.T, freq, cdf) if per_position else x.T
    st, _ = jax.lax.scan(step, st, xs, reverse=True)
    return st


def pop_symbols(st: StackState, n: int, freq: jax.Array, cdf: jax.Array,
                prob_bits: int = C.PROB_BITS, backend: str = "coder"):
    """Pop ``n`` symbols per lane; returns ``(state, symbols (lanes, n))``.

    Table layouts as in :func:`push_symbols`.  ``backend="kernel"`` scans
    the Pallas per-step decode kernel (the fused serve path's primitive);
    both backends are bit-identical.  Pops never write ``buf``, so the
    scan carries only ``(s, ptr, underflow)`` and the kernel path
    transposes the buffer once, not per step.
    """
    if backend not in ("coder", "kernel"):
        raise ValueError(f"unknown pop_symbols backend {backend!r}")
    per_position = _position_tables(freq, cdf, n)
    k = freq.shape[-1]
    buf = st.buf
    buf_t = buf.T if backend == "kernel" else None

    def step(carry, xs):
        s, ptr, under = carry
        f_t, c_t = xs if per_position else (freq, cdf)
        if backend == "kernel":
            from repro.kernels.rans_decode import rans_decode_step
            s, ptr, x, _, u = rans_decode_step(
                buf_t, s, ptr, f_t, c_t, prob_bits=prob_bits)
            return (s, ptr, under | (u > 0)), x
        sub = StackState(s, buf, ptr, under)
        slot = stack_slot(sub, prob_bits)
        x, _ = search.find_symbol(c_t, k, slot)
        sub = pop_update(sub, slot, _gather(c_t[..., :-1], x),
                         _gather(f_t, x), prob_bits)
        return (sub.s, sub.ptr, sub.underflow), x

    xs = (freq, cdf) if per_position else None
    (s, ptr, under), sym_t = jax.lax.scan(
        step, (st.s, st.ptr, st.underflow), xs, length=n)
    return StackState(s, buf, ptr, under), sym_t.T


# ---------------------------------------------------------------------------
# observation codecs: continuous densities -> fixed-point bin codecs
# ---------------------------------------------------------------------------

def std_gaussian_bins(n_bins: int):
    """Equal-mass bins of the standard normal: ``n_bins - 1`` interior
    edges at the quantiles and the per-bin mass centres.  The canonical
    BB-ANS latent discretization: a ``N(0, 1)`` prior over these bins is
    *exactly* uniform, so the top-level prior codec is :func:`Uniform`."""
    i = np.arange(1, n_bins) / n_bins
    edges = jax.scipy.special.ndtri(jnp.asarray(i, jnp.float32))
    centres = jax.scipy.special.ndtri(
        jnp.asarray((np.arange(n_bins) + 0.5) / n_bins, jnp.float32))
    return edges, centres


def gaussian_bin_probs(mu: jax.Array, sigma: jax.Array,
                       edges: jax.Array) -> jax.Array:
    """``N(mu, sigma)`` mass per bin of ``edges`` (batched over leading
    dims; bins on the trailing axis; endpoint bins take the tails)."""
    z = (edges - mu[..., None]) / sigma[..., None]
    cdf = jax.scipy.special.ndtr(z.astype(jnp.float32))
    ones = jnp.ones(cdf.shape[:-1] + (1,), jnp.float32)
    cdf = jnp.concatenate([jnp.zeros_like(ones), cdf, ones], axis=-1)
    return cdf[..., 1:] - cdf[..., :-1]


def DiagGaussian(mu: jax.Array, sigma: jax.Array, edges: jax.Array,
                 prob_bits: int = C.PROB_BITS,
                 backend: str = "coder") -> Codec:
    """Diagonal-Gaussian codec over fixed bin edges: the bits-back
    *posterior* codec (pop a latent bin index against ``q(z|x)``, push it
    back against the same ``q`` on decode).  ``mu``/``sigma`` are per-lane
    ``(lanes,)`` (or any batch matching the lane axis); probabilities ride
    the BF16 storage + quantization path of :mod:`repro.core.spc`."""
    probs = gaussian_bin_probs(mu, sigma, edges)
    freq, cdf = spc.freq_cdf_from_probs(spc.store_bf16(probs), prob_bits)
    return Categorical(freq, cdf, prob_bits, backend=backend)


def logistic_bin_probs(mu: jax.Array, log_s: jax.Array,
                       n_bins: int) -> jax.Array:
    """Discretized-logistic mass over ``n_bins`` equal pixel bins of
    ``[-1, 1]`` (PixelCNN++-style observation model: interior edges through
    the logistic CDF, endpoint bins take the open tails)."""
    i = np.arange(1, n_bins) / n_bins
    edges = jnp.asarray(2.0 * i - 1.0, jnp.float32)
    inv_s = jnp.exp(-log_s.astype(jnp.float32))
    z = (edges - mu[..., None].astype(jnp.float32)) * inv_s[..., None]
    cdf = jax.nn.sigmoid(z)
    ones = jnp.ones(cdf.shape[:-1] + (1,), jnp.float32)
    cdf = jnp.concatenate([jnp.zeros_like(ones), cdf, ones], axis=-1)
    return cdf[..., 1:] - cdf[..., :-1]


def DiscretizedLogistic(mu: jax.Array, log_s: jax.Array, n_bins: int,
                        prob_bits: int = C.PROB_BITS,
                        backend: str = "coder") -> Codec:
    """Discretized-logistic observation codec over ``n_bins`` pixel levels
    in normalized ``[-1, 1]`` units — the ``p(x|z)`` codec of the
    bits-back VAE."""
    probs = logistic_bin_probs(mu, log_s, n_bins)
    freq, cdf = spc.freq_cdf_from_probs(spc.store_bf16(probs), prob_bits)
    return Categorical(freq, cdf, prob_bits, backend=backend)
