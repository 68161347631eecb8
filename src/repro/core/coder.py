"""Vectorized multi-lane rANS coder (the RAS fabric, TPU-native).

This is the paper's Fig. 2 middle block re-derived for a SIMD machine:

  * **multi-lane fabric** (Sec. III): ``lanes`` independent rANS states are
    updated in lockstep as vectors; each lane owns a private byte stream
    (the RTL's per-lane MS/low-bit state memories become a ``(lanes, cap)``
    buffer with per-lane write pointers);
  * **two-stage update** (Sec. IV-B): the quotient path ``a1 = (s//f) << n``
    and remainder path ``a2 = (s mod f) + C`` are independent vector ops —
    we use the algebraically identical ryg form ``s + bias + q * cmpl``
    (bias folds C and the f==1 corner, cmpl = 2**n - f) so the hot loop is
    one mulhi, one shift, one madd;
  * **unified div/mod datapath** (Sec. IV-A): division is Barrett
    multiply-high against the SPC-precomputed reciprocal — exact for every
    state < 2**31 (property-swept in tests), no integer divide on the hot
    path;
  * **byte-level renormalization**: the data-dependent while-loop is a fixed
    ``MAX_RENORM_STEPS``(=2)-stage masked pipeline (provably sufficient,
    see core/constants.py) — the TPU analogue of the paper's staged renorm;
  * **prediction-guided decoding** (Sec. IV-C): window-gated binary search
    with verified fallback, plus the beyond-paper candidate (model-top-k)
    speculation; both leave the bitstream untouched and are instrumented to
    reproduce Fig. 4(b)'s search-step counts.

Bit-exactness contract: for identical tables, :func:`encode` produces byte
streams identical to ``core.golden`` / ``core.python_baseline``, and
:func:`decode` inverts them exactly.  Everything is jit/scan-compatible.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import constants as C
from repro.core import search, update
from repro.core.bitstream import (ChunkedLanes, EncodedLanes,  # noqa: F401
                                  compact_records)
from repro.core.search import take_gather as _gather
from repro.core.spc import TableSet
from repro.core.update import barrett_div, umulhi32  # noqa: F401  (re-export)

_U32 = jnp.uint32
_U8 = jnp.uint8
_I32 = jnp.int32


# ---------------------------------------------------------------------------
# encoder — the two-stage update itself lives in core/update.py (single
# source, shared verbatim with the Pallas encode kernel); this layer owns
# the per-lane backward byte buffers the records land in.
# ---------------------------------------------------------------------------

class EncState(NamedTuple):
    """Multi-lane encoder state.  ``buf[lane, ptr[lane]:]`` is the stream
    (written backward so the decoder reads forward — rANS is LIFO)."""

    s: jax.Array     # (lanes,) uint32
    buf: jax.Array   # (lanes, cap) uint8
    ptr: jax.Array   # (lanes,) int32, next free slot - 1 is at ptr-1


def encoder_init(lanes: int, cap: int) -> EncState:
    return EncState(s=jnp.full((lanes,), C.RANS_L, _U32),
                    buf=jnp.zeros((lanes, cap), _U8),
                    ptr=jnp.full((lanes,), cap, _I32))


def _emit_backward(buf, ptr, byte, cond):
    """Masked one-byte backward emit; non-emitting lanes scatter out of
    bounds and are dropped (the RTL's lane clock gating).  Lanes whose
    cursor ran past the buffer head (cap overflow) also hit the drop
    sentinel — a negative scatter index would *wrap* under numpy semantics
    and silently corrupt the stream tail.  The cursor keeps decrementing so
    the caller can report the true byte need and flag the overflow."""
    lanes, cap = buf.shape
    lane_idx = jnp.arange(lanes)
    widx = jnp.where(cond & (ptr > 0), ptr - 1, cap)
    buf = buf.at[lane_idx, widx].set(byte, mode="drop")
    return buf, ptr - cond.astype(_I32)


def encode_put(st: EncState, x: jax.Array, tbl: TableSet) -> EncState:
    """Push one symbol per lane (Eq. 1 + two-stage renorm).

    Delegates the staged renorm + two-path update to
    :func:`repro.core.update.encode_step` (the single-source core shared
    with the Pallas kernel) and lands the emitted records backward in the
    per-lane buffers.
    """
    e = update.gather_encode_entry(tbl, x)
    s, recs = update.encode_step(st.s, e)
    buf, ptr = st.buf, st.ptr
    for byte, cond in recs:
        buf, ptr = _emit_backward(buf, ptr, byte, cond)
    return EncState(s, buf, ptr)


def encoder_flush(st: EncState) -> EncState:
    """Write the 4-byte big-endian final state header (read first on decode)."""
    s, buf, ptr = st.s, st.buf, st.ptr
    true = jnp.ones_like(s, bool)
    for shift in (0, 8, 16, 24):
        buf, ptr = _emit_backward(
            buf, ptr, ((s >> shift) & _U32(0xFF)).astype(_U8), true)
    return EncState(s, buf, ptr)


def default_cap(n_symbols: int) -> int:
    # worst case 2 bytes/symbol + 4-byte state header, padded for alignment
    return 2 * n_symbols + 8


@functools.partial(jax.jit, static_argnames=("cap",))
def encode_records(symbols: jax.Array, tbl: TableSet,
                   cap: int | None = None) -> EncodedLanes:
    """Scatter-free encode: §Perf hillclimb H2 (see EXPERIMENTS.md).

    The scan carries only the lane states and *stacks* fixed-shape renorm
    records as scan outputs (a sequential write, not a scatter); one
    vectorized compaction pass builds the byte streams.  Bit-identical to
    :func:`encode` (same emission order, same compaction as the Pallas
    kernel path).
    """
    lanes, t_len = symbols.shape
    cap = default_cap(t_len) if cap is None else cap
    per_position = tbl.freq.ndim in (2, 3) and tbl.freq.shape[0] == t_len

    def step(s, xs):
        if per_position:
            x_t, tbl_t = xs
        else:
            x_t, tbl_t = xs, tbl
        e = update.gather_encode_entry(tbl_t, x_t)
        s, recs = update.encode_step(s, e)
        (b0, c0), (b1, c1) = recs
        return s, (b0, c0, b1, c1)

    xs = (symbols.T, tbl) if per_position else symbols.T
    s0 = jnp.full((lanes,), C.RANS_L, _U32)
    s, (b0, c0, b1, c1) = jax.lax.scan(step, s0, xs, reverse=True)
    # stack into kernel-compatible (T, 2, lanes) records and compact
    bytes_rec = jnp.stack([b0, b1], axis=1)
    mask_rec = jnp.stack([c0, c1], axis=1).astype(_U8)
    return compact_records(bytes_rec, mask_rec, s, cap)


def _vary_like(tree, like: jax.Array):
    """Mark a fresh scan carry as varying over the mesh axes ``like`` varies
    over, so that inside ``jax.shard_map`` its type matches what the scan
    body returns (a no-op outside a mesh)."""
    axes = tuple(jax.typeof(like).vma)
    if not axes:
        return tree
    return jax.tree.map(lambda a: jax.lax.pcast(a, axes, to="varying"),
                        tree)


@functools.partial(jax.jit, static_argnames=("cap",))
def encode(symbols: jax.Array, tbl: TableSet,
           cap: int | None = None) -> EncodedLanes:
    """Encode ``(lanes, T)`` int symbols against shared tables ``(K,)``.

    Per-position tables: pass a TableSet whose fields have a leading T dim,
    matched to ``symbols.shape[1]`` (all lanes share position tables — the
    neural-prior layout where the model emits one distribution per step).
    """
    lanes, t_len = symbols.shape
    cap = default_cap(t_len) if cap is None else cap
    # per-position tables: leading T dim, rows either shared (T, K) or
    # per-lane (T, lanes, K) — the neural-prior layouts.
    per_position = tbl.freq.ndim in (2, 3) and tbl.freq.shape[0] == t_len

    def step(st, xs):
        if per_position:
            x_t, tbl_t = xs
            return encode_put(st, x_t, tbl_t), None
        return encode_put(st, xs, tbl), None

    xs = (symbols.T, tbl) if per_position else symbols.T  # scan over T
    st0 = _vary_like(encoder_init(lanes, cap), symbols)
    st, _ = jax.lax.scan(step, st0, xs, reverse=True)
    st = encoder_flush(st)
    # a cursor past the buffer head means the stream did not fit `cap`:
    # the writes were dropped (never wrapped), length reports the need.
    return EncodedLanes(buf=st.buf, start=jnp.maximum(st.ptr, 0),
                        length=jnp.asarray(cap, _I32) - st.ptr,
                        overflow=st.ptr < 0)


# ---------------------------------------------------------------------------
# chunked streaming encode (independent per-chunk flush -> parallel decode)
# ---------------------------------------------------------------------------

def num_chunks(n_symbols: int, chunk_size: int) -> int:
    """Chunk count covering ``n_symbols`` (last chunk may be ragged)."""
    if chunk_size <= 0:
        raise ValueError(f"chunk_size must be positive, got {chunk_size}")
    return -(-n_symbols // chunk_size)


def chunk_lengths(n_symbols: int, chunk_size: int) -> list[int]:
    """Per-chunk symbol counts; all ``chunk_size`` except a ragged tail."""
    n = num_chunks(n_symbols, chunk_size)
    return [min(chunk_size, n_symbols - c * chunk_size) for c in range(n)]


def is_per_position(tbl: TableSet, t_len: int) -> bool:
    """True when the TableSet carries a leading per-position T dim."""
    return tbl.freq.ndim in (2, 3) and tbl.freq.shape[0] == t_len


def slice_tables(tbl: TableSet, t0: int, t1: int) -> TableSet:
    """Per-position table rows for the position range [t0, t1)."""
    return jax.tree.map(lambda a: a[t0:t1], tbl)


def chunk_tables(tbl: TableSet, n_full: int, chunk_size: int) -> TableSet:
    """Per-position tables -> chunk-major ``(n_full, chunk_size, ...)`` form
    (the layout both the vmap and shard_map chunk paths map over)."""
    return jax.tree.map(
        lambda a: a[:n_full * chunk_size].reshape(
            (n_full, chunk_size) + a.shape[1:]), tbl)


def chunk_encoded(enc: ChunkedLanes, c) -> EncodedLanes:
    """View chunk ``c`` as a standalone :class:`EncodedLanes`."""
    return EncodedLanes(buf=enc.buf[c], start=enc.start[c],
                        length=enc.length[c],
                        overflow=None if enc.overflow is None
                        else enc.overflow[c])


def encode_chunked(symbols: jax.Array, tbl: TableSet, chunk_size: int,
                   cap: int | None = None) -> ChunkedLanes:
    """Encode ``(lanes, T)`` as independent fixed-size chunks.

    Every chunk gets its own flush (4-byte state header) so the produced
    streams decode independently — the interleaved-ANS construction that
    turns the LIFO coder into a parallel/streaming one.  Bit-exactness
    contract: chunk ``c``'s bytes equal ``encode(symbols[:, c*S:(c+1)*S],
    tbl_c)`` exactly, where ``tbl_c`` is the matching per-position table
    slice (or the shared table).  The final chunk may be ragged
    (``T % chunk_size`` symbols); all chunks share one ``cap`` so the result
    is a single dense ``(n_chunks, lanes, cap)`` buffer.
    """
    lanes, t_len = symbols.shape
    n_total = num_chunks(t_len, chunk_size)
    n_full, tail_len = divmod(t_len, chunk_size)
    cap = default_cap(min(chunk_size, t_len)) if cap is None else cap
    per_position = is_per_position(tbl, t_len)

    if t_len == 0:  # degenerate: zero chunks, empty (0, lanes, cap) stream
        z = jnp.zeros((0, lanes), _I32)
        return ChunkedLanes(buf=jnp.zeros((0, lanes, cap), _U8),
                            start=z, length=z,
                            overflow=jnp.zeros((0, lanes), bool))

    parts = []
    if n_full:
        full = symbols[:, :n_full * chunk_size]
        full = full.reshape(lanes, n_full, chunk_size).swapaxes(0, 1)
        if per_position:
            enc = jax.vmap(lambda s, tb: encode(s, tb, cap=cap))(
                full, chunk_tables(tbl, n_full, chunk_size))
        else:
            enc = jax.vmap(lambda s: encode(s, tbl, cap=cap))(full)
        parts.append(enc)
    if tail_len:
        tbl_tail = (slice_tables(tbl, n_full * chunk_size, t_len)
                    if per_position else tbl)
        enc_tail = encode(symbols[:, n_full * chunk_size:], tbl_tail, cap=cap)
        parts.append(jax.tree.map(lambda a: a[None], enc_tail))
    out = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)
    assert out.buf.shape[0] == n_total
    return ChunkedLanes(buf=out.buf, start=out.start, length=out.length,
                        overflow=out.overflow)


def decode_chunked(chunks: ChunkedLanes, n_symbols: int, tbl: TableSet,
                   chunk_size: int, prob_bits: int = C.PROB_BITS,
                   use_lut: bool = False, predictor=None,
                   lane_probes: bool = False,
                   candidates: jax.Array | None = None):
    """Decode a chunked stream; returns (symbols (lanes, T), avg_probes).

    Full-size chunks decode in parallel (vmap over the chunk axis — see
    ``repro.parallel.chunked`` for the multi-device shard_map version); the
    ragged tail, if any, decodes standalone.  Bit-exact inverse of
    :func:`encode_chunked`.  ``predictor`` drives prediction-guided search
    inside every chunk (context resets at chunk boundaries — the chunks are
    independent streams); ``lane_probes`` also returns the per-lane probe
    totals summed across chunks.  ``candidates`` is an optional
    ``(T, lanes, topk)`` model-top-k candidate plane, cut chunk-major like
    the per-position tables (rows [c*S, c*S+n) speculate chunk ``c``).
    """
    n_total = num_chunks(n_symbols, chunk_size)
    if chunks.buf.shape[0] != n_total:
        raise ValueError(
            f"stream has {chunks.buf.shape[0]} chunks but n_symbols="
            f"{n_symbols} at chunk_size={chunk_size} implies {n_total}; "
            "decode with the chunk_size the stream was encoded with")
    n_full, tail_len = divmod(n_symbols, chunk_size)
    per_position = is_per_position(tbl, n_symbols)
    if candidates is not None and candidates.shape[-1] == 0:
        candidates = None

    if n_symbols == 0:  # degenerate: no chunks to decode
        lanes = chunks.buf.shape[1] if chunks.buf.ndim == 3 else 0
        out = (jnp.zeros((lanes, 0), _I32), jnp.float32(0.0))
        return out + (jnp.zeros((lanes,), _I32),) if lane_probes else out

    syms, probe_sums, lane_sums, unders = [], [], [], []
    if n_full:
        sub = jax.tree.map(lambda a: a[:n_full], chunks)
        cand_full = (candidates[:n_full * chunk_size].reshape(
            (n_full, chunk_size) + candidates.shape[1:])
            if candidates is not None else None)
        if per_position:
            dec = jax.vmap(
                lambda e, tb, cd: decode(EncodedLanes(*e), chunk_size, tb,
                                         prob_bits, predictor=predictor,
                                         use_lut=use_lut,
                                         lane_probes=lane_probes,
                                         candidates=cd,
                                         return_exhausted=True))(
                sub, chunk_tables(tbl, n_full, chunk_size), cand_full)
        else:
            dec = jax.vmap(
                lambda e, cd: decode(EncodedLanes(*e), chunk_size, tbl,
                                     prob_bits, predictor=predictor,
                                     use_lut=use_lut,
                                     lane_probes=lane_probes,
                                     candidates=cd,
                                     return_exhausted=True))(sub, cand_full)
        if lane_probes:
            sym_full, probes_full, lp_full, und_full = dec
            lane_sums.append(jnp.sum(lp_full, axis=0))
        else:
            sym_full, probes_full, und_full = dec  # (n_full, lanes, S), ...
        unders.append(jnp.any(und_full, axis=0))
        lanes = sym_full.shape[1]
        syms.append(sym_full.swapaxes(0, 1).reshape(
            lanes, n_full * chunk_size))
        probe_sums.append(jnp.sum(probes_full) * chunk_size)
    if tail_len:
        tbl_tail = (slice_tables(tbl, n_full * chunk_size, n_symbols)
                    if per_position else tbl)
        dec_tail = decode(
            chunk_encoded(chunks, n_full), tail_len, tbl_tail, prob_bits,
            predictor=predictor, use_lut=use_lut, lane_probes=lane_probes,
            candidates=(candidates[n_full * chunk_size:]
                        if candidates is not None else None),
            return_exhausted=True)
        if lane_probes:
            sym_tail, probes_tail, lp_tail, und_tail = dec_tail
            lane_sums.append(lp_tail)
        else:
            sym_tail, probes_tail, und_tail = dec_tail
        unders.append(und_tail)
        syms.append(sym_tail)
        probe_sums.append(probes_tail * tail_len)
    under = functools.reduce(jnp.logical_or, unders)
    _check_exhausted(under, "decode_chunked")
    out = jnp.concatenate(syms, axis=1)
    avg_probes = sum(probe_sums) / n_symbols
    if lane_probes:
        return out, avg_probes, sum(lane_sums)
    return out, avg_probes


# ---------------------------------------------------------------------------
# decoder
# ---------------------------------------------------------------------------

class StreamExhaustedError(ValueError):
    """Decode read past the end of a lane's byte window.

    Raised on every *host* decode path when more symbols are requested than
    the stream encodes (or the stream was truncated).  Inside traced
    contexts the condition travels as the per-lane ``DecState.underflow``
    flag instead (checked by the caller once values are concrete)."""


def _check_exhausted(underflow, where: str = "decode") -> None:
    """Host-side gate on the per-lane underflow flag (no-op on tracers)."""
    if underflow is None or isinstance(underflow, jax.core.Tracer):
        return
    u = np.asarray(underflow)
    if u.any():
        bad = np.nonzero(u.reshape(-1))[0].tolist()
        raise StreamExhaustedError(
            f"{where}: {int(u.sum())} lane stream(s) exhausted mid-decode "
            f"(flat lane indices {bad[:16]}{'...' if len(bad) > 16 else ''}) "
            "— more symbols were requested than the stream encodes, or the "
            "stream is truncated; symbols past that point are garbage")


class DecState(NamedTuple):
    s: jax.Array    # (lanes,) uint32
    ptr: jax.Array  # (lanes,) int32 read cursor into buf
    # (lanes,) bool, True once a lane read past its byte window.  Optional
    # (None == all clear) so positional DecState(s, ptr) callers keep working.
    underflow: jax.Array | None = None


def _read_byte(buf, lane_idx, ptr, cap):
    """One guarded forward byte read: out-of-window reads yield 0 (matching
    the kernels' one-hot gather semantics) and report the violation."""
    oob = (ptr < 0) | (ptr >= cap)
    byte = buf[lane_idx, jnp.clip(ptr, 0, cap - 1)].astype(_U32)
    return jnp.where(oob, _U32(0), byte), oob


def decoder_init(enc: EncodedLanes) -> DecState:
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
    return DecState(s=s, ptr=ptr, underflow=under)


def find_symbol(tbl: TableSet, slot: jax.Array,
                mu: jax.Array | None = None,
                delta: int | jax.Array | None = None,
                candidates: jax.Array | None = None):
    """State-to-symbol inversion (Sec. IV-C) — delegates to ``core.search``.

    The search itself (window gating, candidate speculation, fixed-depth
    binary search) and the canonical Fig. 4(b) probe accounting live in
    :mod:`repro.core.search`, shared verbatim with the Pallas decode kernel.
    """
    return search.find_symbol(tbl.cdf, tbl.alphabet_size, slot,
                              mu=mu, delta=delta, candidates=candidates)


def decode_get(st: DecState, buf: jax.Array, tbl: TableSet,
               prob_bits: int = C.PROB_BITS,
               mu: jax.Array | None = None,
               delta: int | jax.Array | None = None,
               candidates: jax.Array | None = None,
               lut: jax.Array | None = None):
    """Pop one symbol per lane.  Returns (state', symbol, probes).

    ``lut``: optional 2**prob_bits slot->symbol table (spc.decode_lut) —
    beyond-paper O(1) inversion for *static* tables: one gather replaces
    the whole CDF search (§Perf hillclimb H3).
    """
    lanes, cap = buf.shape
    lane_idx = jnp.arange(lanes)
    mask = _U32((1 << prob_bits) - 1)
    s, ptr = st.s, st.ptr

    slot = s & mask
    if lut is not None:
        x = lut[slot].astype(_I32)
        probes = jnp.ones((lanes,), _I32)
    else:
        x, probes = find_symbol(tbl, slot, mu=mu, delta=delta,
                                candidates=candidates)
    f = _gather(tbl.freq, x)
    start = _gather(tbl.cdf[..., :-1], x)
    s = f * (s >> prob_bits) + slot - start
    under = (jnp.zeros((lanes,), bool) if st.underflow is None
             else st.underflow)
    # fixed 2-step masked byte refill; a refill that would read past the
    # window injects 0 and raises the lane's underflow flag instead of
    # silently re-reading the final byte.
    for _ in range(C.MAX_RENORM_STEPS):
        cond = s < _U32(C.RANS_L)
        byte, oob = _read_byte(buf, lane_idx, ptr, cap)
        under = under | (cond & oob)
        s = jnp.where(cond, (s << C.RENORM_SHIFT) | byte, s)
        ptr = ptr + cond.astype(_I32)
    return DecState(s, ptr, under), x, probes


@functools.partial(jax.jit, static_argnames=("n_symbols", "prob_bits",
                                             "predictor", "use_lut",
                                             "lane_probes"))
def _decode_traced(enc: EncodedLanes, n_symbols: int, tbl: TableSet,
                   prob_bits: int = C.PROB_BITS, predictor=None,
                   use_lut: bool = False, lane_probes: bool = False,
                   candidates: jax.Array | None = None):
    """Decode ``n_symbols`` per lane.  Returns (symbols (lanes,T), avg_probes).

    ``predictor`` is one of core.predictors (hashable NamedTuple of static
    config) driving prediction-guided decoding; None = baseline full binary
    search.  Per-position tables: TableSet with leading T dim as in encode.
    ``use_lut``: static tables only — O(1) slot->symbol inversion.
    ``lane_probes``: also return the per-lane probe totals ``(lanes,)`` int32
    — the raw Fig. 4(b) counters the cross-backend differential tests pin.
    ``candidates``: optional ``(T, lanes, topk)`` plane of model-top-k trial
    symbols (the serve pipeline's candidate speculation), scanned row-by-row
    into :func:`decode_get` — the pure-JAX reference for the kernel's
    candidate-plane input (topk == 0 disables speculation).
    """
    lanes = enc.buf.shape[0]
    per_position = (tbl.freq.ndim in (2, 3)
                    and tbl.freq.shape[0] == n_symbols)
    if candidates is not None and candidates.shape[-1] == 0:
        candidates = None
    if candidates is not None and candidates.shape[:2] != (n_symbols, lanes):
        raise ValueError(
            f"candidate planes must be (T, lanes, topk)=({n_symbols}, "
            f"{lanes}, *); got {candidates.shape}")
    ctx0 = predictor.init(lanes) if predictor is not None else jnp.zeros((lanes, 0), _I32)
    lut = None
    if use_lut:
        assert not per_position, "LUT path requires a static table"
        if candidates is not None:
            raise ValueError("use_lut and candidate planes are exclusive: "
                             "the LUT already inverts in one probe")
        from repro.core.spc import decode_lut
        lut = decode_lut(tbl, prob_bits)

    def step(carry, xs):
        st, ctx = carry
        tbl_t, cand_t = xs
        t = tbl if not per_position else tbl_t
        if predictor is not None:
            pred = predictor.predict(ctx)
            cands = cand_t if cand_t is not None else pred.candidates
            st, x, probes = decode_get(st, enc.buf, t, prob_bits,
                                       mu=pred.mu, delta=pred.delta,
                                       candidates=cands)
            ctx = predictor.update(ctx, x)
        else:
            st, x, probes = decode_get(st, enc.buf, t, prob_bits, lut=lut,
                                       candidates=cand_t)
        return (st, ctx), (x, probes)

    xs = (tbl if per_position else None,
          candidates.astype(_I32) if candidates is not None else None)
    (st_f, _), (sym_t, probes_t) = jax.lax.scan(
        step, (decoder_init(enc), ctx0), xs, length=n_symbols)
    avg_probes = (jnp.mean(probes_t.astype(jnp.float32)) if n_symbols
                  else jnp.float32(0.0))
    if lane_probes:
        return sym_t.T, avg_probes, jnp.sum(probes_t, axis=0), st_f.underflow
    return sym_t.T, avg_probes, st_f.underflow


def decode(enc: EncodedLanes, n_symbols: int, tbl: TableSet,
           prob_bits: int = C.PROB_BITS, predictor=None,
           use_lut: bool = False, lane_probes: bool = False,
           candidates: jax.Array | None = None,
           return_exhausted: bool = False):
    """Host entry around :func:`_decode_traced`.

    Same return shape as before (``(symbols, avg_probes[, lane_probes])``)
    but raises :class:`StreamExhaustedError` when any lane decoded past the
    end of its byte window — unless ``return_exhausted`` is set, in which
    case the per-lane flag is appended instead (the traced-caller form:
    vmap/shard_map bodies cannot raise, so they thread the flag out).
    """
    out = _decode_traced(enc, n_symbols, tbl, prob_bits, predictor,
                         use_lut, lane_probes, candidates)
    *vals, under = out
    if return_exhausted:
        return (*vals, under)
    _check_exhausted(under)
    return tuple(vals)
