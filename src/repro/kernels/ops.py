"""jit'd public wrappers around the RAS Pallas kernels.

``rans_encode`` / ``rans_encode_chunked`` wrap the **fused-compaction**
encode kernel (``rans_encode_lanes``): the shared ``core.update`` two-stage
update runs in-kernel and the renorm bytes scatter straight into per-lane
output streams (in-kernel byte cursor — DESIGN.md §8), so the wrappers
return packed ``EncodedLanes``/``ChunkedLanes`` with **no host-side
``compact_records`` pass** and every encoded byte crosses HBM once.
Results are byte-identical to ``repro.core.coder.encode`` /
``encode_chunked`` (the pure-JAX records reference) and therefore to the
scalar golden reference, for static ``(K,)``, per-position ``(T, K)`` and
per-lane ``(T, lanes, K)`` TableSets.  The chunked encode is a single
``pallas_call`` (chunk grid axis with in-kernel state + cursor reset).
``rans_decode`` / ``rans_decode_chunked`` wrap the prediction-guided decode
kernel (static and adaptive TableSets plus ``(T, lanes, topk)`` model-top-k
candidate planes; symbols AND per-lane probe counters are bit-identical to
the pure-JAX coder — both consume ``core.search``).  The chunked decode,
like the chunked encode, is a single ``pallas_call`` (chunk grid axis with
in-kernel state/pointer/context reset).  ``rans_decode_step`` (re-exported
from ``kernels.rans_decode``) is the fused serve decode's building block:
ONE symbol pop per lane with caller-threaded coder state, traced inside
the model scan of ``serve.compress`` (DESIGN.md §9).  ``spc_quantize``
wraps the mass-correction kernel.  Every kernel compiles on a TPU and runs
in the Pallas interpreter elsewhere (``kernels.common.interpret_mode``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import constants as C
# stream compaction lives in core (wire format); re-exported here for
# back-compat with the historical kernels-side import path.  The kernel
# encode wrappers below no longer call it — compaction is fused in-kernel
# (rans_encode_lanes) — but it remains the host-side half of the records
# *reference* path (rans_encode_records), which the fused path is
# differential-tested and benchmarked against.
from repro.core.bitstream import compact_records  # noqa: F401
from repro.core.bitstream import ContainerSlab
from repro.core.coder import (ChunkedLanes, EncodedLanes, _check_exhausted,
                              default_cap, num_chunks)
from repro.core.predictors import NeighborAverage
from repro.core.spc import TableSet, build_tables
from repro.kernels.rans_decode import (plan_slab_windows,  # noqa: F401
                                       rans_decode_lanes, rans_decode_slab,
                                       rans_decode_step)
from repro.kernels.rans_encode import (rans_encode_lanes,  # noqa: F401
                                       rans_encode_records)

import numpy as np


def rans_encode(symbols: jax.Array, tbl: TableSet,
                cap: int | None = None,
                prob_bits: int = C.PROB_BITS,
                lane_block: int = 128,
                t_block: int | None = None,
                scatter: str = "ring") -> EncodedLanes:
    """Kernel-backed multi-lane encode (bit-exact vs. core/golden).

    Fused datapath: ONE ``pallas_call`` returning finished wire-format
    streams — the in-kernel byte cursor scatters every renorm byte into
    its lane's stream as it is emitted, so there is no record-plane HBM
    round-trip and no host-side ``compact_records`` pass.  Static ``(K,)``
    and adaptive ``(T, K)`` / ``(T, lanes, K)`` TableSets are all encoded
    in-kernel (adaptive layouts block the T axis through VMEM —
    ``t_block``).  When the lane count does not tile the ``lane_block``
    grid the block collapses to one lane group (correctness over
    occupancy — the serve/parallel paths run narrow lane counts).
    """
    lanes, t_len = symbols.shape
    cap = default_cap(t_len) if cap is None else cap
    if t_len == 0:
        return _header_only_stream(lanes, cap)
    buf, start, length, overflow = rans_encode_lanes(
        symbols, tbl, cap=cap, prob_bits=prob_bits, lane_block=lane_block,
        t_block=t_block, scatter=scatter)
    return EncodedLanes(buf=buf[0], start=start[0], length=length[0],
                        overflow=overflow[0])


def _header_only_stream(lanes: int, cap: int) -> EncodedLanes:
    """The ``n_symbols == 0`` stream: 4 flush bytes of the initial state.

    Byte-identical to ``coder.encode`` on an empty symbol block (including
    the overflow-flagged ``cap < 4`` corner), built host-side — the kernel
    grid has no T blocks to run.
    """
    hdr = [(C.RANS_L >> sh) & 0xFF for sh in (0, 8, 16, 24)]
    buf = np.zeros((lanes, cap), np.uint8)
    p = cap
    for b in hdr:                   # backward emit with the drop sentinel
        if p > 0:
            buf[:, p - 1] = b
        p -= 1
    return EncodedLanes(buf=jnp.asarray(buf),
                        start=jnp.full((lanes,), max(p, 0), jnp.int32),
                        length=jnp.full((lanes,), cap - p, jnp.int32),
                        overflow=jnp.full((lanes,), p < 0))


def rans_encode_chunked(symbols: jax.Array, tbl: TableSet, chunk_size: int,
                        cap: int | None = None,
                        prob_bits: int = C.PROB_BITS,
                        lane_block: int = 128,
                        t_block: int | None = None,
                        scatter: str = "ring") -> ChunkedLanes:
    """Kernel-backed chunked encode (bit-exact vs. coder.encode_chunked).

    ONE ``pallas_call`` for the whole stream: the chunk axis is a grid
    dimension of the fused kernel (in-kernel per-chunk state + byte-cursor
    reset — no host-side loop of kernel launches and no host-side
    compaction), emitting every chunk's packed stream into one dense
    ``(n_chunks, lanes, cap)`` buffer with the chunk-aware cap
    (``default_cap(chunk_size)`` covers the worst case of every chunk,
    ragged tail included).  Static and per-position TableSets both encode
    in-kernel (per-position rows ride the chunk grid axis).  Overflow
    flags are per (chunk, lane) cell, identical to the records reference.
    """
    lanes, t_len = symbols.shape
    num_chunks(t_len, chunk_size)           # validates chunk_size > 0
    cap = default_cap(min(chunk_size, t_len)) if cap is None else cap
    if t_len == 0:                          # degenerate: zero chunks
        z = jnp.zeros((0, lanes), jnp.int32)
        return ChunkedLanes(buf=jnp.zeros((0, lanes, cap), jnp.uint8),
                            start=z, length=z,
                            overflow=jnp.zeros((0, lanes), bool))
    buf, start, length, overflow = rans_encode_lanes(
        symbols, tbl, cap=cap, chunk_size=chunk_size, prob_bits=prob_bits,
        lane_block=lane_block, t_block=t_block, scatter=scatter)
    return ChunkedLanes(buf=buf, start=start, length=length,
                        overflow=overflow)


def rans_decode(enc: EncodedLanes, n_symbols: int, tbl: TableSet,
                prob_bits: int = C.PROB_BITS,
                use_pred: bool = False, window: int = 4, delta: int = 8,
                predictor=None,
                candidates: jax.Array | None = None,
                lane_block: int = 128,
                t_block: int | None = None,
                lane_probes: bool = False,
                exhausted_flags: bool = False):
    """Kernel-backed decode; returns (symbols (lanes,T), avg probes/symbol).

    Static ``(K,)`` and adaptive ``(T, K)`` / ``(T, lanes, K)`` TableSets
    are all decoded in-kernel (the adaptive layouts block the T axis through
    VMEM — ``t_block``).  ``predictor`` is any ``core.predictors`` config;
    ``use_pred``/``window``/``delta`` remain as sugar for the paper's
    neighbour-average predictor.  ``candidates`` is an optional
    ``(T, lanes, topk)`` model-top-k candidate plane verified in-kernel
    (topk == 0 disables speculation).  When the lane count does not tile
    the ``lane_block`` grid the block collapses to one lane group
    (correctness over occupancy — the serve/parallel paths run narrow lane
    counts).  ``lane_probes``: also return the per-lane counters
    ``(lanes,)``.  A decode that reads past a lane's stream end raises
    :class:`~repro.core.coder.StreamExhaustedError` host-side; traced
    callers (shard_map bodies) pass ``exhausted_flags=True`` to get the
    per-lane bool flag appended instead.
    """
    if predictor is None and use_pred:
        predictor = NeighborAverage(window=window, delta=delta)
    lanes = enc.buf.shape[0]
    if lanes % lane_block:
        lane_block = lanes
    if n_symbols == 0:                      # degenerate: nothing to decode
        out = (jnp.zeros((lanes, 0), jnp.int32), jnp.float32(0.0))
        if lane_probes:
            out = out + (jnp.zeros((lanes,), jnp.int32),)
        return out + (jnp.zeros((lanes,), bool),) if exhausted_flags else out
    sym, probes, under = rans_decode_lanes(
        enc.buf, enc.start, tbl.freq, tbl.cdf, t_len=n_symbols,
        prob_bits=prob_bits, predictor=predictor, candidates=candidates,
        lane_block=lane_block, t_block=t_block)
    probes = probes[0]
    under = under[0] > 0
    avg = jnp.mean(probes.astype(jnp.float32)) / n_symbols
    out = (sym, avg, probes) if lane_probes else (sym, avg)
    if exhausted_flags:
        return out + (under,)
    _check_exhausted(under, "rans_decode")
    return out


def rans_decode_chunked(chunks: ChunkedLanes | None = None,
                        n_symbols: int | None = None,
                        tbl: TableSet | None = None,
                        chunk_size: int | None = None,
                        prob_bits: int = C.PROB_BITS,
                        predictor=None,
                        candidates: jax.Array | None = None,
                        lane_block: int = 128,
                        t_block: int | None = None,
                        lane_probes: bool = False,
                        chunk_probes: bool = False,
                        exhausted_flags: bool = False,
                        from_container: ContainerSlab | None = None):
    """Kernel-backed chunked decode (mirrors :func:`rans_encode_chunked`).

    ONE ``pallas_call`` for the whole stream: the chunk axis is a grid
    dimension of the decode kernel (in-kernel per-chunk state/pointer/
    context reset — no host-side loop of kernel launches).  Each (chunk,
    lane) cell is a standalone stream, so the kernel re-reads the 4-byte
    state header at every chunk's first grid step exactly like
    ``coder.decode_chunked``'s per-chunk ``decoder_init``.  Per-position
    TableSets (leading T dim of ``n_symbols``) and ``(T, lanes, topk)``
    candidate planes ride the chunk grid axis; static tables are reused.
    Probe accounting matches the pure-JAX path per lane and per chunk (both
    consume ``core.search``).  Returns ``(symbols (lanes, T), avg_probes
    [, per-lane probes][, per-(chunk, lane) probes])``.

    **Zero-copy entry point**: pass ``from_container=`` a validated
    :class:`~repro.core.bitstream.ContainerSlab` (from
    ``bitstream.parse_chunked``) instead of ``chunks`` and the kernel reads
    straight off the packed payload slab — no host-side right-align copy
    anywhere on the path (DESIGN.md §10).  ``n_symbols``/``chunk_size``
    default to the container's header values.  Symbols and probes are
    bit-identical to the dense ``ChunkedLanes`` path.
    """
    if from_container is not None:
        if chunks is not None:
            raise ValueError(
                "pass either a dense ChunkedLanes stream or "
                "from_container=<ContainerSlab>, not both")
        cs = from_container
        if n_symbols is None:
            n_symbols = cs.meta.n_symbols
        if chunk_size is None:
            chunk_size = cs.meta.chunk_size
        n_chunks, lanes = cs.offset.shape
    else:
        if chunks is None:
            raise ValueError("a ChunkedLanes stream or from_container=... "
                             "is required")
        n_chunks, lanes = chunks.buf.shape[:2]
    n_total = num_chunks(n_symbols, chunk_size)
    if n_chunks != n_total:
        raise ValueError(
            f"stream has {n_chunks} chunks but n_symbols="
            f"{n_symbols} at chunk_size={chunk_size} implies {n_total}; "
            "decode with the chunk_size the stream was encoded with")
    if lanes % lane_block:
        lane_block = lanes
    if n_symbols == 0:                      # degenerate: zero chunks
        out = (jnp.zeros((lanes, 0), jnp.int32), jnp.float32(0.0))
        if lane_probes:
            out = out + (jnp.zeros((lanes,), jnp.int32),)
        if chunk_probes:
            out = out + (jnp.zeros((0, lanes), jnp.int32),)
        if exhausted_flags:
            out = out + (jnp.zeros((0, lanes), bool),)
        return out
    if from_container is not None:
        if cs.slab.shape[0] >= 2 ** 31:
            raise ValueError(
                f"container payload of {cs.slab.shape[0]} bytes exceeds "
                "the int32 index range of the device slab paths")
        # window size: >= 4 so the state-header read always has rows even
        # for degenerate (hostile but validated) all-empty indexes
        cap = max(cs.cap, 4)
        words, base, wstart = plan_slab_windows(
            np.asarray(cs.slab, np.uint8), cs.offset, cap)
        wlen = cs.length.astype(np.int32)
        sym, cprobes, cunder = rans_decode_slab(
            jnp.asarray(words), jnp.asarray(base), jnp.asarray(wstart),
            jnp.asarray(wlen), tbl.freq, tbl.cdf, cap=cap,
            t_len=n_symbols, chunk_size=chunk_size, prob_bits=prob_bits,
            predictor=predictor, candidates=candidates,
            lane_block=lane_block, t_block=t_block)
    else:
        sym, cprobes, cunder = rans_decode_lanes(
            chunks.buf, chunks.start, tbl.freq, tbl.cdf, t_len=n_symbols,
            chunk_size=chunk_size, prob_bits=prob_bits, predictor=predictor,
            candidates=candidates, lane_block=lane_block, t_block=t_block)
    avg_probes = (jnp.sum(cprobes.astype(jnp.float32))
                  / (lanes * n_symbols))
    out = (sym, avg_probes)
    if lane_probes:
        out = out + (jnp.sum(cprobes, axis=0),)
    if chunk_probes:
        out = out + (cprobes,)
    if exhausted_flags:
        return out + (cunder > 0,)
    _check_exhausted(cunder > 0, "rans_decode_chunked")
    return out


def rans_decode_step_rows(buf_t: jax.Array, s: jax.Array, ptr: jax.Array,
                          tbl: TableSet,
                          prob_bits: int = C.PROB_BITS,
                          candidates: jax.Array | None = None,
                          backend: str = "kernel"):
    """One rANS symbol pop across a flattened ``slots x lanes`` row axis.

    The batched serve engine's step primitive (``serve.engine``): rows are
    the engine's continuous-batching batch axis — every row owns a private
    byte stream (one column of ``buf_t``), private coder state and its own
    candidate row, so the per-step kernel that serves one request's lanes
    serves a whole slot batch unchanged (the kernel is row-generic; this
    wrapper is the batch-slot plumbing and the single dispatch point for
    the engine's two step backends).  ``buf_t`` is the ``(cap, rows)``
    TRANSPOSED stream slab — transpose once outside the scan, exactly like
    the fused serve path.  ``tbl`` rows are the per-row per-step TableSet
    ``(rows, K)``; ``candidates`` an optional ``(rows, topk)`` model-top-k
    plane.  ``backend="kernel"`` runs the per-step Pallas kernel
    (``rans_decode_step``), ``backend="coder"`` the pure-JAX
    ``coder.decode_get`` — bit-identical on symbols AND probe counters
    (both consume ``core.search``).  Returns
    ``(s', ptr', symbols (rows,), probes (rows,), under (rows,))`` with
    ``under`` int32 0/1 (this step read past the row's stream end) —
    normalized across both backends.
    """
    if backend == "kernel":
        s2, ptr2, sym, probes, under = rans_decode_step(
            buf_t, s, ptr, tbl.freq, tbl.cdf, prob_bits=prob_bits,
            candidates=candidates)
        return s2, ptr2, sym, probes, (under > 0).astype(jnp.int32)
    if backend != "coder":
        raise ValueError(f"unknown step backend {backend!r}")
    from repro.core import coder
    st, sym, probes = coder.decode_get(
        coder.DecState(s, ptr), buf_t.T, tbl, prob_bits,
        candidates=candidates)
    return st.s, st.ptr, sym, probes, st.underflow.astype(jnp.int32)


def spc_quantize_tables(probs: jax.Array,
                        prob_bits: int = C.PROB_BITS,
                        batch_block: int = 8) -> TableSet:
    """Kernel-backed SPC: batched probs -> full TableSet."""
    from repro.kernels.spc_quantize import spc_quantize
    freq = spc_quantize(probs, prob_bits=prob_bits, batch_block=batch_block)
    return build_tables(freq, prob_bits)
