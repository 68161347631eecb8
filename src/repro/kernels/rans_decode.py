"""Pallas TPU kernel: prediction-guided multi-lane rANS decode (Sec. IV-C, T3).

The decoder inner loop is the paper's focus: its latency is dominated by CDF
probes (state-to-symbol search) and stream reads.  Kernel design:

  * lane-blocked grid as in rans_encode; per-lane state/pointer vectors are
    carried across a ``fori_loop`` (VREGs) and — when the symbol axis is
    blocked — across grid steps in VMEM scratch;
  * the CDF search is **not** reimplemented here: the kernel imports the
    shared search core (:mod:`repro.core.search`) and substitutes its gather
    primitive with a one-hot contraction (VPU/MXU dense math — the TPU
    replacement for the RTL's table SRAM port).  Symbols *and* the canonical
    Fig. 4(b) probe counters are therefore structurally identical to
    ``core.coder.decode``;
  * prediction-guided decoding uses the ``core.predictors`` protocol
    directly (``predictor.init/predict/update`` run inside the kernel), so
    ``NeighborAverage``/``LastValue``/``ZeroPredictor`` behave identically
    in kernel and reference paths — bit-exactness is structural (the
    bracket only narrows the search start, the search itself is unchanged);
  * **candidate planes** (model-top-k speculation, Fig. 2 trial symbols):
    the kernel accepts a ``(T, lanes, topk)`` plane of trial symbols — the
    serve pipeline's model-top-k ids — blocked through VMEM alongside the
    tables.  Each row feeds ``core.search.find_symbol``'s candidate path
    (one O(1) one-hot CDF probe per trial), so in-kernel speculation pays
    exactly the canonical probe accounting of the pure-JAX decoder;
  * **adaptive tables**: besides a static ``(K,)`` TableSet the kernel
    accepts per-position ``(T, K)`` and per-position-per-lane
    ``(T, lanes, K)`` tables — the neural-prior layouts of
    ``serve.compress``.  The T axis is blocked through VMEM
    (``t_block`` rows of freq/cdf per grid step); decoder state persists in
    scratch between T blocks, so arbitrarily long adaptive streams decode
    without holding all T tables on chip;
  * **chunk grid axis**: chunked streams (independent per-chunk flush — the
    interleaved-ANS construction) decode in ONE ``pallas_call``: the chunk
    axis is a grid dimension; at each chunk's first grid step the kernel
    re-reads that chunk's 4-byte state header and resets the read cursors,
    probe counters and predictor context (chunks are standalone streams).
    Ragged chunks are padded to whole T blocks; padding rows decode nothing
    and their output rows are dropped host-side;
  * fixed 2-step masked byte refill mirrors the encoder's renorm bound.

Grid: ``(lanes // lane_block, n_chunks, ceil(chunk_size / t_block))`` — the
T axis iterates fastest (innermost), then chunks, so each (lane block,
chunk) streams its table blocks sequentially while that chunk's byte
stream (cap x Lb) stays resident.

VMEM per grid step: stream (cap x Lb) + tables (t_block x [Lb x] (2K+1)
u32) + candidates (t_block x Lb x topk) + symbols out (t_block x Lb).  For
T=4096, Lb=128, K=256 static: ~3.7 MB; for the (T, lanes, K) adaptive
layout, t_block=8 keeps the table slab at ~2.1 MB.

Context layout note: the predictor protocol's ``(lanes, window)`` context is
kept as-is inside the kernel (sublane-major for the tiny ``window`` axis);
on a real TPU a lane-minor layout would map better onto VREGs, but the
shared-protocol contract wins here and ``window`` is small.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import constants as C
from repro.core import search
from repro.kernels import common
from repro.kernels.common import (SQUEEZED, chunk_row_spec, masked_refill,
                                  onehot_gather, onehot_gather_lanes,
                                  onehot_gather_rows, pad_chunk_rows,
                                  read_state_header, unpad_chunk_rows)

_U32 = jnp.uint32
_I32 = jnp.int32


def _decode_out_specs(tb: int, n_tb: int, lane_block: int) -> list:
    """Symbols (one T block of rows) + per-chunk probe/underflow rows.
    Index maps take the slab path's scalar-prefetch refs as trailing
    args (``*_``)."""
    return [pl.BlockSpec((SQUEEZED, tb, lane_block),
                         lambda i, c, j, *_: (c * n_tb + j, 0, i)),
            chunk_row_spec(lane_block),
            chunk_row_spec(lane_block)]


def _decode_out_shape(n_blocks: int, tb: int, n_chunks: int,
                      lanes: int) -> list:
    return [jax.ShapeDtypeStruct((n_blocks, tb, lanes), _I32),
            jax.ShapeDtypeStruct((n_chunks, 1, lanes), _I32),
            jax.ShapeDtypeStruct((n_chunks, 1, lanes), _I32)]


def _decode_outputs(sym, probes, under, t_len, chunk, n_chunks,
                    padded_chunk):
    """Kernel planes -> ``(symbols (lanes, T), probes, under)`` with the
    padding rows dropped and the per-chunk planes ``(n_chunks, lanes)``."""
    sym = unpad_chunk_rows(sym.reshape(-1, sym.shape[-1]), t_len, chunk,
                           n_chunks, padded_chunk)
    return sym.T, probes[:, 0], under[:, 0]


def _decode_kernel(*refs,
                   t_len: int, chunk_size: int, t_block: int, n_tb: int,
                   prob_bits: int, k: int, layout: str, predictor,
                   ctx_w: int, has_cands: bool, slab: bool = False,
                   cap: int = 0):
    if slab:
        # zero-copy source (DESIGN.md §10): the packed container payload
        # stays one slab of little-endian u32 words in HBM (memory_space=
        # ANY); per-(chunk, lane) DMA block starts ride the grid as a
        # scalar-prefetch plane and each chunk's windows are DMA'd into
        # VMEM, then turned word-major / lane-minor like the dense layout.
        base_ref, slab_ref, wstart_ref, wlen_ref, freq_ref, cdf_ref, \
            *rest = refs
    else:
        buf_ref, start_ref, freq_ref, cdf_ref, *rest = refs
    if has_cands:
        cand_ref, *rest = rest
    if slab:
        sym_ref, probes_ref, under_ref, s_scr, ptr_scr, ctx_scr, win_scr, \
            words_scr, sem = rest
    else:
        sym_ref, probes_ref, under_ref, s_scr, ptr_scr, ctx_scr = rest
    lanes = sym_ref.shape[1]
    mask = _U32((1 << prob_bits) - 1)
    i = pl.program_id(0)      # lane-block index
    c = pl.program_id(1)      # chunk index
    j = pl.program_id(2)      # T-block index (innermost grid axis)
    if slab:
        # per-lane byte access of the slab layout: byte p of a lane's window
        # is byte p & 3 of word p >> 2 (little-endian).  In-kernel span
        # clamp: bytes outside the cell's validated [wstart, wstart + min(
        # length, cap)) span read as 0 — identical to the dense path's
        # out-of-stream reads, and a hostile index can never surface
        # another cell's bytes (the DMA base is host-clipped into the
        # slab, so the copy itself is in-bounds).
        ws = wstart_ref[0].astype(_I32)
        wl = wlen_ref[0].astype(_I32)
        live_end = ws + jnp.minimum(wl, cap)

        def byte_gather(words, p):
            w = onehot_gather_rows(words, p >> 2)
            b = (w >> ((p & 3) << 3).astype(_U32)) & _U32(0xFF)
            return jnp.where((p >= ws) & (p < live_end), b, _U32(0))
    else:
        byte_gather = onehot_gather_rows

    @pl.when(j == 0)
    def _init():
        # per-chunk re-init: every chunk is a standalone stream — read its
        # 4-byte big-endian state header and reset cursors/probes/context
        if slab:
            rows = words_scr.shape[0] // 128     # window rows of 128 words

            def dma(lane, _):
                b = pl.multiple_of(base_ref[c, i * lanes + lane] * 8, 8)
                dst = pl.multiple_of(lane * rows, 8)
                cp = pltpu.make_async_copy(slab_ref.at[pl.ds(b, rows)],
                                           win_scr.at[pl.ds(dst, rows)], sem)
                cp.start()
                cp.wait()
                return 0
            jax.lax.fori_loop(0, lanes, dma, 0)
            # lane-major windows -> word-major (words, lanes): row r of
            # every lane's window is one strided (lanes, 128) tile
            for r in range(rows):
                words_scr[pl.ds(r * 128, 128), :] = (
                    win_scr[pl.ds(r, lanes, stride=rows), :].T)
            s, ptr, und = read_state_header(words_scr[...], ws,
                                            gather=byte_gather,
                                            limit=ws + wl)
        else:
            s, ptr, und = read_state_header(buf_ref[0],
                                            start_ref[0].astype(_I32),
                                            limit=buf_ref.shape[1])
        s_scr[0, :] = s
        ptr_scr[0, :] = ptr
        probes_ref[0, :] = jnp.zeros((lanes,), _I32)
        under_ref[0, :] = und
        if predictor is not None and ctx_w:
            ctx_scr[...] = predictor.init(lanes)

    # this chunk's byte source, resident in VMEM across its T blocks
    buf = words_scr[...] if slab else buf_ref[0]
    # one-past-the-end read bound per lane: the window span end for the
    # slab layout, the (right-aligned) buffer cap for the dense layout
    read_limit = ws + wl if slab else buf.shape[0]

    if layout == "static":
        freq_all = freq_ref[0]        # (K,)
        cdf_all = cdf_ref[0]          # (K+1,)

    # only a predictor with context carries it through the loop: the TPU
    # has no zero-width vectors, so a context-free predictor (ZeroPredictor)
    # reads a host-side empty constant instead
    carry_ctx = predictor is not None and ctx_w > 0
    ctx0 = ctx_scr[...] if carry_ctx else None
    no_ctx = np.zeros((lanes, 0), np.int32)

    # valid rows in this T block: the final chunk may be ragged, and padding
    # rows (up to a whole T block) decode nothing
    chunk_len = jnp.minimum(chunk_size, t_len - c * chunk_size)
    n_t = jnp.clip(chunk_len - j * t_block, 0, t_block)

    # zero the symbol block first: rows >= n_t are padding (dropped by the
    # host-side unpad), and valid rows overwrite below
    sym_ref[...] = jnp.zeros(sym_ref.shape, _I32)

    # the loop carries the per-lane vectors as (1, lanes) rows: the TPU
    # compiler cannot lay out a 1-D carry that feeds back through a one-hot
    # gather (it aborts), and a 2-D row has one unambiguous layout
    def body(t, carry):
        s, ptr, probes, under, ctx = carry
        s, ptr, probes, under = s[0], ptr[0], probes[0], under[0]
        slot = s & mask
        if layout == "static":
            freq_t, cdf_t, g = freq_all, cdf_all, onehot_gather
        elif layout == "perpos":
            freq_t = freq_ref[pl.dslice(t, 1), :][0]       # (K,)
            cdf_t = cdf_ref[pl.dslice(t, 1), :][0]         # (K+1,)
            g = onehot_gather
        else:  # "lane": per-position per-lane rows
            freq_t = freq_ref[pl.dslice(t, 1), :, :][0]    # (lanes, K)
            cdf_t = cdf_ref[pl.dslice(t, 1), :, :][0]      # (lanes, K+1)
            g = onehot_gather_lanes
        cand_t = (cand_ref[pl.dslice(t, 1), :, :][0]       # (lanes, topk)
                  if has_cands else None)
        if predictor is not None:
            pred = predictor.predict(ctx if carry_ctx else no_ctx)
            cands = cand_t if has_cands else pred.candidates
            x, p = search.find_symbol(cdf_t, k, slot, mu=pred.mu,
                                      delta=pred.delta,
                                      candidates=cands, gather=g)
            if carry_ctx:
                ctx = predictor.update(ctx, x)
        else:
            x, p = search.find_symbol(cdf_t, k, slot, candidates=cand_t,
                                      gather=g)
        sym_ref[t, :] = x
        f = g(freq_t, x)
        start = g(cdf_t[..., :k], x)
        s = f * (s >> prob_bits) + slot - start
        s, ptr, u = masked_refill(buf, s, ptr, gather=byte_gather,
                                  limit=read_limit)
        return s[None], ptr[None], (probes + p)[None], (under + u)[None], ctx

    s, ptr, probes, under, ctx = jax.lax.fori_loop(
        0, n_t, body, (s_scr[...], ptr_scr[...], probes_ref[...],
                       under_ref[...], ctx0))
    s_scr[...] = s
    ptr_scr[...] = ptr
    probes_ref[...] = probes
    under_ref[...] = under
    if carry_ctx:
        ctx_scr[...] = ctx


@functools.partial(jax.jit,
                   static_argnames=("t_len", "chunk_size", "prob_bits",
                                    "predictor", "lane_block", "t_block"))
def rans_decode_lanes(buf: jax.Array,      # (lanes, cap) uint8 forward stream
                      start: jax.Array,    # (lanes,) int32
                      freq: jax.Array, cdf: jax.Array,
                      t_len: int,
                      chunk_size: int | None = None,
                      prob_bits: int = C.PROB_BITS,
                      predictor=None,
                      candidates: jax.Array | None = None,
                      lane_block: int = 128,
                      t_block: int | None = None):
    """Decode t_len symbols/lane — ONE ``pallas_call`` for the whole stream.

    Returns ``(symbols (lanes, T), probes (n_chunks, lanes))``: the probe
    plane carries the canonical per-(chunk, lane) Fig. 4(b) counters of
    ``core.search`` — integer-identical to ``core.coder.decode[_chunked]``.

    Stream layouts (detected from ``buf.ndim``):
      * ``(lanes, cap)``            — one monolithic stream per lane
                                      (``chunk_size`` must be None);
      * ``(n_chunks, lanes, cap)``  — chunked streams (``ChunkedLanes``
                                      device form): every (chunk, lane) cell
                                      is standalone; the chunk axis is a
                                      *grid* dimension with in-kernel
                                      state/pointer/context reset, not a
                                      host-side loop of launches.  ``start``
                                      must carry the matching leading axis
                                      and ``chunk_size`` is required.

    Table layouts (detected from ``freq.ndim``):
      * ``(K,)``            — static shared table (classic rANS);
      * ``(T, K)``          — per-position shared rows (neural prior, all
                              lanes share each step's distribution);
      * ``(T, lanes, K)``   — per-position per-lane rows (the
                              ``serve.compress`` TableSet layout).
    ``cdf`` must carry the matching shape with a trailing ``K+1``.

    ``predictor`` is a ``core.predictors`` config (hashable NamedTuple) or
    None; ``candidates`` an optional ``(T, lanes, topk)`` plane of
    model-top-k trial symbols (topk == 0 disables speculation), verified
    in-kernel with O(1) probes each; ``t_block`` blocks the T axis through
    VMEM (None = whole chunk in one block).
    """
    if buf.ndim == 2:
        if chunk_size is not None:
            raise ValueError("monolithic (lanes, cap) stream cannot take a "
                             "chunk_size; pass a (n_chunks, lanes, cap) buf")
        buf3 = buf[None]
        start2 = start.reshape(1, -1)
        chunk = t_len
    elif buf.ndim == 3:
        if chunk_size is None:
            raise ValueError("chunked (n_chunks, lanes, cap) stream needs "
                             "chunk_size")
        buf3, start2, chunk = buf, start, min(chunk_size, t_len)
    else:
        raise ValueError(f"unsupported stream rank {buf.ndim}")
    n_chunks, lanes, cap = buf3.shape
    if n_chunks != -(-t_len // chunk):
        raise ValueError(
            f"stream has {n_chunks} chunks but t_len={t_len} at chunk_size="
            f"{chunk} implies {-(-t_len // chunk)}")
    if lanes % lane_block:
        raise ValueError(f"lanes={lanes} not a multiple of {lane_block}")
    k = freq.shape[-1]
    tb = chunk if t_block is None else max(1, min(t_block, chunk))
    n_tb = -(-chunk // tb)
    padded_chunk = n_tb * tb
    total_rows = n_chunks * padded_chunk

    if freq.ndim == 1:
        layout = "static"
        freq_in, cdf_in = freq.reshape(1, k), cdf.reshape(1, k + 1)
        freq_spec = pl.BlockSpec((1, k), lambda i, c, j: (0, 0))
        cdf_spec = pl.BlockSpec((1, k + 1), lambda i, c, j: (0, 0))
    elif freq.ndim == 2:
        if freq.shape[0] != t_len:
            raise ValueError(
                f"per-position tables carry T={freq.shape[0]} rows but "
                f"t_len={t_len}")
        layout = "perpos"
        freq_in = pad_chunk_rows(freq, t_len, chunk, n_chunks,
                                 padded_chunk).reshape(-1, tb, k)
        cdf_in = pad_chunk_rows(cdf, t_len, chunk, n_chunks,
                                padded_chunk).reshape(-1, tb, k + 1)
        freq_spec = pl.BlockSpec((SQUEEZED, tb, k),
                                 lambda i, c, j: (c * n_tb + j, 0, 0))
        cdf_spec = pl.BlockSpec((SQUEEZED, tb, k + 1),
                                lambda i, c, j: (c * n_tb + j, 0, 0))
    elif freq.ndim == 3:
        if freq.shape[0] != t_len or freq.shape[1] != lanes:
            raise ValueError(
                f"per-lane tables must be (T, lanes, K)=({t_len}, {lanes}, "
                f"{k}); got {freq.shape}")
        layout = "lane"
        freq_in = pad_chunk_rows(freq, t_len, chunk, n_chunks, padded_chunk)
        cdf_in = pad_chunk_rows(cdf, t_len, chunk, n_chunks, padded_chunk)
        freq_spec = pl.BlockSpec((tb, lane_block, k),
                                 lambda i, c, j: (c * n_tb + j, i, 0))
        cdf_spec = pl.BlockSpec((tb, lane_block, k + 1),
                                lambda i, c, j: (c * n_tb + j, i, 0))
    else:
        raise ValueError(f"unsupported table rank {freq.ndim}")

    has_cands = candidates is not None and candidates.shape[-1] > 0
    extra_in, extra_specs = [], []
    if has_cands:
        if candidates.shape[:2] != (t_len, lanes):
            raise ValueError(
                f"candidate planes must be (T, lanes, topk)=({t_len}, "
                f"{lanes}, *); got {candidates.shape}")
        topk = candidates.shape[-1]
        extra_in.append(pad_chunk_rows(candidates.astype(_I32), t_len,
                                       chunk, n_chunks, padded_chunk))
        extra_specs.append(pl.BlockSpec(
            (tb, lane_block, topk), lambda i, c, j: (c * n_tb + j, i, 0)))

    ctx_w = (int(predictor.init(lane_block).shape[-1])
             if predictor is not None else 0)
    grid = (lanes // lane_block, n_chunks, n_tb)

    sym, probes, under = pl.pallas_call(
        functools.partial(_decode_kernel, t_len=t_len, chunk_size=chunk,
                          t_block=tb, n_tb=n_tb, prob_bits=prob_bits, k=k,
                          layout=layout, predictor=predictor, ctx_w=ctx_w,
                          has_cands=has_cands),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, cap, lane_block), lambda i, c, j: (c, 0, i)),
            chunk_row_spec(lane_block),
            freq_spec,
            cdf_spec,
        ] + extra_specs,
        out_specs=_decode_out_specs(tb, n_tb, lane_block),
        out_shape=_decode_out_shape(total_rows // tb, tb, n_chunks, lanes),
        scratch_shapes=[
            pltpu.VMEM((1, lane_block), _U32),              # rANS states
            pltpu.VMEM((1, lane_block), _I32),              # read cursors
            pltpu.VMEM((lane_block, max(1, ctx_w)), _I32),  # predictor ctx
        ],
        interpret=common.interpret_mode(),
    )(buf3.swapaxes(1, 2), start2.astype(_I32)[:, None], freq_in,
      cdf_in, *extra_in)
    return _decode_outputs(sym, probes, under, t_len, chunk, n_chunks,
                           padded_chunk)


# Slab windows move in whole (8, 128) u32 tiles: the TPU's DMA engine copies
# tile-aligned row ranges only, so the packed byte slab is viewed as rows of
# 128 little-endian words and every window starts on a SLAB_BLOCK boundary.
SLAB_BLOCK = 8 * 128 * 4


def slab_window_rows(cap: int) -> int:
    """Rows of 128 words in one lane's window: whole blocks covering any
    ``cap``-byte span that starts anywhere inside its first block."""
    return 8 * -(-(cap + SLAB_BLOCK - 1) // SLAB_BLOCK)


def plan_slab_windows(slab: np.ndarray, offset: np.ndarray, cap: int):
    """Host side of the zero-copy decode: the word view of the payload and
    each (chunk, lane) cell's window.

    Returns ``(words (rows, 128) uint32, base (n_chunks, lanes) int32,
    wstart (n_chunks, lanes) int32)``: ``base`` is the window's first
    block, clipped so the DMA can never leave the (zero-padded) slab, and
    ``wstart = offset - base * SLAB_BLOCK`` re-bases each cell's span into
    its window.
    """
    n_win = slab_window_rows(cap) // 8
    n_blk = max(-(-slab.shape[0] // SLAB_BLOCK), n_win)
    padded = np.zeros(n_blk * SLAB_BLOCK, np.uint8)
    padded[:slab.shape[0]] = slab
    words = padded.view("<u4").astype(np.uint32).reshape(-1, 128)
    base = np.clip(offset // SLAB_BLOCK, 0, n_blk - n_win).astype(np.int64)
    wstart = (offset - base * SLAB_BLOCK).astype(np.int32)
    return words, base.astype(np.int32), wstart


@functools.partial(jax.jit,
                   static_argnames=("cap", "t_len", "chunk_size",
                                    "prob_bits", "predictor", "lane_block",
                                    "t_block"))
def rans_decode_slab(words: jax.Array,     # (rows, 128) uint32 payload words
                     base: jax.Array,      # (n_chunks, lanes) int32 block
                     wstart: jax.Array,    # (n_chunks, lanes) int32 in-window
                     wlen: jax.Array,      # (n_chunks, lanes) int32 span len
                     freq: jax.Array, cdf: jax.Array, *,
                     cap: int,
                     t_len: int,
                     chunk_size: int,
                     prob_bits: int = C.PROB_BITS,
                     predictor=None,
                     candidates: jax.Array | None = None,
                     lane_block: int = 128,
                     t_block: int | None = None):
    """Zero-copy chunked decode: ONE ``pallas_call`` straight off the
    packed container slab (DESIGN.md §10).

    The per-(chunk, lane) index walk that ``bitstream.unpack_chunked`` used
    to run host-side moves into the kernel: ``base`` rides the grid as a
    scalar-prefetch plane (SMEM), the slab's word view stays unblocked in
    HBM (``memory_space=ANY``), and at each chunk's first grid step the
    kernel DMAs every lane's window of :func:`slab_window_rows` rows from
    block ``base`` into VMEM, turns the windows word-major, and reads each
    lane's bytes from ``wstart`` on, with bytes outside the cell's span
    ``[wstart, wstart + min(wlen, cap))`` read as 0.
    :func:`plan_slab_windows` derives ``words``/``base``/``wstart`` from a
    validated :class:`~repro.core.bitstream.ContainerSlab` (see
    :func:`repro.kernels.ops.rans_decode_chunked`).

    Symbols and probe counters are bit-identical to
    :func:`rans_decode_lanes` over the dense right-aligned form: the byte
    sequence each lane reads is identical (span bytes then zeros), and the
    table/candidate/search plumbing is shared.
    """
    n_chunks, lanes = base.shape
    chunk = min(chunk_size, t_len)
    if n_chunks != -(-t_len // chunk):
        raise ValueError(
            f"stream has {n_chunks} chunks but t_len={t_len} at chunk_size="
            f"{chunk} implies {-(-t_len // chunk)}")
    if lanes % lane_block:
        raise ValueError(f"lanes={lanes} not a multiple of {lane_block}")
    k = freq.shape[-1]
    tb = chunk if t_block is None else max(1, min(t_block, chunk))
    n_tb = -(-chunk // tb)
    padded_chunk = n_tb * tb
    total_rows = n_chunks * padded_chunk

    # index maps take the scalar-prefetch refs as trailing args (*_)
    if freq.ndim == 1:
        layout = "static"
        freq_in, cdf_in = freq.reshape(1, k), cdf.reshape(1, k + 1)
        freq_spec = pl.BlockSpec((1, k), lambda i, c, j, *_: (0, 0))
        cdf_spec = pl.BlockSpec((1, k + 1), lambda i, c, j, *_: (0, 0))
    elif freq.ndim == 2:
        if freq.shape[0] != t_len:
            raise ValueError(
                f"per-position tables carry T={freq.shape[0]} rows but "
                f"t_len={t_len}")
        layout = "perpos"
        freq_in = pad_chunk_rows(freq, t_len, chunk, n_chunks,
                                 padded_chunk).reshape(-1, tb, k)
        cdf_in = pad_chunk_rows(cdf, t_len, chunk, n_chunks,
                                padded_chunk).reshape(-1, tb, k + 1)
        freq_spec = pl.BlockSpec((SQUEEZED, tb, k),
                                 lambda i, c, j, *_: (c * n_tb + j, 0, 0))
        cdf_spec = pl.BlockSpec((SQUEEZED, tb, k + 1),
                                lambda i, c, j, *_: (c * n_tb + j, 0, 0))
    elif freq.ndim == 3:
        if freq.shape[0] != t_len or freq.shape[1] != lanes:
            raise ValueError(
                f"per-lane tables must be (T, lanes, K)=({t_len}, {lanes}, "
                f"{k}); got {freq.shape}")
        layout = "lane"
        freq_in = pad_chunk_rows(freq, t_len, chunk, n_chunks, padded_chunk)
        cdf_in = pad_chunk_rows(cdf, t_len, chunk, n_chunks, padded_chunk)
        freq_spec = pl.BlockSpec((tb, lane_block, k),
                                 lambda i, c, j, *_: (c * n_tb + j, i, 0))
        cdf_spec = pl.BlockSpec((tb, lane_block, k + 1),
                                lambda i, c, j, *_: (c * n_tb + j, i, 0))
    else:
        raise ValueError(f"unsupported table rank {freq.ndim}")

    has_cands = candidates is not None and candidates.shape[-1] > 0
    extra_in, extra_specs = [], []
    if has_cands:
        if candidates.shape[:2] != (t_len, lanes):
            raise ValueError(
                f"candidate planes must be (T, lanes, topk)=({t_len}, "
                f"{lanes}, *); got {candidates.shape}")
        topk = candidates.shape[-1]
        extra_in.append(pad_chunk_rows(candidates.astype(_I32), t_len,
                                       chunk, n_chunks, padded_chunk))
        extra_specs.append(pl.BlockSpec(
            (tb, lane_block, topk),
            lambda i, c, j, *_: (c * n_tb + j, i, 0)))

    ctx_w = (int(predictor.init(lane_block).shape[-1])
             if predictor is not None else 0)
    grid = (lanes // lane_block, n_chunks, n_tb)
    win_rows = slab_window_rows(cap)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),              # slab words
            chunk_row_spec(lane_block),
            chunk_row_spec(lane_block),
            freq_spec,
            cdf_spec,
        ] + extra_specs,
        out_specs=_decode_out_specs(tb, n_tb, lane_block),
        scratch_shapes=[
            pltpu.VMEM((1, lane_block), _U32),              # rANS states
            pltpu.VMEM((1, lane_block), _I32),              # read cursors
            pltpu.VMEM((lane_block, max(1, ctx_w)), _I32),  # predictor ctx
            pltpu.VMEM((lane_block * win_rows, 128), _U32),  # windows
            pltpu.VMEM((win_rows * 128, lane_block), _U32),  # word-major
            pltpu.SemaphoreType.DMA,                        # window copies
        ],
    )
    sym, probes, under = pl.pallas_call(
        functools.partial(_decode_kernel, t_len=t_len, chunk_size=chunk,
                          t_block=tb, n_tb=n_tb, prob_bits=prob_bits, k=k,
                          layout=layout, predictor=predictor, ctx_w=ctx_w,
                          has_cands=has_cands, slab=True, cap=cap),
        grid_spec=grid_spec,
        out_shape=_decode_out_shape(total_rows // tb, tb, n_chunks, lanes),
        interpret=common.interpret_mode(),
    )(base.astype(_I32), words, wstart.astype(_I32)[:, None],
      wlen.astype(_I32)[:, None], freq_in, cdf_in, *extra_in)
    return _decode_outputs(sym, probes, under, t_len, chunk, n_chunks,
                           padded_chunk)


# ---------------------------------------------------------------------------
# per-step kernel: ONE symbol pop per lane, coder state threaded through the
# caller.  This is the fused serve decode's building block (DESIGN.md §9):
# the model is autoregressive over its own decoded tokens, so the serve scan
# carries (model cache, rANS state, read cursors) and calls this kernel once
# per position with that step's just-quantized tables and candidate row.  The
# CDF search, probe accounting, state update and masked refill are the same
# shared cores the full-stream kernel consumes — bit-exactness vs both the
# pure coder and the two-pass kernel replay is structural.
# ---------------------------------------------------------------------------

def _decode_step_kernel(buf_ref, s_ref, ptr_ref, freq_ref, cdf_ref, *rest,
                        prob_bits: int, k: int, lane_tables: bool,
                        has_cands: bool):
    if has_cands:
        cand_ref = rest[0]
        s_out, ptr_out, sym_ref, probes_ref, under_ref = rest[1:]
    else:
        s_out, ptr_out, sym_ref, probes_ref, under_ref = rest
    s = s_ref[0, :]
    ptr = ptr_ref[0, :]
    slot = s & _U32((1 << prob_bits) - 1)
    if lane_tables:
        freq_t, cdf_t, g = freq_ref[...], cdf_ref[...], onehot_gather_lanes
    else:
        freq_t, cdf_t, g = freq_ref[0], cdf_ref[0], onehot_gather
    cand = cand_ref[...] if has_cands else None
    x, p = search.find_symbol(cdf_t, k, slot, candidates=cand, gather=g)
    f = g(freq_t, x)
    start = g(cdf_t[..., :k], x)
    s = f * (s >> prob_bits) + slot - start
    s, ptr, u = masked_refill(buf_ref[...], s, ptr,
                              limit=buf_ref.shape[0])
    s_out[0, :] = s
    ptr_out[0, :] = ptr
    sym_ref[0, :] = x
    probes_ref[0, :] = p
    under_ref[0, :] = u


def rans_decode_step(buf: jax.Array,    # (cap, lanes) uint8, lane-minor
                     s: jax.Array,      # (lanes,) uint32 rANS states
                     ptr: jax.Array,    # (lanes,) int32 read cursors
                     freq: jax.Array, cdf: jax.Array,
                     prob_bits: int = C.PROB_BITS,
                     candidates: jax.Array | None = None):
    """Pop ONE symbol per lane; coder state lives with the caller.

    Tables are this step's rows: ``(K,)`` shared or ``(lanes, K)`` per-lane
    (``cdf`` with trailing ``K+1``); ``candidates`` an optional
    ``(lanes, topk)`` row of trial symbols.  Returns
    ``(s', ptr', symbols (lanes,), probes (lanes,), under (lanes,))`` —
    ``under`` counts refills that read past the stream end.  Designed to be
    traced inside a ``lax.scan`` (off the TPU, interpret mode inlines the
    kernel into the surrounding XLA program), with the initial ``(s, ptr)``
    coming from ``core.coder.decoder_init`` and ``buf`` transposed once
    outside the scan.
    """
    cap, lanes = buf.shape
    k = freq.shape[-1]
    lane_tables = freq.ndim == 2
    if lane_tables and freq.shape[0] != lanes:
        raise ValueError(
            f"per-lane tables must be (lanes, K)=({lanes}, {k}); got "
            f"{freq.shape}")
    has_cands = candidates is not None and candidates.shape[-1] > 0
    extra_in, extra_specs = [], []
    tbl_block = (lambda sh: pl.BlockSpec(sh, lambda i: (0,) * len(sh)))
    if has_cands:
        if candidates.shape[0] != lanes:
            raise ValueError(
                f"candidate row must be (lanes, topk)=({lanes}, *); got "
                f"{candidates.shape}")
        extra_in.append(candidates.astype(_I32))
        extra_specs.append(tbl_block(candidates.shape))
    freq_in = freq if lane_tables else freq.reshape(1, k)
    cdf_in = cdf if lane_tables else cdf.reshape(1, k + 1)
    s2, ptr2, sym, probes, under = pl.pallas_call(
        functools.partial(_decode_step_kernel, prob_bits=prob_bits, k=k,
                          lane_tables=lane_tables, has_cands=has_cands),
        grid=(1,),
        in_specs=[
            tbl_block((cap, lanes)),
            tbl_block((1, lanes)),
            tbl_block((1, lanes)),
            tbl_block(freq_in.shape),
            tbl_block(cdf_in.shape),
        ] + extra_specs,
        out_specs=[tbl_block((1, lanes))] * 5,
        out_shape=[
            jax.ShapeDtypeStruct((1, lanes), _U32),
            jax.ShapeDtypeStruct((1, lanes), _I32),
            jax.ShapeDtypeStruct((1, lanes), _I32),
            jax.ShapeDtypeStruct((1, lanes), _I32),
            jax.ShapeDtypeStruct((1, lanes), _I32),
        ],
        interpret=common.interpret_mode(),
        name="rans_decode_step",
    )(buf, s.reshape(1, lanes), ptr.astype(_I32).reshape(1, lanes),
      freq_in, cdf_in, *extra_in)
    return s2[0], ptr2[0], sym[0], probes[0], under[0]
