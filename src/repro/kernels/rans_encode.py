"""Pallas TPU kernel: multi-lane rANS encode (paper Sec. IV-B, T2+T4).

Kernel shape (hardware adaptation — see DESIGN.md §2/§8):

  * grid ``(lane blocks, chunks, T blocks)`` — the lane dim is last in the
    data layout and sized in multiples of 128 (= VREG width); each grid
    step owns ``lane_block`` independent rANS states held in registers
    across a ``fori_loop`` over symbols (the RTL's "stationary dataflow:
    state and symbols stay resident, probabilities stream");
  * the encode update itself is **not** implemented here: the kernel
    imports the shared update core (:mod:`repro.core.update`) and
    substitutes its gather primitive with a one-hot contraction (VPU/MXU
    dense math — the TPU replacement for the RTL's table SRAM port).
    Byte streams are therefore structurally identical to
    ``core.coder.encode``;
  * **fused in-kernel byte compaction** (:func:`rans_encode_lanes`, the
    production datapath): a per-lane byte cursor lives in VMEM scratch and
    every renorm record of :func:`repro.core.update.encode_step` is
    scattered straight into the per-lane output streams (one-hot row
    scatter — ``kernels.common.onehot_scatter_rows``).  The LIFO backward
    block walk already emits bytes in exactly the order the wire format
    stores them reversed, so the cursor simply decrements from ``cap`` —
    the TPU analogue of the RAS byte FIFO.  The kernel emits packed
    ``(cap, lanes)`` byte planes plus per-lane start/length/overflow — no
    host-side compaction pass, so encoded bytes cross HBM once;
  * the **records path** (:func:`rans_encode_records`) is retained as the
    bytes-moved reference: it emits the core's fixed-shape renorm records
    (``bytes (T, 2, lanes)`` + ``mask (T, 2, lanes)``) to HBM and leaves
    compaction to :func:`repro.core.bitstream.compact_records` — every
    encoded byte crosses HBM ~2x.  ``benchmarks/bench_speed.py`` diffs the
    two datapaths; the differential tests pin them byte-identical;
  * **adaptive tables**: besides a static ``(K,)`` TableSet the kernel
    accepts per-position ``(T, K)`` and per-position-per-lane
    ``(T, lanes, K)`` tables — the neural-prior layouts of
    ``serve.compress``.  The T axis is blocked through VMEM (``t_block``
    rows of the five encode planes per grid step); encoder state persists
    in scratch between T blocks, so arbitrarily long adaptive streams
    encode without holding all T tables on chip.  rANS is LIFO, so the
    T-block grid axis walks **backward** (the index maps reverse the block
    order) and each block's inner loop walks its rows in reverse;
  * **chunk grid axis**: chunked streams (independent per-chunk flush — the
    interleaved-ANS construction) are ONE ``pallas_call``: the chunk axis
    is a grid dimension, encoder state (and the fused path's byte cursor)
    resets at each chunk's first grid step and the per-chunk stream
    geometry is written at its last.  Each chunk's rows are padded to a
    whole number of T blocks; padding rows emit nothing.

Grid: ``(lanes // lane_block, n_chunks, ceil(chunk_size / t_block))`` — the
T axis iterates fastest (innermost), then chunks, so each (lane block,
chunk) streams its table blocks sequentially while state — and, fused, the
chunk's ``(cap, lane_block)`` output stream — lives in VMEM across T blocks.

VMEM per grid step (fused): symbols (t_block x Lb x 4 B) + stream block
(cap x Lb x 1 B) + five table planes (t_block x [Lb x] K x 4 B adaptive,
K x 4 B static).  For T=4096, Lb=128, K=256 static: ~5.2 MB; for the
(T, lanes, K) adaptive layout, t_block=8 keeps the table slab at ~1.3 MB.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import constants as C
from repro.core import update
from repro.core.spc import TableSet
from repro.kernels import common
from repro.kernels.autotune import ring_size, select_encode_t_block
from repro.kernels.common import (SQUEEZED, chunk_row_spec, onehot_gather,
                                  onehot_gather_lanes, onehot_scatter_rows,
                                  pad_chunk_rows)

_U32 = jnp.uint32
_U8 = jnp.uint8
_I32 = jnp.int32
_M8 = np.uint32(0xFF)

_PLANES = ("rcp", "rshift", "bias", "cmpl", "x_max")


class _Plan(NamedTuple):
    """Shared grid/layout plan of both encode entrypoints (records and
    fused): table layout, padded chunk geometry, kernel inputs + specs."""

    layout: str                  # "static" | "perpos" | "lane"
    lanes: int
    t_len: int
    chunk: int                   # effective chunk size (t_len if monolithic)
    n_chunks: int
    tb: int                      # T-block rows per grid step
    n_tb: int
    padded_chunk: int
    total_rows: int
    k: int
    grid: tuple
    sym_in: jax.Array
    sym_spec: pl.BlockSpec
    planes_in: list
    tbl_specs: list


def _encode_plan(symbols: jax.Array, tbl: TableSet,
                 chunk_size: int | None, lane_block: int,
                 t_block: int | None) -> _Plan:
    """Validate shapes and build the chunk-padded inputs + BlockSpecs shared
    by the records and fused kernels (the LIFO-reversed T-block maps)."""
    lanes, t_len = symbols.shape
    chunk = t_len if chunk_size is None else chunk_size
    if chunk <= 0:
        raise ValueError(f"chunk_size must be positive, got {chunk}")
    chunk = min(chunk, t_len)
    n_chunks = -(-t_len // chunk)
    tb = chunk if t_block is None else max(1, min(t_block, chunk))
    n_tb = -(-chunk // tb)
    padded_chunk = n_tb * tb
    total_rows = n_chunks * padded_chunk

    k = tbl.freq.shape[-1]
    ndim = tbl.freq.ndim
    planes = update.encode_planes(tbl)
    if ndim == 1:
        layout = "static"
        planes_in = [p.reshape(1, k) for p in planes]
        tbl_specs = [pl.BlockSpec((1, k), lambda i, c, j: (0, 0))] * 5
    elif ndim == 2:
        if tbl.freq.shape[0] != t_len:
            raise ValueError(
                f"per-position tables carry T={tbl.freq.shape[0]} rows but "
                f"t_len={t_len}")
        layout = "perpos"
        planes_in = [pad_chunk_rows(p, t_len, chunk, n_chunks, padded_chunk)
                     .reshape(-1, tb, k) for p in planes]
        tbl_specs = [pl.BlockSpec(
            (SQUEEZED, tb, k),
            lambda i, c, j: (c * n_tb + n_tb - 1 - j, 0, 0))] * 5
    elif ndim == 3:
        if tbl.freq.shape[0] != t_len or tbl.freq.shape[1] != lanes:
            raise ValueError(
                f"per-lane tables must be (T, lanes, K)=({t_len}, {lanes}, "
                f"{k}); got {tbl.freq.shape}")
        layout = "lane"
        planes_in = [pad_chunk_rows(p, t_len, chunk, n_chunks, padded_chunk)
                     for p in planes]
        tbl_specs = [pl.BlockSpec(
            (tb, lane_block, k),
            lambda i, c, j: (c * n_tb + n_tb - 1 - j, i, 0))] * 5
    else:
        raise ValueError(f"unsupported table rank {ndim}")

    sym_in = pad_chunk_rows(symbols.T.astype(jnp.int32), t_len, chunk,
                            n_chunks, padded_chunk).reshape(-1, tb, lanes)
    sym_spec = pl.BlockSpec((SQUEEZED, tb, lane_block),
                            lambda i, c, j: (c * n_tb + n_tb - 1 - j, 0, i))
    grid = (lanes // lane_block, n_chunks, n_tb)
    return _Plan(layout=layout, lanes=lanes, t_len=t_len, chunk=chunk,
                 n_chunks=n_chunks, tb=tb, n_tb=n_tb,
                 padded_chunk=padded_chunk, total_rows=total_rows, k=k,
                 grid=grid, sym_in=sym_in, sym_spec=sym_spec,
                 planes_in=planes_in, tbl_specs=tbl_specs)


def _block_entry(sym_ref, rcp_ref, rshift_ref, bias_ref, cmpl_ref, xmax_ref,
                 t, layout: str, planes_static):
    """Gather the encode-side table entry for row ``t`` of this T block."""
    x = sym_ref[pl.dslice(t, 1), :][0]
    if layout == "static":
        planes, g = planes_static, onehot_gather
    elif layout == "perpos":
        planes = update.EncTables(
            rcp_ref[pl.dslice(t, 1), :][0],
            rshift_ref[pl.dslice(t, 1), :][0],
            bias_ref[pl.dslice(t, 1), :][0],
            cmpl_ref[pl.dslice(t, 1), :][0],
            xmax_ref[pl.dslice(t, 1), :][0])
        g = onehot_gather
    else:  # "lane": per-position per-lane rows (lanes, K)
        planes = update.EncTables(
            rcp_ref[pl.dslice(t, 1), :, :][0],
            rshift_ref[pl.dslice(t, 1), :, :][0],
            bias_ref[pl.dslice(t, 1), :, :][0],
            cmpl_ref[pl.dslice(t, 1), :, :][0],
            xmax_ref[pl.dslice(t, 1), :, :][0])
        g = onehot_gather_lanes
    return x, planes, g


def _encode_kernel(sym_ref, rcp_ref, rshift_ref, bias_ref, cmpl_ref,
                   xmax_ref, bytes_ref, mask_ref, state_ref, s_scr,
                   *, t_len: int, chunk_size: int, t_block: int, n_tb: int,
                   layout: str):
    """Records kernel: fixed-shape renorm record planes out to HBM
    (compaction deferred to ``core.bitstream.compact_records``)."""
    lanes = sym_ref.shape[1]
    c = pl.program_id(1)      # chunk index
    j = pl.program_id(2)      # T-block step (innermost; blocks walk backward)

    @pl.when(j == 0)
    def _reset():
        # per-chunk state reset: every chunk is a standalone stream
        s_scr[0, :] = jnp.full((lanes,), C.RANS_L, _U32)

    b = n_tb - 1 - j          # T-block index within the chunk (LIFO order)
    # valid rows in this block: the final chunk may be ragged, and padding
    # rows (up to a whole T block) must emit nothing
    chunk_len = jnp.minimum(chunk_size, t_len - c * chunk_size)
    n_t = jnp.clip(chunk_len - b * t_block, 0, t_block)

    # zero the record block first: rows >= n_t are padding (mask 0), and
    # valid rows overwrite below
    bytes_ref[...] = jnp.zeros(bytes_ref.shape, _U8)
    mask_ref[...] = jnp.zeros(mask_ref.shape, _U8)

    if layout == "static":
        planes_static = update.EncTables(
            rcp_ref[0], rshift_ref[0], bias_ref[0], cmpl_ref[0], xmax_ref[0])
    else:
        planes_static = None

    def body(i, s):
        t = n_t - 1 - i       # rANS is LIFO: walk rows in reverse
        x, planes, g = _block_entry(sym_ref, rcp_ref, rshift_ref, bias_ref,
                                    cmpl_ref, xmax_ref, t, layout,
                                    planes_static)
        e = update.gather_encode_entry(planes, x, gather=g)
        s, recs = update.encode_step(s, e)
        for r, (byte, cond) in enumerate(recs):
            bytes_ref[pl.dslice(t, 1), pl.dslice(r, 1), :] = (
                byte.reshape(1, 1, lanes))
            mask_ref[pl.dslice(t, 1), pl.dslice(r, 1), :] = (
                cond.astype(_U8).reshape(1, 1, lanes))
        return s

    s = jax.lax.fori_loop(0, n_t, body, s_scr[0, :])
    s_scr[0, :] = s

    @pl.when(j == n_tb - 1)
    def _final():
        # the last (backward) block ends at t=0: the chunk's final state
        state_ref[0, :] = s_scr[0, :]


def _encode_fused_kernel(sym_ref, rcp_ref, rshift_ref, bias_ref, cmpl_ref,
                         xmax_ref, buf_ref, start_ref, len_ref, ovf_ref,
                         s_scr, ptr_scr, *scr,
                         t_len: int, chunk_size: int, t_block: int,
                         n_tb: int, layout: str, cap: int,
                         ring: int | None = None):
    """Fused kernel: renorm bytes scatter straight into the per-lane output
    streams (DESIGN.md §8) — no record planes, no host-side compaction.

    The per-lane byte cursor ``ptr`` starts at ``cap`` and decrements per
    emitted byte.  Two scatter datapaths share the cursor semantics of
    ``coder._emit_backward`` (an overflowed cursor goes negative, its
    writes drop — never wrap — and ``cap - ptr`` still reports the true
    byte need):

    * ``ring=None`` (one-hot): each write lands at ``ptr - 1`` via a
      one-hot row select over the chunk's full ``(cap, lanes)`` stream
      block — O(cap x lanes) VPU work per renorm byte;
    * ``ring=<pow2>`` (banked byte ring, DESIGN.md §10): each write lands
      at ``(ptr - 1) & (ring - 1)`` in a ``(ring, lanes)`` VMEM bank —
      O(ring x lanes) per byte.  Because the write row is the *global*
      cursor mod ring, bank row ``r`` always holds target stream row
      ``r (mod ring)``: the per-grid-step drain needs NO rotation, just a
      vertical tile of the bank to ``cap`` rows masked to the rows this
      step's cursor actually crossed (``[ptr_final, ptr_start)`` — a
      contiguous descending LIFO run of at most ``2*t_block + 4 <= ring``
      bytes, so positions are distinct mod ring and stale bank rows are
      never selected).  One roll/flush per grid step; with an unblocked T
      axis that is literally one per chunk.  Negative cursor rows fall
      outside the clipped drain window, preserving overflow/drop parity
      bit-for-bit (including ``cap < 4`` header clipping).

    At the chunk's last grid step the 4-byte big-endian state header is
    flushed through the same scatter path (low byte first — backward
    writes make it big-endian forward) and start/length/overflow are
    published.
    """
    lanes = sym_ref.shape[1]
    bank_scr = scr[0] if ring is not None else None
    c = pl.program_id(1)      # chunk index
    j = pl.program_id(2)      # T-block step (innermost; blocks walk backward)

    @pl.when(j == 0)
    def _reset():
        # per-chunk reset: fresh state, cursor at the buffer tail, zeroed
        # stream block (bytes outside the final span stay 0 on the wire).
        # The ring bank needs no zeroing: the drain mask only selects rows
        # the cursor crossed this step, which are always freshly written.
        s_scr[0, :] = jnp.full((lanes,), C.RANS_L, _U32)
        ptr_scr[0, :] = jnp.full((lanes,), cap, _I32)
        buf_ref[...] = jnp.zeros(buf_ref.shape, _U8)

    b = n_tb - 1 - j          # T-block index within the chunk (LIFO order)
    chunk_len = jnp.minimum(chunk_size, t_len - c * chunk_size)
    n_t = jnp.clip(chunk_len - b * t_block, 0, t_block)

    if layout == "static":
        planes_static = update.EncTables(
            rcp_ref[0], rshift_ref[0], bias_ref[0], cmpl_ref[0], xmax_ref[0])
    else:
        planes_static = None

    def scatter(buf, ptr, byte, cond):
        if ring is None:
            return onehot_scatter_rows(buf, ptr - 1, byte, cond)
        return onehot_scatter_rows(buf, (ptr - 1) & _I32(ring - 1), byte,
                                   cond)

    def body(i, carry):
        s, ptr, buf = carry
        t = n_t - 1 - i       # rANS is LIFO: walk rows in reverse
        x, planes, g = _block_entry(sym_ref, rcp_ref, rshift_ref, bias_ref,
                                    cmpl_ref, xmax_ref, t, layout,
                                    planes_static)
        e = update.gather_encode_entry(planes, x, gather=g)
        s, recs = update.encode_step(s, e)
        for byte, cond in recs:
            buf = scatter(buf, ptr, byte, cond)
            ptr = ptr - cond.astype(_I32)
        return s, ptr, buf

    ptr0 = ptr_scr[0, :]      # cursor at this grid step's start (drain hi)
    s, ptr, buf = jax.lax.fori_loop(
        0, n_t, body,
        (s_scr[0, :], ptr0, bank_scr[...] if ring is not None
         else buf_ref[0]))

    if ring is None:
        buf_ref[0] = buf
        s_scr[0, :] = s
        ptr_scr[0, :] = ptr

        @pl.when(j == n_tb - 1)
        def _flush():
            # chunk's last (backward) block ends at t=0: flush the 4-byte
            # big-endian state header (low byte first — backward writes
            # make it big-endian forward) and publish the stream geometry.
            # A negative cursor means the stream outgrew `cap` — its writes
            # dropped in the scatter, so the stream is truncated-but-
            # flagged, never wrapped.
            s = s_scr[0, :]
            ptr = ptr_scr[0, :]
            buf = buf_ref[0]
            emit = jnp.ones((lanes,), jnp.bool_)
            for shift in (0, 8, 16, 24):
                byte = ((s >> shift) & _M8).astype(_U8)
                buf = onehot_scatter_rows(buf, ptr - 1, byte, emit)
                ptr = ptr - 1
            buf_ref[0] = buf
            ptr_scr[0, :] = ptr
            start_ref[0, :] = jnp.maximum(ptr, 0)
            len_ref[0, :] = jnp.full((lanes,), cap, _I32) - ptr
            ovf_ref[0, :] = (ptr < 0).astype(_I32)
        return

    # ---- banked-ring drain (one roll/flush per grid step) ----
    # fold the header through the same banked path at the chunk's last step
    last = j == n_tb - 1
    hptr, hbank = ptr, buf
    emit = jnp.ones((lanes,), jnp.bool_)
    for shift in (0, 8, 16, 24):
        byte = ((s >> shift) & _M8).astype(_U8)
        hbank = scatter(hbank, hptr, byte, emit)
        hptr = hptr - 1
    bank = jnp.where(last, hbank, buf)
    ptr_f = jnp.where(last, hptr, ptr)
    bank_scr[...] = bank
    # bank row r holds target stream row r (mod ring): tile vertically to
    # cap rows and keep only the rows this step's cursor crossed
    reps = -(-cap // ring)
    tiled = (jnp.concatenate([bank] * reps, axis=0)[:cap] if reps > 1
             else bank[:cap])
    row = jax.lax.broadcasted_iota(_I32, (cap, lanes), 0)
    lo = jnp.clip(ptr_f, 0, cap)[None, :]
    hi = jnp.clip(ptr0, 0, cap)[None, :]
    drained = (row >= lo) & (row < hi)
    buf_ref[0] = jnp.where(drained, tiled, buf_ref[0])
    s_scr[0, :] = s
    ptr_scr[0, :] = ptr_f

    @pl.when(j == n_tb - 1)
    def _publish():
        ptr = ptr_scr[0, :]
        start_ref[0, :] = jnp.maximum(ptr, 0)
        len_ref[0, :] = jnp.full((lanes,), cap, _I32) - ptr
        ovf_ref[0, :] = (ptr < 0).astype(_I32)


@functools.partial(jax.jit,
                   static_argnames=("chunk_size", "prob_bits", "lane_block",
                                    "t_block"))
def rans_encode_records(symbols: jax.Array,   # (lanes, T) int32
                       tbl: TableSet,
                       chunk_size: int | None = None,
                       prob_bits: int = C.PROB_BITS,
                       lane_block: int = 128,
                       t_block: int | None = None):
    """Records-path encode — the bytes-moved *reference* datapath.

    ONE ``pallas_call`` emitting fixed-shape renorm record planes
    (``bytes``/``mask`` of shape ``(n_chunks, padded_chunk, 2, lanes)``)
    plus per-chunk final states; the caller compacts them host-side with
    :func:`repro.core.bitstream.compact_records`.  Every encoded byte
    crosses HBM ~2x (records out, compaction in) — the production path is
    :func:`rans_encode_lanes`, which fuses compaction into the kernel.
    Kept for the bytes-moved benchmark and as a second in-kernel
    implementation the fused path is differential-tested against.

    Table layouts (detected from ``tbl.freq.ndim``):
      * ``(K,)``            — static shared table (classic rANS);
      * ``(T, K)``          — per-position shared rows (neural prior, all
                              lanes share each step's distribution);
      * ``(T, lanes, K)``   — per-position per-lane rows (the
                              ``serve.compress`` TableSet layout).

    ``chunk_size`` (None = monolithic): cut the stream into independent
    chunks, each flushed separately — the chunk axis is a *grid* dimension
    with in-kernel state reset, not a host-side loop of kernel launches.
    ``t_block`` blocks the T axis through VMEM (None = whole chunk in one
    block).

    Returns ``(bytes, mask, states)`` with shapes
    ``(n_chunks, padded_chunk, 2, lanes)`` / same / ``(n_chunks, lanes)``
    where ``padded_chunk = ceil(chunk_size / t_block) * t_block``; padding
    rows carry mask 0 and are dropped by ``compact_records``.
    """
    lanes, _ = symbols.shape
    if lanes % lane_block:
        lane_block = lanes
    p = _encode_plan(symbols, tbl, chunk_size, lane_block, t_block)

    rec_b, rec_m, states = pl.pallas_call(
        functools.partial(_encode_kernel, t_len=p.t_len, chunk_size=p.chunk,
                          t_block=p.tb, n_tb=p.n_tb, layout=p.layout),
        grid=p.grid,
        in_specs=[p.sym_spec] + p.tbl_specs,
        out_specs=[
            pl.BlockSpec((p.tb, C.MAX_RENORM_STEPS, lane_block),
                         lambda i, c, j: (c * p.n_tb + p.n_tb - 1 - j, 0, i)),
            pl.BlockSpec((p.tb, C.MAX_RENORM_STEPS, lane_block),
                         lambda i, c, j: (c * p.n_tb + p.n_tb - 1 - j, 0, i)),
            chunk_row_spec(lane_block),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p.total_rows, C.MAX_RENORM_STEPS, lanes),
                                 _U8),
            jax.ShapeDtypeStruct((p.total_rows, C.MAX_RENORM_STEPS, lanes),
                                 _U8),
            jax.ShapeDtypeStruct((p.n_chunks, 1, lanes), _U32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, lane_block), _U32),   # encoder states across T
        ],
        interpret=common.interpret_mode(),
    )(p.sym_in, *p.planes_in)
    shape = (p.n_chunks, p.padded_chunk, C.MAX_RENORM_STEPS, lanes)
    return rec_b.reshape(shape), rec_m.reshape(shape), states[:, 0]


@functools.partial(jax.jit,
                   static_argnames=("cap", "chunk_size", "prob_bits",
                                    "lane_block", "t_block", "scatter"))
def rans_encode_lanes(symbols: jax.Array,   # (lanes, T) int32
                      tbl: TableSet,
                      cap: int,
                      chunk_size: int | None = None,
                      prob_bits: int = C.PROB_BITS,
                      lane_block: int = 128,
                      t_block: int | None = None,
                      scatter: str = "ring"):
    """Fused-compaction encode — ONE ``pallas_call``, packed streams out.

    The production encode datapath (DESIGN.md §8): renorm bytes scatter
    directly into per-lane output streams inside the kernel (per-lane byte
    cursor in VMEM scratch), so the kernel emits finished wire-format
    streams — byte-identical to ``coder.encode[_chunked]`` and to the
    records path + ``compact_records``, with no host-side compaction pass.

    ``scatter`` selects the in-kernel byte datapath (byte-identical by
    construction, differential-tested):

    * ``"ring"`` (default, DESIGN.md §10): bytes land in a power-of-two
      ``(ring, lane_block)`` VMEM bank at the cursor mod ring — O(ring)
      selects per byte plus one roll/flush per grid step.  The ring is
      sized from ``t_block`` (:func:`ring_size`), so blocking the T axis
      is what makes it small; with ``t_block=None`` the ring spans the
      whole chunk's worst case.
    * ``"onehot"``: the PR-5 path — every byte is a one-hot select over
      the full ``(cap, lane_block)`` stream block, O(cap) per byte.  Kept
      as the differential reference and for the measured scatter-cost
      reduction in ``BENCH_encode.json``.

    Table layouts and ``chunk_size``/``t_block`` semantics are those of
    :func:`rans_encode_records`.  ``cap`` is the per-(chunk, lane) byte
    budget (static: it sizes the output planes); streams that outgrow it
    are truncated-but-flagged exactly like every other encode path.

    Returns ``(buf, start, length, overflow)`` with shapes
    ``(n_chunks, lanes, cap)`` uint8 / ``(n_chunks, lanes)`` int32 x2 /
    ``(n_chunks, lanes)`` bool — ``ChunkedLanes``-layout planes; a
    monolithic call (``chunk_size=None``) yields ``n_chunks == 1`` and the
    caller drops the leading axis for ``EncodedLanes``.
    """
    lanes, _ = symbols.shape
    if lanes % lane_block:
        lane_block = lanes
    if cap <= 0:
        raise ValueError(f"cap must be positive, got {cap}")
    if scatter not in ("ring", "onehot"):
        raise ValueError(f"scatter must be 'ring' or 'onehot', got "
                         f"{scatter!r}")
    if scatter == "ring" and t_block is None:
        # autotuned T blocking: the ring is sized from t_block, so an
        # unblocked T axis would make it span the whole chunk's worst case
        # (>= cap — no cheaper than one-hot).  The analytic work model
        # picks the blocking that minimizes scatter + drain + step cost
        # within the VMEM budget (kernels/autotune.py).
        _, t_len = symbols.shape
        chunk = t_len if chunk_size is None else min(chunk_size, t_len)
        layout = {1: "static", 2: "perpos", 3: "lane"}.get(tbl.freq.ndim)
        if layout is not None and chunk > 0:
            t_block = select_encode_t_block(chunk, cap, lane_block,
                                            tbl.freq.shape[-1], layout)
    p = _encode_plan(symbols, tbl, chunk_size, lane_block, t_block)
    ring = ring_size(p.tb) if scatter == "ring" else None
    scratch = [
        pltpu.VMEM((1, lane_block), _U32),   # encoder states across T
        pltpu.VMEM((1, lane_block), _I32),   # byte cursors across T
    ]
    if ring is not None:
        scratch.append(pltpu.VMEM((ring, lane_block), _U8))  # byte ring bank

    buf, start, length, ovf = pl.pallas_call(
        functools.partial(_encode_fused_kernel, t_len=p.t_len,
                          chunk_size=p.chunk, t_block=p.tb, n_tb=p.n_tb,
                          layout=p.layout, cap=cap, ring=ring),
        grid=p.grid,
        in_specs=[p.sym_spec] + p.tbl_specs,
        out_specs=[
            pl.BlockSpec((1, cap, lane_block), lambda i, c, j: (c, 0, i)),
        ] + [chunk_row_spec(lane_block)] * 3,
        out_shape=[jax.ShapeDtypeStruct((p.n_chunks, cap, lanes), _U8)]
        + [jax.ShapeDtypeStruct((p.n_chunks, 1, lanes), _I32)] * 3,
        scratch_shapes=scratch,
        interpret=common.interpret_mode(),
    )(p.sym_in, *p.planes_in)
    # (n_chunks, cap, lanes) -> the ChunkedLanes (n_chunks, lanes, cap)
    # device form (the mirror of the decode kernel's input transpose)
    return (buf.swapaxes(1, 2), start[:, 0], length[:, 0],
            ovf[:, 0].astype(jnp.bool_))
