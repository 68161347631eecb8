"""Shared helpers for the RAS Pallas kernels.

TPU adaptation notes (DESIGN.md §2):

  * Dynamic per-lane gathers/scatters (the RTL's per-lane FIFO pointers and
    CDF probes) have no native TPU vector instruction.  We lower every such
    access to a **one-hot contraction**: ``table[idx]`` becomes
    ``sum(onehot(idx, K) * table)`` which the MXU/VPU executes as dense
    vector math.  This is the canonical TPU pattern for data-dependent
    addressing and is what the kernels below emit.
  * The lane dimension is kept **last** and sized in multiples of 128 so a
    lane group maps onto one VREG row; all per-lane quantities are
    ``(lanes,)`` vectors.
  * All integer math is uint32 with the same limb tricks as repro.core, so
    the kernels are bit-exact replicas of the reference pipeline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import constants as C
# single-source integer primitives (core/update.py uses numpy-scalar masks,
# so Pallas kernels see literals, not captured device constants); kept as a
# re-export for the kernels' historical import path.
from repro.core.update import umulhi32  # noqa: F401


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: compiled on a TPU,
    interpreted on every other backend (the CPU test substrate).

    The one place the choice is made, so no kernel can run interpreted on
    the chip.  Kernels read it at trace time.
    """
    return jax.default_backend() != "tpu"


# Row-blocked kernel planes are laid out (blocks, rows, ...) and per-chunk
# vectors (n_chunks, 1, lanes), so every block's last two dims either tile
# the TPU's (8, 128) vreg or span the whole dim, as the compiler requires;
# the leading block axis is squeezed away and the kernels see 2-D refs.
SQUEEZED = pl.Squeezed()


def chunk_row_spec(lane_block: int) -> pl.BlockSpec:
    """One chunk's ``(1, lane_block)`` row of an ``(n_chunks, 1, lanes)``
    per-chunk plane (states, cursors, stream geometry, probe counters) on
    the kernels' ``(lane block, chunk, T block)`` grid; trailing index-map
    args are scalar-prefetch refs."""
    return pl.BlockSpec((SQUEEZED, 1, lane_block),
                        lambda i, c, j, *_: (c, 0, i))


def pad_chunk_rows(a: jax.Array, t_len: int, chunk_size: int,
                   n_chunks: int, padded_chunk: int) -> jax.Array:
    """Re-lay rows [0, t_len) chunk-major with each chunk padded to
    ``padded_chunk`` rows (zeros; padding rows are never read/emitting).

    The shared layout transform of the chunk-grid kernels: both the encode
    and decode kernels cut a stream into a chunk grid axis whose every chunk
    spans a whole number of T blocks, so ragged chunks (and the ragged final
    chunk) get zero rows appended up to ``padded_chunk``.
    """
    if padded_chunk == chunk_size and n_chunks * chunk_size == t_len:
        return a    # aligned layout: the re-lay would be an identity copy
    parts = []
    for ci in range(n_chunks):
        sl = a[ci * chunk_size:min((ci + 1) * chunk_size, t_len)]
        pad = padded_chunk - sl.shape[0]
        parts.append(jnp.pad(sl, ((0, pad),) + ((0, 0),) * (a.ndim - 1)))
    return jnp.concatenate(parts, axis=0)


def unpad_chunk_rows(a: jax.Array, t_len: int, chunk_size: int,
                     n_chunks: int, padded_chunk: int) -> jax.Array:
    """Inverse of :func:`pad_chunk_rows`: gather the ``t_len`` valid rows
    back out of the chunk-major padded layout (padding rows dropped)."""
    if padded_chunk == chunk_size and n_chunks * chunk_size == t_len:
        return a
    rows = np.concatenate([
        ci * padded_chunk
        + np.arange(min(chunk_size, t_len - ci * chunk_size))
        for ci in range(n_chunks)])
    return a[jnp.asarray(rows, jnp.int32)]


def onehot_sum(vals: jax.Array, axis: int) -> jax.Array:
    """Sum a one-hot-masked plane along ``axis`` in ``vals.dtype``.

    At most one element per output is non-zero, so the sum is that element.
    The TPU compiler has no unsigned-integer reduction, so the sum runs in
    int32 and converts back: both conversions keep the bits of a lone
    uint32 or uint8 value, so the result is exact.
    """
    return jnp.sum(vals.astype(jnp.int32), axis=axis).astype(vals.dtype)


def onehot_gather(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` as a one-hot contraction.

    table: (K,) uint32/int32; idx: (lanes,) int32  ->  (lanes,) table dtype.
    Exactly one mask element is hot per lane (see :func:`onehot_sum`).
    """
    k = table.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], k), 1)
    hot = iota == idx[:, None].astype(jnp.int32)
    vals = jnp.where(hot, jnp.broadcast_to(table[None, :], hot.shape),
                     jnp.zeros_like(table, shape=hot.shape))
    return onehot_sum(vals, axis=1)


def onehot_gather_lanes(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[lane, idx[lane]]`` per-lane table gather via one-hot.

    table: (lanes, K); idx: (lanes,) int32 -> (lanes,) table dtype.
    The adaptive-table analogue of :func:`onehot_gather`: each lane owns its
    own table row (the neural-prior layout), so the one-hot mask contracts
    the row dimension lane-locally.
    """
    lanes, k = table.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (lanes, k), 1)
    hot = iota == idx[:, None].astype(jnp.int32)
    vals = jnp.where(hot, table, jnp.zeros_like(table))
    return onehot_sum(vals, axis=1)


def onehot_gather_rows(buf: jax.Array, row_idx: jax.Array) -> jax.Array:
    """``buf[row_idx[lane], lane]`` per-lane row gather via one-hot.

    buf: (cap, lanes); row_idx: (lanes,) int32 -> (lanes,) buf dtype.
    Out-of-range rows gather 0 (used for exhausted stream reads).
    """
    cap, lanes = buf.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (cap, lanes), 0)
    hot = iota == row_idx[None, :].astype(jnp.int32)
    vals = jnp.where(hot, buf, jnp.zeros_like(buf))
    return onehot_sum(vals, axis=0)


def read_state_header(buf: jax.Array, ptr: jax.Array,
                      gather=onehot_gather_rows, limit=None):
    """Per-lane 4-byte big-endian rANS state header read (decoder init).

    buf: (cap, lanes) uint8; ptr: (lanes,) int32 read cursors.  Returns the
    reconstructed ``(lanes,)`` uint32 states, the advanced cursors, and a
    ``(lanes,)`` int32 underflow count (header reads at or past ``limit`` —
    the lane's stream end; the one-hot gather already yields 0 there, the
    count makes the exhaustion *detectable*).  The in-kernel single source
    of ``coder.decoder_init``'s header walk, shared by the full decode
    kernel's per-chunk reset and the fused serve step.

    ``gather`` selects the per-lane byte access: the default reads the
    dense right-aligned ``(cap, lanes)`` layout; the zero-copy slab decode
    passes :func:`onehot_gather_lanes` with a lane-major ``(lanes, cap)``
    VMEM window (DESIGN.md §10).  ``limit`` is an int or ``(lanes,)`` array
    of one-past-the-end read bounds (``cap`` for the dense layout,
    ``wstart + wlen`` for slab windows); None skips the accounting.
    """
    s = jnp.zeros((ptr.shape[0],), jnp.uint32)
    under = jnp.zeros((ptr.shape[0],), jnp.int32)
    for _ in range(4):
        if limit is not None:
            under = under + (ptr >= limit).astype(jnp.int32)
        byte = gather(buf, ptr).astype(jnp.uint32)
        s = (s << 8) | byte
        ptr = ptr + 1
    return s, ptr, under


def masked_refill(buf: jax.Array, s: jax.Array, ptr: jax.Array,
                  gather=onehot_gather_rows, limit=None):
    """Fixed ``MAX_RENORM_STEPS``-stage masked byte refill (decode renorm).

    buf: (cap, lanes) uint8; s: (lanes,) uint32; ptr: (lanes,) int32.
    Mirrors the encoder's staged renorm bound: at most two byte reads per
    symbol, lanes above ``RANS_L`` are masked out (the RTL's clock gating).
    Shared by the full decode kernel and the fused serve step kernel.
    ``gather``/``limit`` follow :func:`read_state_header`'s contract; the
    third return is the per-lane count of *active* refills that read at or
    past ``limit`` (stream exhaustion — the injected byte is 0).
    """
    under = jnp.zeros((s.shape[0],), jnp.int32)
    for _ in range(C.MAX_RENORM_STEPS):
        cond = s < jnp.uint32(C.RANS_L)
        if limit is not None:
            under = under + (cond & (ptr >= limit)).astype(jnp.int32)
        byte = gather(buf, ptr).astype(jnp.uint32)
        s = jnp.where(cond, (s << C.RENORM_SHIFT) | byte, s)
        ptr = ptr + cond.astype(jnp.int32)
    return s, ptr, under


def next_pow2(n: int) -> int:
    """Smallest power of two >= ``n`` (>= 1): ring/bank sizes are pow2 so
    the banked cursor's ``& (ring - 1)`` wrap is one integer mask."""
    return 1 << max(int(n) - 1, 0).bit_length()


def onehot_scatter_rows(buf: jax.Array, row_idx: jax.Array, vals: jax.Array,
                        cond: jax.Array) -> jax.Array:
    """``buf[row_idx[lane], lane] = vals[lane]`` where ``cond[lane]``,
    via one-hot select — the write analogue of :func:`onehot_gather_rows`.

    buf: (cap, lanes); row_idx/vals/cond: (lanes,) -> updated (cap, lanes).
    Out-of-range rows (including the negative indices of an overflowed
    backward cursor) match no iota row, so the write is *dropped* — the
    in-kernel equivalent of the coder's out-of-bounds drop sentinel
    (DESIGN.md §3: truncated-but-flagged, never wrapped).
    """
    cap, lanes = buf.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (cap, lanes), 0)
    hot = (iota == row_idx[None, :].astype(jnp.int32)) & cond[None, :]
    return jnp.where(hot, jnp.broadcast_to(vals[None, :], buf.shape), buf)
