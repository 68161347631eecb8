"""Pallas TPU kernel: SPC BF16 -> fixed-point quantization with mass
correction (paper Sec. IV-A, T1).

The host/XLA reference (core/spc.py) uses two stable argsorts for the
largest-remainder correction.  Sorting is hostile to the TPU vector unit, so
the kernel computes **pairwise stable ranks as dense K x K comparisons** —
an MXU-shaped reformulation that produces *identical* integer frequencies:

    rank_desc(i) = #{j : r_j > r_i} + #{j < i : r_j == r_i}
    rank_asc(i)  = #{j : r_j < r_i} + #{j < i : r_j == r_i}

and the negative-delta waterfill's exclusive prefix-capacity becomes a masked
matrix-vector product  cum_excl(i) = sum_j [rank_asc(j) < rank_asc(i)] cap(j).

The kernel walks its ``Bb`` rows one at a time and each row's (i, j)
comparison planes ``_I_BLOCK`` rows of i at a time, so VMEM holds
(32, K) planes: K=256 -> 32 KB per int32 plane.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import constants as C
from repro.kernels import common

_U32 = jnp.uint32
_I32 = jnp.int32


# rows i of the (i, j) comparison planes per block: the planes of one block
# stay small enough for the vector unit's scoped VMEM at any K
_I_BLOCK = 32


def _spc_quantize_kernel(p_ref, freq_ref, *, prob_bits: int):
    total = 1 << prob_bits
    bb, k = p_ref.shape
    blocks = [(i0, min(_I_BLOCK, k - i0)) for i0 in range(0, k, _I_BLOCK)]

    def per_i(fn):
        """Row ``(1, K)`` of ``fn(i0, n)``'s per-block ``(n,)`` values."""
        return jnp.concatenate([fn(i0, n).reshape(1, n) for i0, n in blocks],
                               axis=1)

    # one distribution per iteration, its (i, j) planes one block of i
    # at a time: a (Bb, K, K) cube leaves the TPU compiler unrolling far
    # more code than it finishes compiling, and whole (K, K) planes
    # overflow the scoped VMEM
    def one_row(b, carry):
        # single BF16 -> fixed-point conversion (the paper's one-shot cast)
        p = p_ref[pl.ds(b, 1), :].astype(jnp.bfloat16).astype(jnp.float32)
        p = jnp.where(jnp.isfinite(p) & (p > 0), p, 0.0)
        scaled = p * jnp.float32(total)
        f0 = jnp.maximum(1, jnp.round(scaled)).astype(_I32)   # (1, K)
        delta = total - jnp.sum(f0, axis=1, keepdims=True)    # (1, 1)
        resid = scaled - f0.astype(jnp.float32)
        col = jnp.transpose(resid)                            # (K, 1)

        # pairwise stable ranks (dense comparisons instead of argsort)
        def rank(cmp):
            def block(i0, n):
                ri = col[i0:i0 + n]                           # (n, 1)
                jlt = (jax.lax.broadcasted_iota(_I32, (n, k), 1)
                       < i0 + jax.lax.broadcasted_iota(_I32, (n, k), 0))
                hit = cmp(resid, ri) | ((resid == ri) & jlt)
                return jnp.sum(hit.astype(_I32), axis=1)
            return per_i(block)
        rank_desc = rank(jnp.greater)
        rank_asc = rank(jnp.less)

        # delta > 0: base share + largest-remainder top-up
        f_pos = f0 + delta // k + (rank_desc < delta % k).astype(_I32)

        # delta < 0: waterfill smallest residual first, capacity f0 - 1;
        # the exclusive prefix capacity sums at most K * 2**prob_bits, so
        # the int32 sum is exact
        need = -delta
        cap = f0 - 1
        asc_col = jnp.transpose(rank_asc)                     # (K, 1)
        cum_excl = per_i(lambda i0, n: jnp.sum(
            jnp.where(rank_asc < asc_col[i0:i0 + n], cap, 0), axis=1))
        take = jnp.clip(need - cum_excl, 0, cap)
        f_neg = f0 - take

        f = jnp.where(delta >= 0, f_pos, f_neg)
        freq_ref[pl.ds(b, 1), :] = f.astype(_U32)
        return carry

    jax.lax.fori_loop(0, bb, one_row, 0)


@functools.partial(jax.jit, static_argnames=("prob_bits", "batch_block"))
def spc_quantize(probs: jax.Array,          # (B, K) float
                 prob_bits: int = C.PROB_BITS,
                 batch_block: int = 8) -> jax.Array:
    """Batched BF16->fixed-point quantization.  Returns (B, K) uint32 freqs."""
    b, k = probs.shape
    if b % batch_block:
        raise ValueError(f"batch {b} not a multiple of {batch_block}")
    return pl.pallas_call(
        functools.partial(_spc_quantize_kernel, prob_bits=prob_bits),
        grid=(b // batch_block,),
        in_specs=[pl.BlockSpec((batch_block, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((batch_block, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k), _U32),
        interpret=common.interpret_mode(),
    )(probs.astype(jnp.float32))
