"""Per-kernel validation: Pallas (interpret mode off the TPU) vs pure-jnp
ref oracles.

Sweeps shapes/dtypes per the kernel-testing contract and asserts exact
integer equality (rANS is bit-exact — allclose degenerates to equality).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import coder, constants as C, spc
from repro.core.predictors import NeighborAverage
from repro.kernels import ops, ref


def _case(seed, k, lanes, t, conc=0.5, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    probs = rng.dirichlet(np.full(k, conc)).astype(np.float32)
    tbl = spc.tables_from_probs(jnp.asarray(probs, dtype))
    syms = jnp.asarray(rng.integers(0, k, (lanes, t)), jnp.int32)
    return tbl, syms


# ---------------------------------------------------------------------------
# rans_encode kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,lanes,t,lane_block", [
    (256, 128, 64, 128),
    (64, 256, 33, 128),     # multi-block grid, odd T
    (17, 128, 128, 64),     # non-pow2 alphabet, smaller block
    (2, 128, 16, 128),      # binary alphabet
])
def test_encode_kernel_bit_exact(k, lanes, t, lane_block):
    tbl, syms = _case(k * 7 + t, k, lanes, t)
    got = ops.rans_encode(syms, tbl, lane_block=lane_block)
    want = ref.rans_encode_ref(syms, tbl)
    np.testing.assert_array_equal(np.asarray(got.start),
                                  np.asarray(want.start))
    np.testing.assert_array_equal(np.asarray(got.buf), np.asarray(want.buf))
    np.testing.assert_array_equal(np.asarray(got.length),
                                  np.asarray(want.length))


@pytest.mark.parametrize("in_dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_encode_kernel_prob_dtypes(in_dtype):
    tbl, syms = _case(5, 32, 128, 40, dtype=in_dtype)
    got = ops.rans_encode(syms, tbl)
    want = ref.rans_encode_ref(syms, tbl)
    np.testing.assert_array_equal(np.asarray(got.buf), np.asarray(want.buf))


def test_encode_kernel_skewed():
    k, lanes, t = 256, 128, 100
    rng = np.random.default_rng(2)
    p = np.full(k, 1e-8)
    p[3] = 1.0
    tbl = spc.tables_from_probs(jnp.asarray(p / p.sum(), jnp.float32))
    syms = jnp.asarray(
        np.where(rng.random((lanes, t)) < 0.97, 3,
                 rng.integers(0, k, (lanes, t))), jnp.int32)
    got = ops.rans_encode(syms, tbl)
    want = ref.rans_encode_ref(syms, tbl)
    np.testing.assert_array_equal(np.asarray(got.buf), np.asarray(want.buf))


# ---------------------------------------------------------------------------
# rans_decode kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,lanes,t,use_pred", [
    (256, 128, 64, False),
    (256, 128, 64, True),
    (40, 256, 50, True),
    (2, 128, 31, False),
])
def test_decode_kernel_roundtrip(k, lanes, t, use_pred):
    tbl, syms = _case(k + lanes + t, k, lanes, t)
    enc = coder.encode(syms, tbl)
    got, _ = ops.rans_decode(enc, t, tbl, use_pred=use_pred)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(syms))


def test_decode_kernel_probes_match_core():
    """The kernel's probe accounting must equal the core decoder's (the
    Fig. 4(b) metric is implementation-independent)."""
    k, lanes, t = 256, 128, 128
    rng = np.random.default_rng(9)
    steps = rng.integers(-3, 4, (lanes, t))
    syms = np.clip(128 + np.cumsum(steps, axis=1), 0, k - 1)
    tbl = jax.tree.map(jnp.asarray, spc.tables_from_counts_np(
        np.bincount(syms.ravel(), minlength=k)))
    enc = coder.encode(jnp.asarray(syms), tbl)
    for use_pred in (False, True):
        got, g_avg, g_lanes = ops.rans_decode(enc, t, tbl, use_pred=use_pred,
                                              lane_probes=True)
        want, w_avg, w_lanes = ref.rans_decode_ref(enc, t, tbl,
                                                   use_pred=use_pred,
                                                   lane_probes=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # canonical accounting (core/search.py): integer-identical per lane
        np.testing.assert_array_equal(np.asarray(g_lanes),
                                      np.asarray(w_lanes))
        assert abs(float(g_avg) - float(w_avg)) < 1e-5
    # prediction must help on this correlated data
    _, base = ops.rans_decode(enc, t, tbl, use_pred=False)
    _, guided = ops.rans_decode(enc, t, tbl, use_pred=True)
    assert float(guided) < 0.75 * float(base)


# ---------------------------------------------------------------------------
# chunked payloads: kernel == coder per chunk (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk_size", [32, 70, 71])   # ragged / exact / one
def test_encode_kernel_chunked_bit_exact(chunk_size):
    """ops.rans_encode_chunked (fused kernel: chunk grid axis + in-kernel
    byte compaction) must be byte-identical to coder.encode_chunked."""
    k, lanes, t = 64, 128, 70
    tbl, syms = _case(99, k, lanes, t)
    got = ops.rans_encode_chunked(syms, tbl, chunk_size)
    want = coder.encode_chunked(syms, tbl, chunk_size)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_encode_kernel_on_chunk_payloads():
    """Standalone kernel encode of each chunk slice == the chunk's cell
    (the chunk-aware cap keeps the fused streams' layout aligned)."""
    k, lanes, t, chunk_size = 64, 128, 70, 32
    tbl, syms = _case(98, k, lanes, t)
    ch = coder.encode_chunked(syms, tbl, chunk_size)
    cap = ch.buf.shape[-1]
    for c, n in enumerate(coder.chunk_lengths(t, chunk_size)):
        sl = syms[:, c * chunk_size:c * chunk_size + n]
        std = ops.rans_encode(sl, tbl, cap=cap)
        got = coder.chunk_encoded(ch, c)
        np.testing.assert_array_equal(np.asarray(std.buf),
                                      np.asarray(got.buf))
        np.testing.assert_array_equal(np.asarray(std.start),
                                      np.asarray(got.start))


@pytest.mark.parametrize("use_pred", [False, True])
def test_decode_kernel_on_chunk_payloads(use_pred):
    """Kernel decode of every chunk matches the core decoder's symbols AND
    probe accounting (the Fig. 4(b) metric survives chunking)."""
    k, lanes, t, chunk_size = 256, 128, 96, 40
    rng = np.random.default_rng(77)
    steps = rng.integers(-3, 4, (lanes, t))
    syms = np.clip(128 + np.cumsum(steps, axis=1), 0, k - 1)
    tbl = jax.tree.map(jnp.asarray, spc.tables_from_counts_np(
        np.bincount(syms.ravel(), minlength=k)))
    ch = coder.encode_chunked(jnp.asarray(syms), tbl, chunk_size)
    for c, n in enumerate(coder.chunk_lengths(t, chunk_size)):
        enc_c = coder.chunk_encoded(ch, c)
        got, g_avg, g_lanes = ops.rans_decode(enc_c, n, tbl,
                                              use_pred=use_pred,
                                              lane_probes=True)
        want, w_avg, w_lanes = ref.rans_decode_ref(enc_c, n, tbl,
                                                   use_pred=use_pred,
                                                   lane_probes=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(got), syms[:, c * chunk_size:c * chunk_size + n])
        np.testing.assert_array_equal(np.asarray(g_lanes),
                                      np.asarray(w_lanes), f"chunk {c}")
        assert abs(float(g_avg) - float(w_avg)) < 1e-5, f"chunk {c} probes"
    # the one-shot chunked wrapper mirrors rans_encode_chunked
    pred = NeighborAverage(window=4, delta=8) if use_pred else None
    got_all, _ = ops.rans_decode_chunked(ch, t, tbl, chunk_size,
                                         predictor=pred)
    np.testing.assert_array_equal(np.asarray(got_all), syms)


# ---------------------------------------------------------------------------
# spc_quantize kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,k,conc", [
    (8, 256, 0.3),
    (16, 64, 2.0),
    (8, 300, 0.1),   # non-pow2 K
])
def test_spc_kernel_matches_ref(b, k, conc):
    rng = np.random.default_rng(b * k)
    probs = jnp.asarray(rng.dirichlet(np.full(k, conc), size=b), jnp.float32)
    got = np.asarray(ops.spc_quantize_tables(probs).freq)
    want = np.asarray(ref.spc_quantize_ref(probs))
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == 1 << C.PROB_BITS).all()


def test_spc_kernel_pathological_rows():
    total = 1 << C.PROB_BITS
    k = 128
    rows = np.stack([
        np.full(k, 1.0 / k),
        np.r_[1.0, np.zeros(k - 1)],
        np.r_[np.full(k - 1, 1e-9), [1.0]],
        np.full(k, 1 / 3),                # unnormalized on purpose
    ] * 2)
    got = np.asarray(ops.spc_quantize_tables(
        jnp.asarray(rows, jnp.float32), batch_block=8).freq)
    want = np.asarray(ref.spc_quantize_ref(jnp.asarray(rows, jnp.float32)))
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == total).all() and got.min() >= 1


def test_spc_kernel_end_to_end_coding():
    """Kernel-built tables must drive a bit-exact encode/decode roundtrip."""
    rng = np.random.default_rng(77)
    k, lanes, t = 64, 128, 64
    probs = jnp.asarray(rng.dirichlet(np.ones(k), size=8), jnp.float32)
    tbl_all = ops.spc_quantize_tables(probs)
    tbl = jax.tree.map(lambda a: a[0], tbl_all)
    syms = jnp.asarray(rng.integers(0, k, (lanes, t)), jnp.int32)
    enc = ops.rans_encode(syms, tbl)
    dec, _ = ops.rans_decode(enc, t, tbl)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(syms))
