"""Decoder over-read bugfix sweep + degenerate streams (ISSUE 9 satellites).

Before this fix a decode that ran past the end of a lane's byte window
silently re-read garbage and returned plausible-looking symbols.  Now every
refill past the window injects 0 and raises the lane's underflow flag, and
every HOST decode entry point turns the flag into a named
:class:`repro.core.coder.StreamExhaustedError`:

  * ``coder.decode`` / ``coder.decode_chunked``
  * ``kernels.ops.rans_decode`` / ``rans_decode_chunked``
  * ``parallel.chunked.decode_chunked`` (flags threaded out of shard_map)
  * ``serve.compress.histogram_decompress`` / ``lm_decompress``
  * the batch engine (the request retires with the error; co-batched
    requests are unaffected)

Traced callers opt into flag form with ``return_exhausted`` /
``exhausted_flags``.  The degenerate-stream sweep pins the boundary cases:
``n_symbols == 0`` (zero chunks AND the monolithic 4-flush-byte header-only
stream), single-symbol chunks, both through pack/unpack and both decode
backends.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bitstream, coder, spc
from repro.core.coder import StreamExhaustedError
from repro.kernels import ops as kops

LANES = 4


def _tbl(k, seed):
    probs = np.random.default_rng(seed).dirichlet(np.full(k, 0.5))
    return spc.tables_from_probs(jnp.asarray(probs.astype(np.float32)))


def _syms(k, t, seed):
    return np.random.default_rng(seed).integers(
        0, k, (LANES, t)).astype(np.int32)


def _truncate(enc: coder.EncodedLanes, d: int) -> coder.EncodedLanes:
    """Drop the last ``d`` stream bytes of every lane (the bytes a decode
    reads LAST), keeping the right-aligned layout the readers expect."""
    buf, start = np.asarray(enc.buf), np.asarray(enc.start)
    cap = buf.shape[1]
    out = np.zeros_like(buf)
    for lane in range(buf.shape[0]):
        out[lane, start[lane] + d:] = buf[lane, start[lane]:cap - d]
    return coder.EncodedLanes(buf=jnp.asarray(out),
                              start=jnp.asarray(start + d),
                              length=jnp.asarray(cap - (start + d)))


def _truncate_chunked(ch: coder.ChunkedLanes, d: int) -> coder.ChunkedLanes:
    """Drop ``d`` tail bytes from every lane of the LAST chunk only."""
    buf = np.array(np.asarray(ch.buf))
    start = np.array(np.asarray(ch.start))
    length = np.array(np.asarray(ch.length))
    c, cap = buf.shape[0] - 1, buf.shape[2]
    for lane in range(buf.shape[1]):
        row = buf[c, lane].copy()
        buf[c, lane] = 0
        buf[c, lane, start[c, lane] + d:] = row[start[c, lane]:cap - d]
    start[c] += d
    length[c] -= d
    return coder.ChunkedLanes(buf=jnp.asarray(buf), start=jnp.asarray(start),
                              length=jnp.asarray(length))


# ---------------------------------------------------------------------------
# monolithic streams: over-read and truncation on both backends
# ---------------------------------------------------------------------------

def test_coder_overread_raises_named_error():
    tbl = _tbl(16, 0)
    enc = coder.encode(jnp.asarray(_syms(16, 12, 1)), tbl)
    sym, _ = coder.decode(enc, 12, tbl)          # exact read: fine
    with pytest.raises(StreamExhaustedError, match="lane indices"):
        coder.decode(enc, 16, tbl)               # 4 symbols past the end


def test_coder_truncated_stream_raises():
    tbl = _tbl(16, 2)
    enc = coder.encode(jnp.asarray(_syms(16, 12, 3)), tbl)
    with pytest.raises(StreamExhaustedError):
        coder.decode(_truncate(enc, 2), 12, tbl)


def test_coder_return_exhausted_flags_instead_of_raising():
    tbl = _tbl(16, 4)
    enc = coder.encode(jnp.asarray(_syms(16, 12, 5)), tbl)
    sym, _, under = coder.decode(_truncate(enc, 2), 12, tbl,
                                 return_exhausted=True)
    assert np.asarray(under).any()
    _, _, clean = coder.decode(enc, 12, tbl, return_exhausted=True)
    assert not np.asarray(clean).any()


def test_kernel_overread_and_truncation_raise():
    tbl = _tbl(16, 6)
    syms = _syms(16, 12, 7)
    enc = kops.rans_encode(jnp.asarray(syms), tbl)
    got, _ = kops.rans_decode(enc, 12, tbl)
    np.testing.assert_array_equal(np.asarray(got), syms)
    with pytest.raises(StreamExhaustedError):
        kops.rans_decode(enc, 16, tbl)
    with pytest.raises(StreamExhaustedError):
        kops.rans_decode(_truncate(enc, 2), 12, tbl)
    *_, under = kops.rans_decode(_truncate(enc, 2), 12, tbl,
                                 exhausted_flags=True)
    assert np.asarray(under).any()


# ---------------------------------------------------------------------------
# chunked streams
# ---------------------------------------------------------------------------

def test_chunked_truncated_tail_raises_both_backends():
    tbl = _tbl(16, 8)
    syms = _syms(16, 40, 9)
    ch = coder.encode_chunked(jnp.asarray(syms), tbl, 16)  # ragged tail 8
    bad = _truncate_chunked(ch, 2)
    with pytest.raises(StreamExhaustedError):
        coder.decode_chunked(bad, 40, tbl, 16)
    with pytest.raises(StreamExhaustedError):
        kops.rans_decode_chunked(bad, 40, tbl, 16)
    *_, under = kops.rans_decode_chunked(bad, 40, tbl, 16,
                                         exhausted_flags=True)
    assert np.asarray(under).any()


def test_parallel_decode_chunked_truncated_raises():
    from repro.parallel import chunked as pchunked
    tbl = _tbl(16, 10)
    syms = _syms(16, 64, 11)
    mesh = pchunked.chunk_mesh()
    ch = pchunked.encode_chunked(jnp.asarray(syms), tbl, 16, mesh=mesh)
    got, _ = pchunked.decode_chunked(ch, 64, tbl, 16, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got), syms)
    with pytest.raises(StreamExhaustedError, match="parallel"):
        pchunked.decode_chunked(_truncate_chunked(ch, 2), 64, tbl, 16,
                                mesh=mesh)


# ---------------------------------------------------------------------------
# serve paths: histogram codec and the batch engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["coder", "kernel"])
def test_histogram_decompress_truncated_raises(backend):
    from repro.serve.compress import histogram_compress, histogram_decompress
    rows = _syms(64, 32, 12).astype(np.int64)
    enc, tbl = histogram_compress(rows, 64)
    got = histogram_decompress(enc, 32, tbl, backend=backend)
    np.testing.assert_array_equal(np.asarray(got[0]), rows)
    with pytest.raises(StreamExhaustedError):
        histogram_decompress(_truncate(enc, 2), 32, tbl, backend=backend)


def test_engine_retires_truncated_decompress_with_error():
    """A truncated container retires ITS request with StreamExhaustedError;
    a co-batched healthy request still completes byte-identically."""
    from repro.configs import get_smoke_config
    from repro.data.pipeline import token_stream
    from repro.models import init_model
    from repro.serve.compress import lm_compress_chunked
    from repro.serve.engine import BatchEngine

    cfg = get_smoke_config("ras-pimc")
    params = init_model(cfg, jax.random.PRNGKey(2))
    toks = np.asarray(token_stream(cfg.vocab_size, (LANES, 16), seed=13),
                      np.int32)
    stats = lm_compress_chunked(params, cfg, jnp.asarray(toks), chunk_size=8)
    ch = jax.tree.map(np.asarray, stats.chunks)
    good = bitstream.pack_chunked(ch.buf, ch.start, ch.length, ch.overflow,
                                  chunk_size=8, n_symbols=16)
    bad_ch = _truncate_chunked(stats.chunks, 2)
    bad = bitstream.pack_chunked(
        np.asarray(bad_ch.buf), np.asarray(bad_ch.start),
        np.asarray(bad_ch.length), None, chunk_size=8, n_symbols=16)

    eng = BatchEngine(params, cfg, slots=2, lanes=LANES, chunk_size=8,
                      max_len=32)
    r_bad = eng.submit_decompress(bad)
    r_ok = eng.submit_decompress(good)
    res = eng.run()
    assert not res[r_bad].ok
    assert isinstance(res[r_bad].error, StreamExhaustedError)
    assert "over-read" in str(res[r_bad].error)
    assert res[r_ok].ok, res[r_ok].error
    np.testing.assert_array_equal(np.asarray(res[r_ok].tokens), toks)


# ---------------------------------------------------------------------------
# degenerate streams: n_symbols == 0, header-only, single-symbol chunks
# ---------------------------------------------------------------------------

def test_empty_symbol_block_monolithic_header_only():
    """t = 0 monolithic: the stream is exactly the 4 flush bytes of the
    initial state, identical from the coder and the kernel path, packs and
    unpacks, and decodes to an empty block with no exhaustion."""
    tbl = _tbl(16, 14)
    empty = jnp.zeros((LANES, 0), jnp.int32)
    enc_c = coder.encode(empty, tbl)
    enc_k = kops.rans_encode(empty, tbl)
    np.testing.assert_array_equal(np.asarray(enc_c.length),
                                  np.full(LANES, 4))
    for field in ("buf", "start", "length"):
        np.testing.assert_array_equal(
            np.asarray(getattr(enc_c, field)),
            np.asarray(getattr(enc_k, field)), err_msg=field)
    blob = bitstream.pack(*map(np.asarray, enc_c), n_symbols=0)
    buf, start, meta = bitstream.unpack(blob)
    assert meta.n_symbols == 0
    enc_r = coder.EncodedLanes(jnp.asarray(buf), jnp.asarray(start),
                               jnp.asarray(buf.shape[1] - start))
    for enc, dec in ((enc_r, coder.decode), (enc_r, kops.rans_decode)):
        sym, _, under = dec(enc, 0, tbl, return_exhausted=True) \
            if dec is coder.decode else dec(enc, 0, tbl,
                                            exhausted_flags=True)
        assert sym.shape == (LANES, 0)
        assert not np.asarray(under).any()


def test_empty_symbol_block_chunked_zero_chunks():
    tbl = _tbl(16, 15)
    empty = jnp.zeros((LANES, 0), jnp.int32)
    ch_c = coder.encode_chunked(empty, tbl, 8)
    ch_k = kops.rans_encode_chunked(empty, tbl, 8)
    assert ch_c.buf.shape[0] == 0 and ch_k.buf.shape[0] == 0
    sym, _ = coder.decode_chunked(ch_c, 0, tbl, 8)
    assert sym.shape == (LANES, 0)
    sym_k, _ = kops.rans_decode_chunked(ch_c, 0, tbl, 8)
    assert sym_k.shape == (LANES, 0)
    blob = bitstream.pack_chunked(*map(np.asarray, ch_c), chunk_size=8,
                                  n_symbols=0)
    buf, start, meta = bitstream.unpack_chunked(blob)
    assert meta.n_symbols == 0 and meta.n_chunks == 0


@pytest.mark.parametrize("t", [1, 6])
def test_single_symbol_chunks_roundtrip_both_backends(t):
    """chunk_size = 1: every chunk is one symbol + a full flush header —
    the minimal-chunk corner of the interleaved construction."""
    tbl = _tbl(16, 16)
    syms = _syms(16, t, 17)
    ch = coder.encode_chunked(jnp.asarray(syms), tbl, 1)
    assert ch.buf.shape[0] == t
    got_c, _ = coder.decode_chunked(ch, t, tbl, 1)
    got_k, _ = kops.rans_decode_chunked(ch, t, tbl, 1)
    np.testing.assert_array_equal(np.asarray(got_c), syms)
    np.testing.assert_array_equal(np.asarray(got_k), syms)
    ch_k = kops.rans_encode_chunked(jnp.asarray(syms), tbl, 1)
    for field in ("buf", "start", "length"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ch, field)),
            np.asarray(getattr(ch_k, field)), err_msg=field)


def test_single_symbol_monolithic_roundtrip():
    tbl = _tbl(16, 18)
    syms = _syms(16, 1, 19)
    enc = coder.encode(jnp.asarray(syms), tbl)
    got, _ = coder.decode(enc, 1, tbl)
    np.testing.assert_array_equal(np.asarray(got), syms)
    got_k, _ = kops.rans_decode(enc, 1, tbl)
    np.testing.assert_array_equal(np.asarray(got_k), syms)
