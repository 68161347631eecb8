"""End-to-end LM-driven compression (the paper's full pipeline) + serving."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core import bitstream
from repro.data.pipeline import image_rows, synthetic_image, token_stream
from repro.models import init_model
from repro.serve.compress import (histogram_compress, histogram_decompress,
                                  lm_compress, lm_decompress)
from repro.serve.engine import generate, prefill

CFG = get_smoke_config("ras-pimc")
KEY = jax.random.PRNGKey(1)


@pytest.fixture(scope="module")
def params():
    return init_model(CFG, KEY)


def test_lm_compress_roundtrip_bit_exact(params):
    toks = jnp.asarray(token_stream(CFG.vocab_size, (4, 64), seed=3),
                       jnp.int32)
    stats = lm_compress(params, CFG, toks)
    dec, probes = lm_decompress(params, CFG, stats.enc, 64)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(toks))
    assert float(probes) > 0


def test_lm_compress_kernel_backend_bit_exact(params):
    """backend="kernel" feeds the teacher-forced (T, lanes, K) tables
    straight into the Pallas encode kernel: bytes identical to the coder
    backend, and the stream round-trips through lm_decompress."""
    toks = jnp.asarray(token_stream(CFG.vocab_size, (4, 48), seed=13),
                       jnp.int32)
    a = lm_compress(params, CFG, toks)
    b = lm_compress(params, CFG, toks, backend="kernel")
    for x, y in zip(a.enc, b.enc):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    dec, _ = lm_decompress(params, CFG, b.enc, 48)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(toks))
    with pytest.raises(ValueError, match="backend"):
        lm_compress(params, CFG, toks, backend="nope")


def test_lm_compress_chunked_kernel_backend_bit_exact(params):
    """The chunked serve path through the kernel's chunk grid axis."""
    from repro.serve.compress import (lm_compress_chunked,
                                      lm_decompress_chunked)
    toks = jnp.asarray(token_stream(CFG.vocab_size, (2, 40), seed=14),
                       jnp.int32)
    a = lm_compress_chunked(params, CFG, toks, chunk_size=16)
    b = lm_compress_chunked(params, CFG, toks, chunk_size=16,
                            backend="kernel")
    for x, y in zip(a.chunks, b.chunks):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    dec, _ = lm_decompress_chunked(params, CFG, b.chunks, 40, 16)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(toks))


def test_lm_decompress_kernel_backend_bit_exact(params):
    """The FUSED serve decode: lm_decompress(backend="kernel") — one traced
    program of model step + SPC decode fast path + per-step Pallas kernel —
    round-trips lm_compress(backend="kernel") bit-exactly, with per-lane
    probe counters integer-identical to backend="coder" (all backends
    consume core.search, so the model-top-k candidate planes charge the
    canonical Fig. 4(b) accounting in-kernel)."""
    from repro.serve.compress import lm_decompress
    toks = jnp.asarray(token_stream(CFG.vocab_size, (4, 40), seed=15),
                       jnp.int32)
    stats = lm_compress(params, CFG, toks, backend="kernel")
    dc, ac, lc = lm_decompress(params, CFG, stats.enc, 40,
                               backend="coder", lane_probes=True)
    dk, ak, lk = lm_decompress(params, CFG, stats.enc, 40,
                               backend="kernel", lane_probes=True)
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(toks))
    np.testing.assert_array_equal(np.asarray(dc), np.asarray(dk))
    np.testing.assert_array_equal(np.asarray(lc), np.asarray(lk))
    assert abs(float(ac) - float(ak)) < 1e-5
    # speculation pays off: the model's own top-k resolves most symbols in
    # ~1 probe, far under the log2(vocab) baseline
    assert float(ak) < np.ceil(np.log2(CFG.vocab_size))
    with pytest.raises(ValueError, match="backend"):
        lm_decompress(params, CFG, stats.enc, 40, backend="nope")


def test_lm_decompress_chunked_kernel_backend_bit_exact(params):
    """Chunked FUSED serve decode: one fused program per chunk with the
    model cache and token carried across chunk boundaries — must match the
    sequential coder path symbol-for-symbol and probe-for-probe, ragged
    tail included."""
    from repro.serve.compress import (lm_compress_chunked,
                                      lm_decompress_chunked)
    toks = jnp.asarray(token_stream(CFG.vocab_size, (2, 40), seed=16),
                       jnp.int32)
    st = lm_compress_chunked(params, CFG, toks, chunk_size=16,
                             backend="kernel")   # ragged tail of 8
    dc, ac, lc = lm_decompress_chunked(params, CFG, st.chunks, 40, 16,
                                       backend="coder", lane_probes=True)
    dk, ak, lk = lm_decompress_chunked(params, CFG, st.chunks, 40, 16,
                                       backend="kernel", lane_probes=True)
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(toks))
    np.testing.assert_array_equal(np.asarray(dc), np.asarray(dk))
    np.testing.assert_array_equal(np.asarray(lc), np.asarray(lk))
    assert abs(float(ac) - float(ak)) < 1e-5
    with pytest.raises(ValueError, match="backend"):
        lm_decompress_chunked(params, CFG, st.chunks, 40, 16,
                              backend="nope")


@pytest.mark.slow
def test_lm_decompress_chunked_on_mesh(params):
    """Mesh placement of both kernel decode flavours: backend="two_pass"
    puts pass 2 on the ("chunks",) mesh via parallel.chunked.decode_chunked
    (candidate planes shard with the chunk slab); backend="kernel" (fused)
    shards its independent lane axis on a ("lanes",) mesh.  Symbols and
    probe averages match the no-mesh paths; mis-matched mesh kinds raise."""
    from repro.parallel.chunked import chunk_mesh, lane_mesh
    from repro.serve.compress import (lm_compress_chunked,
                                      lm_decompress_chunked)
    toks = jnp.asarray(token_stream(CFG.vocab_size, (2, 32), seed=17),
                       jnp.int32)
    st = lm_compress_chunked(params, CFG, toks, chunk_size=16,
                             backend="kernel")   # 2 aligned chunks
    d0, a0 = lm_decompress_chunked(params, CFG, st.chunks, 32, 16,
                                   backend="two_pass")
    dm, am = lm_decompress_chunked(params, CFG, st.chunks, 32, 16,
                                   backend="two_pass", mesh=chunk_mesh())
    np.testing.assert_array_equal(np.asarray(dm), np.asarray(toks))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(dm))
    assert abs(float(a0) - float(am)) < 1e-5
    df, af = lm_decompress_chunked(params, CFG, st.chunks, 32, 16,
                                   backend="kernel", mesh=lane_mesh())
    np.testing.assert_array_equal(np.asarray(df), np.asarray(toks))
    assert abs(float(a0) - float(af)) < 1e-5
    with pytest.raises(ValueError, match="lane_probes"):
        lm_decompress_chunked(params, CFG, st.chunks, 32, 16,
                              backend="two_pass", mesh=chunk_mesh(),
                              lane_probes=True)
    with pytest.raises(ValueError, match="lanes"):
        lm_decompress_chunked(params, CFG, st.chunks, 32, 16,
                              backend="kernel", mesh=chunk_mesh())
    with pytest.raises(ValueError, match="mesh"):
        lm_decompress_chunked(params, CFG, st.chunks, 32, 16,
                              backend="coder", mesh=chunk_mesh())


def _teacher_tables_cands(params, cfg, toks, topk):
    """Independent reference: teacher-forced tables + top-k candidate planes
    rebuilt outside serve.compress's decode paths."""
    from repro.core import constants as C
    from repro.core.predictors import model_topk_candidates
    from repro.serve.compress import BOS, _step_tables
    from repro.serve.engine import teacher_forced_scan
    lanes, t = toks.shape
    inputs = jnp.concatenate(
        [jnp.full((lanes, 1), BOS, jnp.int32), toks[:, :-1]], axis=1)

    def per_step(lg, _):
        return (_step_tables(lg, cfg.vocab_size, C.PROB_BITS),
                model_topk_candidates(lg[:, :cfg.vocab_size], topk))

    _, (tables, cands) = teacher_forced_scan(params, cfg, inputs, t,
                                             step_fn=per_step)
    return tables, cands


def test_two_pass_lane_probes_are_kernel_pure(params):
    """Regression: backend="two_pass" lane_probes must come from the kernel
    replay ONLY — integer-identical to coder.decode(candidates=...) on the
    same tables/planes.  The historical bug accumulated pass-1 (pure-scan)
    counters into the reported telemetry, double-charging Fig. 4(b)."""
    from repro.core import coder
    toks = jnp.asarray(token_stream(CFG.vocab_size, (4, 40), seed=19),
                       jnp.int32)
    stats = lm_compress(params, CFG, toks)
    tables, cands = _teacher_tables_cands(params, CFG, toks, topk=4)
    rs, ra, rl = coder.decode(stats.enc, 40, tables, candidates=cands,
                              lane_probes=True)
    sym, avg, lane = lm_decompress(params, CFG, stats.enc, 40,
                                   backend="two_pass", lane_probes=True)
    np.testing.assert_array_equal(np.asarray(sym), np.asarray(toks))
    np.testing.assert_array_equal(np.asarray(rs), np.asarray(sym))
    np.testing.assert_array_equal(np.asarray(rl), np.asarray(lane))
    assert abs(float(ra) - float(avg)) < 1e-6


def test_two_pass_chunked_lane_probes_are_kernel_pure(params):
    """Chunked analogue: pass 1 walks every chunk through the pure scan, so
    a purity bug there inflates counters chunk by chunk; the reported
    per-lane counters must equal coder.decode_chunked(candidates=...)."""
    from repro.core import coder
    from repro.serve.compress import (lm_compress_chunked,
                                      lm_decompress_chunked)
    toks = jnp.asarray(token_stream(CFG.vocab_size, (2, 40), seed=20),
                       jnp.int32)
    st = lm_compress_chunked(params, CFG, toks, chunk_size=16)  # ragged 8
    tables, cands = _teacher_tables_cands(params, CFG, toks, topk=4)
    rs, ra, rl = coder.decode_chunked(st.chunks, 40, tables, 16,
                                      candidates=cands, lane_probes=True)
    sym, avg, lane = lm_decompress_chunked(params, CFG, st.chunks, 40, 16,
                                           backend="two_pass",
                                           lane_probes=True)
    np.testing.assert_array_equal(np.asarray(sym), np.asarray(toks))
    np.testing.assert_array_equal(np.asarray(rs), np.asarray(sym))
    np.testing.assert_array_equal(np.asarray(rl), np.asarray(lane))
    assert abs(float(ra) - float(avg)) < 1e-6


def test_lm_compress_chunked_overflow_parity(params):
    """An under-provisioned cap comes back truncated-but-flagged with the
    SAME per-(chunk, lane) overflow plane on both encode backends, and the
    flagged stream refuses to pack."""
    from repro.serve.compress import lm_compress_chunked
    toks = jnp.asarray(token_stream(CFG.vocab_size, (2, 32), seed=18),
                       jnp.int32)
    a = lm_compress_chunked(params, CFG, toks, chunk_size=16, cap=6)
    b = lm_compress_chunked(params, CFG, toks, chunk_size=16, cap=6,
                            backend="kernel")
    assert np.asarray(a.chunks.overflow).any()
    for x, y in zip(a.chunks, b.chunks):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    with pytest.raises(ValueError, match="overflow"):
        bitstream.pack_chunked(*map(np.asarray, b.chunks), chunk_size=16,
                               n_symbols=32)


def test_lm_compress_respects_model_bound(params):
    """Coded bits/symbol ~ model cross entropy + quantization overhead."""
    toks = jnp.asarray(token_stream(CFG.vocab_size, (8, 128), seed=5),
                       jnp.int32)
    stats = lm_compress(params, CFG, toks)
    bound = float(stats.model_xent_bits)
    got = float(stats.bits_per_symbol)
    assert got >= bound - 0.05            # can't beat the model's entropy
    assert got <= bound + 1.5             # bounded SPC/quantization overhead


def test_lm_compress_across_lane_counts(params):
    """Multi-lane scaling never changes content (per-lane independence)."""
    t = 48
    base = token_stream(CFG.vocab_size, (8, t), seed=9)
    full = lm_compress(params, CFG, jnp.asarray(base, jnp.int32))
    # encode lanes 0..3 alone: identical per-lane payloads
    half = lm_compress(params, CFG, jnp.asarray(base[:4], jnp.int32))
    fb, fs, fl, _ = map(np.asarray, full.enc)
    hb, hs, hl, _ = map(np.asarray, half.enc)
    for i in range(4):
        a = fb[i, fs[i]:fs[i] + fl[i]].tobytes()
        b = hb[i, hs[i]:hs[i] + hl[i]].tobytes()
        assert a == b, f"lane {i} bitstream changed with lane count"


def test_histogram_compress_images():
    img = synthetic_image(32, 64, seed=1)
    rows = img.reshape(8, -1).astype(np.int64)
    enc, tbl = histogram_compress(rows, 256)
    from repro.core import coder
    dec, _ = coder.decode(coder.EncodedLanes(*enc), rows.shape[1], tbl)
    np.testing.assert_array_equal(np.asarray(dec), rows)
    # smooth images compress well below 8 bits/px even with a static table
    bits = float(np.asarray(enc.length).sum()) * 8 / rows.size
    assert bits < 6.0, bits


def test_histogram_decompress_backends_agree():
    """The serve static path decodes through the Pallas kernel by default;
    both backends share core/search.py so symbols and probe telemetry are
    identical."""
    from repro.core import coder
    from repro.core.predictors import NeighborAverage
    img = synthetic_image(32, 64, seed=7)
    rows = img.reshape(8, -1).astype(np.int64)
    enc, tbl = histogram_compress(rows, 256)
    t = rows.shape[1]
    for pred in (None, NeighborAverage(window=4, delta=8)):
        ks, kp = histogram_decompress(coder.EncodedLanes(*enc), t, tbl,
                                      predictor=pred, backend="kernel")
        cs, cp = histogram_decompress(coder.EncodedLanes(*enc), t, tbl,
                                      predictor=pred, backend="coder")
        np.testing.assert_array_equal(np.asarray(ks), rows)
        np.testing.assert_array_equal(np.asarray(ks), np.asarray(cs))
        assert abs(float(kp) - float(cp)) < 1e-6
    with pytest.raises(ValueError, match="backend"):
        histogram_decompress(coder.EncodedLanes(*enc), t, tbl,
                             backend="nope")


def test_container_integration(params):
    toks = jnp.asarray(token_stream(CFG.vocab_size, (4, 32), seed=11),
                       jnp.int32)
    stats = lm_compress(params, CFG, toks)
    blob = bitstream.pack(np.asarray(stats.enc.buf),
                          np.asarray(stats.enc.start),
                          np.asarray(stats.enc.length), n_symbols=32)
    buf, start, meta = bitstream.unpack(blob)
    from repro.core.coder import EncodedLanes
    enc2 = EncodedLanes(jnp.asarray(buf), jnp.asarray(start),
                        jnp.asarray(buf.shape[1] - start))
    dec, _ = lm_decompress(params, CFG, enc2, 32)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(toks))


def test_generate_shapes_and_determinism(params):
    prompt = jnp.asarray(token_stream(CFG.vocab_size, (2, 8), seed=2),
                         jnp.int32)
    out1 = generate(params, CFG, prompt, 12, max_len=32)
    out2 = generate(params, CFG, prompt, 12, max_len=32)
    assert out1.shape == (2, 12)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_prefill_matches_decode_logits(params):
    toks = jnp.asarray(token_stream(CFG.vocab_size, (2, 10), seed=4),
                       jnp.int32)
    _, last = prefill(params, CFG, toks, max_len=16)
    assert last.shape == (2, CFG.vocab_padded)
    assert np.isfinite(np.asarray(last)).all()


def test_data_pipeline_determinism():
    a = token_stream(100, (4, 32), seed=5)
    b = token_stream(100, (4, 32), seed=5)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, token_stream(100, (4, 32), seed=6))
    img = synthetic_image(16, 16, seed=3)
    np.testing.assert_array_equal(img, synthetic_image(16, 16, seed=3))
    rows = image_rows(4, 64, seed=1)
    assert rows.min() >= 0 and rows.max() <= 255
