"""Batched serving engine: continuous batching + byte-identity contract.

The engine's whole claim is that co-batching requests into slots of one
traced step program changes THROUGHPUT and nothing else: every per-request
blob (compress) and token matrix (decompress) is byte-identical to the
single-request ``lm_compress_chunked`` / ``lm_decompress_chunked`` path.
These tests pin that contract across the scheduler's moving parts —
ragged chunk-boundary join/retire, seeded Poisson arrivals, per-request
cap overflow, queue overflow, both step backends, and the golden-vector
corpus payloads.
"""

import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core import bitstream
from repro.data.pipeline import token_stream
from repro.models import init_model
from repro.serve.compress import lm_compress_chunked, lm_decompress_chunked
from repro.serve.engine import (BatchEngine, EngineQueueFullError,
                                RequestOverflowError)

CFG = get_smoke_config("ras-pimc")
KEY = jax.random.PRNGKey(2)
LANES = 4

_GEN_PATH = os.path.join(os.path.dirname(__file__), "golden_vectors")
sys.path.insert(0, _GEN_PATH)
from generate import CASES, build_case  # noqa: E402


@pytest.fixture(scope="module")
def params():
    return init_model(CFG, KEY)


def _tokens(t_len, seed):
    return np.asarray(token_stream(CFG.vocab_size, (LANES, t_len),
                                   seed=seed), np.int32)


def _ref_blob(params, toks, chunk_size, prob_bits=None):
    """The single-request reference: lm_compress_chunked -> container."""
    stats = lm_compress_chunked(params, CFG, jnp.asarray(toks),
                                chunk_size=chunk_size)
    enc = jax.tree.map(np.asarray, stats.chunks)
    kw = {} if prob_bits is None else {"prob_bits": prob_bits}
    return bitstream.pack_chunked(enc.buf, enc.start, enc.length,
                                  enc.overflow, chunk_size=chunk_size,
                                  n_symbols=toks.shape[1], **kw)


def test_ragged_join_retire_byte_identity(params):
    """Three ragged requests through two slots: requests join and retire at
    chunk boundaries mid-run (the third admits only once a slot frees) and
    every blob still equals its single-request reference byte for byte."""
    eng = BatchEngine(params, CFG, slots=2, lanes=LANES, chunk_size=8,
                      max_len=32)
    toks = [_tokens(20, 3), _tokens(16, 4), _tokens(9, 5)]
    rids = [eng.submit_compress(t) for t in toks]
    res = eng.run()
    for rid, t in zip(rids, toks):
        assert res[rid].ok, res[rid].error
        assert res[rid].blob == _ref_blob(params, t, 8)
    # continuous batching actually happened: the third request was queued
    # behind a full engine and admitted on a later cycle into a freed slot
    cycles = {rid: cyc for rid, _slot, cyc in eng.trace.admissions}
    assert cycles[rids[0]] == 0 and cycles[rids[1]] == 0
    assert cycles[rids[2]] > 0


def test_mixed_compress_decompress_cobatch(params):
    """Compress and decompress requests share one step program; decoded
    tokens equal the single-request decode AND the original stream."""
    t_a, t_b = _tokens(16, 6), _tokens(12, 7)
    blob_b = _ref_blob(params, t_b, 8)
    eng = BatchEngine(params, CFG, slots=2, lanes=LANES, chunk_size=8,
                      max_len=32)
    rc = eng.submit_compress(t_a)
    rd = eng.submit_decompress(blob_b)
    res = eng.run()
    assert res[rc].ok and res[rc].blob == _ref_blob(params, t_a, 8)
    assert res[rd].ok
    np.testing.assert_array_equal(res[rd].tokens, t_b)
    single = lm_decompress_chunked(params, CFG, bitstream.parse_chunked(blob_b),
                                   t_b.shape[1], 8)[0]
    np.testing.assert_array_equal(res[rd].tokens, np.asarray(single))


def test_golden_vector_corpus_identity(params):
    """The committed golden-vector symbol payloads, fed as token streams
    (every case is lanes=4 with k < vocab), compress through the engine
    byte-identically to the single-request path — the corpus the container
    format is pinned on also pins the scheduler."""
    eng = BatchEngine(params, CFG, slots=2, lanes=LANES, chunk_size=16,
                      max_len=64)
    payloads, rids = [], []
    for case in CASES:
        _tbl, syms = build_case(case)
        payloads.append(np.asarray(syms, np.int32))
        rids.append(eng.submit_compress(payloads[-1]))
    res = eng.run()
    for rid, toks in zip(rids, payloads):
        assert res[rid].ok, res[rid].error
        assert res[rid].blob == _ref_blob(params, toks, 16)


def test_poisson_admission_determinism(params):
    """Seeded Poisson arrivals on the virtual clock: two runs of the same
    workload produce the same admission schedule and identical bytes."""
    rng = np.random.default_rng(17)
    arrivals = np.cumsum(rng.exponential(2.0, size=5))
    toks = [_tokens(12 + 4 * (i % 2), 20 + i) for i in range(5)]

    def run_once():
        eng = BatchEngine(params, CFG, slots=2, lanes=LANES, chunk_size=8,
                          max_len=16)
        rids = [eng.submit_compress(t, arrival=float(a))
                for t, a in zip(toks, arrivals)]
        res = eng.run(clock="virtual")
        return list(eng.trace.admissions), [res[r].blob for r in rids]

    log1, blobs1 = run_once()
    log2, blobs2 = run_once()
    assert log1 == log2
    assert blobs1 == blobs2
    for t, b in zip(toks, blobs1):
        assert b == _ref_blob(params, t, 8)


def test_overflow_isolation(params):
    """A request whose byte budget overflows dies with a named error;
    the co-batched neighbor's output is untouched, byte for byte."""
    t_small_cap, t_ok = _tokens(16, 30), _tokens(16, 31)
    eng = BatchEngine(params, CFG, slots=2, lanes=LANES, chunk_size=8,
                      max_len=16)
    r_bad = eng.submit_compress(t_small_cap, cap=5)
    r_ok = eng.submit_compress(t_ok)
    res = eng.run()
    assert not res[r_bad].ok
    assert isinstance(res[r_bad].error, RequestOverflowError)
    assert "cap=5" in str(res[r_bad].error)
    assert res[r_ok].ok
    assert res[r_ok].blob == _ref_blob(params, t_ok, 8)


def test_queue_full_rejects_at_the_door(params):
    eng = BatchEngine(params, CFG, slots=1, lanes=LANES, chunk_size=8,
                      max_len=16, max_queue=1)
    eng.submit_compress(_tokens(8, 40))
    with pytest.raises(EngineQueueFullError):
        eng.submit_compress(_tokens(8, 41))


def test_kernel_step_backend_parity(params):
    """The fused Pallas decode step and the pure-XLA coder step are the
    same codec: identical blobs from the same engine workload."""
    toks = _tokens(12, 50)
    blobs = {}
    for backend in ("coder", "kernel"):
        eng = BatchEngine(params, CFG, slots=1, lanes=LANES, chunk_size=8,
                          max_len=16, step_backend=backend)
        rid = eng.submit_compress(toks)
        res = eng.run()
        assert res[rid].ok, res[rid].error
        blobs[backend] = res[rid].blob
    assert blobs["coder"] == blobs["kernel"]
    assert blobs["coder"] == _ref_blob(params, toks, 8)


def test_lane_mesh_parity(params):
    """shard_map over the ("lanes",) mesh changes placement, not bytes."""
    from repro.parallel.chunked import lane_mesh
    toks = _tokens(12, 51)
    eng = BatchEngine(params, CFG, slots=1, lanes=LANES, chunk_size=8,
                      max_len=16, mesh=lane_mesh())
    rid = eng.submit_compress(toks)
    res = eng.run()
    assert res[rid].ok, res[rid].error
    assert res[rid].blob == _ref_blob(params, toks, 8)


def test_prefill_fast_path_byte_identity(params):
    """Compress-only cycles take the batched prefill program (teacher-
    forced inputs are known up front) and every blob still equals both the
    ``prefill="off"`` step path and the single-request reference."""
    toks = [_tokens(20, 70), _tokens(16, 71), _tokens(9, 72)]
    blobs, pf = {}, {}
    for mode in ("auto", "off"):
        eng = BatchEngine(params, CFG, slots=2, lanes=LANES, chunk_size=8,
                          max_len=32, prefill=mode)
        rids = [eng.submit_compress(t) for t in toks]
        res = eng.run()
        for rid in rids:
            assert res[rid].ok, res[rid].error
        blobs[mode] = [res[r].blob for r in rids]
        pf[mode] = eng.prefill_cycles
    assert pf["auto"] > 0 and pf["off"] == 0
    assert blobs["auto"] == blobs["off"]
    for t, b in zip(toks, blobs["auto"]):
        assert b == _ref_blob(params, t, 8)


def test_prefill_steps_down_for_wrap_and_decode(params):
    """Wrapped streams and decompress rows feed back step to step, so the
    scheduler must dispatch the sequential program for those cycles:
    ``prefill_cycles`` stays 0 and the outputs stay exact."""
    toks = _tokens(24, 73)
    eng = BatchEngine(params, CFG, slots=1, lanes=LANES, chunk_size=8,
                      max_len=16, prefill="auto")
    rid = eng.submit_compress(toks, allow_wrap=True)
    res = eng.run()
    assert res[rid].ok, res[rid].error
    assert eng.prefill_cycles == 0

    t_b = _tokens(12, 74)
    blob = _ref_blob(params, t_b, 8)
    eng2 = BatchEngine(params, CFG, slots=1, lanes=LANES, chunk_size=8,
                       max_len=16, prefill="auto")
    rd = eng2.submit_decompress(blob)
    res2 = eng2.run()
    assert res2[rd].ok, res2[rd].error
    assert eng2.prefill_cycles == 0
    np.testing.assert_array_equal(res2[rd].tokens, t_b)


def test_wrap_rejected_then_allowed_roundtrip(params):
    """seq > max_len is refused with a named error by default; with
    allow_wrap=True the stream conditions on the ring window and a second
    engine at the same geometry round-trips it exactly."""
    toks = _tokens(24, 60)
    eng = BatchEngine(params, CFG, slots=1, lanes=LANES, chunk_size=8,
                      max_len=16)
    with pytest.raises(ValueError, match="allow_wrap"):
        eng.submit_compress(toks)
    rid = eng.submit_compress(toks, allow_wrap=True)
    res = eng.run()
    assert res[rid].ok, res[rid].error
    eng2 = BatchEngine(params, CFG, slots=1, lanes=LANES, chunk_size=8,
                       max_len=16)
    rid2 = eng2.submit_decompress(res[rid].blob, allow_wrap=True)
    res2 = eng2.run()
    assert res2[rid2].ok, res2[rid2].error
    np.testing.assert_array_equal(res2[rid2].tokens, toks)
