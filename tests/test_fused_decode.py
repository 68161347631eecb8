"""Fused serve-decode differentials (DESIGN.md §9).

Two layers of evidence that the fused path is bit-exact:

  * **per-step kernel vs pure coder** — ``kernels.rans_decode.rans_decode_step``
    (the symbol-pop primitive inside the fused ``lax.scan``) driven over the
    frozen golden-vector corpus: static / per-position / per-lane tables,
    v1 monolithic and v2 chunked blobs with ragged tails.  Symbols AND
    per-lane probe counters must be integer-identical to ``coder.decode``;
  * **three-backend serve sweep** — ``lm_decompress[_chunked]`` with
    ``backend`` in {coder, kernel (fused), two_pass} on the same bitstream,
    with and without model-top-k speculation (``topk=0`` exercises the
    no-candidate kernel specialization), ragged chunk tails included.
"""

import importlib.util
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core import coder, constants as C
from repro.data.pipeline import token_stream
from repro.kernels.rans_decode import rans_decode_step
from repro.models import init_model

_GEN_PATH = os.path.join(os.path.dirname(__file__), "golden_vectors",
                         "generate.py")
_spec = importlib.util.spec_from_file_location("golden_generate", _GEN_PATH)
golden = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(golden)

CFG = get_smoke_config("ras-pimc")
KEY = jax.random.PRNGKey(1)


@pytest.fixture(scope="module")
def params():
    return init_model(CFG, KEY)


def _step_decode_stream(enc, t, tbl, t0=0, prob_bits=C.PROB_BITS):
    """Drive the per-step kernel over a monolithic stream via lax.scan —
    the same shape the fused serve program uses, minus the model."""
    if tbl.freq.ndim == 1:            # static: one table for every step
        fseq = jnp.broadcast_to(tbl.freq, (t,) + tbl.freq.shape)
        cseq = jnp.broadcast_to(tbl.cdf, (t,) + tbl.cdf.shape)
    else:                             # (T, K) per-position / (T, lanes, K)
        fseq = tbl.freq[t0:t0 + t]
        cseq = tbl.cdf[t0:t0 + t]
    dec = coder.decoder_init(enc)
    buf_t = enc.buf.T

    def body(carry, xs):
        s, ptr = carry
        f, c = xs
        s, ptr, sym, p, _ = rans_decode_step(buf_t, s, ptr, f, c,
                                             prob_bits=prob_bits)
        return (s, ptr), (sym, p)

    (_, _), (sym, probes) = jax.lax.scan(body, (dec.s, dec.ptr),
                                         (fseq, cseq))
    return sym.T, jnp.sum(probes, axis=0)


@pytest.mark.parametrize("case", golden.CASES,
                         ids=[c["name"] for c in golden.CASES])
def test_step_kernel_decodes_golden_corpus(case):
    """The fused path's symbol-pop primitive decodes every frozen golden
    vector with symbols and probe counters identical to the pure coder,
    across every table layout and both container formats."""
    tbl, syms = golden.build_case(case)
    t = case["t"]
    if case["fmt"] == "v1":
        enc = coder.encode(jnp.asarray(syms), tbl)
        ref_sym, _, ref_lane = coder.decode(enc, t, tbl, lane_probes=True)
        got, lane = _step_decode_stream(enc, t, tbl)
    else:
        cs = case["chunk_size"]
        ch = coder.encode_chunked(jnp.asarray(syms), tbl, cs)
        ref_sym, _, ref_lane = coder.decode_chunked(ch, t, tbl, cs,
                                                    lane_probes=True)
        outs, lane = [], jnp.zeros((case["lanes"],), jnp.int32)
        for c, n in enumerate(coder.chunk_lengths(t, cs)):
            sym_c, lane_c = _step_decode_stream(
                coder.chunk_encoded(ch, c), n, tbl, t0=c * cs)
            outs.append(sym_c)
            lane = lane + lane_c
        got = jnp.concatenate(outs, axis=1)
    np.testing.assert_array_equal(np.asarray(got), syms)
    np.testing.assert_array_equal(np.asarray(ref_sym), syms)
    np.testing.assert_array_equal(np.asarray(lane), np.asarray(ref_lane))


@pytest.mark.parametrize("topk", [0, 4])
def test_serve_three_backend_sweep(params, topk):
    """coder vs fused vs two-pass on one bitstream: bit-exact symbols and
    integer-identical per-lane probe counters (topk=0 = no speculation —
    the kernels' no-candidate specialization)."""
    from repro.serve.compress import lm_compress, lm_decompress
    toks = jnp.asarray(token_stream(CFG.vocab_size, (4, 40), seed=21),
                       jnp.int32)
    enc = lm_compress(params, CFG, toks, backend="kernel").enc
    res = {b: lm_decompress(params, CFG, enc, 40, topk=topk, backend=b,
                            lane_probes=True)
           for b in ("coder", "kernel", "two_pass")}
    for b, (sym, _, lane) in res.items():
        np.testing.assert_array_equal(np.asarray(sym), np.asarray(toks),
                                      err_msg=f"backend={b}")
        np.testing.assert_array_equal(
            np.asarray(lane), np.asarray(res["coder"][2]),
            err_msg=f"backend={b} probe counters diverge (topk={topk})")


@pytest.mark.parametrize("topk", [0, 4])
def test_serve_three_backend_sweep_chunked_ragged(params, topk):
    """The chunked analogue with a ragged tail (40 symbols, chunk 16): the
    fused path re-initializes coder state per chunk while carrying the model
    cache, the two-pass path replays the chunk grid in one kernel launch —
    both must land on the coder's exact symbols and counters."""
    from repro.serve.compress import (lm_compress_chunked,
                                      lm_decompress_chunked)
    toks = jnp.asarray(token_stream(CFG.vocab_size, (2, 40), seed=22),
                       jnp.int32)
    st = lm_compress_chunked(params, CFG, toks, chunk_size=16,
                             backend="kernel")
    res = {b: lm_decompress_chunked(params, CFG, st.chunks, 40, 16,
                                    topk=topk, backend=b, lane_probes=True)
           for b in ("coder", "kernel", "two_pass")}
    for b, (sym, _, lane) in res.items():
        np.testing.assert_array_equal(np.asarray(sym), np.asarray(toks),
                                      err_msg=f"backend={b}")
        np.testing.assert_array_equal(
            np.asarray(lane), np.asarray(res["coder"][2]),
            err_msg=f"backend={b} probe counters diverge (topk={topk})")
