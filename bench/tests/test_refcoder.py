"""The plain reference coder agrees with the program's integer datapath."""

from __future__ import annotations

import numpy as np
import pytest

import refcoder


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_quantize_matches_program(seed):
    import jax.numpy as jnp
    from repro.core import spc
    rng = np.random.default_rng(seed)
    logits = rng.normal(0, 1 + 3 * seed, (64, 256)).astype(np.float32)
    probs = refcoder.softmax(logits)
    probs[0] = 0.0
    probs[0, 3] = 1.0                       # one spike: delta < 0
    want = np.asarray(spc.quantize_probs(jnp.asarray(probs), 14))
    got = refcoder.quantize(probs, 14)
    assert (got.sum(-1) == 1 << 14).all()
    np.testing.assert_array_equal(got, want)


def test_streams_and_container_match_program():
    import jax.numpy as jnp
    from repro.core import bitstream, coder, spc
    rng = np.random.default_rng(7)
    lanes, t_len, chunk = 4, 40, 16
    syms = rng.integers(0, 256, (lanes, t_len)).astype(np.int32)
    probs = refcoder.softmax(rng.normal(0, 2, (t_len, lanes, 256)))
    freq = refcoder.quantize(probs, 14)
    tbl = spc.build_tables(jnp.asarray(freq, jnp.uint32), 14)
    ch = coder.encode_chunked(jnp.asarray(syms), tbl, chunk)
    ch = [np.asarray(a) for a in ch]
    blob = bitstream.pack_chunked(*ch, chunk_size=chunk, n_symbols=t_len)
    cells = [refcoder.encode_streams(
        syms[:, c:c + chunk], freq[c:c + chunk], 14)
        for c in range(0, t_len, chunk)]
    ref = refcoder.pack_v2(cells, lanes=lanes, n_symbols=t_len,
                           chunk_size=chunk, prob_bits=14)
    assert refcoder.byte_gap(blob, ref) == 0
    assert refcoder.byte_gap(blob, ref[:-1] + bytes([ref[-1] ^ 0xFF])) == 1
