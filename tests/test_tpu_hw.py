"""Real-hardware kernel tier (``-m tpu``): compiled kernels on a TPU.

Off the TPU every suite runs the Pallas kernels in interpret mode; on a TPU
they compile (``kernels.common.interpret_mode``).  These tests run the
compiled kernels and re-pin the cross-backend bit-exactness contract there
— run them on a TPU host with

    JAX_PLATFORMS= pytest tests/test_tpu_hw.py -m tpu

(``conftest.py`` pins the CPU unless ``JAX_PLATFORMS`` is set).  They skip
(not fail) anywhere else, so the tier is a no-op on CPU CI and a readiness
gate on hardware.  The backend is asked inside a fixture, never while the
module is imported, so collecting this file initialises no backend.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import coder, spc
from repro.kernels import ops

pytestmark = pytest.mark.tpu


@pytest.fixture(autouse=True, scope="module")
def _on_tpu():
    if jax.default_backend() != "tpu":
        pytest.skip("real-TPU tier: needs a TPU backend (compiled kernels)")


def _case(seed, k, lanes, t):
    rng = np.random.default_rng(seed)
    tbl = spc.tables_from_probs(
        jnp.asarray(rng.dirichlet(np.ones(k) * 0.5), jnp.float32))
    return tbl, jnp.asarray(rng.integers(0, k, (lanes, t)), jnp.int32)


def test_encode_kernel_compiled_bit_exact():
    tbl, syms = _case(400, k=256, lanes=128, t=256)
    got = ops.rans_encode(syms, tbl)
    want = coder.encode(syms, tbl)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_encode_kernel_compiled_chunked_adaptive():
    rng = np.random.default_rng(401)
    k, lanes, t = 64, 128, 192
    probs = rng.dirichlet(np.ones(k) * 0.5, size=t).astype(np.float32)
    tbl = spc.tables_from_probs(jnp.asarray(probs))
    syms = jnp.asarray(rng.integers(0, k, (lanes, t)), jnp.int32)
    got = ops.rans_encode_chunked(syms, tbl, 80, t_block=16)
    want = coder.encode_chunked(syms, tbl, 80)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_decode_kernel_compiled_roundtrip():
    tbl, syms = _case(402, k=256, lanes=128, t=256)
    enc = coder.encode(syms, tbl)
    dec, _ = ops.rans_decode(enc, 256, tbl)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(syms))


def test_spc_kernel_compiled_matches_ref():
    rng = np.random.default_rng(403)
    probs = jnp.asarray(rng.dirichlet(np.ones(256), size=8), jnp.float32)
    got = np.asarray(ops.spc_quantize_tables(probs).freq)
    want = np.asarray(spc.quantize_probs(probs))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("chunk", [None, 80])
def test_ring_scatter_compiled_matches_onehot(chunk):
    """The banked byte-ring encode datapath compiled for real hardware is
    byte-identical to the one-hot row scatter it replaced (both compiled —
    the cross-scatter contract must survive the Mosaic lowering, not just
    the interpreter)."""
    tbl, syms = _case(404, k=256, lanes=128, t=256)
    if chunk is None:
        ring = ops.rans_encode(syms, tbl)
        onehot = ops.rans_encode(syms, tbl, scatter="onehot")
    else:
        ring = ops.rans_encode_chunked(syms, tbl, chunk)
        onehot = ops.rans_encode_chunked(syms, tbl, chunk, scatter="onehot")
    for g, w in zip(ring, onehot):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_zero_copy_slab_decode_compiled_roundtrip():
    """The zero-copy container decode (scalar-prefetch index planes +
    in-kernel window DMA) compiled for real hardware round-trips the
    packed v2 container bit-exactly against the dense-slab kernel."""
    from repro.core import bitstream
    tbl, syms = _case(405, k=256, lanes=128, t=256)
    ch = ops.rans_encode_chunked(syms, tbl, 80)
    blob = bitstream.pack_chunked(*map(np.asarray, ch), chunk_size=80,
                                  n_symbols=256)
    cs = bitstream.parse_chunked(blob)
    dense, _, lp_d = ops.rans_decode_chunked(ch, 256, tbl, 80,
                                             lane_probes=True)
    slab, _, lp_s = ops.rans_decode_chunked(
        n_symbols=256, tbl=tbl, chunk_size=80, lane_probes=True,
        from_container=cs)
    np.testing.assert_array_equal(np.asarray(slab), np.asarray(syms))
    np.testing.assert_array_equal(np.asarray(slab), np.asarray(dense))
    np.testing.assert_array_equal(np.asarray(lp_s), np.asarray(lp_d))


def test_model_logits_extent_invariant_full_width():
    """BatchEngine's byte identity rests on the model pricing a row the
    same whatever else is batched with it: full-width ``ras-pimc`` logits
    must not depend on the row count (32 single-request lanes vs 128
    engine rows) nor on the path (sequential ``decode_step`` vs the
    ``prefill_chunk`` block pass)."""
    from repro.configs import get_config
    from repro.models import decode_step, init_model, init_state
    from repro.models import prefill_chunk
    cfg = get_config("ras-pimc")
    params = init_model(cfg, jax.random.PRNGKey(0))
    s_len, cache_len = 256, 1536        # the engine's chunk and ring
    toks = jnp.asarray(np.random.default_rng(406).integers(
        0, cfg.vocab_size, (128, s_len)), jnp.int32)

    @jax.jit
    def stepwise(toks):
        def body(cache, t):
            lg, cache = decode_step(params, cache, toks[:, t][:, None], t,
                                    cfg)
            return cache, lg
        cache = init_state(cfg, toks.shape[0], cache_len)
        return jnp.moveaxis(jax.lax.scan(body, cache,
                                         jnp.arange(s_len))[1], 0, 1)

    @jax.jit
    def blockwise(toks):
        b = toks.shape[0]
        return prefill_chunk(params, init_state(cfg, b, cache_len), toks,
                             jnp.zeros(b, jnp.int32),
                             jnp.full(b, s_len, jnp.int32), cfg)[0]

    ref = np.asarray(stepwise(toks[:32]))
    np.testing.assert_array_equal(np.asarray(stepwise(toks))[:32], ref)
    np.testing.assert_array_equal(np.asarray(blockwise(toks[:32])), ref)
    np.testing.assert_array_equal(np.asarray(blockwise(toks))[:32], ref)
