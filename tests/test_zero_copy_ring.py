"""Zero-copy container decode + banked-ring encode (DESIGN.md §10).

Four contracts of the fused-container PR, pinned:

  * **golden-corpus parity** — decoding every frozen golden container
    ZERO-COPY from the packed slab (``parse_chunked`` ->
    ``from_container``) returns symbols and per-lane probe counters
    identical to the host-unpack dense reference, across v1/v2,
    CRC/no-CRC, static/per-position/per-lane tables, ragged and aligned
    chunking;
  * **the host copy is off the hot path** — with the host right-align
    gather poisoned to raise, the zero-copy kernel path, the threaded
    ``parallel.chunked`` path, and the container-fed serve decodes all
    still run (and the host reference demonstrably trips the poison);
  * **banked-ring identity** — the ring scatter is byte-identical to the
    one-hot scatter it replaced AND to the records reference, across
    table families x chunking x caps including the degenerate cap < 4
    (position-exact overflow/drop semantics);
  * **autotuner model** — the VMEM occupancy model shares one machine
    constant with ``analysis.roofline`` and its selections always fit the
    budget.
"""

import importlib.util
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bitstream, coder, spc
from repro.kernels import ops

_GEN_PATH = os.path.join(os.path.dirname(__file__), "golden_vectors",
                         "generate.py")
_spec = importlib.util.spec_from_file_location("golden_generate_zc",
                                               _GEN_PATH)
golden = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(golden)

_IDS = [c["name"] for c in golden.CASES]


def _stored(case):
    with open(golden.blob_path(case), "rb") as f:
        return f.read()


# ---------------------------------------------------------------------------
# golden-corpus zero-copy parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", golden.CASES, ids=_IDS)
def test_golden_container_zero_copy_parity(case):
    """from_container(stored bytes) == host-unpack dense reference, symbols
    and per-lane probes, on every golden case (v1 included: it parses as a
    single-chunk slab)."""
    tbl, syms = golden.build_case(case)
    blob = _stored(case)
    cs = bitstream.parse_chunked(blob)
    t = case["t"]
    chunk = case["chunk_size"] if case["fmt"] == "v2" else t

    buf, start, meta = bitstream.unpack_chunked(blob)
    ch = coder.ChunkedLanes(jnp.asarray(buf), jnp.asarray(start),
                            jnp.asarray(buf.shape[2] - start))
    ref, _, lp_ref = ops.rans_decode_chunked(ch, t, tbl, chunk,
                                             lane_probes=True)
    got, _, lp_got = ops.rans_decode_chunked(
        n_symbols=t, tbl=tbl, chunk_size=chunk, lane_probes=True,
        from_container=cs)
    np.testing.assert_array_equal(np.asarray(got), syms)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(lp_got), np.asarray(lp_ref))


def test_zero_copy_with_candidates_and_t_block():
    """Speculative candidates and explicit T-blocking ride the zero-copy
    path unchanged (probe accounting identical to the dense kernel)."""
    rng = np.random.default_rng(7)
    k, lanes, t, chunk = 32, 4, 50, 13
    probs = rng.dirichlet(np.full(k, 0.5), size=t).astype(np.float32)
    tbl = spc.tables_from_probs(jnp.asarray(probs))
    syms = jnp.asarray(rng.integers(0, k, (lanes, t)), jnp.int32)
    topk = 4
    cands = jnp.asarray(rng.integers(0, k, (t, lanes, topk)), jnp.int32)
    ch = coder.encode_chunked(syms, tbl, chunk)
    blob = bitstream.pack_chunked(*map(np.asarray, ch), chunk_size=chunk,
                                  n_symbols=t)
    cs = bitstream.parse_chunked(blob)
    ref, _, lp_ref = ops.rans_decode_chunked(ch, t, tbl, chunk,
                                             candidates=cands, t_block=5,
                                             lane_probes=True)
    got, _, lp_got = ops.rans_decode_chunked(
        n_symbols=t, tbl=tbl, chunk_size=chunk, candidates=cands,
        t_block=5, lane_probes=True, from_container=cs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(syms))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(lp_got), np.asarray(lp_ref))


# ---------------------------------------------------------------------------
# the host right-align copy never runs on the zero-copy hot paths
# ---------------------------------------------------------------------------

def _poison(monkeypatch):
    def boom(*a, **k):
        raise AssertionError(
            "host right-align copy ran on a zero-copy hot path")
    monkeypatch.setattr(bitstream, "_right_align_cells", boom)


def test_poisoned_host_copy_trips_the_reference(monkeypatch):
    """Positive control: the poison is real — the host unpack paths die."""
    case = golden.CASES[1]
    blob = _stored(case)
    _poison(monkeypatch)
    with pytest.raises(AssertionError, match="zero-copy hot path"):
        bitstream.unpack_chunked(blob)
    with pytest.raises(AssertionError, match="zero-copy hot path"):
        bitstream.unpack(_stored(golden.CASES[0]))


def test_zero_copy_kernel_paths_never_touch_host_copy(monkeypatch):
    """With the host gather poisoned: parse_chunked + from_container and
    the threaded parallel path still decode correctly."""
    from repro.parallel import chunked as par
    case = golden.CASES[1]
    tbl, syms = golden.build_case(case)
    blob = _stored(case)
    t, chunk = case["t"], case["chunk_size"]
    _poison(monkeypatch)
    cs = bitstream.parse_chunked(blob)
    got, _ = ops.rans_decode_chunked(n_symbols=t, tbl=tbl, chunk_size=chunk,
                                     from_container=cs)
    np.testing.assert_array_equal(np.asarray(got), syms)
    got2, _ = par.decode_chunked(cs, t, tbl, chunk, backend="kernel")
    np.testing.assert_array_equal(np.asarray(got2), syms)
    # coder backend threads through the device-side gather, not the host
    got3, _ = par.decode_chunked(cs, t, tbl, chunk, backend="coder")
    np.testing.assert_array_equal(np.asarray(got3), syms)


def test_serve_container_paths_never_touch_host_copy(monkeypatch):
    """Container-fed serve decodes (fused per-chunk windows and the
    two-pass zero-copy replay) run with the host gather poisoned and
    return the original tokens."""
    from repro.configs import get_smoke_config
    from repro.data.pipeline import token_stream
    from repro.models import init_model
    from repro.serve.compress import (lm_compress_chunked,
                                      lm_decompress_chunked)
    cfg = get_smoke_config("ras-pimc")
    params = init_model(cfg, jax.random.PRNGKey(2))
    toks = jnp.asarray(token_stream(cfg.vocab_size, (2, 26), seed=21),
                       jnp.int32)
    st = lm_compress_chunked(params, cfg, toks, chunk_size=13,
                             backend="kernel")
    blob = bitstream.pack_chunked(*map(np.asarray, st.chunks),
                                  chunk_size=13, n_symbols=26)
    _poison(monkeypatch)
    cs = bitstream.parse_chunked(blob)
    for backend in ("kernel", "two_pass"):
        dec, _ = lm_decompress_chunked(params, cfg, cs, 26, 13,
                                       backend=backend)
        np.testing.assert_array_equal(np.asarray(dec), np.asarray(toks),
                                      backend)


# ---------------------------------------------------------------------------
# banked-ring scatter identity (incl. cap < 4 overflow parity)
# ---------------------------------------------------------------------------

def _family(kind, rng, k, lanes, t):
    if kind == "static":
        probs = rng.dirichlet(np.full(k, 0.5))
    elif kind == "perpos":
        probs = rng.dirichlet(np.full(k, 0.5), size=t)
    else:
        probs = rng.dirichlet(np.full(k, 0.5), size=(t, lanes))
    tbl = spc.tables_from_probs(jnp.asarray(probs.astype(np.float32)))
    syms = jnp.asarray(rng.integers(0, k, (lanes, t)), jnp.int32)
    return tbl, syms


@pytest.mark.parametrize("kind", ["static", "perpos", "perlane"])
@pytest.mark.parametrize("chunk", [None, 13])
def test_ring_scatter_byte_identical(kind, chunk):
    """ring == onehot == pure-JAX coder on every table family x chunking,
    with and without explicit T-blocking."""
    rng = np.random.default_rng(60)
    k, lanes, t = 16, 4, 48
    tbl, syms = _family(kind, rng, k, lanes, t)
    if chunk is None:
        want = coder.encode(syms, tbl)
        ring = ops.rans_encode(syms, tbl)
        onehot = ops.rans_encode(syms, tbl, scatter="onehot")
        ring_tb = ops.rans_encode(syms, tbl, t_block=5)
    else:
        want = coder.encode_chunked(syms, tbl, chunk)
        ring = ops.rans_encode_chunked(syms, tbl, chunk)
        onehot = ops.rans_encode_chunked(syms, tbl, chunk, scatter="onehot")
        ring_tb = ops.rans_encode_chunked(syms, tbl, chunk, t_block=5)
    for a, b, c, d in zip(want, ring, onehot, ring_tb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(d))


@pytest.mark.parametrize("cap", [1, 3, 7])
def test_ring_overflow_parity_tiny_caps(cap):
    """Under-provisioned caps (including cap < 4, where even the state
    header cannot fit): truncated cells carry position-exact bytes and
    identical overflow flags on ring, one-hot and coder paths — negative
    ring cursors drop exactly like negative one-hot rows."""
    rng = np.random.default_rng(61)
    k, lanes, t, chunk = 16, 4, 48, 13
    tbl, syms = _family("static", rng, k, lanes, t)
    want = coder.encode_chunked(syms, tbl, chunk, cap=cap)
    ring = ops.rans_encode_chunked(syms, tbl, chunk, cap=cap)
    onehot = ops.rans_encode_chunked(syms, tbl, chunk, cap=cap,
                                     scatter="onehot")
    assert bool(np.asarray(want.overflow).any())   # caps genuinely tight
    for a, b, c in zip(want, ring, onehot):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_unknown_scatter_rejected():
    rng = np.random.default_rng(62)
    tbl, syms = _family("static", rng, 16, 4, 8)
    with pytest.raises(ValueError, match="scatter"):
        ops.rans_encode(syms, tbl, scatter="nope")


# ---------------------------------------------------------------------------
# autotuner model
# ---------------------------------------------------------------------------

def test_autotuner_and_roofline_share_the_machine_model():
    from repro.analysis import roofline
    from repro.kernels import autotune
    assert roofline.VMEM_BYTES is autotune.VMEM_BYTES
    assert autotune.VMEM_BUDGET <= autotune.VMEM_BYTES


def test_ring_size_covers_worst_case_emission():
    """ring(t_block) must cover the worst-case bytes of one grid step —
    MAX_RENORM_STEPS per symbol plus the 4-byte header — and stay a power
    of two within 2x of that bound."""
    from repro.core import constants as C
    from repro.kernels.autotune import ring_size
    for tb in (1, 5, 8, 13, 16, 48, 128, 512):
        need = C.MAX_RENORM_STEPS * tb + 4
        r = ring_size(tb)
        assert r >= need and r < 2 * need
        assert r & (r - 1) == 0


@pytest.mark.parametrize("layout,k", [("static", 256), ("perpos", 64),
                                      ("lane", 32)])
def test_selected_blocks_fit_the_vmem_budget(layout, k):
    from repro.kernels import autotune as at
    for chunk in (13, 48, 128, 1024):
        cap = coder.default_cap(chunk)
        tb = at.select_encode_t_block(chunk, cap, 128, k, layout)
        assert 1 <= tb <= chunk
        assert at.encode_vmem_bytes(tb, 128, k, layout, cap,
                                    ring=at.ring_size(tb)) <= at.VMEM_BUDGET
        dtb = at.select_decode_t_block(chunk, cap, 128, k, layout, topk=4)
        assert 1 <= dtb <= chunk
        if dtb < chunk:     # only blocked when the full chunk didn't fit
            assert at.decode_vmem_bytes(chunk, 128, k, layout, cap,
                                        topk=4) > at.VMEM_BUDGET


# ---------------------------------------------------------------------------
# vectorized right-align micro-assert (RAS_BITSTREAM_SELFTEST)
# ---------------------------------------------------------------------------

def test_right_align_vectorized_equals_loop_oracle(monkeypatch):
    """The one-gather right-align equals the per-cell loop oracle on both
    branches — checked directly and via the env-gated in-function
    self-assert."""
    rng = np.random.default_rng(63)
    cap, cells = 9, 12
    length = rng.integers(0, cap + 1, size=cells).astype(np.int64)
    starts = np.array([rng.integers(0, cap - ln + 1) for ln in length],
                      np.int64)
    buf = rng.integers(0, 256, size=(cells, cap)).astype(np.uint8)
    payload = np.concatenate(
        [buf[i, s:s + ln] for i, (s, ln) in enumerate(zip(starts, length))])
    offsets = np.concatenate([[0], np.cumsum(length)[:-1]])
    fast = bitstream._right_align_cells(payload, offsets.reshape(1, -1),
                                        length.reshape(1, -1), cap)
    slow = bitstream._right_align_cells_loop(payload, offsets.reshape(1, -1),
                                             length.reshape(1, -1), cap)
    np.testing.assert_array_equal(fast, slow)
    for i, (s, ln) in enumerate(zip(starts, length)):
        np.testing.assert_array_equal(fast[0, i, cap - ln:],
                                      buf[i, s:s + ln])

    monkeypatch.setenv("RAS_BITSTREAM_SELFTEST", "1")
    case = golden.CASES[1]
    tbl, syms = golden.build_case(case)
    buf2, start2, meta = bitstream.unpack_chunked(_stored(case))
    ch = coder.ChunkedLanes(jnp.asarray(buf2), jnp.asarray(start2),
                            jnp.asarray(buf2.shape[2] - start2))
    got, _ = coder.decode_chunked(ch, case["t"], tbl, case["chunk_size"])
    np.testing.assert_array_equal(np.asarray(got), syms)
