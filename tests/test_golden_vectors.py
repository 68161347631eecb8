"""Golden-vector container back-compat (ISSUE 4 satellite).

Frozen blobs under ``tests/golden_vectors/`` pin the wire format across
refactors:

  * **pack identity** — re-encoding each case's seeded symbols through the
    current coder + container writers must reproduce the stored blob
    byte-for-byte (v1, v2, v2+checksums; static/adaptive/chunked tables);
  * **decode identity** — unpacking the *stored bytes* and decoding them on
    every backend (pure-JAX coder AND Pallas kernel, monolithic AND
    chunked single-``pallas_call`` grid) must return the seeded symbols
    exactly;
  * **loud failure** — the suite itself verifies it would catch a
    perturbation: a flipped payload byte in a checksummed blob raises a
    named-cell error, and a single-symbol change produces different
    container bytes (the deliberate-mutation check of the acceptance
    criteria).
"""

import importlib.util
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bitstream, coder
from repro.kernels import ops

_GEN_PATH = os.path.join(os.path.dirname(__file__), "golden_vectors",
                         "generate.py")
_spec = importlib.util.spec_from_file_location("golden_generate", _GEN_PATH)
golden = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(golden)

_IDS = [c["name"] for c in golden.CASES]


def _stored(case):
    with open(golden.blob_path(case), "rb") as f:
        return f.read()


@pytest.mark.parametrize("case", golden.CASES, ids=_IDS)
def test_pack_is_byte_identical_to_golden(case):
    """The current writers reproduce the frozen blob bit-for-bit."""
    assert golden.pack_case(case) == _stored(case), (
        f"{case['name']}: container bytes drifted from the golden vector — "
        "either the wire format changed (version it + regenerate) or the "
        "coder/SPC produced a different stream (a bit-exactness break)")


@pytest.mark.parametrize("case", golden.CASES, ids=_IDS)
def test_stored_blob_decodes_on_every_backend(case):
    """unpack(stored bytes) -> symbol-identical decode, coder AND kernel."""
    tbl, syms = golden.build_case(case)
    blob = _stored(case)
    if case["fmt"] == "v1":
        buf, start, meta = bitstream.unpack(blob)
        assert meta.n_symbols == case["t"] and meta.lanes == case["lanes"]
        enc = coder.EncodedLanes(jnp.asarray(buf), jnp.asarray(start),
                                 jnp.asarray(buf.shape[1] - start))
        got_c, _, lp_c = coder.decode(enc, case["t"], tbl, lane_probes=True)
        got_k, _, lp_k = ops.rans_decode(enc, case["t"], tbl,
                                         lane_probes=True)
    else:
        buf, start, meta = bitstream.unpack_chunked(blob)
        assert (meta.n_symbols, meta.chunk_size) == (case["t"],
                                                     case["chunk_size"])
        ch = coder.ChunkedLanes(jnp.asarray(buf), jnp.asarray(start),
                                jnp.asarray(buf.shape[2] - start))
        got_c, _, lp_c = coder.decode_chunked(ch, case["t"], tbl,
                                              case["chunk_size"],
                                              lane_probes=True)
        got_k, _, lp_k = ops.rans_decode_chunked(ch, case["t"], tbl,
                                                 case["chunk_size"],
                                                 lane_probes=True)
    np.testing.assert_array_equal(np.asarray(got_c), syms)
    np.testing.assert_array_equal(np.asarray(got_k), syms)
    np.testing.assert_array_equal(np.asarray(lp_c), np.asarray(lp_k))


@pytest.mark.parametrize("case", golden.CASES, ids=_IDS)
def test_fused_kernel_encode_repacks_golden(case):
    """Re-encoding each case through the FUSED kernel datapath
    (``ops.rans_encode[_chunked]`` — in-kernel byte compaction, no
    host-side ``compact_records``) and packing reproduces the frozen blob
    byte-for-byte: the fused path lands on the identical wire format."""
    tbl, syms = golden.build_case(case)
    if case["fmt"] == "v1":
        enc = ops.rans_encode(jnp.asarray(syms), tbl)
        blob = bitstream.pack(*map(np.asarray, enc), n_symbols=case["t"])
    else:
        ch = ops.rans_encode_chunked(jnp.asarray(syms), tbl,
                                     case["chunk_size"])
        blob = bitstream.pack_chunked(*map(np.asarray, ch),
                                      chunk_size=case["chunk_size"],
                                      n_symbols=case["t"],
                                      checksums=case["checksums"])
    assert blob == _stored(case), (
        f"{case['name']}: fused kernel encode drifted from the golden "
        "container bytes")


def test_v1_blob_unpacks_through_chunked_reader():
    """Back-compat: v1 golden blob presents as a single-chunk v2 stream."""
    case = golden.CASES[0]
    assert case["fmt"] == "v1"
    tbl, syms = golden.build_case(case)
    buf, start, meta = bitstream.unpack_chunked(_stored(case))
    assert meta.n_chunks == 1 and meta.n_symbols == case["t"]
    ch = coder.ChunkedLanes(jnp.asarray(buf), jnp.asarray(start),
                            jnp.asarray(buf.shape[2] - start))
    got, _ = coder.decode_chunked(ch, case["t"], tbl, meta.chunk_size)
    np.testing.assert_array_equal(np.asarray(got), syms)


# ---------------------------------------------------------------------------
# stack golden vectors (core/stack.py): frozen flushed-stack streams for
# the push/pop interface — uniform, NonUniform statfun, serial-composed,
# and a bits-back schedule with nonzero initial bits (DESIGN.md §12)
# ---------------------------------------------------------------------------

_STACK_IDS = [c["name"] for c in golden.STACK_CASES]


@pytest.mark.parametrize("case", golden.STACK_CASES, ids=_STACK_IDS)
def test_stack_pack_is_byte_identical_to_golden(case):
    """Re-running each push schedule through the live stack + stack_flush
    reproduces the frozen container bytes bit-for-bit."""
    assert golden.pack_stack_case(case) == _stored(case), (
        f"{case['name']}: flushed stack bytes drifted from the golden "
        "vector — the push path (barrett_planes/encode_step/_emit_backward) "
        "no longer lands the same stream")


def test_stack_bitsback_kernel_pops_same_bytes():
    """The bits-back schedule's encode-time pops routed through the Pallas
    per-step kernel must land the identical frozen bytes."""
    case = next(c for c in golden.STACK_CASES
                if c["name"] == "stack_bitsback")
    from repro.core import bitstream, stack
    _, st, _ = golden.run_stack_case(case, backend="kernel")
    blob = bitstream.pack(*map(np.asarray, stack.stack_flush(st)),
                          n_symbols=case["t"])
    assert blob == _stored(case)


@pytest.mark.parametrize("backend", ["coder", "kernel"])
@pytest.mark.parametrize("case", golden.STACK_CASES, ids=_STACK_IDS)
def test_stored_stack_blob_pops_on_every_backend(case, backend):
    """``stack_open(unpack(stored bytes))`` -> the pop schedule recovers
    the seeded symbols exactly, on the pure-JAX coder AND the Pallas
    per-step kernel backend."""
    from repro.core import stack
    st0, st_ref, aux = golden.run_stack_case(case)
    buf, start, meta = bitstream.unpack(_stored(case))
    enc = coder.EncodedLanes(jnp.asarray(buf), jnp.asarray(start),
                             jnp.asarray(buf.shape[1] - start))
    st = stack.stack_open(enc)
    assert not np.asarray(st.underflow).any()
    np.testing.assert_array_equal(np.asarray(st.s), np.asarray(st_ref.s))
    st, got = golden.pop_stack_case(case, st, aux, backend=backend)
    if case["name"] == "stack_bitsback":
        np.testing.assert_array_equal(got["x"], aux["x"])
        np.testing.assert_array_equal(got["k"], aux["k"])
        # the bits-back identity: the reverse schedule re-pushes the
        # posterior bins, restoring the *initial* stack's state exactly
        np.testing.assert_array_equal(np.asarray(st.s), np.asarray(st0.s))
    elif case["name"] == "stack_serial":
        for g, x in zip(got, aux["x"]):
            np.testing.assert_array_equal(g, x)
    else:
        np.testing.assert_array_equal(got, aux["x"])
    assert not np.asarray(st.underflow).any()


def test_stack_overpop_of_stored_blob_flags_underflow():
    """Popping past the end of a frozen stack stream must raise the
    per-lane underflow flag — exhaustion is detectable, never silent."""
    from repro.core import stack
    case = next(c for c in golden.STACK_CASES
                if c["name"] == "stack_uniform")
    _, _, aux = golden.run_stack_case(case)
    buf, start, _ = bitstream.unpack(_stored(case))
    enc = coder.EncodedLanes(jnp.asarray(buf), jnp.asarray(start),
                             jnp.asarray(buf.shape[1] - start))
    st = stack.stack_open(enc)
    codec = stack.Uniform(case["bits"])
    for _ in range(case["t"]):
        st, _x = codec.pop(st)
    assert not np.asarray(st.underflow).any()
    for _ in range(24):                    # drain well past the stream end
        st, _x = codec.pop(st)
    assert np.asarray(st.underflow).all()


# ---------------------------------------------------------------------------
# deliberate-mutation checks: the suite must fail loudly when perturbed
# ---------------------------------------------------------------------------

def test_flipped_payload_byte_is_caught():
    """A checksummed golden blob with one payload byte flipped raises a
    named-cell error instead of silently mis-decoding."""
    case = next(c for c in golden.CASES
                if c["fmt"] == "v2" and c["checksums"])
    blob = bytearray(_stored(case))
    blob[-1] ^= 0xFF                       # last payload byte
    with pytest.raises(ValueError, match=r"chunk \d+, lane \d+"):
        bitstream.unpack_chunked(bytes(blob))


def test_symbol_perturbation_changes_container_bytes():
    """Changing ONE symbol must change the packed bytes — proof the pack
    identity above has teeth."""
    case = golden.CASES[0]
    tbl, syms = golden.build_case(case)
    mut = syms.copy()
    mut[0, 0] = (mut[0, 0] + 1) % case["k"]
    enc = coder.encode(jnp.asarray(mut), tbl)
    blob = bitstream.pack(*map(np.asarray, enc), n_symbols=case["t"])
    assert blob != _stored(case)


def test_truncated_golden_blob_raises_named_error():
    for case in golden.CASES:
        blob = _stored(case)
        with pytest.raises(ValueError, match="truncated|not a RAS"):
            (bitstream.unpack if case["fmt"] == "v1"
             else bitstream.unpack_chunked)(blob[:len(blob) // 2])
