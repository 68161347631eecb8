"""Unified encode datapath (ISSUE 3 + ISSUE 5): one shared update core,
every backend, fused in-kernel compaction.

Acceptance pins:
  * the two-stage rANS update + fixed-depth renorm record emission exist
    exactly once, in ``core/update.py`` — ``coder.encode_put``,
    ``coder.encode_records`` and ``kernels/rans_encode.py`` all consume it
    (source-inspection guard below; ``core/golden.py`` and
    ``core/python_baseline.py`` are exempt as intentionally naive scalar
    references);
  * seeded property sweep of ``umulhi32``/``barrett_div``/``encode_step``
    against Python ``//`` + ``%`` big-int arithmetic, including the f==1
    corner and states near 2**31;
  * kernel-backed encode is byte-identical to the coder for static
    ``(K,)``, per-position ``(T, K)``, per-lane ``(T, lanes, K)`` and
    chunked streams (ragged tails included), with
    ``ops.rans_encode_chunked`` issuing a SINGLE ``pallas_call``;
  * **fused compaction** (ISSUE 5): ``ops.rans_encode[_chunked]`` return
    packed streams straight off the kernel — ``compact_records`` is never
    called on the kernel path — and the fused outputs are byte-identical
    to the records reference (records kernel + host compaction) on every
    table family;
  * cap overflow is flagged, truncated writes are dropped (never wrapped),
    and the behavior is identical across all encode paths — records,
    fused-kernel and pure-JAX — down to caps smaller than the 4-byte state
    header, with the container writers refusing every flagged stream.
"""

import inspect

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bitstream, coder, constants as C, spc, update
from repro.kernels import common as kcommon
from repro.kernels import ops, rans_encode, ref


def _assert_streams_equal(got, want):
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# property sweeps: update-core arithmetic vs Python big-int // and %
# ---------------------------------------------------------------------------

def _py_encode_step(s: int, f: int, start: int, prob_bits: int):
    """Scalar reference: staged renorm + textbook two-stage update."""
    x_max = C.x_max_scale(prob_bits) * f
    recs = []
    for _ in range(C.MAX_RENORM_STEPS):
        cond = s >= x_max
        recs.append((s & 0xFF, cond))
        if cond:
            s >>= 8
    return ((s // f) << prob_bits) + (s % f) + start, recs


def _sweep_cases():
    """(f, s) cases: random + f==1 corner + states near 2**31 and the
    renorm thresholds."""
    rng = np.random.default_rng(301)
    total = 1 << C.PROB_BITS
    cases = [(int(rng.integers(1, total)),
              int(rng.integers(C.RANS_L, C.STATE_UPPER)))
             for _ in range(200)]
    for f in (1, 2, 3, total - 1, total // 2):
        x_max = C.x_max_scale(C.PROB_BITS) * f
        for s in (C.RANS_L, C.RANS_L + 1, x_max - 1, x_max, x_max + 1,
                  2**31 - 1, 2**31 - f, C.STATE_UPPER - 1):
            if C.RANS_L <= s < C.STATE_UPPER:
                cases.append((f, s))
    return cases


def test_encode_step_matches_python_reference():
    cases = _sweep_cases()
    total = 1 << C.PROB_BITS
    for f, s in cases:
        tbl = spc.build_tables(jnp.asarray([f, total - f], jnp.uint32))
        e = update.gather_encode_entry(tbl, jnp.zeros((1,), jnp.int32))
        got_s, got_recs = update.encode_step(
            jnp.asarray([s], jnp.uint32), e)
        want_s, want_recs = _py_encode_step(s, f, 0, C.PROB_BITS)
        assert int(got_s[0]) == want_s, (f, s)
        for (gb, gc), (wb, wc) in zip(got_recs, want_recs):
            assert int(gb[0]) == wb and bool(gc[0]) == wc, (f, s)


def test_encode_step_second_symbol_bias_folds_cdf():
    """bias folds C(x): symbol 1 of a two-symbol table lands at start=f0."""
    total = 1 << C.PROB_BITS
    rng = np.random.default_rng(302)
    for _ in range(50):
        f0 = int(rng.integers(1, total))
        f1 = total - f0
        s = int(rng.integers(C.RANS_L, C.STATE_UPPER))
        tbl = spc.build_tables(jnp.asarray([f0, f1], jnp.uint32))
        e = update.gather_encode_entry(tbl, jnp.ones((1,), jnp.int32))
        got_s, _ = update.encode_step(jnp.asarray([s], jnp.uint32), e)
        want_s, _ = _py_encode_step(s, f1, f0, C.PROB_BITS)
        assert int(got_s[0]) == want_s, (f0, s)


def test_barrett_div_and_umulhi_property():
    """update.umulhi32 / update.barrett_div vs Python big-int arithmetic
    (the re-exports in core.coder / kernels.common are this same object)."""
    assert coder.umulhi32 is update.umulhi32
    assert kcommon.umulhi32 is update.umulhi32
    assert coder.barrett_div is update.barrett_div
    rng = np.random.default_rng(303)
    a = rng.integers(0, 2**32, 300, dtype=np.uint64)
    b = rng.integers(0, 2**32, 300, dtype=np.uint64)
    got = np.asarray(update.umulhi32(jnp.asarray(a, jnp.uint32),
                                     jnp.asarray(b, jnp.uint32)))
    np.testing.assert_array_equal(got, ((a * b) >> 32).astype(np.uint32))
    total = 1 << C.PROB_BITS
    f = rng.integers(2, total + 1, 300)
    s = rng.integers(0, 2**31, 300)
    tbl = spc.build_tables(jnp.asarray(
        np.stack([f, total - f + (f == total)], -1), jnp.uint32))
    q = np.asarray(update.barrett_div(jnp.asarray(s, jnp.uint32),
                                      tbl.rcp[:, 0], tbl.rshift[:, 0]))
    np.testing.assert_array_equal(q, (s // f).astype(np.uint32))


# ---------------------------------------------------------------------------
# cross-backend byte differentials: static / (T,K) / (T,lanes,K) / chunked
# ---------------------------------------------------------------------------

def test_encode_kernel_static_differential(rans_case):
    tbl, syms = rans_case(310, k=64, lanes=8, t=70)
    syms = jnp.asarray(syms, jnp.int32)
    _assert_streams_equal(ops.rans_encode(syms, tbl),
                          ref.rans_encode_ref(syms, tbl))


@pytest.fixture(scope="module")
def perpos_enc_case():
    rng = np.random.default_rng(311)
    k, lanes, t = 32, 4, 48
    probs = rng.dirichlet(np.ones(k) * 0.5, size=t).astype(np.float32)
    tbl = spc.tables_from_probs(jnp.asarray(probs))        # (T, K)
    syms = jnp.asarray(rng.integers(0, k, (lanes, t)), jnp.int32)
    return tbl, syms


@pytest.fixture(scope="module")
def perlane_enc_case():
    rng = np.random.default_rng(312)
    k, lanes, t = 16, 4, 32
    probs = rng.dirichlet(np.ones(k) * 0.5,
                          size=(t, lanes)).astype(np.float32)
    tbl = spc.tables_from_probs(jnp.asarray(probs))        # (T, lanes, K)
    syms = jnp.asarray(rng.integers(0, k, (lanes, t)), jnp.int32)
    return tbl, syms


def test_encode_kernel_perpos_differential(perpos_enc_case):
    """Per-position (T, K) tables encode in-kernel — the adaptive case the
    static-table kernel could never serve."""
    tbl, syms = perpos_enc_case
    _assert_streams_equal(ops.rans_encode(syms, tbl),
                          coder.encode(syms, tbl))


def test_encode_kernel_perlane_differential(perlane_enc_case):
    """(T, lanes, K) TableSets — the serve.compress neural-prior layout."""
    tbl, syms = perlane_enc_case
    _assert_streams_equal(ops.rans_encode(syms, tbl),
                          coder.encode(syms, tbl))


def test_t_blocked_encode_matches_single_block(perpos_enc_case,
                                               perlane_enc_case):
    """Blocking the T axis through VMEM (t_block < T) must not change a
    byte: encoder state carries across blocks in scratch."""
    for tbl, syms in (perpos_enc_case, perlane_enc_case):
        whole = ops.rans_encode(syms, tbl)
        for t_block in (5, 16, syms.shape[1]):
            _assert_streams_equal(
                ops.rans_encode(syms, tbl, t_block=t_block), whole)


@pytest.mark.parametrize("chunk_size", [13, 16, 48, 49])
def test_encode_kernel_chunked_differential(perpos_enc_case, chunk_size):
    """ops.rans_encode_chunked == coder.encode_chunked per chunk and per
    lane (per-position tables ride the chunk grid axis; tails ragged)."""
    tbl, syms = perpos_enc_case
    _assert_streams_equal(
        ops.rans_encode_chunked(syms, tbl, chunk_size),
        ref.rans_encode_chunked_ref(syms, tbl, chunk_size))


def test_encode_kernel_chunked_static_and_t_blocked(rans_case):
    tbl, syms = rans_case(313, k=64, lanes=8, t=70)
    syms = jnp.asarray(syms, jnp.int32)
    want = coder.encode_chunked(syms, tbl, 17)
    _assert_streams_equal(ops.rans_encode_chunked(syms, tbl, 17), want)
    _assert_streams_equal(
        ops.rans_encode_chunked(syms, tbl, 17, t_block=5), want)


def test_chunked_encode_is_one_pallas_call(perpos_enc_case, monkeypatch):
    """The chunk axis is a grid dimension, not a host-side loop: a 4-chunk
    adaptive encode must launch exactly ONE pallas_call."""
    tbl, syms = perpos_enc_case
    calls = []
    real = rans_encode.pl.pallas_call

    def counting(*args, **kwargs):
        calls.append(kwargs.get("grid"))
        return real(*args, **kwargs)

    monkeypatch.setattr(rans_encode.pl, "pallas_call", counting)
    # fresh shapes so the jit cache cannot satisfy the call without tracing
    sub = syms[:, :45]
    tbl_sub = jax.tree.map(lambda a: a[:45], tbl)
    ops.rans_encode_chunked(sub, tbl_sub, 12)    # 3 full chunks + tail of 9
    assert len(calls) == 1, f"expected 1 pallas_call, saw {len(calls)}"
    assert calls[0][1] == 4                      # chunk grid axis


def _records_reference(syms, tbl, cap, chunk_size=None):
    """The records datapath: records kernel + host-side compact_records —
    the bytes-moved reference the fused kernel must match byte-for-byte."""
    if chunk_size is None:
        b, m, s = rans_encode.rans_encode_records(syms, tbl)
        return bitstream.compact_records(b[0], m[0], s[0], cap)
    b, m, s = rans_encode.rans_encode_records(syms, tbl,
                                              chunk_size=chunk_size)
    enc = jax.vmap(lambda bb, mm, ss:
                   bitstream.compact_records(bb, mm, ss, cap))(b, m, s)
    return coder.ChunkedLanes(enc.buf, enc.start, enc.length, enc.overflow)


def test_fused_encode_matches_records_reference(rans_case, perpos_enc_case,
                                                perlane_enc_case):
    """The fused in-kernel compaction reproduces the records path (records
    kernel + ``compact_records``) byte-for-byte on every table family —
    same buffers, same geometry, same overflow plane (ISSUE 5 tentpole)."""
    tbl_s, syms_s = rans_case(315, k=64, lanes=8, t=70)
    syms_s = jnp.asarray(syms_s, jnp.int32)
    cases = [(tbl_s, syms_s), perpos_enc_case, perlane_enc_case]
    for tbl, syms in cases:
        cap = coder.default_cap(syms.shape[1])
        _assert_streams_equal(ops.rans_encode(syms, tbl, cap=cap),
                              _records_reference(syms, tbl, cap))
    # chunked (ragged tail): per-chunk cap, per-cell overflow plane
    for tbl, syms in (cases[0], cases[1]):
        cap = coder.default_cap(13)
        _assert_streams_equal(
            ops.rans_encode_chunked(syms, tbl, 13, cap=cap),
            _records_reference(syms, tbl, cap, chunk_size=13))


def test_kernel_encode_path_never_calls_compact_records(rans_case,
                                                        monkeypatch):
    """``ops.rans_encode[_chunked]`` return packed streams with NO host-side
    compaction pass: poison ``compact_records`` everywhere and the kernel
    path must still produce coder-identical streams (the acceptance
    criterion of the fused datapath)."""
    def _boom(*a, **k):
        raise AssertionError(
            "compact_records called on the fused kernel encode path")

    tbl, syms = rans_case(316, k=32, lanes=4, t=41)
    syms = jnp.asarray(syms, jnp.int32)
    want = coder.encode(syms, tbl)
    want_ch = coder.encode_chunked(syms, tbl, 11)
    monkeypatch.setattr(bitstream, "compact_records", _boom)
    monkeypatch.setattr(ops, "compact_records", _boom)
    monkeypatch.setattr(coder, "compact_records", _boom)
    _assert_streams_equal(ops.rans_encode(syms, tbl), want)
    _assert_streams_equal(ops.rans_encode_chunked(syms, tbl, 11), want_ch)


def test_parallel_kernel_encode_backend(rans_case):
    """parallel.chunked.encode_chunked(backend="kernel") under shard_map ==
    the coder path, byte for byte (ragged tail included)."""
    from repro.parallel import chunked as pchunked
    tbl, syms = rans_case(314, k=64, lanes=3, t=131)
    syms = jnp.asarray(syms, jnp.int32)
    mesh = pchunked.chunk_mesh()
    want = coder.encode_chunked(syms, tbl, 17)
    got = pchunked.encode_chunked(syms, tbl, 17, mesh=mesh,
                                  backend="kernel")
    _assert_streams_equal(got, want)
    with pytest.raises(ValueError, match="backend"):
        pchunked.encode_chunked(syms, tbl, 17, backend="nope")


# ---------------------------------------------------------------------------
# cap overflow: flagged, truncated, never wrapped — identically everywhere
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def overflow_case():
    rng = np.random.default_rng(320)
    k, lanes, t = 256, 4, 64
    p = np.full(k, 1e-9)
    p[3] = 1.0
    tbl = spc.tables_from_probs(jnp.asarray(p / p.sum(), jnp.float32))
    syms = rng.integers(0, k, (lanes, t))
    syms[0] = 3                    # lane 0: near-zero-bit stream (fits)
    return tbl, jnp.asarray(syms, jnp.int32)


def test_overflow_flagged_and_truncated_not_wrapped(overflow_case):
    tbl, syms = overflow_case
    big = coder.encode(syms, tbl)
    assert not np.asarray(big.overflow).any()
    need = np.asarray(big.length)
    cap = int(need[0]) + 4         # fits lane 0 only
    small = coder.encode(syms, tbl, cap=cap)
    ovf = np.asarray(small.overflow)
    assert not ovf[0] and ovf[1:].all()
    # length reports the true byte need of the overflowed lanes
    np.testing.assert_array_equal(np.asarray(small.length), need)
    # no wrap corruption: every surviving byte equals the ample-cap
    # encode's buffer tail (pre-fix, wrapped writes clobbered it)
    bb = np.asarray(big.buf)
    np.testing.assert_array_equal(np.asarray(small.buf),
                                  bb[:, bb.shape[1] - cap:])
    # the non-overflowed lane still decodes clean and unflagged; a
    # truncated lane that over-reads its window is detected (post-sweep:
    # the plain entry raises, the flags form isolates it per lane)
    dec, _, under = coder.decode(small, syms.shape[1], tbl,
                                 return_exhausted=True)
    np.testing.assert_array_equal(np.asarray(dec)[0], np.asarray(syms)[0])
    under = np.asarray(under)
    assert not under[0] and under.any()
    with pytest.raises(coder.StreamExhaustedError):
        coder.decode(small, syms.shape[1], tbl)


def test_overflow_identical_across_encode_paths(overflow_case):
    tbl, syms = overflow_case
    cap = 16                       # overflows lanes 1..3
    want = coder.encode(syms, tbl, cap=cap)
    _assert_streams_equal(coder.encode_records(syms, tbl, cap=cap), want)
    _assert_streams_equal(ops.rans_encode(syms, tbl, cap=cap), want)


def test_overflowed_streams_refuse_to_pack(overflow_case):
    """The container writers validate the overflow plane: a truncated
    stream raises instead of shipping an undecodable blob (the plane rides
    the ``pack(*map(np.asarray, enc), ...)`` idiom as the 4th field)."""
    tbl, syms = overflow_case
    small = coder.encode(syms, tbl, cap=16)
    with pytest.raises(ValueError, match="overflow"):
        bitstream.pack(*map(np.asarray, small), n_symbols=syms.shape[1])
    ch = coder.encode_chunked(syms, tbl, 16, cap=12)
    with pytest.raises(ValueError, match="overflow"):
        bitstream.pack_chunked(*map(np.asarray, ch), chunk_size=16,
                               n_symbols=syms.shape[1])
    # healthy streams still pack
    ok = coder.encode(syms, tbl)
    blob = bitstream.pack(*map(np.asarray, ok), n_symbols=syms.shape[1])
    assert bitstream.unpack(blob)[2].n_symbols == syms.shape[1]


def test_overflow_chunked(overflow_case):
    tbl, syms = overflow_case
    want = coder.encode_chunked(syms, tbl, 16, cap=12)
    assert np.asarray(want.overflow).any()
    got = ops.rans_encode_chunked(syms, tbl, 16, cap=12)
    _assert_streams_equal(got, want)
    # ample cap: no flags anywhere
    ok = coder.encode_chunked(syms, tbl, 16)
    assert not np.asarray(ok.overflow).any()


@pytest.mark.parametrize("cap", [3, 5, 12])
def test_overflow_parity_tiny_caps_all_paths(overflow_case, cap):
    """Overflow propagation is identical across the pure-JAX coder, the
    records reference and the fused kernel, down to caps smaller than the
    4-byte state header (where even the header is clipped), on both the
    monolithic and chunked paths — and every flagged stream refuses to
    pack (ISSUE 5 satellite: no path may under-flag a too-small cap)."""
    tbl, syms = overflow_case
    want = coder.encode(syms, tbl, cap=cap)
    assert np.asarray(want.overflow).any()
    _assert_streams_equal(coder.encode_records(syms, tbl, cap=cap), want)
    _assert_streams_equal(ops.rans_encode(syms, tbl, cap=cap), want)
    _assert_streams_equal(_records_reference(syms, tbl, cap), want)
    want_ch = coder.encode_chunked(syms, tbl, 16, cap=cap)
    assert np.asarray(want_ch.overflow).any()
    fused_ch = ops.rans_encode_chunked(syms, tbl, 16, cap=cap)
    _assert_streams_equal(fused_ch, want_ch)
    _assert_streams_equal(
        _records_reference(syms, tbl, cap, chunk_size=16), want_ch)
    # truncated-but-flagged streams refuse to pack on every path
    for enc in (want, ops.rans_encode(syms, tbl, cap=cap)):
        with pytest.raises(ValueError, match="overflow"):
            bitstream.pack(*map(np.asarray, enc), n_symbols=syms.shape[1])
    for ch in (want_ch, fused_ch):
        with pytest.raises(ValueError, match="overflow"):
            bitstream.pack_chunked(*map(np.asarray, ch), chunk_size=16,
                                   n_symbols=syms.shape[1])


# ---------------------------------------------------------------------------
# structural guard: no private update logic outside core/update.py
# (core/golden.py and core/python_baseline.py are exempt: intentionally
# naive scalar references)
# ---------------------------------------------------------------------------

def test_no_private_update_logic_outside_core():
    csrc = inspect.getsource(coder)
    ksrc = inspect.getsource(rans_encode)
    gsrc = inspect.getsource(kcommon)
    for src, name in ((csrc, "core/coder.py"), (ksrc, "kernels/rans_encode"),
                      (gsrc, "kernels/common.py")):
        assert "def umulhi32" not in src, f"{name} redefines umulhi32"
        assert "def barrett_div" not in src, f"{name} redefines barrett_div"
        # the encode-side renorm shift appears only in the update core
        # (decode-side refill shifts left, which is allowed)
        assert ">> C.RENORM_SHIFT" not in src, (
            f"{name} reimplements the encode renorm")
        assert "x_max" not in src or name != "core/coder.py", (
            "core/coder.py touches the renorm threshold directly")
    # both consumers run the shared core
    for src, name in ((csrc, "core/coder.py"),
                      (ksrc, "kernels/rans_encode")):
        assert "update.encode_step" in src, f"{name} bypasses the core"
        assert "update.gather_encode_entry" in src
    # compaction is single-sourced in core/bitstream (kernels re-export)
    osrc = inspect.getsource(ops)
    assert "from repro.core.bitstream import compact_records" in osrc
    assert "def compact_records" not in osrc
    assert "def compact_records" in inspect.getsource(bitstream)
    assert ops.compact_records is bitstream.compact_records
    assert coder.compact_records is bitstream.compact_records


def test_update_module_is_single_source():
    doc = update.__doc__
    for anchor in ("Sec. IV-B", "Sec. IV-A", "DESIGN.md §6",
                   "MAX_RENORM_STEPS"):
        assert anchor in doc
