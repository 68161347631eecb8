"""Unified decode datapath (ISSUE 2): one shared search core, every backend.

Acceptance pins:
  * ``kernels/rans_decode.py`` and ``kernels/ref.py`` contain no private
    CDF-search or predictor logic — both consume ``core/search.py`` and the
    ``core/predictors`` protocol (source-inspection guard below);
  * kernel vs ``coder.decode`` is byte-identical in symbols AND
    integer-identical in per-lane probe counters for static, adaptive
    (per-position shared and per-lane) and chunked streams, for each
    predictor family;
  * the canonical probe accounting of ``core/search.py`` (window verify
    charged once, skipped after a candidate hit) holds on both backends;
  * predictor edge cases (delta=0, window > T, empty context, degenerate
    candidate lists) stay bit-exact and fall back safely.
"""

import inspect

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import coder, search, spc
from repro.core.predictors import LastValue, NeighborAverage, ZeroPredictor
from repro.data.pipeline import candidate_planes
from repro.kernels import ops, rans_decode, ref


def _candidate_planes(syms, k, topk, hit_rate, seed):
    """jnp view of the shared model-top-k plane synthesizer (the benchmark
    sweep consumes the same one, so it measures what these tests pin)."""
    return jnp.asarray(candidate_planes(np.asarray(syms), k, topk,
                                        hit_rate, seed), jnp.int32)

PREDICTORS = [
    None,
    NeighborAverage(window=4, delta=8),
    NeighborAverage(window=2, delta=4),
    LastValue(delta=8),
    ZeroPredictor(delta=8),
]

_IDS = ["baseline", "navg4", "navg2", "last", "zero"]


def _assert_identical(dec_kernel, dec_coder, syms):
    gsym, gavg, glanes = dec_kernel
    wsym, wavg, wlanes = dec_coder
    np.testing.assert_array_equal(np.asarray(gsym), np.asarray(wsym))
    np.testing.assert_array_equal(np.asarray(gsym), np.asarray(syms))
    np.testing.assert_array_equal(np.asarray(glanes), np.asarray(wlanes))
    assert abs(float(gavg) - float(wavg)) < 1e-5


# ---------------------------------------------------------------------------
# cross-backend differentials: static / per-position / per-lane / chunked
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("predictor", PREDICTORS, ids=_IDS)
def test_static_differential(rans_case, predictor):
    tbl, syms = rans_case(70, k=64, lanes=8, t=64)
    enc = coder.encode(jnp.asarray(syms), tbl)
    got = ops.rans_decode(enc, 64, tbl, predictor=predictor,
                          lane_probes=True)
    want = ref.rans_decode_ref(enc, 64, tbl, predictor=predictor,
                               lane_probes=True)
    _assert_identical(got, want, syms)


@pytest.fixture(scope="module")
def perpos_case():
    rng = np.random.default_rng(71)
    k, lanes, t = 32, 4, 48
    probs = rng.dirichlet(np.ones(k) * 0.5, size=t).astype(np.float32)
    tbl = spc.tables_from_probs(jnp.asarray(probs))        # (T, K)
    syms = jnp.asarray(rng.integers(0, k, (lanes, t)), jnp.int32)
    return tbl, syms


@pytest.fixture(scope="module")
def perlane_case():
    rng = np.random.default_rng(72)
    k, lanes, t = 16, 4, 32
    probs = rng.dirichlet(np.ones(k) * 0.5,
                          size=(t, lanes)).astype(np.float32)
    tbl = spc.tables_from_probs(jnp.asarray(probs))        # (T, lanes, K)
    syms = jnp.asarray(rng.integers(0, k, (lanes, t)), jnp.int32)
    return tbl, syms


@pytest.mark.parametrize("predictor", PREDICTORS, ids=_IDS)
def test_adaptive_perpos_differential(perpos_case, predictor):
    """Per-position (T, K) tables decode in-kernel — the adaptive case the
    static-table kernel could never serve."""
    tbl, syms = perpos_case
    t = syms.shape[1]
    enc = coder.encode(syms, tbl)
    got = ops.rans_decode(enc, t, tbl, predictor=predictor, lane_probes=True)
    want = coder.decode(enc, t, tbl, predictor=predictor, lane_probes=True)
    _assert_identical(got, want, syms)


@pytest.mark.parametrize("predictor", [None, NeighborAverage(2, 4)],
                         ids=["baseline", "navg2"])
def test_adaptive_perlane_differential(perlane_case, predictor):
    """(T, lanes, K) TableSets — the serve.compress neural-prior layout."""
    tbl, syms = perlane_case
    t = syms.shape[1]
    enc = coder.encode(syms, tbl)
    got = ops.rans_decode(enc, t, tbl, predictor=predictor, lane_probes=True)
    want = coder.decode(enc, t, tbl, predictor=predictor, lane_probes=True)
    _assert_identical(got, want, syms)


@pytest.mark.parametrize("predictor", [None, NeighborAverage(4, 8),
                                       LastValue(8)],
                         ids=["baseline", "navg4", "last"])
def test_chunked_differential(perpos_case, predictor):
    """ops.rans_decode_chunked == coder.decode_chunked per lane and per
    chunk, ragged tail included (chunk_size 13 over T=48)."""
    tbl, syms = perpos_case
    t = syms.shape[1]
    ch = coder.encode_chunked(syms, tbl, 13)
    got = ops.rans_decode_chunked(ch, t, tbl, 13, predictor=predictor,
                                  lane_probes=True)
    want = coder.decode_chunked(ch, t, tbl, 13, predictor=predictor,
                                lane_probes=True)
    _assert_identical(got, want, syms)


@pytest.mark.parametrize("layout", ["static", "perpos", "perlane"])
def test_candidate_plane_differential(rans_case, perpos_case, perlane_case,
                                      layout):
    """(T, lanes, topk) model-top-k candidate planes decode identically on
    both backends — symbols AND per-lane probe counters — for every table
    layout (the kernel's in-kernel speculation vs the coder's scanned
    ``decode_get`` candidates)."""
    if layout == "static":
        tbl, syms = rans_case(80, k=64, lanes=8, t=64)
        syms = jnp.asarray(syms, jnp.int32)
    elif layout == "perpos":
        tbl, syms = perpos_case
    else:
        tbl, syms = perlane_case
    k, t = tbl.freq.shape[-1], syms.shape[1]
    cands = _candidate_planes(syms, k, topk=4, hit_rate=0.7, seed=81)
    enc = coder.encode(syms, tbl)
    got = ops.rans_decode(enc, t, tbl, candidates=cands, lane_probes=True)
    want = coder.decode(enc, t, tbl, candidates=cands, lane_probes=True)
    _assert_identical(got, want, syms)


def test_chunked_candidate_plane_differential(perpos_case):
    """Candidate planes ride the chunk grid axis (ragged tail included):
    kernel single-launch chunked decode == coder per chunk and per lane."""
    tbl, syms = perpos_case
    t = syms.shape[1]
    k = tbl.freq.shape[-1]
    cands = _candidate_planes(syms, k, topk=4, hit_rate=0.7, seed=82)
    ch = coder.encode_chunked(syms, tbl, 13)
    got = ops.rans_decode_chunked(ch, t, tbl, 13, candidates=cands,
                                  lane_probes=True)
    want = coder.decode_chunked(ch, t, tbl, 13, candidates=cands,
                                lane_probes=True)
    _assert_identical(got, want, syms)


def test_chunked_decode_is_one_pallas_call(perpos_case, monkeypatch):
    """The chunk axis is a grid dimension, not a host-side loop: a 4-chunk
    adaptive decode must launch exactly ONE pallas_call (the decode-side
    mirror of PR 3's encode assertion)."""
    tbl, syms = perpos_case
    calls = []
    real = rans_decode.pl.pallas_call

    def counting(*args, **kwargs):
        calls.append(kwargs.get("grid"))
        return real(*args, **kwargs)

    monkeypatch.setattr(rans_decode.pl, "pallas_call", counting)
    # fresh shapes so the jit cache cannot satisfy the call without tracing
    sub = syms[:, :45]
    tbl_sub = jax.tree.map(lambda a: a[:45], tbl)
    ch = coder.encode_chunked(sub, tbl_sub, 12)  # 3 full chunks + tail of 9
    got, _ = ops.rans_decode_chunked(ch, 45, tbl_sub, 12)
    assert len(calls) == 1, f"expected 1 pallas_call, saw {len(calls)}"
    assert calls[0][1] == 4                      # chunk grid axis
    np.testing.assert_array_equal(np.asarray(got), np.asarray(sub))


def test_t_blocked_decode_matches_single_block(perpos_case):
    """Blocking the T axis through VMEM (t_block < T) must not change a
    single bit or probe: decoder state carries across blocks in scratch."""
    tbl, syms = perpos_case
    t = syms.shape[1]
    enc = coder.encode(syms, tbl)
    pred = NeighborAverage(window=4, delta=8)
    whole = ops.rans_decode(enc, t, tbl, predictor=pred, lane_probes=True)
    for t_block in (7, 16, t):
        blocked = ops.rans_decode(enc, t, tbl, predictor=pred,
                                  t_block=t_block, lane_probes=True)
        _assert_identical(blocked, whole, syms)


# ---------------------------------------------------------------------------
# canonical probe accounting (core/search.py docstring rules)
# ---------------------------------------------------------------------------

def test_window_probe_skipped_on_candidate_hit(rans_case):
    """Rule 2: a lane resolved by candidate speculation does not pay the
    window verify — total cost of an oracle first candidate is exactly 1
    probe even when a window predictor is also active."""
    tbl, syms = rans_case(73, k=64, lanes=4, t=1)
    enc = coder.encode(jnp.asarray(syms), tbl)
    st = coder.decoder_init(coder.EncodedLanes(*enc))
    cand = jnp.asarray(syms[:, 0], jnp.int32)[:, None]
    mu = jnp.zeros((4,), jnp.int32)
    _, x, probes = coder.decode_get(st, enc.buf, tbl, candidates=cand,
                                    mu=mu, delta=4)
    np.testing.assert_array_equal(np.asarray(x), syms[:, 0])
    np.testing.assert_array_equal(np.asarray(probes), 1)


def test_bracket_miss_accounting_symmetry():
    """The window-verify probe is charged identically on hit and miss in
    both backends: force guaranteed misses (ZeroPredictor, delta=0, symbols
    far from zero) and pin per-lane integer equality."""
    rng = np.random.default_rng(74)
    k, lanes, t = 64, 8, 40
    probs = np.full(k, 1e-6)
    probs[40:] = 1.0                      # mass far from the zero anchor
    tbl = spc.tables_from_probs(jnp.asarray(probs / probs.sum(), jnp.float32))
    syms = jnp.asarray(rng.integers(40, k, (lanes, t)), jnp.int32)
    enc = coder.encode(syms, tbl)
    pred = ZeroPredictor(delta=0)
    got = ops.rans_decode(enc, t, tbl, predictor=pred, lane_probes=True)
    want = coder.decode(enc, t, tbl, predictor=pred, lane_probes=True)
    _assert_identical(got, want, syms)
    # every symbol missed the bracket: cost >= baseline (verify + search)
    base = coder.decode(enc, t, tbl, lane_probes=True)
    assert (np.asarray(got[2]) >= np.asarray(base[2])).all()


# ---------------------------------------------------------------------------
# Fig. 4(b) probe-count regression: speculation must keep paying off
# ---------------------------------------------------------------------------

def test_fig4b_speculation_probe_regression(rans_case):
    """Pins the Fig. 4(b) trajectory on a seeded stream.

    Baseline binary search over K=256 costs ~7 probes/symbol (paper: 7.00);
    model-top-k speculation with a realistic 80% top-1 hit rate must land
    in the paper's guided band (~3.15: hits pay 1 verify, misses pay the
    bounded penalty), and the per-lane counters must be integer-identical
    between coder and kernel on the monolithic AND the chunked
    single-launch path.  A perturbed accounting rule — an extra or missing
    probe anywhere — shifts the integer counters and fails this loudly.
    """
    k, t, topk = 256, 128, 4
    tbl, syms = rans_case(85, k=k, lanes=8, t=t)
    syms = jnp.asarray(syms, jnp.int32)
    cands = _candidate_planes(syms, k, topk=topk, hit_rate=0.8, seed=86)
    enc = coder.encode(syms, tbl)

    base = coder.decode(enc, t, tbl, lane_probes=True)
    spec = coder.decode(enc, t, tbl, candidates=cands, lane_probes=True)
    kspec = ops.rans_decode(enc, t, tbl, candidates=cands, lane_probes=True)
    _assert_identical(kspec, spec, syms)

    base_avg, spec_avg = float(base[1]), float(spec[1])
    # Fig. 4(b): ~7.00 baseline -> ~3.15 guided (bands, not exact floats —
    # the integer counters above are the exact pin)
    assert 6.0 <= base_avg <= 8.0, base_avg
    assert 2.5 <= spec_avg <= 4.5, spec_avg
    assert spec_avg < 0.55 * base_avg, (spec_avg, base_avg)

    # same contract on the chunked single-pallas_call path (ragged tail)
    ch = coder.encode_chunked(syms, tbl, 48)
    cspec = coder.decode_chunked(ch, t, tbl, 48, candidates=cands,
                                 lane_probes=True)
    kchunk = ops.rans_decode_chunked(ch, t, tbl, 48, candidates=cands,
                                     lane_probes=True)
    _assert_identical(kchunk, cspec, syms)
    cbase = coder.decode_chunked(ch, t, tbl, 48, lane_probes=True)
    assert float(cspec[1]) < 0.55 * float(cbase[1])


def test_fig4b_probe_count_monotone_in_hit_rate(rans_case):
    """More accurate speculation can only help: mean probes decrease
    monotonically with the candidate top-1 hit rate, identically on both
    backends (the regression guard for the speculation *trend*, not just
    one point)."""
    k, t = 64, 64
    tbl, syms = rans_case(87, k=k, lanes=4, t=t)
    syms = jnp.asarray(syms, jnp.int32)
    enc = coder.encode(syms, tbl)
    totals = []
    for hit_rate in (0.0, 0.5, 0.9):
        cands = _candidate_planes(syms, k, topk=4, hit_rate=hit_rate,
                                  seed=88)
        got = ops.rans_decode(enc, t, tbl, candidates=cands,
                              lane_probes=True)
        want = coder.decode(enc, t, tbl, candidates=cands, lane_probes=True)
        _assert_identical(got, want, syms)
        totals.append(int(np.asarray(got[2]).sum()))
    assert totals[0] > totals[1] > totals[2], totals


def test_topk0_plane_equals_no_speculation(rans_case):
    """topk=0 candidate planes are the explicit 'no speculation' sweep
    point: identical counters to passing no plane at all, on both
    backends."""
    tbl, syms = rans_case(89, k=64, lanes=4, t=32)
    syms = jnp.asarray(syms, jnp.int32)
    enc = coder.encode(syms, tbl)
    empty = jnp.zeros((32, 4, 0), jnp.int32)
    base = coder.decode(enc, 32, tbl, lane_probes=True)
    for got in (coder.decode(enc, 32, tbl, candidates=empty,
                             lane_probes=True),
                ops.rans_decode(enc, 32, tbl, candidates=empty,
                                lane_probes=True)):
        _assert_identical(got, base, syms)


# ---------------------------------------------------------------------------
# predictor edge cases: bit-exact, safe fallback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("predictor", [
    NeighborAverage(window=2, delta=0),       # delta=0: single-symbol bracket
    NeighborAverage(window=64, delta=8),      # window > T: mostly-empty ctx
    LastValue(delta=0),
], ids=["delta0", "window_gt_T", "last_delta0"])
def test_predictor_edge_configs_bit_exact(rans_case, predictor):
    tbl, syms = rans_case(75, k=64, lanes=4, t=16)
    enc = coder.encode(jnp.asarray(syms), tbl)
    got = ops.rans_decode(enc, 16, tbl, predictor=predictor,
                          lane_probes=True)
    want = coder.decode(enc, 16, tbl, predictor=predictor, lane_probes=True)
    _assert_identical(got, want, syms)


def test_all_empty_context_first_symbol(rans_case):
    """t=1: the context holds no decoded symbols yet (all -1 slots) — the
    neighbour average must fall back to the zero anchor and stay exact."""
    tbl, syms = rans_case(76, k=64, lanes=4, t=1)
    enc = coder.encode(jnp.asarray(syms), tbl)
    for pred in (NeighborAverage(4, 8), NeighborAverage(8, 0)):
        got, _, gl = ops.rans_decode(enc, 1, tbl, predictor=pred,
                                     lane_probes=True)
        want, _, wl = coder.decode(enc, 1, tbl, predictor=pred,
                                   lane_probes=True)
        np.testing.assert_array_equal(np.asarray(got), syms)
        np.testing.assert_array_equal(np.asarray(gl), np.asarray(wl))


def test_candidates_duplicates_and_out_of_alphabet(rans_case):
    """ModelTopK-style candidate lists with duplicate, out-of-alphabet and
    negative ids: every verify stays in-bounds (ids clip to [0, K)) and the
    decode falls back to the exact search."""
    k = 64
    tbl, syms = rans_case(77, k=k, lanes=4, t=1)
    enc = coder.encode(jnp.asarray(syms), tbl)
    st = coder.decoder_init(coder.EncodedLanes(*enc))
    wrong = (syms[:, 0] + 7) % k
    cands = jnp.stack([
        jnp.asarray(wrong, jnp.int32),
        jnp.asarray(wrong, jnp.int32),              # duplicate
        jnp.full((4,), k + 9, jnp.int32),           # out of alphabet
        jnp.full((4,), -3, jnp.int32),              # negative id
        jnp.full((4,), 10 ** 6, jnp.int32),         # absurdly large
    ], axis=1)
    _, x, probes = coder.decode_get(st, enc.buf, tbl, candidates=cands)
    np.testing.assert_array_equal(np.asarray(x), syms[:, 0])
    # all 5 candidate verifies paid (none can resolve unless clipping lands
    # on the true symbol), then the exact fallback search
    assert int(np.asarray(probes).min()) >= 5


def test_candidate_duplicate_of_truth_charges_once(rans_case):
    """A duplicated *correct* candidate resolves on the first copy; the
    second copy is free (rule 1: resolved lanes stop paying)."""
    tbl, syms = rans_case(78, k=64, lanes=4, t=1)
    enc = coder.encode(jnp.asarray(syms), tbl)
    st = coder.decoder_init(coder.EncodedLanes(*enc))
    truth = jnp.asarray(syms[:, 0], jnp.int32)
    cands = jnp.stack([truth, truth, truth], axis=1)
    _, x, probes = coder.decode_get(st, enc.buf, tbl, candidates=cands)
    np.testing.assert_array_equal(np.asarray(x), syms[:, 0])
    np.testing.assert_array_equal(np.asarray(probes), 1)


def test_predictor_configs_are_type_distinct_static_keys():
    """Predictor configs are static jit/trace-cache keys; bare-NamedTuple
    equality made ``LastValue(8) == ZeroPredictor(8)`` and let a decode
    traced with one serve the other's program (right symbols, wrong probe
    accounting — the cross-backend differential above only caught it when
    the two backends desynced).  The keys must be type-tagged."""
    assert LastValue(delta=8) != ZeroPredictor(delta=8)
    assert hash(LastValue(delta=8)) != hash(ZeroPredictor(delta=8))
    assert NeighborAverage(2, 4) != (2, 4)
    assert (2, 4) != NeighborAverage(2, 4)       # reflected op, tuple on LHS
    assert LastValue(delta=8) == LastValue(delta=8)
    assert hash(NeighborAverage(4, 8)) == hash(NeighborAverage(4, 8))


def test_zero_after_lastvalue_trace_order_stays_exact(rans_case):
    """The trace-order regression behind the key fix: LastValue first, then
    ZeroPredictor at identical shapes in the same process — the second trace
    must NOT reuse the first's program on either backend."""
    tbl, syms = rans_case(70, k=64, lanes=8, t=64)
    enc = coder.encode(jnp.asarray(syms), tbl)
    probes = {}
    for pred in (LastValue(delta=8), ZeroPredictor(delta=8)):
        got = ops.rans_decode(enc, 64, tbl, predictor=pred, lane_probes=True)
        want = ref.rans_decode_ref(enc, 64, tbl, predictor=pred,
                                   lane_probes=True)
        _assert_identical(got, want, syms)
        probes[type(pred).__name__] = np.asarray(got[2])
    # distinct programs: anchor-by-last and anchor-at-zero pay different
    # probe bills on this stream (equal bills would mean a shared trace)
    assert not np.array_equal(probes["LastValue"], probes["ZeroPredictor"])


# ---------------------------------------------------------------------------
# structural guard: no private search/predictor logic outside core/search.py
# ---------------------------------------------------------------------------

def test_kernel_and_ref_have_no_private_search_logic():
    ksrc = inspect.getsource(rans_decode)
    rsrc = inspect.getsource(ref)
    for src, name in ((ksrc, "kernels/rans_decode.py"),
                      (rsrc, "kernels/ref.py")):
        assert "_bsearch" not in src, f"{name} reimplements the CDF search"
        assert "go_right" not in src, f"{name} reimplements the CDF search"
    # the kernel consumes the shared core and the predictor protocol
    assert "from repro.core import search" in ksrc
    assert "predictor.predict" in ksrc and "predictor.update" in ksrc
    # ref delegates to the coder (itself a core.search consumer)
    assert "coder.decode" in rsrc
    # and the coder's own search lives in core/search.py only
    csrc = inspect.getsource(coder)
    assert "go_right" not in csrc
    assert "search.find_symbol" in csrc


def test_search_module_is_single_source_of_probe_rules():
    doc = search.__doc__
    for anchor in ("Sec. IV-C", "Fig. 2", "Fig. 4(b)",
                   "Canonical probe accounting"):
        assert anchor in doc
