"""Push/pop stack interface over the lane coder (DESIGN.md §12).

The stack's contract has three legs, each pinned here:

  * **inverse-ness** — push-then-pop and pop-then-push restore the state
    bit-exactly (s, ptr AND buffer bytes), for every codec constructor
    (``Uniform`` / ``NonUniform`` / ``Categorical`` / ``from_tableset``)
    and combinator (``serial`` / ``substack`` / array codecs);
  * **coder equivalence** — ``stack_init + push_symbols + stack_flush``
    lands byte-identical streams to the batch ``coder.encode`` (shared
    single-source cores), and the kernel pop backend evolves the stack
    byte-identically to the pure-JAX pop;
  * **explicit initial bits + detectable exhaustion** — a pop from an
    empty stack *flags* per-lane underflow (never silently recycles
    bytes), ``stack_init_bits`` seeds drawable entropy, and the bits-back
    VAE round trip restores the initial bits exactly.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import coder, constants as C, spc, stack

LANES, CAP = 4, 512


def _tables(k, seed, lanes=None, t=None):
    rng = np.random.default_rng(seed)
    size = tuple(d for d in (t, lanes) if d is not None) or None
    probs = rng.dirichlet(np.full(k, 0.5), size=size)
    return spc.freq_cdf_from_probs(
        spc.store_bf16(jnp.asarray(probs, jnp.float32)))


def _syms(k, t, seed):
    return np.random.default_rng(seed).integers(
        0, k, (LANES, t)).astype(np.int32)


def _state_equal(a: stack.StackState, b: stack.StackState,
                 full_buf: bool = False):
    """Bit-equality of the live stack: s, ptr and the stream bytes at
    ``buf[lane, ptr:]``.  Bytes below ``ptr`` are dead (pops never zero
    them), so they only must match when a re-push overwrote them
    (``full_buf=True`` — the pop-then-push bits-back direction)."""
    np.testing.assert_array_equal(np.asarray(a.s), np.asarray(b.s))
    np.testing.assert_array_equal(np.asarray(a.ptr), np.asarray(b.ptr))
    ab, bb = np.asarray(a.buf), np.asarray(b.buf)
    if full_buf:
        np.testing.assert_array_equal(ab, bb)
        return
    for lane, p in enumerate(np.asarray(a.ptr)):
        np.testing.assert_array_equal(ab[lane, max(int(p), 0):],
                                      bb[lane, max(int(p), 0):])


# ---------------------------------------------------------------------------
# inverse-ness per codec
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["coder", "kernel"])
def test_categorical_push_then_pop_is_identity(backend):
    freq, cdf = _tables(16, 0)
    codec = stack.Categorical(freq, cdf, backend=backend)
    st0 = stack.stack_init(LANES, CAP)
    x = jnp.asarray(_syms(16, 1, seed=1)[:, 0])
    st = codec.push(st0, x)
    st, got = codec.pop(st)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))
    _state_equal(st, st0)
    assert not np.asarray(st.underflow).any()


@pytest.mark.parametrize("backend", ["coder", "kernel"])
def test_categorical_pop_then_push_is_identity(backend):
    """The bits-back primitive: pop a symbol against one distribution from
    seeded initial bits, push it back against the SAME distribution — the
    stack (including the byte buffer) must return bit-for-bit."""
    freq, cdf = _tables(16, 2, lanes=LANES)     # per-lane tables
    codec = stack.Categorical(freq, cdf, backend=backend)
    st0 = stack.stack_init_bits(LANES, CAP, n_bytes=32, seed=3)
    st, x = codec.pop(st0)
    assert not np.asarray(st.underflow).any()
    st = codec.push(st, x)
    _state_equal(st, st0, full_buf=True)


def test_uniform_roundtrip_and_validation():
    codec = stack.Uniform(6)
    st0 = stack.stack_init(LANES, CAP)
    xs = _syms(1 << 6, 8, seed=4)
    st = st0
    for i in reversed(range(8)):
        st = codec.push(st, jnp.asarray(xs[:, i]))
    for i in range(8):
        st, got = codec.pop(st)
        np.testing.assert_array_equal(np.asarray(got), xs[:, i])
    _state_equal(st, st0)
    with pytest.raises(ValueError, match="Uniform bits"):
        stack.Uniform(0)
    with pytest.raises(ValueError, match="Uniform bits"):
        stack.Uniform(C.PROB_BITS + 1)
    with pytest.raises(ValueError, match="backend"):
        stack.Categorical(*_tables(8, 0), backend="gpu")


def test_nonuniform_statfun_matches_categorical():
    """A NonUniform built from a table's statfuns must land the identical
    bytes as the Categorical over the same table (shared barrett_planes)."""
    from repro.core import search
    freq, cdf = _tables(16, 5)

    def enc_statfun(x):
        return stack._gather(cdf[..., :-1], x), stack._gather(freq, x)

    def dec_statfun(slot):
        return search.find_symbol(cdf, 16, slot)[0]

    nu = stack.NonUniform(enc_statfun, dec_statfun)
    cat = stack.Categorical(freq, cdf)
    xs = _syms(16, 6, seed=6)
    st_a = st_b = stack.stack_init(LANES, CAP)
    for i in reversed(range(6)):
        st_a = nu.push(st_a, jnp.asarray(xs[:, i]))
        st_b = cat.push(st_b, jnp.asarray(xs[:, i]))
    _state_equal(st_a, st_b)
    for i in range(6):
        st_a, ga = nu.pop(st_a)
        st_b, gb = cat.pop(st_b)
        np.testing.assert_array_equal(np.asarray(ga), np.asarray(gb))
    _state_equal(st_a, st_b)


def test_serial_roundtrip_and_arity_check():
    freq, cdf = _tables(16, 7)
    codec = stack.serial([stack.Uniform(4), stack.Categorical(freq, cdf)])
    st0 = stack.stack_init(LANES, CAP)
    xa, xb = _syms(16, 1, seed=8)[:, 0], _syms(16, 1, seed=9)[:, 0]
    st = codec.push(st0, (jnp.asarray(xa), jnp.asarray(xb)))
    st, (ga, gb) = codec.pop(st)
    np.testing.assert_array_equal(np.asarray(ga), xa)
    np.testing.assert_array_equal(np.asarray(gb), xb)
    _state_equal(st, st0)
    with pytest.raises(ValueError, match="serial push"):
        codec.push(st0, (jnp.asarray(xa),))


def test_substack_leaves_other_lanes_untouched():
    freq, cdf = _tables(16, 10)
    idx = jnp.asarray([0, 2])
    codec = stack.substack(stack.Categorical(freq, cdf), idx)
    st0 = stack.stack_init_bits(LANES, CAP, n_bytes=16, seed=11)
    x = jnp.asarray([3, 9], jnp.int32)
    st = codec.push(st0, x)
    for lane in (1, 3):                      # untouched lanes: bit-for-bit
        np.testing.assert_array_equal(np.asarray(st.buf[lane]),
                                      np.asarray(st0.buf[lane]))
        assert int(st.s[lane]) == int(st0.s[lane])
        assert int(st.ptr[lane]) == int(st0.ptr[lane])
    st, got = codec.pop(st)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))
    _state_equal(st, st0)


# ---------------------------------------------------------------------------
# coder equivalence + array codecs over every table layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["static", "perpos", "perlane"])
def test_push_symbols_flush_matches_batch_coder(layout):
    """stack_init + push_symbols + stack_flush == coder.encode, byte for
    byte — the stack IS the batch encoder when used batch-wise."""
    t, k = 20, 16
    rng = np.random.default_rng(12)
    size = (None if layout == "static"
            else (t,) if layout == "perpos" else (t, LANES))
    probs = rng.dirichlet(np.full(k, 0.5), size=size)
    tbl = spc.tables_from_probs(jnp.asarray(probs.astype(np.float32)))
    syms = _syms(k, t, seed=13)
    enc_ref = coder.encode(jnp.asarray(syms), tbl)
    st = stack.stack_init(LANES, CAP)
    st = stack.push_symbols(st, jnp.asarray(syms), tbl.freq, tbl.cdf)
    enc = stack.stack_flush(st)
    ref_buf, ref_start = np.asarray(enc_ref.buf), np.asarray(enc_ref.start)
    got_buf, got_start = np.asarray(enc.buf), np.asarray(enc.start)
    for lane in range(LANES):
        np.testing.assert_array_equal(got_buf[lane, got_start[lane]:],
                                      ref_buf[lane, ref_start[lane]:])


@pytest.mark.parametrize("layout", ["static", "perpos", "perlane"])
@pytest.mark.parametrize("backend", ["coder", "kernel"])
def test_array_codec_roundtrip_all_layouts(layout, backend):
    t, k = 12, 16
    freq, cdf = _tables(k, 14, t=t if layout != "static" else None,
                        lanes=LANES if layout == "perlane" else None)
    syms = _syms(k, t, seed=15)
    st0 = stack.stack_init_bits(LANES, CAP, n_bytes=8, seed=16)
    st = stack.push_symbols(st0, jnp.asarray(syms), freq, cdf)
    st, got = stack.pop_symbols(st, t, freq, cdf, backend=backend)
    np.testing.assert_array_equal(np.asarray(got), syms)
    _state_equal(st, st0)
    with pytest.raises(ValueError, match="backend"):
        stack.pop_symbols(st, t, freq, cdf, backend="tpu")


def test_kernel_and_coder_pops_evolve_identical_stacks():
    freq, cdf = _tables(32, 17)
    st = stack.stack_init(LANES, CAP)
    st = stack.push_symbols(st, jnp.asarray(_syms(32, 16, seed=18)),
                            freq, cdf)
    st_c, sym_c = stack.pop_symbols(st, 16, freq, cdf, backend="coder")
    st_k, sym_k = stack.pop_symbols(st, 16, freq, cdf, backend="kernel")
    np.testing.assert_array_equal(np.asarray(sym_c), np.asarray(sym_k))
    _state_equal(st_c, st_k)
    np.testing.assert_array_equal(np.asarray(st_c.underflow),
                                  np.asarray(st_k.underflow))


def test_from_tableset_equals_categorical():
    tbl = spc.tables_from_probs(jnp.asarray(
        np.random.default_rng(19).dirichlet(np.full(16, 0.5)), jnp.float32))
    x = jnp.asarray(_syms(16, 1, seed=20)[:, 0])
    st0 = stack.stack_init(LANES, CAP)
    a = stack.from_tableset(tbl).push(st0, x)
    b = stack.Categorical(tbl.freq, tbl.cdf).push(st0, x)
    _state_equal(a, b)


# ---------------------------------------------------------------------------
# initial bits, exhaustion, flush/open
# ---------------------------------------------------------------------------

def test_empty_stack_pop_flags_underflow():
    """A pop with no entropy to draw on FLAGS — stream exhaustion is
    detectable at the stack level, never a silent byte recycle."""
    codec = stack.Categorical(*_tables(16, 21))
    st, _x = codec.pop(stack.stack_init(LANES, CAP))
    assert np.asarray(st.underflow).all()


def test_initial_bits_are_deterministic_and_sized():
    a = stack.stack_init_bits(LANES, CAP, n_bytes=24, seed=5)
    b = stack.stack_init_bits(LANES, CAP, n_bytes=24, seed=5)
    _state_equal(a, b)
    np.testing.assert_array_equal(np.asarray(stack.stack_bytes(a)),
                                  np.full(LANES, 24 + 4))
    assert (np.asarray(a.s) >= C.RANS_L).all()
    with pytest.raises(ValueError, match="exceeds stack cap"):
        stack.stack_init_bits(LANES, 16, n_bytes=32)


def test_flush_open_roundtrip_and_truncated_header_flags():
    st = stack.stack_init_bits(LANES, CAP, n_bytes=16, seed=22)
    enc = stack.stack_flush(st)
    st_r = stack.stack_open(enc)
    _state_equal(st_r, st)
    assert not np.asarray(st_r.underflow).any()
    # a header cut short (stream shorter than the 4 state bytes) flags
    short = coder.EncodedLanes(buf=enc.buf,
                               start=jnp.full((LANES,), CAP - 2, jnp.int32),
                               length=jnp.full((LANES,), 2, jnp.int32))
    assert np.asarray(stack.stack_open(short).underflow).all()


# ---------------------------------------------------------------------------
# observation codecs + the bits-back VAE round trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["coder", "kernel"])
def test_observation_codecs_roundtrip(backend):
    rng = np.random.default_rng(23)
    edges, _ = stack.std_gaussian_bins(16)
    mu = jnp.asarray(rng.normal(0, 1, LANES), jnp.float32)
    sig = jnp.asarray(rng.uniform(0.5, 2.0, LANES), jnp.float32)
    g = stack.DiagGaussian(mu, sig, edges, backend=backend)
    dl = stack.DiscretizedLogistic(mu * 0.1, mu * 0.0 - 2.0, 256,
                                   backend=backend)
    st0 = stack.stack_init_bits(LANES, CAP, n_bytes=32, seed=24)
    kz = jnp.asarray(rng.integers(0, 16, LANES), jnp.int32)
    px = jnp.asarray(rng.integers(0, 256, LANES), jnp.int32)
    st = g.push(st0, kz)
    st = dl.push(st, px)
    st, got_px = dl.pop(st)
    st, got_kz = g.pop(st)
    np.testing.assert_array_equal(np.asarray(got_px), np.asarray(px))
    np.testing.assert_array_equal(np.asarray(got_kz), np.asarray(kz))
    _state_equal(st, st0)


def test_gaussian_bins_uniform_prior_mass():
    """N(0,1) over its own equal-mass quantile bins is exactly uniform —
    the identity that lets the VAE's top prior ride the exact Uniform
    codec instead of a quantized table."""
    edges, _ = stack.std_gaussian_bins(16)
    mass = stack.gaussian_bin_probs(jnp.zeros(()), jnp.ones(()), edges)
    np.testing.assert_allclose(np.asarray(mass), np.full(16, 1 / 16),
                               atol=1e-6)


@pytest.mark.parametrize("backend", ["coder", "kernel"])
def test_vae_bitsback_roundtrip_small(backend):
    """End-to-end Bit-Swap on a barely-trained tiny VAE: pixels bit-exact,
    initial stack restored bit-for-bit, no underflow — correctness is
    independent of model quality."""
    from repro.models import vae
    cfg = vae.VAEConfig(d_x=16, d_h=16)
    rng = np.random.default_rng(25)
    params, _ = vae.train_vae(
        cfg, lambda i: np.random.default_rng(i).integers(
            0, cfg.x_bins, (LANES, cfg.d_x)),
        steps=3, lr=1e-3, seed=0)
    x = jnp.asarray(rng.integers(0, cfg.x_bins, (LANES, cfg.d_x)),
                    jnp.int32)
    st0 = stack.stack_init_bits(LANES, 2048, n_bytes=64, seed=26)
    st = vae.bb_encode(st0, params, x, cfg, backend=backend)
    assert not np.asarray(st.underflow).any()
    st_d, x_d = vae.bb_decode(st, params, cfg, backend=backend)
    np.testing.assert_array_equal(np.asarray(x_d), np.asarray(x))
    _state_equal(st_d, st0)
    assert not np.asarray(st_d.underflow).any()
