"""The model-state protocol: one compression stack for the whole zoo.

Pins the tentpole refactor's contracts:

* registry integrity — every ``ARCH_IDS`` entry loads both configs, its
  ``abstract_model`` shapes build, and its family dispatches a protocol;
* ``StateSpec`` classification — ring vs recurrent leaves, bounded vs
  unbounded ring windows, and the derived wrap/ring lengths the engine's
  admission guard runs on;
* the serve layer imports ONLY the protocol surface (grep-guard: no
  ``repro.models.transformer`` import survives in serve/);
* named errors instead of silent mis-batching — ``prefill_chunk`` on a
  recurrent family and ``BatchEngine(prefill="force")`` both raise
  :class:`PrefillUnsupportedError`;
* zoo round trips — Mamba2 (pure recurrent) and RecurrentGemma (ring +
  recurrent hybrid) smoke configs: kernel/coder containers byte-identical
  and the FUSED kernel decompress bit-exact, state carried across chunk
  boundaries (ragged tail included);
* engine semantics for recurrent state — streams longer than ``max_len``
  are accepted (recurrent state never wraps) and stay byte-identical to
  the single-request path; ``prefill="auto"`` steps down cleanly; frozen
  rows keep their recurrent leaves bit-exactly (the freeze-select
  regression at the ``_chunk_body`` level); windowed-dense prefill steps
  down by RING length, not ``max_len`` (the mixtral wrap fix).
"""

import glob
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import (ARCH_IDS, SERVE_SMOKE_ARCHS, get_config,
                           get_protocol as registry_protocol,
                           get_smoke_config)
from repro.core import bitstream
from repro.data.pipeline import token_stream
from repro.models import (PrefillUnsupportedError, abstract_model,
                          can_prefill, decode_step, init_model, init_state,
                          prefill_chunk, recurrent_state_tree, ring_length,
                          state_spec, wrap_length)
from repro.serve.compress import lm_compress_chunked, lm_decompress_chunked
from repro.serve.engine import BatchEngine, _chunk_body

CHUNK = 8


@pytest.fixture(scope="module")
def zoo():
    """Initialized smoke params for the serve-wired archs (built once)."""
    out = {}
    for arch in ("mamba2-130m", "recurrentgemma-2b"):
        cfg = get_smoke_config(arch)
        out[arch] = (cfg, init_model(cfg, jax.random.PRNGKey(0)))
    return out


def _toks(cfg, lanes, t_len, seed):
    return np.asarray(token_stream(cfg.vocab_size, (lanes, t_len),
                                   seed=seed), np.int32)


def _blob(params, cfg, toks, backend="coder"):
    stats = lm_compress_chunked(params, cfg, jnp.asarray(toks), CHUNK,
                                backend=backend)
    enc = jax.tree.map(np.asarray, stats.chunks)
    return bitstream.pack_chunked(enc.buf, enc.start, enc.length,
                                  enc.overflow, chunk_size=CHUNK,
                                  n_symbols=toks.shape[1])


# ---------------------------------------------------------------------------
# registry integrity + protocol dispatch
# ---------------------------------------------------------------------------

def test_registry_integrity():
    for arch in ARCH_IDS:
        cfg, smoke = get_config(arch), get_smoke_config(arch)
        assert cfg.name and smoke.vocab_size >= 256
        proto = registry_protocol(arch)
        assert proto.family == cfg.family
        # abstract shapes build without allocating anything
        tree = abstract_model(smoke)
        assert jax.tree.leaves(tree), arch
        spec = state_spec(smoke)
        assert spec.ring or spec.recurrent or spec.kinds == ("cross",), arch


def test_unknown_arch_and_family_are_named_errors():
    with pytest.raises(KeyError, match="unknown arch"):
        get_smoke_config("no-such-arch")
    from repro.models import get_protocol
    bad = get_smoke_config("ras-pimc").with_(family="holographic")
    with pytest.raises(KeyError, match="no model protocol"):
        get_protocol(bad)


def test_state_spec_classification():
    pimc = get_smoke_config("ras-pimc")       # pure unbounded ring
    ssm = get_smoke_config("mamba2-130m")     # pure recurrent
    hyb = get_smoke_config("recurrentgemma-2b")   # ring(16) + recurrent
    moe = get_smoke_config("mixtral-8x22b")   # sliding-window ring(16)
    sp, ss, sh, sm = map(state_spec, (pimc, ssm, hyb, moe))
    assert sp.ring and not sp.recurrent and sp.ring_window == -1
    assert ss.recurrent and not ss.ring and ss.ring_window == 0
    assert sh.ring and sh.recurrent
    assert sh.ring_window == hyb.local_window == 16
    assert sm.ring_window == moe.sliding_window == 16
    # wrap/ring lengths drive the engine admission guard
    assert wrap_length(pimc, 32) == 32          # unbounded ring wraps
    assert wrap_length(ssm, 32) is None         # O(1) state never wraps
    assert wrap_length(hyb, 32) is None         # 32 >= window: saturates
    assert wrap_length(hyb, 8) == 8             # under-sized ring wraps
    assert ring_length(hyb, 32) == 16           # allocated = min(len, win)
    assert ring_length(pimc, 32) == 32


def test_state_leaves_row_axis_and_recurrent_tree():
    for arch in SERVE_SMOKE_ARCHS:
        cfg = get_smoke_config(arch)
        st = init_state(cfg, 3, 16)
        for leaf in jax.tree.leaves(st):
            assert leaf.shape[1] == 3, arch     # protocol row-axis pin
            assert not np.asarray(leaf).any(), arch  # zeros = fresh reset
        rec = recurrent_state_tree(st)
        assert any(jax.tree.leaves(rec)) == state_spec(cfg).recurrent


def test_serve_imports_protocol_only():
    """Grep-guard: serve/ never imports an architecture module again."""
    serve_dir = os.path.join(os.path.dirname(__file__), os.pardir, "src",
                             "repro", "serve")
    paths = glob.glob(os.path.join(serve_dir, "*.py"))
    assert paths
    for path in paths:
        src = open(path).read()
        assert "models.transformer" not in src, path
        assert "models import transformer" not in src, path


# ---------------------------------------------------------------------------
# named errors instead of silent mis-batching
# ---------------------------------------------------------------------------

def test_prefill_unsupported_is_named(zoo):
    cfg, params = zoo["mamba2-130m"]
    st = init_state(cfg, 2, 16)
    toks = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(PrefillUnsupportedError, match="sequential state"):
        prefill_chunk(params, st, toks, jnp.zeros(2, jnp.int32),
                      jnp.full(2, 4, jnp.int32), cfg)
    with pytest.raises(PrefillUnsupportedError, match="prefill='force'"):
        BatchEngine(params, cfg, slots=1, lanes=2, chunk_size=CHUNK,
                    prefill="force")
    assert not can_prefill(cfg)


# ---------------------------------------------------------------------------
# zoo round trips: compress -> container -> fused kernel decompress
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["mamba2-130m", "recurrentgemma-2b"])
def test_zoo_chunked_roundtrip_bit_exact(zoo, arch):
    cfg, params = zoo[arch]
    toks = _toks(cfg, 2, 20, seed=3)            # 20 = 2 full chunks + tail
    blob_c = _blob(params, cfg, toks, backend="coder")
    blob_k = _blob(params, cfg, toks, backend="kernel")
    assert blob_c == blob_k                     # backends byte-identical
    slab = bitstream.parse_chunked(blob_k)
    dec, _ = lm_decompress_chunked(params, cfg, slab, 20, CHUNK,
                                   backend="kernel")
    assert np.array_equal(np.asarray(dec), toks)
    dec2, _ = lm_decompress_chunked(params, cfg, slab, 20, CHUNK,
                                    backend="coder")
    assert np.array_equal(np.asarray(dec2), toks)


# ---------------------------------------------------------------------------
# engine: recurrent state across slot join/retire and long streams
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["mamba2-130m", "recurrentgemma-2b"])
def test_engine_recurrent_long_stream_byte_identical(zoo, arch):
    """T > max_len is ACCEPTED for recurrent/window-saturated state (the
    old transformer-only guard raised) and stays byte-identical to the
    single-request path; prefill='auto' steps down to the step program."""
    cfg, params = zoo[arch]
    eng = BatchEngine(params, cfg, slots=2, lanes=2, chunk_size=CHUNK,
                      max_len=16)
    assert eng._prog_prefill is None            # clean step-down
    long_toks = _toks(cfg, 2, 40, seed=5)       # 40 > max_len=16
    short_toks = _toks(cfg, 2, 20, seed=6)
    rid_l = eng.submit_compress(long_toks)      # no allow_wrap needed
    rid_s = eng.submit_compress(short_toks)
    res = eng.run()
    assert res[rid_l].ok and res[rid_s].ok
    assert eng.prefill_cycles == 0
    assert res[rid_l].blob == _blob(params, cfg, long_toks)
    assert res[rid_s].blob == _blob(params, cfg, short_toks)
    did = eng.submit_decompress(res[rid_l].blob)
    out = eng.run()[did]
    assert out.ok and np.array_equal(out.tokens, long_toks)


def test_engine_unbounded_ring_still_guards():
    """Full-attention archs keep the wrap guard — state-spec-driven, not
    dropped: T > max_len without allow_wrap is still a named rejection."""
    cfg = get_smoke_config("ras-pimc")
    params = init_model(cfg, jax.random.PRNGKey(1))
    eng = BatchEngine(params, cfg, slots=1, lanes=2, chunk_size=CHUNK,
                      max_len=16)
    with pytest.raises(ValueError, match="exceeds the engine ring"):
        eng.submit_compress(_toks(cfg, 2, 24, seed=1))


def test_frozen_rows_keep_recurrent_state(zoo):
    """_chunk_body-level freeze regression: a row with n_valid < chunk_size
    must end the cycle with recurrent state INDEPENDENT of whatever sits in
    its teacher-forced inputs past n_valid (before the freeze-select,
    frozen steps kept mutating (h, conv) on garbage tokens)."""
    cfg, params = zoo["mamba2-130m"]
    rows = 2
    kw = dict(cfg=cfg, chunk_size=CHUNK, prob_bits=12, topk=4,
              backend="coder")

    def run(tf):
        cache = init_state(cfg, rows, 16)
        tok = jnp.zeros((rows, 1), jnp.int32)
        fresh = jnp.ones(rows, bool)
        pos0 = jnp.zeros(rows, jnp.int32)
        mode = jnp.full(rows, 1, jnp.int32)             # MODE_COMPRESS
        n_valid = jnp.asarray([CHUNK, 3], jnp.int32)    # row 1 freezes at 3
        # compress rows carry an empty stream window, as in _build_cycle
        buf = jnp.zeros((rows, 64), jnp.uint8)
        start = jnp.zeros(rows, jnp.int32)
        cache, *_ = _chunk_body(params, cache, tok, fresh, pos0, mode,
                                n_valid, jnp.asarray(tf), buf, start, **kw)
        return jax.tree.map(lambda a: np.asarray(a[:, 1]), cache)

    base = _toks(cfg, rows, CHUNK, seed=9)
    poisoned = base.copy()
    poisoned[1, 3:] = (poisoned[1, 3:] + 7) % cfg.vocab_size
    a, b = run(base), run(poisoned)
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(x, y), a, b)


def test_windowed_dense_prefill_steps_down_by_ring_length():
    """Sliding-window dense (mixtral): the allocated ring is min(max_len,
    window), so a request with window < T <= max_len must NOT take the
    prefill fast path (attn_prefill needs pos0 + S <= ring slots) — and
    the step-path output stays byte-identical to the single-request path.
    Before the ring_len fix this wrongly prefilled on a wrapped ring."""
    cfg = get_smoke_config("mixtral-8x22b")     # sliding_window = 16
    params = init_model(cfg, jax.random.PRNGKey(3))
    eng = BatchEngine(params, cfg, slots=1, lanes=2, chunk_size=CHUNK,
                      max_len=32)
    assert eng.ring_len == 16 and eng._prog_prefill is not None
    toks = _toks(cfg, 2, 24, seed=4)            # 16 < 24 <= 32
    rid = eng.submit_compress(toks)             # accepted: window saturates
    res = eng.run()
    assert res[rid].ok and eng.prefill_cycles == 0
    assert res[rid].blob == _blob(params, cfg, toks)
    # an in-ring request still rides the fast path
    short = _toks(cfg, 2, 12, seed=8)
    rid2 = eng.submit_compress(short)
    res2 = eng.run()
    assert res2[rid2].ok and eng.prefill_cycles > 0
    assert res2[rid2].blob == _blob(params, cfg, short)
