"""Serve engine (prefill / generate) regression tests.

The engine drove the model zoo since the seed but was only shape/determinism
tested, so a position off-by-one in ``generate`` rotted silently: prefill
consumes prompt positions ``[0, s)``, yet the generation scan consumed the
first sampled token at position ``s + 1`` — cache slot ``s`` was never
written and every subsequent step attended over a zero row.  The manual
per-step rollout below pins the position contract exactly; the round-trip
test pins the engine into the compression stack (one shared cache
evolution — ``serve.engine.teacher_forced_scan`` backs both).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.data.pipeline import token_stream
from repro.models import init_model
from repro.models.transformer import decode_step, init_cache
from repro.serve.engine import generate, prefill, teacher_forced_scan

CFG = get_smoke_config("ras-pimc")
KEY = jax.random.PRNGKey(2)


@pytest.fixture(scope="module")
def params():
    return init_model(CFG, KEY)


def _manual_greedy(params, cfg, prompt, n_new, max_len):
    """Explicit per-step greedy rollout: the position-contract reference.

    Returns (tokens (B, n_new), logits (B, n_new, Vpad)) — the logits that
    produced each token, computed with an unrolled python loop where every
    ``decode_step`` position is written out literally.
    """
    b, s = prompt.shape
    cache = init_cache(cfg, b, max_len)
    lg = None
    for t in range(s):
        lg, cache = decode_step(params, cache, prompt[:, t][:, None], t, cfg)
    out, lgs = [], []
    for i in range(n_new):
        lgs.append(lg)
        nxt = jnp.argmax(lg[:, :cfg.vocab_size], -1).astype(jnp.int32)
        out.append(nxt)
        if i + 1 < n_new:
            lg, cache = decode_step(params, cache, nxt[:, None], s + i, cfg)
    return jnp.stack(out, axis=1), jnp.stack(lgs, axis=1)


def test_generate_matches_manual_rollout(params):
    """generate == the explicit rollout, token for token AND logit for logit.

    This is the regression the old shape-only tests missed: the first
    generated token must be consumed at position ``s`` (the slot right
    after the prompt), not ``s + 1``.  The logits assertion is the teeth —
    on a smoke-sized model the off-by-one perturbs every post-first-step
    logit by ~3e-2 (slot ``s`` left as an attended-over zero row) without
    necessarily flipping any argmax, so token equality alone would pass on
    the broken code.
    """
    prompt = jnp.asarray(token_stream(CFG.vocab_size, (2, 12), seed=5),
                         jnp.int32)
    out, lgs = generate(params, CFG, prompt, 8, max_len=32,
                        return_logits=True)
    ref, ref_lgs = _manual_greedy(params, CFG, prompt, 8, 32)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    np.testing.assert_allclose(np.asarray(lgs), np.asarray(ref_lgs),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_generate_matches_manual_rollout_windowed():
    """Same contract on a ring-buffered (windowed/recurrent) cache, where a
    skipped slot additionally corrupts the ring arithmetic."""
    cfg = get_smoke_config("recurrentgemma-2b")
    params = init_model(cfg, KEY)
    prompt = jnp.asarray(token_stream(cfg.vocab_size, (2, 10), seed=6),
                         jnp.int32)
    out, lgs = generate(params, cfg, prompt, 6, max_len=24,
                        return_logits=True)
    ref, ref_lgs = _manual_greedy(params, cfg, prompt, 6, 24)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    np.testing.assert_allclose(np.asarray(lgs), np.asarray(ref_lgs),
                               rtol=1e-5, atol=1e-5)


def test_teacher_forced_scan_backs_prefill(params):
    """prefill is the shared teacher-forced scan's last step, and the
    step_fn hook maps per-step logits without disturbing the cache."""
    toks = jnp.asarray(token_stream(CFG.vocab_size, (3, 9), seed=7),
                       jnp.int32)
    cache_a, last = prefill(params, CFG, toks, max_len=16)
    cache_b, all_lg = teacher_forced_scan(params, CFG, toks, 16)
    np.testing.assert_array_equal(np.asarray(last), np.asarray(all_lg[-1]))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), cache_a, cache_b)
    _, picked = teacher_forced_scan(
        params, CFG, toks, 16,
        step_fn=lambda lg, t: jnp.argmax(lg[:, :CFG.vocab_size], -1))
    np.testing.assert_array_equal(
        np.asarray(picked[-1]),
        np.asarray(jnp.argmax(last[:, :CFG.vocab_size], -1)))


def test_generate_then_fused_compress_roundtrip(params):
    """Engine output round-trips through the serve compression stack: the
    tokens generate produced compress and fused-decode bit-exactly (the
    engine and compressor share one cache evolution via
    teacher_forced_scan, so this is a true end-to-end serving loop)."""
    from repro.serve.compress import lm_compress, lm_decompress
    prompt = jnp.asarray(token_stream(CFG.vocab_size, (2, 8), seed=8),
                         jnp.int32)
    out = generate(params, CFG, prompt, 8, max_len=16)
    toks = jnp.concatenate([prompt, out], axis=1)
    stats = lm_compress(params, CFG, toks)
    dec, _ = lm_decompress(params, CFG, stats.enc, toks.shape[1],
                           backend="kernel")
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(toks))


def test_ring_cache_wrap_matches_sliding_window(params):
    """The shared-cache wrap contract, pinned logit-level: a cache of
    ``max_len=W`` driven past W positions IS sliding-window-W attention.
    The docstring promised "(possibly ring-buffered)" since the seed but
    nothing ever exercised seq > max_len — an off-by-one in the age mask
    would have rotted silently.  Also asserts the test has teeth: the
    windowed logits genuinely differ from full-context attention."""
    from dataclasses import replace
    from repro.models.transformer import forward, logits as lm_logits
    W, S = 8, 24
    toks = jnp.asarray(token_stream(CFG.vocab_size, (2, S), seed=11),
                       jnp.int32)
    _, ring_lg = teacher_forced_scan(params, CFG, toks, W)  # rings at W
    ring_lg = jnp.stack(list(ring_lg), axis=0) if isinstance(ring_lg, list) \
        else ring_lg                                        # (S, B, V)
    cfg_w = replace(CFG, sliding_window=W)
    x, _ = forward(params, toks, cfg_w)
    full_w = lm_logits(params["tok"], x, cfg_w)             # (B, S, V)
    np.testing.assert_allclose(np.asarray(ring_lg),
                               np.asarray(jnp.swapaxes(full_w, 0, 1)),
                               atol=2e-4, rtol=2e-4)
    # teeth: past t >= W the window must change the distribution
    x_full, _ = forward(params, toks, CFG)
    full = lm_logits(params["tok"], x_full, CFG)
    assert np.max(np.abs(np.asarray(full - full_w))[:, W:]) > 1e-2


def test_ring_cache_length_invariance(params):
    """Ring length is NOT part of the model function below capacity: the
    same stream decoded under different cache lengths produces bit-exact
    identical logits (the tiled attention reduction makes every float a
    function of slot content, never of ring extent).  This is what lets
    the batched engine serve a request under its shared ``max_len`` cache
    byte-identically to the single-request scan at ``t_len``."""
    from repro.models.transformer import decode_step, init_cache
    toks = jnp.asarray(token_stream(CFG.vocab_size, (2, 12), seed=13),
                       jnp.int32)

    def roll(ml):
        cache = init_cache(CFG, 2, ml)
        out = []
        for t in range(12):
            lg, cache = decode_step(params, cache, toks[:, t:t + 1], t, CFG)
            out.append(np.asarray(lg))
        return np.stack(out)

    a = roll(12)
    for ml in (16, 33, 64):
        np.testing.assert_array_equal(a, roll(ml))


def test_prefill_chunk_bitwise_matches_decode_steps(params):
    """The batched-prefill fast path IS the sequential step path, bit for
    bit: one ``prefill_chunk`` over S teacher-forced positions (starting
    mid-stream, pos0 > 0) produces the identical logits and cache as S
    ``decode_step`` calls.  This is the identity that lets the engine
    dispatch compress-only cycles through one fused pass — the attend
    core runs at query extent 1 either way (a multi-query einsum rounds
    ~1 ulp differently than S single-query ones)."""
    from repro.models.transformer import can_prefill, prefill_chunk
    assert can_prefill(CFG)
    b, s, warm, max_len = 4, 8, 3, 16
    toks = jnp.asarray(token_stream(CFG.vocab_size, (b, warm + s), seed=9),
                       jnp.int32)

    cache = init_cache(CFG, b, max_len)
    for t in range(warm):
        _, cache = decode_step(params, cache, toks[:, t:t + 1], t, CFG)
    seq_cache, lgs = cache, []
    for t in range(warm, warm + s):
        lg, seq_cache = decode_step(params, seq_cache, toks[:, t:t + 1], t,
                                    CFG)
        lgs.append(lg)

    pos0 = jnp.full((b,), warm, jnp.int32)
    pf_lgs, pf_cache = prefill_chunk(params, cache, toks[:, warm:], pos0,
                                     jnp.full((b,), s, jnp.int32), CFG)
    np.testing.assert_array_equal(np.stack([np.asarray(x) for x in lgs], 1),
                                  np.asarray(pf_lgs))
    for a, bb in zip(jax.tree.leaves(seq_cache), jax.tree.leaves(pf_cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))


def test_prefill_chunk_ragged_live_rows_exact(params):
    """Rows with ``n_valid < S`` freeze after their live steps; every live
    (row, position) logit still equals the all-rows-live sequential
    reference bitwise (same batch extent — rows are data-independent, so
    a neighbor's freeze must not perturb a live row by even one ulp;
    frozen positions are discarded by the engine and excluded here)."""
    from repro.models.transformer import prefill_chunk
    b, s, max_len = 4, 8, 16
    toks = jnp.asarray(token_stream(CFG.vocab_size, (b, s), seed=11),
                       jnp.int32)
    cache, ref = init_cache(CFG, b, max_len), []
    for t in range(s):
        lg, cache = decode_step(params, cache, toks[:, t:t + 1], t, CFG)
        ref.append(np.asarray(lg))
    ref = np.stack(ref, axis=1)                    # (b, s, Vpad)
    nv = np.asarray([s, 5, 1, 0], np.int32)
    pf_lgs, _ = prefill_chunk(params, init_cache(CFG, b, max_len), toks,
                              jnp.zeros((b,), jnp.int32), jnp.asarray(nv),
                              CFG)
    pf_lgs = np.asarray(pf_lgs)
    for i in range(b):
        np.testing.assert_array_equal(pf_lgs[i, :nv[i]], ref[i, :nv[i]])
