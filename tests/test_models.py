"""Model-zoo correctness: per-arch smoke steps + algorithm equivalences."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.models import (decode_step, forward, init_cache, init_model,
                          loss_fn)
from repro.models.attention import _blockwise_attn, _naive_attn
from repro.models.config import ModelConfig
from repro.models.moe import make_moe_defs, moe_capacity, moe_dense
from repro.models.param import init_params
from repro.models.ssm import ssd_chunked, ssd_sequential

KEY = jax.random.PRNGKey(0)

# frontier-scale archs: their smoke configs still dominate suite wall time,
# so they run in the slow tier (pytest.ini deselects `slow` by default; CI's
# slow-model-tier job and `-m slow` cover them).
_SLOW_ARCHS = {"llama3-405b", "llama-3.2-vision-11b"}


def _arch_params(archs):
    return [pytest.param(a, marks=pytest.mark.slow) if a in _SLOW_ARCHS
            else a for a in archs]


def _batch(cfg, b=2, s=16, seed=0):
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s))),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)))}
    if cfg.family == "vlm":
        batch["memory"] = jnp.asarray(
            rng.normal(size=(b, cfg.memory_tokens, cfg.d_model)) * 0.02,
            jnp.float32)
    if cfg.is_encdec:
        batch["enc_inputs"] = jnp.asarray(
            rng.normal(size=(b, cfg.memory_tokens, cfg.d_model)) * 0.02,
            jnp.float32)
    return batch


# ---------------------------------------------------------------------------
# per-arch smoke: one forward/train step, output shapes, no NaNs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", _arch_params(ARCH_IDS))
def test_smoke_forward_and_grad_step(arch):
    cfg = get_smoke_config(arch)
    params = init_model(cfg, KEY)
    batch = _batch(cfg)
    loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg)
    assert np.isfinite(float(loss))
    leaves = jax.tree.leaves(grads)
    assert leaves and all(np.isfinite(np.asarray(g)).all() for g in leaves)
    x, _ = forward(params, batch["tokens"], cfg,
                   memory=batch.get("memory"),
                   enc_inputs=batch.get("enc_inputs"))
    assert x.shape == (2, 16, cfg.d_model)


@pytest.mark.parametrize("arch", _arch_params(ARCH_IDS))
def test_smoke_decode_steps(arch):
    cfg = get_smoke_config(arch)
    params = init_model(cfg, KEY)
    batch = _batch(cfg)
    mem = batch.get("memory", batch.get("enc_inputs"))
    if cfg.is_encdec:
        from repro.models.transformer import encode_memory
        mem = encode_memory(params, batch["enc_inputs"], cfg)
    cache = init_cache(cfg, 2, 32)
    tok = batch["tokens"][:, :1]
    for pos in range(3):
        lg, cache = decode_step(params, cache, tok, jnp.int32(pos), cfg,
                                memory=mem)
        assert lg.shape == (2, cfg.vocab_padded)
        assert np.isfinite(np.asarray(lg)).all()
        tok = jnp.argmax(lg[:, :cfg.vocab_size], -1)[:, None]


# ---------------------------------------------------------------------------
# prefill/decode consistency: the serving path must reproduce teacher-forced
# forward logits (this is what makes LM-driven decompression bit-exact).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", _arch_params([
    "qwen3-4b", "mixtral-8x22b", "mamba2-130m", "recurrentgemma-2b",
    "seamless-m4t-large-v2", "llama-3.2-vision-11b"]))
def test_prefill_decode_consistency(arch):
    cfg = get_smoke_config(arch)
    if cfg.n_experts:
        # uncap MoE capacity: prefill ranks tokens jointly and may drop some
        # that per-step decode would keep — a property of capacity dispatch,
        # not an inconsistency (serve/compress.py therefore feeds the rANS
        # coder from the *decode* path on both sides).
        cfg = cfg.with_(capacity_factor=16.0)
    params = init_model(cfg, KEY)
    b, s = 2, 12
    batch = _batch(cfg, b, s, seed=3)
    mem = batch.get("memory")
    if cfg.is_encdec:
        from repro.models.transformer import encode_memory
        mem = encode_memory(params, batch["enc_inputs"], cfg)
    x, _ = forward(params, batch["tokens"], cfg, memory=mem)
    from repro.models.layers import logits as logits_fn
    full = np.asarray(logits_fn(params["tok"], x, cfg))   # (B,S,V)

    cache = init_cache(cfg, b, s)
    got = []
    for t in range(s):
        lg, cache = decode_step(params, cache, batch["tokens"][:, t:t + 1],
                                jnp.int32(t), cfg, memory=mem)
        got.append(np.asarray(lg))
    got = np.stack(got, axis=1)
    np.testing.assert_allclose(got, full, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# algorithm equivalences
# ---------------------------------------------------------------------------

def test_ssd_chunked_matches_sequential():
    rng = np.random.default_rng(5)
    b, s, h, p, n = 2, 64, 4, 8, 16
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, s, 1, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, s, 1, n)), jnp.float32)
    for chunk in (8, 16, 64):
        got = np.asarray(ssd_chunked(x, dt, a, bm, cm, chunk))
        want = np.asarray(ssd_sequential(x, dt, a, bm, cm))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 7), (False, 0)])
def test_blockwise_attention_matches_naive(causal, window):
    rng = np.random.default_rng(11)
    b, s, h, dh = 2, 33, 4, 16
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    want = np.asarray(_naive_attn(q, k, v, causal, window))
    for blk in (8, 16, 64):
        got = np.asarray(_blockwise_attn(q, k, v, causal, window, blk))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_moe_capacity_matches_dense_when_uncapped():
    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                      n_experts=4, topk_experts=2, capacity_factor=8.0,
                      tp=1, dtype="float32")
    p = init_params(make_moe_defs(cfg), KEY)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 32)),
                    jnp.float32)
    yd, aux_d = moe_dense(p, x, cfg)
    yc, aux_c = moe_capacity(p, x, cfg)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(yd),
                               rtol=1e-5, atol=1e-5)
    assert abs(float(aux_d) - float(aux_c)) < 1e-6


def test_moe_capacity_drops_are_bounded():
    """With a tight capacity factor output differs but stays finite."""
    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                      n_experts=4, topk_experts=2, capacity_factor=0.5,
                      tp=1, dtype="float32")
    p = init_params(make_moe_defs(cfg), KEY)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 16, 32)),
                    jnp.float32)
    y, _ = moe_capacity(p, x, cfg)
    assert np.isfinite(np.asarray(y)).all()


def test_head_padding_preserves_function():
    """tp-padded q heads (zero-init) must not change the forward output."""
    base = get_smoke_config("qwen1.5-4b")
    cfg1 = base.with_(tp=1)
    cfg8 = base.with_(tp=8)   # 4 heads -> padded to 8
    assert cfg8.n_heads_padded == 8 and cfg1.n_heads_padded == 4
    p1 = init_model(cfg1, KEY)
    p8 = init_model(cfg8, KEY)
    batch = _batch(cfg1)
    x1, _ = forward(p1, batch["tokens"], cfg1)
    x8, _ = forward(p8, batch["tokens"], cfg8)
    assert x8.shape == x1.shape
    assert np.isfinite(np.asarray(x8)).all()


def test_sliding_window_masks_past():
    """A token far outside the window must not influence attention output."""
    cfg = get_smoke_config("mixtral-8x22b").with_(sliding_window=4,
                                                  n_experts=0,
                                                  block_pattern=("attn",))
    params = init_model(cfg, KEY)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (1, 16))
    t2 = toks.copy()
    t2[0, 0] = (t2[0, 0] + 17) % cfg.vocab_size  # mutate far-past token
    x1, _ = forward(params, jnp.asarray(toks), cfg)
    x2, _ = forward(params, jnp.asarray(t2), cfg)
    # receptive field = n_layers * window = 8; beyond that position 0 is
    # invisible, while positions inside it must differ.
    np.testing.assert_allclose(np.asarray(x1[0, 9:]), np.asarray(x2[0, 9:]),
                               rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(x1[0, 1]) - np.asarray(x2[0, 1])).max() > 1e-4
