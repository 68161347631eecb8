"""Substrate tests: optimizer, train loop, checkpoint/restart, elastic
re-mesh, straggler detection, gradient compression."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.data.pipeline import train_batch
from repro.models import init_model
from repro.parallel.collectives import (compressed_psum, dequantize_int8,
                                        init_error_tree, quantize_int8)
from repro.train import checkpoint
from repro.train.fault_tolerance import RestartManager, StragglerMonitor
from repro.train.optimizer import (adamw_init, adamw_update,
                                   clip_by_global_norm, cosine_lr,
                                   global_norm)
from repro.train.train_loop import init_train_state, make_train_step

CFG = get_smoke_config("ras-pimc").with_(grad_accum=1)
KEY = jax.random.PRNGKey(0)


def _state():
    return init_train_state(init_model(CFG, KEY))


def _batch(i=0, b=4, s=32):
    return jax.tree.map(jnp.asarray, train_batch(CFG, b, s, step=i))


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_decreases_loss():
    state = _state()
    step = jax.jit(make_train_step(CFG, base_lr=1e-3))
    losses = []
    for i in range(20):
        state, m = step(state, _batch(i % 2))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.05, losses[::6]


def test_grad_accum_matches_full_batch():
    """accum=4 over a batch == single step on the same batch (same grads)."""
    from repro.train.train_loop import grads_fn
    params = init_model(CFG, KEY)
    batch = _batch(b=8)
    l1, g1 = grads_fn(params, batch, CFG.with_(grad_accum=1))
    l4, g4 = grads_fn(params, batch, CFG.with_(grad_accum=4))
    assert abs(float(l1) - float(l4)) < 1e-5
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_clip_by_global_norm():
    tree = {"a": jnp.ones((4,)) * 10.0, "b": jnp.ones((2, 2)) * -10.0}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-5
    assert float(norm) > 1.0


def test_cosine_lr_schedule():
    assert float(cosine_lr(jnp.int32(0), base_lr=1.0, warmup=10)) == 0.0
    assert abs(float(cosine_lr(jnp.int32(10), base_lr=1.0, warmup=10))
               - 1.0) < 1e-5
    late = float(cosine_lr(jnp.int32(10_000), base_lr=1.0, warmup=10))
    assert late <= 0.1 + 1e-5


def test_bf16_moments():
    params = init_model(CFG, KEY)
    st = adamw_init(params, moment_dtype=jnp.bfloat16)
    assert all(m.dtype == jnp.bfloat16 for m in jax.tree.leaves(st.m))
    grads = jax.tree.map(lambda p: jnp.ones_like(p, jnp.float32) * 0.1,
                         params)
    new_p, st2 = adamw_update(grads, st, params, 1e-3)
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(new_p))


# ---------------------------------------------------------------------------
# checkpoint / restart / elastic
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    state = _state()
    step = jax.jit(make_train_step(CFG))
    state, _ = step(state, _batch())
    checkpoint.save(str(tmp_path), 1, state)
    assert checkpoint.latest_step(str(tmp_path)) == 1
    restored = checkpoint.restore(str(tmp_path), 1, state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomic_publish(tmp_path):
    state = _state()
    checkpoint.save(str(tmp_path), 5, state)
    checkpoint.save(str(tmp_path), 7, state)
    assert checkpoint.latest_step(str(tmp_path)) == 7
    # a half-written dir (no manifest) must be ignored
    os.makedirs(tmp_path / "step_00000009")
    assert checkpoint.latest_step(str(tmp_path)) == 7


def test_restart_manager_recovers(tmp_path):
    state = _state()
    step = jax.jit(make_train_step(CFG))
    crashes = {"armed": True}

    def fault_hook(i):
        if i == 7 and crashes["armed"]:
            crashes["armed"] = False
            raise RuntimeError("synthetic node failure")

    mgr = RestartManager(str(tmp_path), save_every=5, max_failures=2)
    final = mgr.run(state, lambda s, b: step(s, b),
                    lambda i: _batch(i), 10, fault_hook=fault_hook)
    assert int(final.step) == 10
    assert mgr.failures == 1


def test_restart_manager_gives_up(tmp_path):
    state = _state()
    step = jax.jit(make_train_step(CFG))

    def always_fail(i):
        raise RuntimeError("deterministic crash")

    mgr = RestartManager(str(tmp_path), save_every=5, max_failures=2)
    with pytest.raises(RuntimeError):
        mgr.run(state, lambda s, b: step(s, b), lambda i: _batch(i), 10,
                fault_hook=always_fail)


def test_straggler_monitor():
    mon = StragglerMonitor(factor=2.0)
    for _ in range(5):
        mon.observe(0, 0.1)
    assert mon.observe(5, 0.5) is True     # 5x slower than EMA
    assert len(mon.slow_steps) == 1


def test_elastic_remesh(tmp_path):
    """Checkpoint saved under one sharding restores onto another mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    state = _state()
    checkpoint.save(str(tmp_path), 3, state)
    mesh = jax.make_mesh((1,), ("data",))   # the survivor mesh (1 CPU here)
    shardings = jax.tree.map(
        lambda x: NamedSharding(mesh, P(*([None] * np.ndim(x)))), state)
    restored = checkpoint.restore(str(tmp_path), 3, state,
                                  shardings=shardings)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_int8_quantization_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale) - x))
    assert err.max() <= float(scale) * 0.5 + 1e-7


def test_compressed_psum_single_device_identity():
    """On a 1-member axis, compressed psum == dequant(quant(x)) and the
    error feedback captures exactly the quantization residual."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(64,)), jnp.float32)
    err0 = jnp.zeros_like(x)

    def f(x, e):
        return compressed_psum(x, "i", e, 1)

    out, err = jax.vmap(f, axis_name="i")(x[None], err0[None])
    np.testing.assert_allclose(np.asarray(out[0] + err[0]), np.asarray(x),
                               rtol=1e-5, atol=1e-6)


def test_error_feedback_reduces_bias():
    """Accumulated compressed sums converge to the true sum over steps."""
    rng = np.random.default_rng(2)
    g = jnp.asarray(rng.normal(size=(256,)) * 1e-3, jnp.float32)
    err = jnp.zeros_like(g)
    acc = np.zeros(256, np.float64)

    def f(x, e):
        return compressed_psum(x, "i", e, 1)

    for _ in range(50):
        out, err = jax.vmap(f, axis_name="i")(g[None], err[None])
        err = err[0]
        acc += np.asarray(out[0], np.float64)
    np.testing.assert_allclose(acc, np.asarray(g, np.float64) * 50,
                               rtol=0.02, atol=5e-4)
