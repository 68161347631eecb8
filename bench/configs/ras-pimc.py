"""ras-pimc: the paper's compact autoregressive image model over 8-bit
symbols, served by the program's batched engine.

This file builds the system under test from ``ras-pimc.json`` and the seed,
and holds the plain reference of the model: a float32 ``jax.numpy`` forward
written from the architecture and not from the program (pre-norm RMSNorm
blocks, rotary positions on query and key, causal softmax attention, SwiGLU
MLP, final RMSNorm, logits tied to the embedding).

Precision: the source states float32 and no matmul precision.  The
compared reference computes the products with the weights (projections,
MLP, logits) at the precision the program runs them at, recorded under
``assumed.matmul_precision`` (default: plain ``einsum`` at JAX's default
precision, which on a TPU rounds their operands to bfloat16 and
accumulates in float32); attention (scores and values) and everything else
is float32 throughout (``HIGHEST``).  A second reference at ``HIGHEST``
everywhere is run beside it, and its table mismatch printed, not compared.
"""

from __future__ import annotations

import functools
import math

import numpy as np

import engine_probe

BOS = 0


def model_config(cfg: dict, dtype: str | None = None):
    """The program's model config for the sizes in ``cfg``."""
    from repro.models.config import ModelConfig
    m = cfg["model"]
    return ModelConfig(
        name=cfg["name"], family=m["family"], n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], d_ff=m["d_ff"],
        vocab_size=m["vocab_size"], head_dim=m["head_dim"],
        tie_embeddings=m["tie_embeddings"], rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"], dtype=dtype or m["dtype"], remat=False)


def _shapes(mcfg) -> dict:
    n, d, ff = mcfg.n_layers, mcfg.d_model, mcfg.d_ff
    h, kv, dh, v = (mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim,
                    mcfg.vocab_size)
    return {
        "tok": {"embedding": (v, d)},
        "final_norm": {"scale": (d,)},
        "stages": {"s0": {"b0_attn": {
            "ln1": {"scale": (n, d)},
            "ln2": {"scale": (n, d)},
            "attn": {"wq": (n, d, h, dh), "wk": (n, d, kv, dh),
                     "wv": (n, d, kv, dh), "wo": (n, h, dh, d)},
            "ffn": {"wi_gate": (n, d, ff), "wi_up": (n, d, ff),
                    "wo": (n, ff, d)}}}},
    }


def make_weights(mcfg, seed: int):
    """Every weight from the seed, on the device, in one jitted call, in the
    dtype the model is served in: normal(0, 0.02) matrices, unit norm
    scales."""
    import jax
    import jax.numpy as jnp
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        _shapes(mcfg), is_leaf=lambda x: isinstance(x, tuple))
    dtype = jnp.dtype(mcfg.dtype)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, shp) in zip(keys, leaves):
            if path[-1].key == "scale":
                out.append(jnp.ones(shp, dtype))
            else:
                out.append((0.02 * jax.random.normal(k, shp)).astype(dtype))
        return jax.tree.unflatten(tree, out)

    seed = int(seed) % (1 << 64)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    params = make(key)
    from repro.models import abstract_model
    want = jax.tree.map(lambda a: a.shape, abstract_model(mcfg))
    got = jax.tree.map(lambda a: a.shape, params)
    if want != got:
        raise ValueError(f"weights do not match the program's layout: "
                         f"{got} vs {want}")
    return params


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * scale


def _rope(x, theta):
    import jax.numpy as jnp
    t, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dh, 2) / dh))
    ang = np.arange(t)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), x.dtype)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


PRECISION = {"default": "DEFAULT", "high": "HIGH", "highest": "HIGHEST"}


def _forward(params, tokens, *, n_layers, n_heads, n_kv_heads, eps, theta,
             dtype, weights_precision):
    """tokens (B, T) -> logits (B, T, V), causal, teacher-forced."""
    import jax
    import jax.numpy as jnp
    f32 = dtype == jnp.float32
    exact = jax.lax.Precision.HIGHEST if f32 else jax.lax.Precision.DEFAULT
    wp = getattr(jax.lax.Precision, weights_precision) if f32 \
        else jax.lax.Precision.DEFAULT
    mm = functools.partial(jnp.einsum, precision=wp)
    att = functools.partial(jnp.einsum, precision=exact)
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    blk = p["stages"]["s0"]["b0_attn"]
    emb = p["tok"]["embedding"]
    x = emb[tokens]
    t = tokens.shape[1]
    group = n_heads // n_kv_heads
    causal = np.tril(np.ones((t, t), bool))
    for i in range(n_layers):
        a = jax.tree.map(lambda w: w[i], blk)
        h = _rms(x, a["ln1"]["scale"], eps)
        q = mm("btd,dhk->bthk", h, a["attn"]["wq"])
        k = mm("btd,dhk->bthk", h, a["attn"]["wk"])
        v = mm("btd,dhk->bthk", h, a["attn"]["wv"])
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
        s = att("bqhk,bphk->bhqp", q, k) / math.sqrt(q.shape[-1])
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s.astype(jnp.float32), -1).astype(dtype)
        o = att("bhqp,bphk->bqhk", w, v)
        x = x + mm("bqhk,hkd->bqd", o, a["attn"]["wo"])
        h = _rms(x, a["ln2"]["scale"], eps)
        g = mm("btd,df->btf", h, a["ffn"]["wi_gate"])
        u = mm("btd,df->btf", h, a["ffn"]["wi_up"])
        x = x + mm("btf,fd->btd", jax.nn.silu(g) * u, a["ffn"]["wo"])
    x = _rms(x, p["final_norm"]["scale"], eps)
    return mm("btd,vd->btv", x, emb).astype(jnp.float32)


def reference_logits(params, mcfg, tile: np.ndarray, precision: str,
                     dtype: str = "float32", rows: int = 8) -> np.ndarray:
    """Logits ``(lanes, T, V)`` pricing every symbol of ``tile`` (lanes, T):
    position ``t`` sees BOS then ``tile[:, :t]``.  Runs ``rows`` lanes at a
    time so that it fits beside nothing else."""
    import jax
    import jax.numpy as jnp
    tokens = np.concatenate(
        [np.full((tile.shape[0], 1), BOS, np.int32), tile[:, :-1]], axis=1)
    fwd = _jitted_forward(mcfg.n_layers, mcfg.n_heads, mcfg.n_kv_heads,
                          mcfg.norm_eps, mcfg.rope_theta, jnp.dtype(dtype),
                          PRECISION[precision])
    out = [np.asarray(fwd(params, jnp.asarray(tokens[r:r + rows])))
           for r in range(0, tokens.shape[0], rows)]
    return np.concatenate(out, axis=0)


@functools.lru_cache(maxsize=None)
def _jitted_forward(n_layers, n_heads, n_kv_heads, eps, theta, dtype,
                    weights_precision):
    import jax
    return jax.jit(functools.partial(
        _forward, n_layers=n_layers, n_heads=n_heads, n_kv_heads=n_kv_heads,
        eps=eps, theta=theta, dtype=dtype,
        weights_precision=weights_precision))


def build(cfg: dict, traffic: dict, seed: int, seconds: float, probe,
          dtype: str | None = None):
    import types
    model = types.SimpleNamespace(model_config=model_config,
                                  make_weights=make_weights,
                                  reference_logits=reference_logits)
    return engine_probe.EngineSystem(cfg, traffic, seed, seconds, probe,
                                     model, dtype=dtype)


def control(cfg: dict) -> tuple[dict, str | None]:
    """The control: the program's own model path in bfloat16, the precision
    below the float32 the configuration states (weights cast, activations
    and matrix products in bfloat16)."""
    return cfg, "bfloat16"
