"""Shared layers: RMSNorm, RoPE, gated MLP, embeddings, losses."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.param import ParamDef


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def rmsnorm_defs() -> dict:
    return {"scale": None}  # shape filled by caller via make


def make_rmsnorm(d: int) -> dict:
    return {"scale": ParamDef((d,), ("embed",), init="ones")}


def tree_sum(x: jax.Array, axis: int = -1) -> jax.Array:
    """Sum over ``axis`` as a fixed binary tree of elementwise adds.

    The association order depends on the axis length alone.  A reduce (or
    a contraction) lets the compiler pick the order per program: on the
    TPU the same row's sum of squares rounds differently when the array
    holds 1 or 256 positions, which is enough to flip a quantized coding
    table between the engine's prefill and step programs.  Elementwise
    adds of slices cannot be reordered.  The axis is zero-padded to a
    power of two (adding +0.0 is exact).
    """
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    width = 1 << max(n - 1, 0).bit_length()
    if width != n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - n)])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    # f32 second moment (bf16 squares are exact in f32), summed in a fixed
    # order so a row normalizes identically at any batch or chunk extent
    xf = x.astype(jnp.float32)
    var = tree_sum(xf * xf)[..., None]
    var = var / x.shape[-1]
    inv = jax.lax.rsqrt(var + eps)
    return (x.astype(jnp.float32) * inv
            * p["scale"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float) -> jax.Array:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(dh, theta), jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# gated (SiLU) MLP
# ---------------------------------------------------------------------------

def make_mlp(d: int, ff: int) -> dict:
    return {
        "wi_gate": ParamDef((d, ff), ("embed", "mlp")),
        "wi_up": ParamDef((d, ff), ("embed", "mlp")),
        "wo": ParamDef((ff, d), ("mlp", "embed")),
    }


def mlp(p: dict, x: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, p["wi_gate"])
    u = jnp.einsum("...d,df->...f", x, p["wi_up"])
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, p["wo"])


# ---------------------------------------------------------------------------
# embeddings / logits
# ---------------------------------------------------------------------------

def make_embedding(cfg: ModelConfig) -> dict:
    v, d = cfg.vocab_padded, cfg.d_model
    out = {"embedding": ParamDef((v, d), ("vocab", "embed"))}
    if not cfg.tie_embeddings:
        out["lm_head"] = ParamDef((d, v), ("embed", "vocab"))
    return out


def embed(p: dict, tokens: jax.Array, dtype) -> jax.Array:
    return p["embedding"].astype(dtype)[tokens]


def logits(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", x, p["embedding"])
    return jnp.einsum("...d,dv->...v", x, p["lm_head"])


def xent_loss(lg: jax.Array, labels: jax.Array,
              vocab_size: int) -> jax.Array:
    """Mean token cross-entropy in f32; padded vocab tail masked out."""
    lg = lg.astype(jnp.float32)
    v = lg.shape[-1]
    if v > vocab_size:
        neg = jnp.full((v - vocab_size,), -1e30, jnp.float32)
        lg = lg.at[..., vocab_size:].add(neg)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def chunked_xent_loss(p: dict, x: jax.Array, labels: jax.Array,
                      cfg: ModelConfig, chunk: int) -> jax.Array:
    """Sequence-chunked loss: never materializes the full (B,S,V) logits.

    Memory-roofline lever for the 128k-vocab archs (see EXPERIMENTS.md §Perf).
    """
    b, s, _ = x.shape
    assert s % chunk == 0, (s, chunk)
    xc = x.reshape(b, s // chunk, chunk, -1).swapaxes(0, 1)
    lc = labels.reshape(b, s // chunk, chunk).swapaxes(0, 1)

    def step(acc, xs):
        xi, li = xs
        lg = logits(p, xi, cfg)
        return acc + xent_loss(lg, li, cfg.vocab_size), None

    total, _ = jax.lax.scan(step, jnp.float32(0.0), (xc, lc))
    return total / (s // chunk)
