"""Bit-Swap hierarchical VAE over the lane stack (bits-back coding).

A small 2-level VAE whose *coding path* runs entirely through the
craystack-style stack of :mod:`repro.core.stack` — the latent-variable
workload family of the roadmap (DESIGN.md §12).  Each lane is one data
vector (an image patch of ``d_x`` pixels); the lane axis is the coder's
SIMD axis, so a whole batch of patches is coded in lockstep.

Generative model / inference model (all diagonal Gaussians, latents
discretized to the standard normal's equal-mass quantile bins for coding):

    p(z2) = N(0, I)                q2(z2 | z1) = N(mu2(z1), sig2(z1))
    p(z1 | z2) = N(mu, sig)(z2)    q1(z1 | x)  = N(mu1(x), sig1(x))
    p(x | z1)  = DiscretizedLogistic(mu(z1), s(z1)) per pixel

Bit-Swap coding order (encode; decode is the exact reverse with push and
pop swapped — the stack restores its initial state bit-for-bit, which is
the bits-back identity the tests pin):

    A. pop  k1 ~ q1(. | x)      (recovers bits — the bits-back credit)
    B. push x  ~ p(x | z1)
    C. pop  k2 ~ q2(. | z1)
    D. push k1 ~ p(z1 | z2)
    E. push k2 ~ p(z2)          (equal-mass bins -> exactly Uniform)

Training maximizes the continuous ELBO with reparameterized samples; the
networks are built from the repo's own layer substrate
(:mod:`repro.models.layers` gated-SiLU MLP blocks, :mod:`repro.models.param`
ParamDefs) and train with :mod:`repro.train.optimizer` AdamW.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import constants as C
from repro.core import spc, stack
from repro.models import layers
from repro.models.param import ParamDef, init_params
from repro.train import optimizer


class VAEConfig(NamedTuple):
    d_x: int = 64        # pixels per lane (one 8x8 patch)
    d_z: int = 4         # latent dims per level
    d_h: int = 48        # hidden width
    z_bins: int = 16     # latent quantile bins (power of two: exact Uniform)
    x_bins: int = 256    # pixel levels
    prob_bits: int = C.PROB_BITS


# ---------------------------------------------------------------------------
# networks: in-proj -> gated-SiLU MLP residual core -> out-proj
# ---------------------------------------------------------------------------

def _net_defs(d_in: int, d_h: int, d_out: int) -> dict:
    return {
        "win": ParamDef((d_in, d_h), ("embed", "mlp"), scale=0.1),
        "core": layers.make_mlp(d_h, 2 * d_h),
        "wout": ParamDef((d_h, d_out), ("mlp", "embed"), scale=0.1),
        "bout": ParamDef((d_out,), ("embed",), init="zeros"),
    }


def _net(p: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ p["win"])
    h = h + layers.mlp(p["core"], h)
    return h @ p["wout"] + p["bout"]


def vae_defs(cfg: VAEConfig) -> dict:
    return {
        "enc1": _net_defs(cfg.d_x, cfg.d_h, 2 * cfg.d_z),  # x  -> q1
        "enc2": _net_defs(cfg.d_z, cfg.d_h, 2 * cfg.d_z),  # z1 -> q2
        "dec2": _net_defs(cfg.d_z, cfg.d_h, 2 * cfg.d_z),  # z2 -> p(z1|z2)
        "dec1": _net_defs(cfg.d_z, cfg.d_h, 2 * cfg.d_x),  # z1 -> p(x|z1)
    }


def init_vae(cfg: VAEConfig, key: jax.Array) -> dict:
    return init_params(vae_defs(cfg), key)


def _mu_sig(raw: jax.Array):
    """Split a ``(..., 2d)`` net output into (mu, sigma); log-sigma clamped
    for optimizer stability (coding re-clamps identically, so train and
    code see the same distributions)."""
    mu, logsig = jnp.split(raw, 2, axis=-1)
    return mu, jnp.exp(jnp.clip(logsig, -4.0, 2.0))


def _mu_logs(raw: jax.Array):
    """Pixel-likelihood head: (mu in [-1,1]-ish, log-scale clamped)."""
    mu, log_s = jnp.split(raw, 2, axis=-1)
    return mu, jnp.clip(log_s, -7.0, 1.0)


def normalize(x: jax.Array, x_bins: int) -> jax.Array:
    """Integer pixel levels -> bin centres in [-1, 1]."""
    return 2.0 * (x.astype(jnp.float32) + 0.5) / x_bins - 1.0


# ---------------------------------------------------------------------------
# continuous ELBO (training)
# ---------------------------------------------------------------------------

def _gauss_logpdf(z, mu, sig):
    zn = (z - mu) / sig
    return -0.5 * zn * zn - jnp.log(sig) - 0.5 * np.log(2 * np.pi)


def _dlogistic_loglik(x, mu, log_s, x_bins: int):
    """log p(x) of the discretized logistic over ``x_bins`` levels in
    [-1, 1] — the same binning the coding path quantizes
    (``stack.logistic_bin_probs``), endpoint bins take the open tails."""
    lower = 2.0 * x.astype(jnp.float32) / x_bins - 1.0
    upper = 2.0 * (x.astype(jnp.float32) + 1.0) / x_bins - 1.0
    inv_s = jnp.exp(-log_s)
    cdf_lo = jax.nn.sigmoid((lower - mu) * inv_s)
    cdf_hi = jax.nn.sigmoid((upper - mu) * inv_s)
    cdf_lo = jnp.where(x <= 0, 0.0, cdf_lo)
    cdf_hi = jnp.where(x >= x_bins - 1, 1.0, cdf_hi)
    return jnp.log(jnp.maximum(cdf_hi - cdf_lo, 1e-12))


def elbo_loss(params: dict, x: jax.Array, cfg: VAEConfig,
              key: jax.Array) -> jax.Array:
    """Negative ELBO in nats per lane (mean over the batch/lane axis)."""
    xn = normalize(x, cfg.x_bins)
    k1, k2 = jax.random.split(key)

    mu1, sig1 = _mu_sig(_net(params["enc1"], xn))
    z1 = mu1 + sig1 * jax.random.normal(k1, mu1.shape)
    mu2, sig2 = _mu_sig(_net(params["enc2"], z1))
    z2 = mu2 + sig2 * jax.random.normal(k2, mu2.shape)

    mu1p, sig1p = _mu_sig(_net(params["dec2"], z2))
    mux, log_sx = _mu_logs(_net(params["dec1"], z1))

    log_px = jnp.sum(_dlogistic_loglik(x, mux, log_sx, cfg.x_bins), -1)
    kl1 = jnp.sum(_gauss_logpdf(z1, mu1, sig1)
                  - _gauss_logpdf(z1, mu1p, sig1p), -1)
    kl2 = jnp.sum(_gauss_logpdf(z2, mu2, sig2)
                  - _gauss_logpdf(z2, jnp.zeros_like(mu2),
                                  jnp.ones_like(sig2)), -1)
    return jnp.mean(-log_px + kl1 + kl2)


def train_vae(cfg: VAEConfig, batches, *, steps: int = 300,
              lr: float = 3e-3, seed: int = 0) -> dict:
    """Train on ``batches`` (callable ``step -> (lanes, d_x)`` int array).
    Small and CPU-friendly by design — the example/CI budget."""
    key = jax.random.PRNGKey(seed)
    params = init_vae(cfg, key)
    opt = optimizer.adamw_init(params)

    @jax.jit
    def step_fn(params, opt, x, k):
        loss, grads = jax.value_and_grad(elbo_loss)(params, x, cfg, k)
        grads, _ = optimizer.clip_by_global_norm(grads, 1.0)
        params, opt = optimizer.adamw_update(grads, opt, params, lr,
                                             weight_decay=1e-4)
        return params, opt, loss

    loss = None
    for i in range(steps):
        x = jnp.asarray(batches(i), jnp.int32)
        params, opt, loss = step_fn(params, opt, x,
                                    jax.random.fold_in(key, i + 1))
    return params, float(loss)


# ---------------------------------------------------------------------------
# bits-back coding over the stack
# ---------------------------------------------------------------------------

def _latent_tables(mu: jax.Array, sig: jax.Array, edges: jax.Array,
                   prob_bits: int):
    """Per-dim Gaussian bin tables: (lanes, d) nets -> (d, lanes, B) freq/cdf
    (the ``(T, lanes, K)`` per-position layout of the stack array codecs)."""
    probs = stack.gaussian_bin_probs(mu.T, sig.T, edges)
    return spc.freq_cdf_from_probs(spc.store_bf16(probs), prob_bits)


def _pixel_tables(params: dict, z1c: jax.Array, cfg: VAEConfig):
    """p(x | z1) tables: (d_x, lanes, x_bins) discretized logistic."""
    mux, log_sx = _mu_logs(_net(params["dec1"], z1c))
    probs = stack.logistic_bin_probs(mux.T, log_sx.T, cfg.x_bins)
    return spc.freq_cdf_from_probs(spc.store_bf16(probs), cfg.prob_bits)


def _uniform_tables(k: int, prob_bits: int):
    """Exact uniform tables over ``k`` symbols (requires 2**prob_bits % k
    == 0 — the equal-mass standard-normal prior over its own quantile
    bins)."""
    total = 1 << prob_bits
    if total % k:
        raise ValueError(f"uniform prior needs 2**{prob_bits} % {k} == 0")
    f = total // k
    freq = jnp.full((k,), f, jnp.uint32)
    cdf = (jnp.arange(k + 1, dtype=jnp.uint32) * f).astype(jnp.uint32)
    return freq, cdf


@functools.lru_cache(maxsize=8)
def _bins(z_bins: int):
    edges, centres = stack.std_gaussian_bins(z_bins)
    return edges, centres


def bb_encode(st: stack.StackState, params: dict, x: jax.Array,
              cfg: VAEConfig, backend: str = "coder") -> stack.StackState:
    """Bits-back encode one ``(lanes, d_x)`` batch onto the stack (the
    A-E Bit-Swap schedule in the module docstring).  The net message cost
    is ``stack.stack_bytes`` growth — the posterior pop's recovered bits
    are credited automatically by the stack discipline."""
    pb = cfg.prob_bits
    edges, centres = _bins(cfg.z_bins)
    xn = normalize(x, cfg.x_bins)

    # A: pop k1 ~ q1(. | x)
    mu1, sig1 = _mu_sig(_net(params["enc1"], xn))
    f1, c1 = _latent_tables(mu1, sig1, edges, pb)
    st, k1 = stack.pop_symbols(st, cfg.d_z, f1, c1, pb, backend=backend)
    z1c = centres[k1]

    # B: push x ~ p(x | z1)
    fx, cx = _pixel_tables(params, z1c, cfg)
    st = stack.push_symbols(st, x, fx, cx, pb)

    # C: pop k2 ~ q2(. | z1)
    mu2, sig2 = _mu_sig(_net(params["enc2"], z1c))
    f2, c2 = _latent_tables(mu2, sig2, edges, pb)
    st, k2 = stack.pop_symbols(st, cfg.d_z, f2, c2, pb, backend=backend)
    z2c = centres[k2]

    # D: push k1 ~ p(z1 | z2)
    mu1p, sig1p = _mu_sig(_net(params["dec2"], z2c))
    fp, cp = _latent_tables(mu1p, sig1p, edges, pb)
    st = stack.push_symbols(st, k1, fp, cp, pb)

    # E: push k2 ~ p(z2) (exactly uniform over equal-mass bins)
    fu, cu = _uniform_tables(cfg.z_bins, pb)
    return stack.push_symbols(st, k2, fu, cu, pb)


def bb_decode(st: stack.StackState, params: dict, cfg: VAEConfig,
              backend: str = "coder"):
    """Exact reverse of :func:`bb_encode` (push and pop swapped, E' -> A').
    Returns ``(state, x)``; the state equals the pre-encode stack
    bit-for-bit — the bits-back identity."""
    pb = cfg.prob_bits
    edges, centres = _bins(cfg.z_bins)

    # E': pop k2 ~ p(z2)
    fu, cu = _uniform_tables(cfg.z_bins, pb)
    st, k2 = stack.pop_symbols(st, cfg.d_z, fu, cu, pb, backend=backend)
    z2c = centres[k2]

    # D': pop k1 ~ p(z1 | z2)
    mu1p, sig1p = _mu_sig(_net(params["dec2"], z2c))
    fp, cp = _latent_tables(mu1p, sig1p, edges, pb)
    st, k1 = stack.pop_symbols(st, cfg.d_z, fp, cp, pb, backend=backend)
    z1c = centres[k1]

    # C': push k2 ~ q2(. | z1)
    mu2, sig2 = _mu_sig(_net(params["enc2"], z1c))
    f2, c2 = _latent_tables(mu2, sig2, edges, pb)
    st = stack.push_symbols(st, k2, f2, c2, pb)

    # B': pop x ~ p(x | z1)
    fx, cx = _pixel_tables(params, z1c, cfg)
    st, x = stack.pop_symbols(st, cfg.d_x, fx, cx, pb, backend=backend)

    # A': push k1 ~ q1(. | x)
    xn = normalize(x, cfg.x_bins)
    mu1, sig1 = _mu_sig(_net(params["enc1"], xn))
    f1, c1 = _latent_tables(mu1, sig1, edges, pb)
    return stack.push_symbols(st, k1, f1, c1, pb), x
