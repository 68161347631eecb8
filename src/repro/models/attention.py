"""GQA attention: self/cross, naive & blockwise(flash-style), KV/ring caches.

TP mapping (DESIGN.md §5): q/out heads are padded to a multiple of ``cfg.tp``
and sharded over the model axis; kv projections shard only when
``n_kv_heads % tp == 0`` (else they replicate over model and FSDP-shard over
data).  Padded q heads are zero-initialized in both wq and wo so the function
equals the true-head architecture at init.

Two attention schedules:
  * ``naive``     — full (B,H,Sq,Skv) score tensor; baseline for roofline.
  * ``blockwise`` — lax.scan over KV chunks with online softmax (flash-style
    in pure XLA); the memory-roofline lever for the 32k shapes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, rmsnorm, tree_sum
from repro.models.param import ParamDef

_NEG = -1e30

# Ring-reduction tile (slots).  The decode softmax/value reduction runs in
# fixed tiles of this many cache slots, accumulated sequentially, so the
# reduction tree is a function of slot *content* only — never of the ring
# length.  See _ring_blocks below for why that invariance is load-bearing.
_RING_BLOCK = 32


def _ring_blocks(x: jax.Array, axis: int) -> jax.Array:
    """Zero-pad ``axis`` to a multiple of ``_RING_BLOCK`` and split it into
    a leading scan axis of ``(n_blocks, ..., _RING_BLOCK, ...)`` tiles.

    §Bit-exactness: XLA retiles a fused reduction with the extent of the
    reduced axis, so the *same* 16 live cache slots summed under a 20-slot
    vs a 32-slot ring round differently (~1 ulp).  One ulp is enough to
    flip a quantized coding table entry, and a flipped table desyncs the
    batched engine's rANS decode from the single-request encode it must be
    byte-identical to.  Scanning fixed-size tiles pins every reduction tree:
    a longer ring only appends all-zero tiles, each contributing an exact
    +0.0 to the running accumulator.
    """
    n = x.shape[axis]
    nb = -(-n // _RING_BLOCK)
    pad = nb * _RING_BLOCK - n
    ax = axis % x.ndim
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[ax] = (0, pad)
        x = jnp.pad(x, widths)
    x = x.reshape(x.shape[:ax] + (nb, _RING_BLOCK) + x.shape[ax + 1:])
    return jnp.moveaxis(x, ax, 0)


def _ring_attn(prob: jax.Array, v: jax.Array, contract) -> jax.Array:
    """Ring-length-invariant ``contract(prob, v)`` summed over cache tiles.

    ``prob`` carries the cache axis last, ``v`` carries it at axis 1;
    ``contract`` reduces one ``_RING_BLOCK`` tile pair.  Invalid slots must
    already hold exact zeros in ``prob`` (padding adds more zeros).
    """
    pb = _ring_blocks(prob, -1)
    vb = _ring_blocks(v, 1)
    out0 = jax.eval_shape(contract, pb[0], vb[0])

    def body(acc, xs):
        return acc + contract(*xs), None

    acc, _ = jax.lax.scan(body, jnp.zeros(out0.shape, out0.dtype), (pb, vb))
    return acc


def _ring_sum(e: jax.Array) -> jax.Array:
    """Ring-length-invariant sum of ``e`` over its last (cache) axis."""
    eb = _ring_blocks(e, -1)

    def body(acc, blk):
        return acc + tree_sum(blk), None

    acc, _ = jax.lax.scan(body, jnp.zeros(e.shape[:-1], e.dtype), eb)
    return acc


def kv_head_map(cfg: ModelConfig) -> np.ndarray:
    """Static q-head -> kv-head index map (GQA groups; padded heads -> 0)."""
    h, kv, hp = cfg.n_heads, cfg.n_kv_heads, cfg.n_heads_padded
    g = h // kv
    return np.asarray([min(i // g, kv - 1) for i in range(h)]
                      + [0] * (hp - h), np.int32)


def make_attn_defs(cfg: ModelConfig, cross: bool = False) -> dict:
    d, dh = cfg.d_model, cfg.head_dim_
    hp, kv = cfg.n_heads_padded, cfg.n_kv_heads
    kv_axis = "kv_heads" if cfg.kv_sharded else "kv_heads_repl"
    out = {
        "wq": ParamDef((d, hp, dh), ("embed", "heads", None),
                       true_sizes=(None, cfg.n_heads, None)),
        "wk": ParamDef((d, kv, dh), ("embed", kv_axis, None)),
        "wv": ParamDef((d, kv, dh), ("embed", kv_axis, None)),
        "wo": ParamDef((hp, dh, d), ("heads", None, "embed"),
                       true_sizes=(cfg.n_heads, None, None)),
    }
    if cfg.qkv_bias:
        out["bq"] = ParamDef((hp, dh), ("heads", None), init="zeros")
        out["bk"] = ParamDef((kv, dh), (kv_axis, None), init="zeros")
        out["bv"] = ParamDef((kv, dh), (kv_axis, None), init="zeros")
    if cfg.qk_norm:
        out["q_norm"] = ParamDef((dh,), (None,), init="ones")
        out["k_norm"] = ParamDef((dh,), (None,), init="ones")
    return out


def _project_qkv(p: dict, x: jax.Array, cfg: ModelConfig,
                 mem: jax.Array | None = None):
    """x -> q (B,S,Hp,Dh); kv source is ``mem`` for cross attention."""
    src = x if mem is None else mem
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = rmsnorm({"scale": p["q_norm"]}, q, cfg.norm_eps)
        k = rmsnorm({"scale": p["k_norm"]}, k, cfg.norm_eps)
    return q, k, v


def _expand_kv(k: jax.Array, cfg: ModelConfig) -> jax.Array:
    """(B,S,KV,Dh) -> (B,S,Hp,Dh) via the static GQA head map."""
    if k.shape[2] == cfg.n_heads_padded:
        return k
    return jnp.take(k, jnp.asarray(kv_head_map(cfg)), axis=2)


def _mask(q_idx, kv_idx, causal: bool, window: int):
    ok = jnp.ones(jnp.broadcast_shapes(q_idx.shape, kv_idx.shape), bool)
    if causal:
        ok &= kv_idx <= q_idx
    if window:
        ok &= kv_idx > q_idx - window
    return ok


def _naive_attn(q, k, v, causal, window, q_offset=0):
    b, sq, h, dh = q.shape
    skv = k.shape[1]
    scale = 1.0 / math.sqrt(dh)
    # bf16 operands, f32 accumulation (MXU-native); no f32 copies of q/k —
    # §Perf memory-term lever (bit-identical: bf16 products are exact in f32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    q_idx = (jnp.arange(sq) + q_offset)[:, None]
    kv_idx = jnp.arange(skv)[None, :]
    s = jnp.where(_mask(q_idx, kv_idx, causal, window)[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _blockwise_attn(q, k, v, causal, window, block, q_offset=0):
    """Flash-style online-softmax scan over KV chunks (pure XLA)."""
    b, sq, h, dh = q.shape
    skv = k.shape[1]
    blk = min(block, skv)
    n_chunks = math.ceil(skv / blk)
    pad = n_chunks * blk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, blk, h, dh).swapaxes(0, 1)
    vc = v.reshape(b, n_chunks, blk, h, dh).swapaxes(0, 1)
    scale = 1.0 / math.sqrt(dh)
    q_idx = (jnp.arange(sq) + q_offset)[:, None]

    def step(carry, xs):
        m, l, acc = carry
        j, kj, vj = xs
        kv_idx = j * blk + jnp.arange(blk)[None, :]
        ok = _mask(q_idx, kv_idx, causal, window)          # (Sq, blk)
        ok = ok & (kv_idx < skv)                           # kv padding
        # bf16-in / f32-accumulate: no materialized f32 q/k/v copies
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kj,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok[None, None], s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None]) * ok[None, None]
        l = l * corr + jnp.sum(p, axis=-1)
        acc = (acc * corr[..., None]
               + jnp.einsum("bhqk,bkhd->bhqd", p.astype(q.dtype), vj,
                            preferred_element_type=jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((b, h, sq), _NEG, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.swapaxes(1, 2).astype(q.dtype)


def attn_forward(p: dict, x: jax.Array, cfg: ModelConfig,
                 mem: jax.Array | None = None,
                 window: int | None = None,
                 positions: jax.Array | None = None) -> jax.Array:
    """Full-sequence attention (training / prefill).  Cross if mem given."""
    cross = mem is not None
    q, k, v = _project_qkv(p, x, cfg, mem)
    if not cross:
        pos = (positions if positions is not None
               else jnp.arange(x.shape[1])[None, :])
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    k = _expand_kv(k, cfg)
    v = _expand_kv(v, cfg)
    win = cfg.sliding_window if window is None else window
    causal = not cross
    if cfg.attn_impl == "blockwise":
        out = _blockwise_attn(q, k, v, causal, win, cfg.attn_block)
    else:
        out = _naive_attn(q, k, v, causal, win)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# ---------------------------------------------------------------------------
# KV cache (decode) — linear or ring-buffer (local attention)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype) -> dict:
    kv, dh = cfg.n_kv_heads, cfg.head_dim_
    return {"k": jnp.zeros((batch, max_len, kv, dh), dtype),
            "v": jnp.zeros((batch, max_len, kv, dh), dtype)}


def _attend_slots(q: jax.Array, ck: jax.Array, cv: jax.Array,
                  valid: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Single-position ring attention core: q (B,1,Hp,Dh) against the cache
    (B,R,KV,Dh) under a (B|1, R) slot-validity mask -> (B,1,Hp,Dh).

    This is the ONE implementation of the decode score/softmax/value chain.
    Both the step path (:func:`attn_decode`) and the prefill fast path
    (:func:`attn_prefill`, which ``lax.map``s it over chunk positions) go
    through it with identical q-extent-1 shapes — a multi-query einsum
    rounds ~1 ulp differently than S single-query ones, which is enough to
    flip a quantized coding table, so the shapes must literally match.
    """
    scale = 1.0 / math.sqrt(cfg.head_dim_)
    hp, kv = cfg.n_heads_padded, cfg.n_kv_heads
    if not (kv > 0 and hp % kv == 0):
        ck, cv, kv = _expand_kv(ck, cfg), _expand_kv(cv, cfg), hp
    # §Perf: grouped GQA decode — q-head groups contract against the kv
    # cache directly, never materializing the (S, H) expanded cache (16x
    # the cache bytes for kv=8, H=128).  Every contraction below is an
    # elementwise product summed by ``tree_sum``, tile by tile of
    # _RING_BLOCK cache slots: a contraction or reduce lets the compiler
    # choose the summation order per program (on the TPU it differs with
    # the row count), and the order must be one fixed function of the
    # shapes the step path shares with the prefill path.
    g = hp // kv
    qg = q[:, 0].reshape(q.shape[0], kv, g, 1, 1, q.shape[-1])
    qg = qg.astype(jnp.float32)

    def scores(kb):                                  # (B, BLOCK, KV, Dh)
        kt = jnp.moveaxis(kb, 1, 2)[:, :, None, None].astype(jnp.float32)
        return tree_sum(qg * kt)                     # (B, KV, G, 1, BLOCK)

    sb = jax.lax.map(scores, _ring_blocks(ck, 1))
    # (nb, ..., BLOCK) -> (..., nb * BLOCK): the padded cache axis
    sb = jnp.moveaxis(sb, 0, -2)
    s = sb.reshape(sb.shape[:-2] + (-1,)) * scale
    # Slots past cache_len are tile padding: never valid.
    validp = jnp.pad(valid, ((0, 0), (0, s.shape[-1] - valid.shape[-1])))
    vshape = (validp.shape[0],) + (1,) * (s.ndim - 2) + (s.shape[-1],)
    vmask = validp.reshape(vshape)
    s = jnp.where(vmask, s, _NEG)
    # Ring-length-invariant softmax: max is exactly associative, exp is
    # elementwise, and the two reductions (denominator, weighted values)
    # run over fixed slot tiles — see _ring_blocks.  Invalid slots are
    # forced to an exact 0.0 weight rather than trusting exp underflow.
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(vmask, jnp.exp(s - m), 0.0)
    prob = (e / _ring_sum(e)[..., None]).astype(q.dtype)

    def values(pb, vb):             # (B, KV, G, 1, BLOCK), (B, BLOCK, KV, Dh)
        vt = jnp.moveaxis(vb, 1, 2)[:, :, None, None]
        return tree_sum(pb[..., None] * vt, axis=-2)  # (B, KV, G, 1, Dh)

    out = _ring_attn(prob, cv, values)
    return jnp.moveaxis(out, 3, 1).reshape(out.shape[0], 1, hp,
                                           out.shape[-1])


def attn_decode(p: dict, x1: jax.Array, cache: dict, pos: jax.Array,
                cfg: ModelConfig, mem: jax.Array | None = None,
                window: int | None = None):
    """One-token decode.  x1: (B,1,D); pos: absolute position — a scalar
    int32, or a ``(B,)`` vector of per-row positions (the batched serve
    engine's continuous-batching slots: every row advances its own ring
    independently).  The scalar path is float-identical to the vector path
    with a constant vector (same broadcasted graph, one row of masks).

    With ``window`` (or cfg.sliding_window/local_window) and a cache sized
    to the window, indexing is a ring buffer — O(window) memory at 500k+
    context.  A cache shorter than the sequence *always* rings (slot =
    pos % cache_len; entries older than cache_len are overwritten and
    masked out by age), window or not — the engine's shared-cache wrap
    contract, pinned logit-level in tests/test_serve_engine.py.
    Cross-attention decodes against full ``mem`` (no cache).
    """
    if mem is not None:
        q, k, v = _project_qkv(p, x1, cfg, mem)
        k = _expand_kv(k, cfg)
        v = _expand_kv(v, cfg)
        out = _naive_attn(q, k, v, causal=False, window=0)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache

    q, k, v = _project_qkv(p, x1, cfg, None)
    pos_v = jnp.asarray(pos)
    # rows: (B,) per-row positions, or a broadcast (1,) row for scalar pos
    pos_b = pos_v if pos_v.ndim == 1 else pos_v[None]
    q = apply_rope(q, pos_b[:, None], cfg.rope_theta)
    k = apply_rope(k, pos_b[:, None], cfg.rope_theta)
    cache_len = cache["k"].shape[1]
    slot = pos_b % cache_len
    # §Perf (llama3-405b decode_32k): masked ring write instead of
    # dynamic_update_slice — elementwise select keeps the context-parallel
    # cache sharded (DUS at a traced offset forced SPMD to materialize the
    # full cache per chip: 2x cache temp + reshard).
    hot = (jnp.arange(cache_len)[None, :] == slot[:, None])[:, :, None, None]
    ck = jnp.where(hot, k.astype(cache["k"].dtype), cache["k"])
    cv = jnp.where(hot, v.astype(cache["v"].dtype), cache["v"])

    idx = jnp.arange(cache_len)
    # Unified ring semantics (covers the linear cache too, where slot == pos):
    # age of the entry in each slot; unwritten slots have age > pos.
    # Per-row when pos is a vector — each batch row masks its own ring.
    age = (slot[:, None] - idx[None, :]) % cache_len      # (1|B, cache_len)
    valid = age <= pos_b[:, None]
    win = window if window is not None else (cfg.local_window
                                             or cfg.sliding_window)
    if win:
        valid &= age < win
    out = _attend_slots(q, ck, cv, valid, cfg)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"k": ck, "v": cv}


def attn_prefill(p: dict, xs: jax.Array, cache: dict, pos0: jax.Array,
                 n_valid: jax.Array, cfg: ModelConfig,
                 window: int | None = None):
    """Teacher-forced multi-position decode: one block-parallel pass over
    ``S`` positions per row, bit-identical to ``S`` sequential
    :func:`attn_decode` steps.  ``xs``: (B,S,D); ``pos0``/``n_valid``: (B,)
    per-row chunk start and live step count (rows beyond ``n_valid`` are
    frozen — their queries are computed and discarded, nothing is written).

    Identity argument: the projections are batch-extent-independent GEMMs
    (checked on the TPU as well as the CPU), the norms sum in a fixed
    order (:func:`repro.models.layers.tree_sum` — a compiler-chosen reduce
    is not extent-independent on the TPU), and the attend itself runs the
    SAME q-extent-1 :func:`_attend_slots` core as the step path,
    ``lax.map``-ed over the S positions — a multi-query score/value einsum
    rounds ~1 ulp differently than S single-query ones, so the shapes must
    literally match.  The one
    structural divergence — this writes all S entries before any query
    attends — is masked out: a future in-chunk entry is ``valid=False``
    for earlier queries exactly where the step path would have seen a dead
    zero slot.  That argument needs ``pos0 + S <= cache_len`` (no slot
    still visible to a query is overwritten); callers gate wrapped streams
    to the step path.
    """
    q, k, v = _project_qkv(p, xs, cfg, None)
    S = xs.shape[1]
    pq = pos0[:, None] + jnp.minimum(jnp.arange(S)[None, :],
                                     n_valid[:, None])          # (B, S)
    q = apply_rope(q, pq, cfg.rope_theta)
    k = apply_rope(k, pq, cfg.rope_theta)
    cache_len = cache["k"].shape[1]
    offs = (jnp.arange(cache_len)[None, :] - pos0[:, None]) % cache_len
    wr = offs < n_valid[:, None]                                # (B, R)
    src = jnp.minimum(offs, S - 1)
    knew = jnp.take_along_axis(k, src[..., None, None], axis=1)
    vnew = jnp.take_along_axis(v, src[..., None, None], axis=1)
    ck = jnp.where(wr[..., None, None], knew.astype(cache["k"].dtype),
                   cache["k"])
    cv = jnp.where(wr[..., None, None], vnew.astype(cache["v"].dtype),
                   cache["v"])
    # absolute position each slot holds after the chunk's writes; slots the
    # chunk left alone hold pre-chunk entries (negative = never written)
    spos = jnp.where(wr, pos0[:, None] + offs,
                     pos0[:, None] - cache_len + offs)          # (B, R)
    valid = ((spos[:, None, :] <= pq[:, :, None])
             & (spos[:, None, :] >= 0))                         # (B, S, R)
    win = window if window is not None else (cfg.local_window
                                             or cfg.sliding_window)
    if win:
        valid &= (pq[:, :, None] - spos[:, None, :]) < win

    # Per-position attend at the step path's exact q-extent-1 shapes; only
    # the O(S·R) attend loops — the O(S·D²) projections/norms stay batched,
    # which is where the prefill speedup lives.
    def one_pos(xs_t):
        q1, val = xs_t                                      # (B,Hp,Dh),(B,R)
        return _attend_slots(q1[:, None], ck, cv, val, cfg)[:, 0]

    out = jax.lax.map(one_pos, (jnp.moveaxis(q, 1, 0),
                                jnp.moveaxis(valid, 1, 0)))
    out = jnp.moveaxis(out, 0, 1)                           # (B,S,Hp,Dh)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"k": ck, "v": cv}
