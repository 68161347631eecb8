"""Model assembly: heterogeneous layer patterns, scanned stages, enc-dec.

A model is a sequence of **stages**; each stage is a layer-kind *pattern*
(e.g. recurrentgemma's ("rec","rec","attn")) stacked ``reps`` times and run
under ``lax.scan`` (optionally ``jax.checkpoint``-rematerialized).  Layer
kinds:

    attn       self-attention + gated MLP            (dense archs)
    attn_moe   self-attention + MoE FFN              (mixtral, phi3.5)
    cross      cross-attention + MLP                 (llama3.2-vision layers)
    dec        self-attn + cross-attn + MLP          (seamless decoder)
    ssm        mamba2 SSD mixer (no FFN)
    rec        RG-LRU recurrent block + MLP          (recurrentgemma)

Both directions are provided: ``forward``/``loss_fn`` (training & prefill)
and ``init_cache``/``decode_step`` (serving, one token against a cache).
"""

from __future__ import annotations

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp

from repro.models.attention import (attn_decode, attn_forward, attn_prefill,
                                    init_kv_cache, make_attn_defs)
from repro.models.config import ModelConfig
from repro.models.layers import (chunked_xent_loss, embed, logits,
                                 make_embedding, make_mlp, make_rmsnorm, mlp,
                                 rmsnorm, xent_loss)
from repro.models.moe import make_moe_defs, moe
from repro.models.param import ParamDef, abstract_params, init_params
from repro.models.rglru import (init_rglru_cache, make_rglru_defs,
                                rglru_decode_step, rglru_forward)
from repro.models.ssm import (init_ssm_cache, make_ssm_defs, ssm_decode_step,
                              ssm_forward)

FFN_KINDS = ("attn", "attn_moe", "cross", "dec", "rec")


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# per-kind block definitions
# ---------------------------------------------------------------------------

def make_block_defs(kind: str, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    out = {"ln1": make_rmsnorm(d)}
    if kind in ("attn", "attn_moe", "dec"):
        out["attn"] = make_attn_defs(cfg)
    if kind == "cross":
        out["cross"] = make_attn_defs(cfg, cross=True)
    if kind == "dec":
        out["ln_cross"] = make_rmsnorm(d)
        out["cross"] = make_attn_defs(cfg, cross=True)
    if kind == "ssm":
        out["ssm"] = make_ssm_defs(cfg)
        return out
    if kind == "rec":
        out["rec"] = make_rglru_defs(cfg)
    out["ln2"] = make_rmsnorm(d)
    out["ffn"] = make_moe_defs(cfg) if kind == "attn_moe" else \
        make_mlp(d, cfg.d_ff)
    return out


def block_forward(kind: str, p: dict, x: jax.Array, cfg: ModelConfig,
                  mem: jax.Array | None = None):
    aux = jnp.float32(0.0)
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if kind in ("attn", "attn_moe", "dec"):
        h = attn_forward(p["attn"], h, cfg)
    elif kind == "cross":
        h = attn_forward(p["cross"], h, cfg, mem=mem)
    elif kind == "ssm":
        return x + ssm_forward(p["ssm"], h, cfg), aux
    elif kind == "rec":
        h = rglru_forward(p["rec"], h, cfg)
    x = x + h
    if kind == "dec":
        h = rmsnorm(p["ln_cross"], x, cfg.norm_eps)
        x = x + attn_forward(p["cross"], h, cfg, mem=mem)
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if kind == "attn_moe":
        h, aux = moe(p["ffn"], h, cfg)
    else:
        h = mlp(p["ffn"], h)
    return x + h, aux


# Serve-path sublayers sit behind optimization barriers.  The TPU compiler
# fuses across sublayer boundaries differently for a 256-position prefill
# block than for a one-position step, and the fused code rounds a row
# differently (measured on a v5e: step and prefill logits differed at 256
# positions, matched at 64 and 128, and matched at 256 with barriers); the
# engine's byte identity needs prefill bitwise equal to the step scan.
_bar = jax.lax.optimization_barrier


def _norm(p: dict, x: jax.Array, eps: float) -> jax.Array:
    return _bar(rmsnorm(p, _bar(x), eps))


def _ffn(p: dict, x: jax.Array) -> jax.Array:
    return _bar(mlp(p, _bar(x)))


def block_decode(kind: str, p: dict, x1: jax.Array, cache: dict,
                 pos: jax.Array, cfg: ModelConfig,
                 mem: jax.Array | None = None,
                 layer: jax.Array | None = None):
    """One block, one position.  ``cache["kv"]`` is the stage's stacked
    ring, this block's at ``layer`` (:func:`stage_decode`)."""
    h = _norm(p["ln1"], x1, cfg.norm_eps)
    new_cache = cache
    if kind in ("attn", "attn_moe", "dec"):
        h, kv = attn_decode(p["attn"], h, cache["kv"], pos, cfg,
                            layer=layer)
        h = _bar(h)
        new_cache = dict(cache, kv=kv)
    elif kind == "cross":
        h, _ = attn_decode(p["cross"], h, None, pos, cfg, mem=mem)
        h = _bar(h)
    elif kind == "ssm":
        y, st = ssm_decode_step(p["ssm"], h, cache["ssm"], cfg)
        return x1 + y, dict(cache, ssm=st)
    elif kind == "rec":
        h, st = rglru_decode_step(p["rec"], h, cache["rec"], cfg)
        new_cache = dict(cache, rec=st)
    x1 = x1 + h
    if kind == "dec":
        h = _norm(p["ln_cross"], x1, cfg.norm_eps)
        y, _ = attn_decode(p["cross"], h, None, pos, cfg, mem=mem)
        x1 = x1 + _bar(y)
    h = _norm(p["ln2"], x1, cfg.norm_eps)
    if kind == "attn_moe":
        h, _ = moe(p["ffn"], h, cfg)
    else:
        h = _ffn(p["ffn"], h)
    return x1 + h, new_cache


def block_prefill(kind: str, p: dict, xs: jax.Array, cache: dict,
                  pos0: jax.Array, n_valid: jax.Array, cfg: ModelConfig):
    """Teacher-forced block over S positions — bit-identical to S
    sequential ``block_decode`` steps (attention kinds only; see
    :func:`can_prefill`)."""
    h = _norm(p["ln1"], xs, cfg.norm_eps)
    h, kv = attn_prefill(p["attn"], h, cache["kv"], pos0, n_valid, cfg)
    new_cache = dict(cache, kv=kv)
    xs = xs + _bar(h)
    h = _norm(p["ln2"], xs, cfg.norm_eps)
    if kind == "attn_moe":
        h, _ = moe(p["ffn"], h, cfg)
    else:
        h = _ffn(p["ffn"], h)
    return xs + h, new_cache


def can_prefill(cfg: ModelConfig) -> bool:
    """True when every block is a self-attention kind, so teacher-forced
    chunks can run block-parallel (ssm/rec/cross carry sequential state or
    memory the prefill path does not model)."""
    return not cfg.is_encdec and all(
        kind in ("attn", "attn_moe")
        for pat, _reps in cfg.stages for kind in pat)


def init_block_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                     dtype) -> dict:
    if kind in ("attn", "attn_moe", "dec"):
        # local/sliding-window archs only ever need a window-sized ring
        win = cfg.local_window or cfg.sliding_window
        eff = min(max_len, win) if win else max_len
        return {"kv": init_kv_cache(cfg, batch, eff, dtype)}
    if kind == "ssm":
        return {"ssm": init_ssm_cache(cfg, batch, dtype)}
    if kind == "rec":
        return {"rec": init_rglru_cache(cfg, batch, dtype)}
    return {}  # cross: attends precomputed memory, nothing cached


# ---------------------------------------------------------------------------
# stages (pattern x reps, scanned)
# ---------------------------------------------------------------------------

def _stack_defs(tree, reps: int):
    def f(d: ParamDef) -> ParamDef:
        ts = None if d.true_sizes is None else (None,) + d.true_sizes
        return ParamDef((reps,) + d.shape, ("layers",) + d.axes,
                        init=d.init, scale=d.scale, true_sizes=ts)
    return jax.tree.map(f, tree, is_leaf=lambda x: isinstance(x, ParamDef))


def make_stage_defs(pattern: tuple[str, ...], reps: int,
                    cfg: ModelConfig) -> dict:
    unit = {f"b{i}_{kind}": make_block_defs(kind, cfg)
            for i, kind in enumerate(pattern)}
    return _stack_defs(unit, reps)


def stage_forward(params: dict, x: jax.Array, pattern: tuple[str, ...],
                  cfg: ModelConfig, mem: jax.Array | None = None):
    def unit(x, layer_p):
        aux = jnp.float32(0.0)
        for i, kind in enumerate(pattern):
            x, a = block_forward(kind, layer_p[f"b{i}_{kind}"], x, cfg, mem)
            if cfg.act_pspec is not None:
                # e.g. sequence-parallel residuals (llama3-405b fit lever)
                from jax.sharding import PartitionSpec as P
                x = jax.lax.with_sharding_constraint(x, P(*cfg.act_pspec))
            aux = aux + a
        return x, aux

    body = jax.checkpoint(unit) if cfg.remat else unit
    if cfg.scan_layers:
        x, auxs = jax.lax.scan(lambda c, p: body(c, p), x, params)
        return x, jnp.sum(auxs)
    reps = jax.tree.leaves(params)[0].shape[0]
    aux = jnp.float32(0.0)
    for r in range(reps):
        layer_p = jax.tree.map(lambda a: a[r], params)
        x, a = body(x, layer_p)
        aux = aux + a
    return x, aux


def stage_decode(params: dict, cache: dict, x1: jax.Array, pos: jax.Array,
                 pattern: tuple[str, ...], cfg: ModelConfig,
                 mem: jax.Array | None = None):
    # §Perf: the KV rings ride the layer loop's carry whole, and each layer
    # writes its one slot per row in place (attn_decode).  A ring passed as
    # scan xs -> ys is sliced out and restacked every step, and a step scan
    # around this one then copies its carry.  Recurrent leaves (ssm/rec),
    # mutated whole each step, stay per-layer slabs.
    from repro.models.protocol import recurrent_state_tree
    leaves, tdef = jax.tree.flatten(cache)
    is_rec = jax.tree.leaves(recurrent_state_tree(cache))

    def split(ls):
        return ([a for a, r in zip(ls, is_rec) if not r],
                [a for a, r in zip(ls, is_rec) if r])

    def join(rings, slabs):
        rings, slabs = iter(rings), iter(slabs)
        return tdef.unflatten([next(slabs) if r else next(rings)
                               for r in is_rec])

    def body(carry, xs):
        x1, rings = carry
        layer, layer_p, slabs = xs
        layer_c = join(rings, slabs)
        new_c = {}
        for i, kind in enumerate(pattern):
            key = f"b{i}_{kind}"
            x1, new_c[key] = block_decode(kind, layer_p[key], x1,
                                          layer_c[key], pos, cfg, mem, layer)
        rings, slabs = split(jax.tree.leaves(new_c))
        return (x1, rings), slabs

    rings, slabs = split(leaves)
    reps = jax.tree.leaves(params)[0].shape[0]
    if cfg.scan_layers:
        (x1, rings), slabs = jax.lax.scan(
            body, (x1, rings), (jnp.arange(reps), params, slabs))
        return x1, join(rings, slabs)
    outs = []
    for r in range(reps):
        layer_p = jax.tree.map(lambda a: a[r], params)
        (x1, rings), out = body((x1, rings),
                                (r, layer_p, [a[r] for a in slabs]))
        outs.append(out)
    return x1, join(rings, [jnp.stack(z) for z in zip(*outs)])


def stage_prefill(params: dict, cache: dict, xs: jax.Array, pos0: jax.Array,
                  n_valid: jax.Array, pattern: tuple[str, ...],
                  cfg: ModelConfig):
    def unit(xs, layer_p, layer_c):
        new_c = {}
        for i, kind in enumerate(pattern):
            key = f"b{i}_{kind}"
            xs, c = block_prefill(kind, layer_p[key], xs, layer_c[key],
                                  pos0, n_valid, cfg)
            new_c[key] = c
        return xs, new_c

    if cfg.scan_layers:
        def body(carry, xs_):
            layer_p, layer_c = xs_
            return unit(carry, layer_p, layer_c)
        xs, new_cache = jax.lax.scan(body, xs, (params, cache))
        return xs, new_cache
    reps = jax.tree.leaves(params)[0].shape[0]
    outs = []
    for r in range(reps):
        layer_p = jax.tree.map(lambda a: a[r], params)
        layer_c = jax.tree.map(lambda a: a[r], cache)
        xs, c = unit(xs, layer_p, layer_c)
        outs.append(c)
    new_cache = jax.tree.map(lambda *x: jnp.stack(x), *outs)
    return xs, new_cache


def init_stage_cache(pattern: tuple[str, ...], reps: int, cfg: ModelConfig,
                     batch: int, max_len: int, dtype) -> dict:
    unit = {f"b{i}_{kind}": init_block_cache(kind, cfg, batch, max_len,
                                             dtype)
            for i, kind in enumerate(pattern)}
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (reps,) + a.shape).copy(), unit)


# ---------------------------------------------------------------------------
# whole models
# ---------------------------------------------------------------------------

def make_model_defs(cfg: ModelConfig) -> dict:
    out = {"tok": make_embedding(cfg), "final_norm": make_rmsnorm(cfg.d_model)}
    out["stages"] = {f"s{i}": make_stage_defs(pat, reps, cfg)
                     for i, (pat, reps) in enumerate(cfg.stages)}
    if cfg.is_encdec:
        enc_cfg = replace(cfg, sliding_window=0)
        out["encoder"] = {
            "stack": make_stage_defs(("attn",), cfg.encoder_layers, enc_cfg),
            "final_norm": make_rmsnorm(cfg.d_model),
        }
    return out


def encode_memory(params: dict, enc_inputs: jax.Array, cfg: ModelConfig):
    """Encoder pass over stub frontend embeddings (non-causal self-attn)."""
    # bidirectional: reuse attn_forward but disable the causal mask by
    # running with cross-style memory = itself?  Simpler: the encoder uses
    # causal=False via a one-off config flag in attn_forward -> we emulate
    # bidirectionality with mem=x (cross attention against itself).
    x = enc_inputs.astype(_dtype(cfg))
    def unit(x, layer_p):
        p = layer_p["b0_attn"]
        h = rmsnorm(p["ln1"], x, cfg.norm_eps)
        h = attn_forward(p["attn"], h, cfg, mem=h)   # non-causal self-attn
        x = x + h
        h = rmsnorm(p["ln2"], x, cfg.norm_eps)
        return x + mlp(p["ffn"], h), None
    body = jax.checkpoint(unit) if cfg.remat else unit
    x, _ = jax.lax.scan(lambda c, p: body(c, p), x,
                        params["encoder"]["stack"])
    return rmsnorm(params["encoder"]["final_norm"], x, cfg.norm_eps)


def forward(params: dict, tokens: jax.Array, cfg: ModelConfig,
            memory: jax.Array | None = None,
            enc_inputs: jax.Array | None = None):
    """tokens (B,S) -> hidden states (B,S,D) + aux loss."""
    if cfg.is_encdec and enc_inputs is not None:
        memory = encode_memory(params, enc_inputs, cfg)
    x = embed(params["tok"], tokens, _dtype(cfg))
    if cfg.act_pspec is not None:
        # pin the residual stream's batch sharding: the vocab/FSDP-sharded
        # embedding gather otherwise poisons propagation (activations would
        # replicate over data — observed 32 GB score tensors per chip).
        from jax.sharding import PartitionSpec as P
        x = jax.lax.with_sharding_constraint(x, P(*cfg.act_pspec))
    aux = jnp.float32(0.0)
    for i, (pat, reps) in enumerate(cfg.stages):
        x, a = stage_forward(params["stages"][f"s{i}"], x, pat, cfg, memory)
        aux = aux + a
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), aux


def loss_fn(params: dict, batch: dict, cfg: ModelConfig):
    """batch: tokens (B,S), labels (B,S), optional memory/enc_inputs."""
    x, aux = forward(params, batch["tokens"], cfg,
                     memory=batch.get("memory"),
                     enc_inputs=batch.get("enc_inputs"))
    if cfg.logits_chunk:
        ce = chunked_xent_loss(params["tok"], x, batch["labels"], cfg,
                               cfg.logits_chunk)
    else:
        lg = logits(params["tok"], x, cfg)
        ce = xent_loss(lg, batch["labels"], cfg.vocab_size)
    return ce + 0.01 * aux


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    dt = _dtype(cfg)
    return {f"s{i}": init_stage_cache(pat, reps, cfg, batch, max_len, dt)
            for i, (pat, reps) in enumerate(cfg.stages)}


def decode_step(params: dict, cache: dict, token: jax.Array, pos: jax.Array,
                cfg: ModelConfig, memory: jax.Array | None = None):
    """One serving step: token (B,1) int32 -> (logits (B,V), cache').

    ``pos`` is a scalar int32 absolute position, or a ``(B,)`` vector of
    per-row positions (batched serving: each row of the shared ring cache
    advances independently — see ``serve.engine.BatchEngine``).  Only
    attention consumes positions; ssm/rglru decode steps ignore them.
    """
    x1 = embed(params["tok"], token, _dtype(cfg))
    new_cache = {}
    for i, (pat, reps) in enumerate(cfg.stages):
        x1, c = stage_decode(params["stages"][f"s{i}"], cache[f"s{i}"], x1,
                             pos, pat, cfg, memory)
        new_cache[f"s{i}"] = c
    x1 = _norm(params["final_norm"], x1, cfg.norm_eps)
    lg = logits(params["tok"], x1, cfg)[:, 0]
    return lg, new_cache


def prefill_chunk(params: dict, cache: dict, tokens: jax.Array,
                  pos0: jax.Array, n_valid: jax.Array, cfg: ModelConfig):
    """Teacher-forced serving chunk: tokens (B,S) int32 inputs at per-row
    positions ``pos0 + [0, S)`` -> (logits (B,S,V), cache').

    Bit-identical to S sequential ``decode_step`` calls when the chunk
    stays inside the ring (``pos0 + S <= cache_len``) — the batched
    engine's fast path for compress rows, whose inputs are all known up
    front.  Gate on :func:`can_prefill`.  Rows with ``n_valid < S`` freeze
    after their live steps (queries discarded, no cache writes).
    """
    xs = embed(params["tok"], tokens, _dtype(cfg))
    new_cache = {}
    for i, (pat, reps) in enumerate(cfg.stages):
        xs, c = stage_prefill(params["stages"][f"s{i}"], cache[f"s{i}"], xs,
                              pos0, n_valid, pat, cfg)
        new_cache[f"s{i}"] = c
    xs = _norm(params["final_norm"], xs, cfg.norm_eps)
    return logits(params["tok"], xs, cfg), new_cache


# convenience -----------------------------------------------------------------

def init_model(cfg: ModelConfig, key: jax.Array):
    return init_params(make_model_defs(cfg), key, _dtype(cfg))


def abstract_model(cfg: ModelConfig):
    return abstract_params(make_model_defs(cfg), _dtype(cfg))
