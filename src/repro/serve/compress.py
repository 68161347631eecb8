"""LM-driven lossless compression — the paper's full pipeline, end to end.

Fig. 1/2 of the RAS paper: a learned probability generator feeds calibrated
distributions through the SPC (BF16 -> mass-corrected fixed point) into the
multi-lane rANS fabric.  Here the generator is any model-zoo LM and the text
stream is the payload:

  compress    — teacher-forced scan of the *decode* path produces one
                distribution per (lane, position); the SPC quantizes them;
                the multi-lane coder encodes in reverse (rANS is LIFO).
  decompress  — the same scan, except each step's symbol comes out of the
                rANS decoder (prediction-guided: the model's own top-k are
                the trial symbols, verified with O(1) CDF probes and a safe
                binary-search fallback) and is fed back into the model.
                Three backends (DESIGN.md §9):
                  * ``backend="kernel"`` — the FUSED serve path: ONE traced
                    program (a ``lax.scan`` carrying model cache + rANS
                    state) where each step runs the model, quantizes its
                    distribution through the SPC decode fast path, and pops
                    one symbol per lane with the per-step Pallas kernel.
                    No pure-JAX reference decode runs on this path;
                  * ``backend="two_pass"`` — the differential reference:
                    pass 1 runs the pure-JAX model scan collecting the
                    per-step tables and top-k candidate planes, pass 2
                    replays the whole bitstream in ONE Pallas launch with
                    in-kernel candidate speculation (chunked streams ride
                    the kernel's chunk grid axis);
                  * ``backend="coder"`` — pure-JAX end to end.
                All three are bit-exact on symbols AND integer-identical on
                the Fig. 4(b) probe counters (single-source search core).

Bit-exactness: both directions run the *identical* decode_step function on
the identical cache evolution, so the distributions (and therefore tables
and bitstream) match float-for-float on a given backend — the software
analogue of the paper's determinism contract.  Each batch row is one rANS
lane (the multi-lane fabric, T4).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitstream, coder, constants as C, spc
from repro.core.predictors import model_topk_candidates
from repro.models import ModelConfig, decode_step, init_state

BOS = 0


class CompressStats(NamedTuple):
    enc: coder.EncodedLanes
    bits_per_symbol: jax.Array
    model_xent_bits: jax.Array     # model cross entropy (bits/symbol) = bound
    avg_probes: jax.Array | None = None


def step_tables(logits: jax.Array, vocab: int, prob_bits: int):
    """Model logits (rows, Vpad) -> TableSet (rows, V) via the SPC.

    THE single-source per-step quantizer of the serve layer: one f32
    softmax, BF16 storage, mass correction, CDF construction.  Every path
    that prices or decodes a stream — ``collect_tables``, the sequential
    and fused decompress scans here, and the batched engine's chunk
    program (``serve.engine._chunk_body``) — calls this function, so the
    tables (and therefore the bytes) cannot drift between the
    single-request and batched services.  Rows are whatever the caller
    batches: lanes, or the engine's flattened slots x lanes.
    """
    lg = logits[:, :vocab].astype(jnp.float32)
    probs = jax.nn.softmax(lg, axis=-1)
    return spc.tables_from_probs(spc.store_bf16(probs), prob_bits)


_step_tables = step_tables      # historical internal alias


def _step_freq_cdf(logits: jax.Array, vocab: int, prob_bits: int):
    """Model logits (lanes, Vpad) -> ``(freq, cdf)`` — decode-side SPC.

    The identical quantization to :func:`_step_tables` (same f32 softmax,
    same BF16 storage, same mass correction, same CDF construction) minus
    the encoder-only Barrett planes — the fused decode's just-in-time table
    path (``spc.freq_cdf_from_probs`` is pinned bit-equal in tests).
    """
    lg = logits[:, :vocab].astype(jnp.float32)
    probs = jax.nn.softmax(lg, axis=-1)
    return spc.freq_cdf_from_probs(spc.store_bf16(probs), prob_bits)


@functools.partial(jax.jit, static_argnames=("cfg", "prob_bits"))
def collect_tables(params, cfg: ModelConfig, tokens: jax.Array,
                   prob_bits: int = C.PROB_BITS):
    """Teacher-forced pass: per-(position, lane) coding tables + xent.

    Runs on ``serve.engine.teacher_forced_scan`` — the same shared scan that
    backs ``prefill``/``generate`` — so the cache evolution pricing the
    bitstream is structurally the serving cache evolution (not a drifting
    private copy of the loop).
    """
    from repro.serve.engine import teacher_forced_scan
    lanes, t_len = tokens.shape
    inputs = jnp.concatenate(
        [jnp.full((lanes, 1), BOS, tokens.dtype), tokens[:, :-1]], axis=1)

    def per_step(lg, t):
        tbl = _step_tables(lg, cfg.vocab_size, prob_bits)
        lp = jax.nn.log_softmax(lg[:, :cfg.vocab_size].astype(jnp.float32))
        gold = jnp.take_along_axis(lp, tokens[:, t][:, None], -1)[:, 0]
        return tbl, -jnp.mean(gold)

    _, (tables, nll) = teacher_forced_scan(params, cfg, inputs, t_len,
                                           step_fn=per_step)
    xent_bits = jnp.mean(nll) / jnp.log(2.0)
    return tables, xent_bits   # TableSet fields: (T, lanes, K)


def lm_compress(params, cfg: ModelConfig, tokens: jax.Array,
                prob_bits: int = C.PROB_BITS,
                backend: str = "coder") -> CompressStats:
    """tokens (lanes, T) -> multi-lane rANS bitstream + stats.

    ``backend="kernel"`` feeds the teacher-forced ``(T, lanes, K)`` tables
    of :func:`collect_tables` straight into the fused-compaction Pallas
    encode kernel (the adaptive per-lane layout encodes in-kernel and the
    packed stream comes straight off the kernel — no host-side
    ``compact_records`` pass); ``backend="coder"`` runs the pure-JAX lane
    scan.  Both consume ``core.update``, so the
    produced bitstream — including the per-lane ``overflow`` flags on the
    returned ``EncodedLanes`` — is byte-identical either way and
    round-trips through :func:`lm_decompress` bit-exactly.
    """
    lanes, t_len = tokens.shape
    tables, xent_bits = collect_tables(params, cfg, tokens, prob_bits)
    if backend == "kernel":
        from repro.kernels.ops import rans_encode
        enc = rans_encode(tokens.astype(jnp.int32), tables,
                          prob_bits=prob_bits)
    elif backend == "coder":
        enc = coder.encode(tokens.astype(jnp.int32), tables)
    else:
        raise ValueError(f"unknown encode backend {backend!r}")
    bits = jnp.mean(enc.length.astype(jnp.float32)) * 8.0 / t_len
    return CompressStats(enc=enc, bits_per_symbol=bits,
                         model_xent_bits=xent_bits)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "n_symbols", "prob_bits", "topk",
                                    "collect_planes"))
def _lm_decompress_scan(params, cfg: ModelConfig, enc: coder.EncodedLanes,
                        n_symbols: int, prob_bits: int, topk: int,
                        collect_planes: bool = False):
    """Sequential model-driven decode scan (the pure-JAX reference pass).

    With ``collect_planes`` the scan also stacks each step's quantized
    TableSet and model-top-k candidate row — the ``(T, lanes, K)`` tables
    and ``(T, lanes, topk)`` candidate planes the Pallas decode kernel
    consumes (the serve two-pass kernel decode, see :func:`lm_decompress`).
    """
    lanes = enc.buf.shape[0]
    cache = init_state(cfg, lanes, n_symbols)
    dec0 = coder.decoder_init(enc)
    tok0 = jnp.full((lanes, 1), BOS, jnp.int32)

    def body(carry, t):
        cache, dec, tok = carry
        lg, cache = decode_step(params, cache, tok, t, cfg)
        tbl = _step_tables(lg, cfg.vocab_size, prob_bits)
        cands = model_topk_candidates(lg[:, :cfg.vocab_size], topk)
        dec, sym, probes = coder.decode_get(dec, enc.buf, tbl, prob_bits,
                                            candidates=cands)
        ys = (sym, probes) + ((tbl, cands) if collect_planes else ())
        return (cache, dec, sym[:, None].astype(jnp.int32)), ys

    (_, dec_f, _), ys = jax.lax.scan(
        body, (cache, dec0, tok0), jnp.arange(n_symbols))
    # (symbols (T, lanes), probes (T, lanes)[, tables, cands], underflow)
    return ys + (dec_f.underflow,)


def _fused_scan(params, cfg: ModelConfig, enc: coder.EncodedLanes,
                cache, tok, t0, n: int, prob_bits: int, topk: int):
    """The fused serve decode core (DESIGN.md §9): ONE traced program.

    A ``lax.scan`` over positions ``[t0, t0+n)`` carrying BOTH the model
    cache and the rANS coder state ``(s, ptr)``.  Each step runs the model
    ``decode_step``, quantizes its distribution through the SPC decode fast
    path (:func:`_step_freq_cdf` — no Barrett planes, no ``(T, lanes, K)``
    plane stacking), ranks its top-k trial symbols, and pops one symbol per
    lane with the per-step Pallas kernel
    (``kernels.rans_decode.rans_decode_step``; off the TPU, interpret mode
    inlines the kernel into this very program).  The decoded symbol feeds
    straight back into the model — no pure-JAX reference decode runs
    anywhere on this path, and no table plane ever round-trips through HBM.
    """
    from repro.kernels.rans_decode import rans_decode_step
    dec0 = coder.decoder_init(enc)
    buf_t = enc.buf.T      # (cap, lanes): transposed ONCE, outside the scan

    def body(carry, t):
        cache, s, ptr, under, tok = carry
        lg, cache = decode_step(params, cache, tok, t, cfg)
        freq, cdf = _step_freq_cdf(lg, cfg.vocab_size, prob_bits)
        cands = model_topk_candidates(lg[:, :cfg.vocab_size], topk)
        s, ptr, sym, probes, u = rans_decode_step(
            buf_t, s, ptr, freq, cdf, prob_bits=prob_bits,
            candidates=cands)
        carry = (cache, s, ptr, under | (u > 0),
                 sym[:, None].astype(jnp.int32))
        return carry, (sym, probes)

    (cache, _, _, under, tok), (sym, probes) = jax.lax.scan(
        body, (cache, dec0.s, dec0.ptr, dec0.underflow, tok),
        t0 + jnp.arange(n))
    # sym (lanes, n), probes (n, lanes), under (lanes,)
    return cache, tok, sym.T, probes, under


@functools.partial(jax.jit,
                   static_argnames=("cfg", "n_symbols", "prob_bits", "topk"))
def _lm_decompress_fused(params, cfg: ModelConfig, enc: coder.EncodedLanes,
                         n_symbols: int, prob_bits: int, topk: int):
    """Monolithic fused decode: whole stream in one traced program."""
    lanes = enc.buf.shape[0]
    cache = init_state(cfg, lanes, n_symbols)
    tok = jnp.full((lanes, 1), BOS, jnp.int32)
    _, _, sym, probes, under = _fused_scan(params, cfg, enc, cache, tok,
                                           jnp.int32(0), n_symbols,
                                           prob_bits, topk)
    return sym, probes, under


def _lane_mesh_check(mesh, lanes: int) -> bool:
    """Validate/route a mesh for the fused path (lanes are its parallel
    axis — decode is sequential over positions).  Delegates to the shared
    routing contract ``parallel.chunked.lane_mesh_usable`` (also consumed
    by the batched engine for its slots x lanes row axis): True = place on
    mesh; False = degrade to the single-device program (divisibility
    fallback); wrong-axis meshes raise."""
    from repro.parallel.chunked import lane_mesh_usable
    return lane_mesh_usable(mesh, lanes, what="fused decode "
                            "(backend='kernel')")


def _fused_on_lane_mesh(params, enc, mesh, local_fn):
    """Shard the fused program over a ``("lanes",)`` mesh.

    Lanes are independent end to end (the model treats lanes as batch, the
    coder state and byte streams are per-lane), so each device runs the
    whole fused scan over its local lane slab with zero collectives;
    ``local_fn(params, enc_local) -> (sym (lanes_loc, T), probes)`` is the
    single-device program.  Bit-exact vs the unsharded path.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    lane_axis = 0 if enc.buf.ndim == 2 else 1   # EncodedLanes|ChunkedLanes
    espec = jax.tree.map(lambda _: P(*([None] * lane_axis + ["lanes"])), enc)
    pspec = jax.tree.map(lambda _: P(), params)
    probes_spec = P("lanes") if enc.buf.ndim == 3 else P(None, "lanes")
    # third output: the per-lane stream-exhaustion flag (lanes,)
    return shard_map(local_fn, mesh=mesh, in_specs=(pspec, espec),
                     out_specs=(P("lanes"), probes_spec, P("lanes")),
                     check_vma=False)(params, enc)


def lm_decompress(params, cfg: ModelConfig, enc: coder.EncodedLanes,
                  n_symbols: int, prob_bits: int = C.PROB_BITS,
                  topk: int = 4, backend: str = "coder",
                  mesh=None, lane_probes: bool = False):
    """Bitstream -> tokens, decoding with model-top-k speculation (T3).

    ``backend="coder"`` pops every symbol inside the sequential model scan
    (the pure-JAX path).  ``backend="kernel"`` is the FUSED serve decode:
    one traced program (``lax.scan`` carrying model cache + rANS state)
    whose every step runs the model, the SPC decode fast path, and the
    per-step Pallas decode kernel — the pure-JAX per-symbol reference scan
    never executes on this path.  ``backend="two_pass"`` is the retained
    differential reference: pass 1 runs the pure-JAX scan collecting the
    per-step ``(T, lanes, K)`` tables and ``(T, lanes, topk)`` candidate
    planes; pass 2 re-decodes the untouched bitstream in ONE Pallas launch
    (its reported counters come from the kernel pass ONLY).  All three
    consume ``core.search``, so symbols and per-lane probe counters are
    integer-identical across backends.

    ``mesh``: optional ``("lanes",)`` mesh (``parallel.chunked.lane_mesh``)
    placing the fused program's independent lane axis across devices
    (``backend="kernel"`` only).

    Returns ``(tokens (lanes, T), avg_probes[, per-lane probes])``.
    """
    if mesh is not None and backend != "kernel":
        raise ValueError(
            "mesh= requires backend='kernel': only the fused program has "
            "an independent (lane) axis to place — the coder and two-pass "
            "reference paths are single-device")
    if backend == "coder":
        symbols, probes, under = _lm_decompress_scan(
            params, cfg, enc, n_symbols, prob_bits, topk)
        coder._check_exhausted(under, "lm_decompress")
        out = (symbols.T, jnp.mean(probes.astype(jnp.float32)))
        if lane_probes:
            out = out + (jnp.sum(probes, axis=0),)
        return out
    if backend == "kernel":
        if _lane_mesh_check(mesh, enc.buf.shape[0]):
            def local(params_l, enc_l):
                return _lm_decompress_fused(params_l, cfg, enc_l, n_symbols,
                                            prob_bits, topk)
            sym, probes, under = _fused_on_lane_mesh(params, enc, mesh,
                                                     local)
        else:
            sym, probes, under = _lm_decompress_fused(
                params, cfg, enc, n_symbols, prob_bits, topk)
        coder._check_exhausted(under, "lm_decompress")
        out = (sym, jnp.mean(probes.astype(jnp.float32)))
        if lane_probes:
            out = out + (jnp.sum(probes, axis=0),)
        return out
    if backend != "two_pass":
        raise ValueError(f"unknown decode backend {backend!r}")
    from repro.kernels.ops import rans_decode
    # pass-1 flags are discarded: pass 2 (the kernel replay) re-detects
    # exhaustion on the authoritative stream walk and raises host-side
    _, _, tables, cands, _ = _lm_decompress_scan(params, cfg, enc,
                                                 n_symbols, prob_bits, topk,
                                                 collect_planes=True)
    sym, avg, per_lane = rans_decode(enc, n_symbols, tables,
                                     prob_bits=prob_bits, candidates=cands,
                                     lane_probes=True)
    if lane_probes:
        return sym, avg, per_lane
    return sym, avg


# ---------------------------------------------------------------------------
# chunked streaming path: payloads longer than one coder buffer.  Encode
# flushes every ``chunk_size`` symbols (chunks stay independently decodable
# and shard across devices — repro.parallel.chunked); decompression walks the
# chunks sequentially with the model cache carried across chunk boundaries,
# so peak coder-buffer memory is O(chunk_size), not O(T).
# ---------------------------------------------------------------------------

class ChunkedCompressStats(NamedTuple):
    chunks: coder.ChunkedLanes
    chunk_size: int
    n_symbols: int
    bits_per_symbol: jax.Array
    model_xent_bits: jax.Array


def lm_compress_chunked(params, cfg: ModelConfig, tokens: jax.Array,
                        chunk_size: int, prob_bits: int = C.PROB_BITS,
                        mesh=None, backend: str = "coder",
                        cap: int | None = None) -> ChunkedCompressStats:
    """tokens (lanes, T) -> chunked multi-lane bitstream + stats.

    Tables still come from one teacher-forced pass (the model cache spans
    chunk boundaries — chunking changes the *coder* framing, never the
    distributions), then the chunk x lane grid is encoded on ``mesh`` via
    ``repro.parallel.chunked`` (vmap fallback on one device).
    ``backend="kernel"`` routes the encode through the fused Pallas
    kernel's chunk grid axis — one ``pallas_call`` per device emitting
    packed streams.  ``cap`` optionally bounds the per-(chunk, lane) byte
    budget; under-provisioned cells come back truncated-but-flagged on
    ``chunks.overflow`` (identically on either backend) and refuse to pack.
    """
    from repro.parallel.chunked import encode_chunked
    lanes, t_len = tokens.shape
    tables, xent_bits = collect_tables(params, cfg, tokens, prob_bits)
    chunks = encode_chunked(tokens.astype(jnp.int32), tables, chunk_size,
                            mesh=mesh, backend=backend, cap=cap)
    bits = (jnp.sum(chunks.length.astype(jnp.float32)) * 8.0
            / (lanes * t_len))
    return ChunkedCompressStats(chunks=chunks, chunk_size=chunk_size,
                                n_symbols=t_len, bits_per_symbol=bits,
                                model_xent_bits=xent_bits)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "n", "prob_bits", "topk",
                                    "collect_planes"))
def _lm_decompress_chunk(params, cfg: ModelConfig, enc: coder.EncodedLanes,
                         cache, tok, t0, n: int, prob_bits: int, topk: int,
                         collect_planes: bool = False):
    """Decode one chunk (positions [t0, t0+n)) with carried model cache.

    ``collect_planes`` also stacks the chunk's ``(n, lanes, K)`` TableSet
    and ``(n, lanes, topk)`` candidate rows for the kernel's second pass.
    """
    dec0 = coder.decoder_init(enc)

    def body(carry, t):
        cache, dec, tok = carry
        lg, cache = decode_step(params, cache, tok, t, cfg)
        tbl = _step_tables(lg, cfg.vocab_size, prob_bits)
        cands = model_topk_candidates(lg[:, :cfg.vocab_size], topk)
        dec, sym, probes = coder.decode_get(dec, enc.buf, tbl, prob_bits,
                                            candidates=cands)
        ys = (sym, probes) + ((tbl, cands) if collect_planes else ())
        return (cache, dec, sym[:, None].astype(jnp.int32)), ys

    (cache, dec_f, tok), ys = jax.lax.scan(
        body, (cache, dec0, tok), t0 + jnp.arange(n))
    symbols, probes = ys[0], ys[1]
    out = (cache, tok, symbols.T, jnp.sum(probes, axis=0), dec_f.underflow)
    if collect_planes:
        out = out + (ys[2], ys[3])
    return out


@functools.partial(jax.jit,
                   static_argnames=("cfg", "n", "prob_bits", "topk"))
def _lm_decompress_fused_chunk(params, cfg: ModelConfig,
                               enc: coder.EncodedLanes, cache, tok, t0,
                               n: int, prob_bits: int, topk: int):
    """Fused decode of one chunk (positions [t0, t0+n)), carried cache."""
    return _fused_scan(params, cfg, enc, cache, tok, t0, n, prob_bits,
                       topk)


def _fused_chunked_local(params, cfg: ModelConfig,
                         chunks: "coder.ChunkedLanes | bitstream.ContainerSlab",
                         n_symbols: int, chunk_size: int, prob_bits: int,
                         topk: int):
    """Fused chunked decode over (this device's slab of) the lane axis.

    The rANS state re-initializes per chunk (standalone streams); the model
    cache and fed-back token carry across chunk boundaries, exactly like the
    coder path — one fused program per chunk, only that chunk's byte buffer
    live at a time.  Returns ``(symbols (lanes, T), lane probe sums)``.

    ``chunks`` may be a :class:`~repro.core.bitstream.ContainerSlab`: each
    chunk's window then comes straight off the packed payload with one
    device-side gather per chunk (``bitstream.chunk_encoded_from_slab``) —
    the host right-align copy never runs and only one chunk's bytes are
    ever materialized at a time (the streaming-decode shape, kept).
    """
    slab_in = isinstance(chunks, bitstream.ContainerSlab)
    lanes = chunks.offset.shape[1] if slab_in else chunks.buf.shape[1]
    cache = init_state(cfg, lanes, n_symbols)
    tok = jnp.full((lanes, 1), BOS, jnp.int32)
    outs, lane_sum = [], jnp.zeros((lanes,), jnp.int32)
    under = jnp.zeros((lanes,), bool)
    for c, n in enumerate(coder.chunk_lengths(n_symbols, chunk_size)):
        enc = (bitstream.chunk_encoded_from_slab(chunks, c) if slab_in
               else coder.chunk_encoded(chunks, c))
        cache, tok, sym, probes, und = _lm_decompress_fused_chunk(
            params, cfg, enc, cache, tok, jnp.int32(c * chunk_size), n=n,
            prob_bits=prob_bits, topk=topk)
        outs.append(sym)
        lane_sum = lane_sum + jnp.sum(probes, axis=0)
        under = under | und
    return jnp.concatenate(outs, axis=1), lane_sum, under


def lm_decompress_chunked(params, cfg: ModelConfig,
                          chunks: "coder.ChunkedLanes | bitstream.ContainerSlab",
                          n_symbols: int,
                          chunk_size: int, prob_bits: int = C.PROB_BITS,
                          topk: int = 4, backend: str = "coder",
                          mesh=None,
                          lane_probes: bool = False):
    """Chunked bitstream -> tokens (bit-exact inverse of lm_compress_chunked).

    The rANS decoder re-initializes per chunk (each chunk is a standalone
    stream); the model cache and fed-back token carry across chunks, so the
    distribution sequence is float-identical to the monolithic path.  With
    ``backend="coder"`` only one chunk's byte buffer is live at a time —
    the streaming-decode shape.

    ``backend="kernel"`` is the FUSED chunked serve decode: one fused
    program per chunk (model step + SPC decode fast path + per-step Pallas
    kernel, the ``lax.scan`` carrying model cache AND rANS state), cache
    and token carried across chunk boundaries — the pure-JAX per-symbol
    reference scan never executes, and it keeps the streaming shape (one
    chunk's byte buffer live at a time).

    ``backend="two_pass"`` is the retained differential reference: pass 1
    walks the chunks sequentially through the pure-JAX scan (the model must
    see its own decoded tokens) while collecting every chunk's tables and
    model-top-k candidate planes; pass 2 re-decodes the *entire* chunked
    stream in ONE Pallas launch — the kernel's chunk grid axis replays
    every (chunk, lane) cell with in-kernel state reset and candidate
    speculation.  Returned symbols and probe counters come from the kernel
    pass ONLY (pass 1's counters are never accumulated), integer-identical
    to the other backends (all consume ``core.search``).

    ``mesh``: for ``backend="kernel"`` a ``("lanes",)`` mesh
    (``parallel.chunked.lane_mesh``) shards the fused program's independent
    lane axis — decode is sequential over chunks, so the chunk axis cannot
    shard the fused path.  For ``backend="two_pass"`` a ``("chunks",)``
    mesh places pass 2 via ``repro.parallel.chunked.decode_chunked`` (the
    collected candidate planes shard chunk-major with the stream slab);
    per-lane counters are not aggregated across chunk shards, so
    ``lane_probes`` there requires ``mesh=None``.

    ``chunks`` may also be a :class:`~repro.core.bitstream.ContainerSlab`
    (``bitstream.parse_chunked`` of a serialized container) on every
    backend: the two_pass kernel replay then decodes ZERO-COPY straight
    from the packed payload slab (the in-kernel DMA window path), while
    the sequential fused/coder scans pull each chunk's window with one
    device-side gather per chunk (``bitstream.chunk_encoded_from_slab``)
    — the host right-align copy never runs on any serve path.  Symbols
    and probe counters are bit-identical to passing the equivalent
    ``ChunkedLanes``.

    Returns ``(tokens (lanes, T), avg_probes[, per-lane probes])``.
    """
    if backend not in ("coder", "kernel", "two_pass"):
        raise ValueError(f"unknown decode backend {backend!r}")
    if mesh is not None and backend == "coder":
        raise ValueError(
            "mesh= requires backend='kernel' or 'two_pass': the coder "
            "backend decodes inside the sequential model scan, so there is "
            "neither a fused program nor a pass 2 to place on a mesh")
    slab_in = isinstance(chunks, bitstream.ContainerSlab)
    n_have = chunks.offset.shape[0] if slab_in else chunks.buf.shape[0]
    lanes = chunks.offset.shape[1] if slab_in else chunks.buf.shape[1]
    n_total = coder.num_chunks(n_symbols, chunk_size)
    if n_have != n_total:
        raise ValueError(
            f"stream has {n_have} chunks but n_symbols="
            f"{n_symbols} at chunk_size={chunk_size} implies {n_total}")
    if backend == "kernel":
        if _lane_mesh_check(mesh, lanes):
            if slab_in:
                # the lane mesh shards dense (…, lanes, cap) arrays; one
                # device-side gather rebuilds them (host copy still never
                # runs) — the unsharded fused path stays per-chunk windows
                chunks = bitstream.slab_to_chunked(chunks)

            def local(params_l, chunks_l):
                return _fused_chunked_local(params_l, cfg, chunks_l,
                                            n_symbols, chunk_size,
                                            prob_bits, topk)
            sym, lane_sum, under = _fused_on_lane_mesh(params, chunks, mesh,
                                                       local)
        else:
            sym, lane_sum, under = _fused_chunked_local(
                params, cfg, chunks, n_symbols, chunk_size, prob_bits,
                topk)
        coder._check_exhausted(under, "lm_decompress_chunked")
        out = (sym, jnp.sum(lane_sum.astype(jnp.float32))
               / (lanes * n_symbols))
        if lane_probes:
            out = out + (lane_sum,)
        return out
    collect = backend == "two_pass"
    cache = init_state(cfg, lanes, n_symbols)
    tok = jnp.full((lanes, 1), BOS, jnp.int32)
    outs, lane_sum, planes = [], jnp.zeros((lanes,), jnp.int32), []
    under = jnp.zeros((lanes,), bool)
    for c, n in enumerate(coder.chunk_lengths(n_symbols, chunk_size)):
        enc = (bitstream.chunk_encoded_from_slab(chunks, c) if slab_in
               else coder.chunk_encoded(chunks, c))
        res = _lm_decompress_chunk(
            params, cfg, enc, cache, tok, jnp.int32(c * chunk_size), n=n,
            prob_bits=prob_bits, topk=topk, collect_planes=collect)
        cache, tok, sym, probes, und = res[:5]
        if collect:
            # two-pass probe purity: pass-1 counters are NEVER accumulated —
            # the reported Fig. 4(b) accounting comes from the kernel pass
            # only (and pass-1 symbols, exhaustion flags are likewise
            # discarded — pass 2 re-detects and raises)
            planes.append(res[5:])
        else:
            outs.append(sym)
            lane_sum = lane_sum + probes
            under = under | und
    if collect:
        tables = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                              *[p[0] for p in planes])
        cands = jnp.concatenate([p[1] for p in planes], axis=0)
        if mesh is not None:
            if lane_probes:
                raise ValueError(
                    "lane_probes requires mesh=None: the sharded decode "
                    "does not aggregate per-lane counters across devices")
            from repro.parallel.chunked import decode_chunked as pdecode
            return pdecode(chunks, n_symbols, tables, chunk_size, mesh=mesh,
                           prob_bits=prob_bits, backend="kernel",
                           candidates=cands)
        from repro.kernels.ops import rans_decode_chunked
        if slab_in:
            # pass 2 zero-copy: the kernel DMAs each (chunk, lane) window
            # out of the packed slab — no dense stream rebuild at all
            sym, avg, per_lane = rans_decode_chunked(
                n_symbols=n_symbols, tbl=tables, chunk_size=chunk_size,
                prob_bits=prob_bits, candidates=cands,
                lane_probes=True, from_container=chunks)
        else:
            sym, avg, per_lane = rans_decode_chunked(
                chunks, n_symbols, tables, chunk_size, prob_bits=prob_bits,
                candidates=cands, lane_probes=True)
        if lane_probes:
            return sym, avg, per_lane
        return sym, avg
    coder._check_exhausted(under, "lm_decompress_chunked")
    out = (jnp.concatenate(outs, axis=1),
           jnp.sum(lane_sum.astype(jnp.float32)) / (lanes * n_symbols))
    if lane_probes:
        out = out + (lane_sum,)
    return out


# ---------------------------------------------------------------------------
# static-table path (classic rANS with an empirical histogram) — the
# "software rANS" rung of Fig. 1's algorithmic ladder, used by benchmarks.
# ---------------------------------------------------------------------------

def histogram_compress(symbols: np.ndarray, k: int,
                       prob_bits: int = C.PROB_BITS):
    counts = np.bincount(symbols.ravel(), minlength=k)
    tbl = jax.tree.map(jnp.asarray, spc.tables_from_counts_np(
        counts, prob_bits))
    enc = coder.encode(jnp.asarray(symbols, jnp.int32), tbl)
    return enc, tbl


def histogram_decompress(enc: coder.EncodedLanes, n_symbols: int, tbl,
                         prob_bits: int = C.PROB_BITS, predictor=None,
                         backend: str = "kernel"):
    """Static-table decode — through the Pallas kernel by default.

    The serving counterpart of :func:`histogram_compress`: both backends
    consume ``core.search``, so symbols and probe telemetry are identical
    whether the decode ran in-kernel (``backend="kernel"``) or in the
    pure-JAX lane coder (``backend="coder"``).
    ``predictor`` enables prediction-guided search (e.g. the paper's
    ``NeighborAverage`` for image rows).  Returns (symbols, avg_probes).
    """
    if backend == "kernel":
        from repro.kernels.ops import rans_decode
        return rans_decode(enc, n_symbols, tbl, prob_bits=prob_bits,
                           predictor=predictor)
    if backend == "coder":
        return coder.decode(enc, n_symbols, tbl, prob_bits,
                            predictor=predictor)
    raise ValueError(f"unknown decode backend {backend!r}")
