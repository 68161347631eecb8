"""Chunk x lane placement of the chunked rANS codec on a device mesh.

The chunked streams of ``core.coder.encode_chunked`` are independent by
construction (every chunk has its own flush), so the chunk axis is an
embarrassingly-parallel device axis: this module places the full-size
chunks of a ``(n_chunks, lanes, cap)`` stream on a 1-D ``("chunks",)``
mesh with ``shard_map`` — each device runs the vmap'd single-chunk
coder over its local chunk slab, no collectives at all (the multi-device
generalization of the paper's multi-lane fabric, Sec. III).

Fallback contract: with one device, a ``None`` mesh, or a chunk count not
divisible by the mesh size, both entry points degrade to the plain vmap
path in ``core.coder`` — bit-exactly the same streams/symbols either way
(the tier-1 differential test pins shard_map == vmap symbol-for-symbol).
The ragged tail chunk, when present, is always coded on the default device.

:func:`lane_mesh` is the companion 1-D ``("lanes",)`` mesh for the FUSED
serve decode (``serve.compress``, ``backend="kernel"``): that program is
sequential over positions/chunks, so its parallel axis is the lane, not
the chunk (see the function docstring and DESIGN.md §9).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core import bitstream, coder, constants as C
from repro.core.bitstream import ContainerSlab
from repro.core.coder import ChunkedLanes, EncodedLanes
from repro.core.spc import TableSet


def chunk_mesh(devices=None) -> Mesh:
    """1-D ``("chunks",)`` mesh over ``devices`` (default: all devices)."""
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.asarray(devices), ("chunks",))


def lane_mesh(devices=None) -> Mesh:
    """1-D ``("lanes",)`` mesh over ``devices`` (default: all devices).

    The placement axis of the FUSED serve decode (``serve.compress``,
    ``backend="kernel"``): that program is sequential over positions and
    chunks — the model is autoregressive over its own decoded tokens — so
    the chunk axis cannot shard it.  Lanes can: each lane owns a private
    byte stream, a private rANS state and an independent model batch row,
    so the fused scan runs per-device on a lane slab with no collectives
    (the multi-device form of the paper's multi-lane fabric for the decode
    direction).  Same fallback contract as :func:`chunk_mesh`: indivisible
    lane counts degrade to the single-device program, bit-exactly.
    """
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.asarray(devices), ("lanes",))


def _usable(mesh: Mesh | None, n_full: int) -> bool:
    return (mesh is not None and "chunks" in mesh.axis_names
            and n_full > 0 and n_full % mesh.shape["chunks"] == 0)


def lane_mesh_usable(mesh: Mesh | None, rows: int,
                     what: str = "fused serve decode") -> bool:
    """Validate/route a ``("lanes",)`` mesh for an independent row axis.

    The ONE routing contract shared by every lane-parallel serve program
    (the fused decode of ``serve.compress`` and the batched engine's
    slots x lanes row axis — both are sequential over positions, so their
    only parallel axis is the row).  True = place ``rows`` rows on the
    mesh; False = degrade to the single-device program (row counts that
    don't divide the mesh fall back bit-exactly — same contract as the
    chunk mesh's :func:`_usable`).  A mesh without a ``"lanes"`` axis
    raises: chunk meshes place the two-pass kernel replay, not the
    sequential row-parallel programs.
    """
    if mesh is None:
        return False
    if "lanes" not in mesh.axis_names:
        raise ValueError(
            f"the {what} parallelizes over the lane axis: pass a "
            '("lanes",) mesh (parallel.chunked.lane_mesh).  Chunk meshes '
            "place the two-pass kernel replay — use backend='two_pass' "
            "with a ('chunks',) mesh instead")
    return rows > 0 and rows % mesh.shape["lanes"] == 0


def state_row_specs(state, row_axis: int = 1):
    """PartitionSpec tree sharding a model-state pytree's row axis.

    The model-state protocol (``repro.models.state_spec``) pins every
    state leaf — KV rings and recurrent ``(h, conv)`` alike — to carry
    the batch row on axis ``row_axis`` (axis 1 behind the stage ``reps``
    axis), so ONE spec tree places *arbitrary* state on a ``("lanes",)``
    mesh: ``P(None, "lanes")`` shards rows and replicates every
    trailing per-leaf dimension (ring slots, conv taps, SSD planes —
    a PartitionSpec shorter than the leaf rank replicates the rest).
    Consumed by the batched engine's shard_map carry; the companion of
    :func:`lane_mesh_usable` on the same routing contract.
    """
    spec = P(*([None] * row_axis + ["lanes"]))
    return jax.tree.map(lambda _: spec, state)


def _chunked_table_specs(tbl: TableSet, sharded: bool):
    spec = P("chunks") if sharded else P()
    return jax.tree.map(lambda _: spec, tbl)


def encode_chunked(symbols: jax.Array, tbl: TableSet, chunk_size: int,
                   mesh: Mesh | None = None,
                   cap: int | None = None,
                   backend: str = "coder") -> ChunkedLanes:
    """Device-parallel chunked encode over either encode backend.

    Full chunks are sharded over the mesh's ``chunks`` axis; per-position
    tables (leading T dim) are split chunk-major and ride on the same axis.
    ``backend="coder"`` runs the pure-JAX lane encoder (vmap over the local
    chunk slab); ``backend="kernel"`` runs the fused-compaction Pallas
    encode kernel — one ``pallas_call`` per device covering its whole local
    slab (the kernel's chunk grid axis) and emitting packed streams
    directly (no host-side ``compact_records`` pass).  Both consume
    ``core.update``, so the produced streams — and the per-cell
    overflow flags — are byte-identical across backends and mesh shapes.
    Falls back to the single-device path whenever the mesh cannot evenly
    take the chunk axis.
    """
    if backend == "kernel":
        from repro.kernels import ops as kops
    elif backend != "coder":
        raise ValueError(f"unknown encode backend {backend!r}")
    lanes, t_len = symbols.shape
    coder.num_chunks(t_len, chunk_size)     # validates chunk_size > 0
    n_full, tail_len = divmod(t_len, chunk_size)
    cap = coder.default_cap(min(chunk_size, t_len)) if cap is None else cap
    if not _usable(mesh, n_full):
        if backend == "kernel":
            return kops.rans_encode_chunked(symbols, tbl, chunk_size,
                                            cap=cap)
        return coder.encode_chunked(symbols, tbl, chunk_size, cap=cap)

    per_position = coder.is_per_position(tbl, t_len)
    full = symbols[:, :n_full * chunk_size]
    full = full.reshape(lanes, n_full, chunk_size).swapaxes(0, 1)

    def _slab_encode(sym_loc, tbl_loc, chunk_major: bool):
        """Encode the local (n_loc, lanes, chunk_size) chunk slab.
        ``tbl_loc`` is chunk-major ``(n_loc, chunk_size, ...)`` when
        ``chunk_major`` else a replicated static/shared TableSet."""
        if backend == "kernel":
            # one pallas_call for the whole local slab: stitch the local
            # chunks back into a (lanes, n_loc * chunk_size) stream and let
            # the fused kernel's chunk grid axis re-cut it — packed streams
            # (and per-cell overflow flags) come straight off the kernel,
            # no host-side compact_records pass
            n_loc = sym_loc.shape[0]
            flat = sym_loc.swapaxes(0, 1).reshape(lanes, n_loc * chunk_size)
            tbl_flat = (jax.tree.map(
                lambda a: a.reshape((n_loc * chunk_size,) + a.shape[2:]),
                tbl_loc) if chunk_major else tbl_loc)
            ch = kops.rans_encode_chunked(flat, tbl_flat, chunk_size,
                                          cap=cap)
            return EncodedLanes(ch.buf, ch.start, ch.length, ch.overflow)
        if chunk_major:
            return jax.vmap(lambda s, tb: coder.encode(s, tb, cap=cap))(
                sym_loc, tbl_loc)
        return jax.vmap(lambda s: coder.encode(s, tbl_loc, cap=cap))(sym_loc)

    spec = P("chunks")
    out_specs = EncodedLanes(buf=spec, start=spec, length=spec,
                             overflow=spec)
    check_vma = {"check_vma": False} if backend == "kernel" else {}
    if per_position:
        tbl_full = coder.chunk_tables(tbl, n_full, chunk_size)
        enc = shard_map(lambda s, tb: _slab_encode(s, tb, True), mesh=mesh,
                        in_specs=(spec,
                                  _chunked_table_specs(tbl, sharded=True)),
                        out_specs=out_specs, **check_vma)(full, tbl_full)
    else:
        enc = shard_map(lambda s, tb: _slab_encode(s, tb, False), mesh=mesh,
                        in_specs=(spec,
                                  _chunked_table_specs(tbl, sharded=False)),
                        out_specs=out_specs, **check_vma)(full, tbl)
    enc = ChunkedLanes(buf=enc.buf, start=enc.start, length=enc.length,
                       overflow=enc.overflow)

    if tail_len:
        tbl_tail = (coder.slice_tables(tbl, n_full * chunk_size, t_len)
                    if per_position else tbl)
        sym_tail = symbols[:, n_full * chunk_size:]
        if backend == "kernel":
            tail = kops.rans_encode(sym_tail, tbl_tail, cap=cap)
        else:
            tail = coder.encode(sym_tail, tbl_tail, cap=cap)
        enc = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b[None]], axis=0), enc,
            ChunkedLanes(buf=tail.buf, start=tail.start, length=tail.length,
                         overflow=tail.overflow))
    return enc


def decode_chunked(chunks: ChunkedLanes | ContainerSlab, n_symbols: int,
                   tbl: TableSet,
                   chunk_size: int, mesh: Mesh | None = None,
                   prob_bits: int = C.PROB_BITS, use_lut: bool = False,
                   predictor=None, backend: str = "coder",
                   candidates: jax.Array | None = None):
    """Device-parallel chunked decode over either decode backend.

    ``backend="coder"`` runs the pure-JAX lane decoder (vmap per local
    chunk slab); ``backend="kernel"`` runs the Pallas decode kernel — one
    ``pallas_call`` per device covering its whole local slab (the kernel's
    chunk grid axis).  Both consume ``core.search``, so the returned
    (symbols (lanes, T), avg_probes) are bit-identical across backends and
    mesh shapes (chunks carry no cross-device state).
    ``predictor`` drives prediction-guided search inside every chunk.
    ``candidates`` is an optional ``(T, lanes, topk)`` model-top-k plane
    (the serve pipeline's trial symbols): full-size chunks' rows are cut
    chunk-major and sharded with the chunk slab on the same mesh axis, the
    ragged tail's rows ride the tail decode — probe accounting is
    identical to ``coder.decode_chunked(candidates=...)`` on every backend
    and mesh shape (topk == 0 disables speculation).

    ``chunks`` may also be a :class:`~repro.core.bitstream.ContainerSlab`
    (``bitstream.parse_chunked`` of a serialized container): the
    single-device kernel path then decodes ZERO-COPY straight from the
    packed payload slab (per-window DMA inside the kernel — no host- or
    device-side right-align materialization at all), while the mesh and
    coder paths rebuild the dense ``(n_chunks, lanes, cap)`` slab with one
    device-side gather (``bitstream.slab_to_chunked``) — still never the
    host copy.  Symbols and probe counts are bit-identical to passing the
    equivalent ``ChunkedLanes`` on every path.
    """
    if backend == "kernel":
        from repro.kernels import ops as kops
    elif backend != "coder":
        raise ValueError(f"unknown decode backend {backend!r}")
    slab_in = isinstance(chunks, ContainerSlab)
    n_have = chunks.offset.shape[0] if slab_in else chunks.buf.shape[0]
    n_total = coder.num_chunks(n_symbols, chunk_size)
    if n_have != n_total:
        raise ValueError(
            f"stream has {n_have} chunks but n_symbols="
            f"{n_symbols} at chunk_size={chunk_size} implies {n_total}")
    n_full, tail_len = divmod(n_symbols, chunk_size)
    if candidates is not None and candidates.shape[-1] == 0:
        candidates = None
    if candidates is not None:
        lanes = chunks.offset.shape[1] if slab_in else chunks.buf.shape[1]
        if candidates.shape[:2] != (n_symbols, lanes):
            raise ValueError(
                f"candidate planes must be (T, lanes, topk)=({n_symbols}, "
                f"{lanes}, *); got {candidates.shape}")
        candidates = candidates.astype(jnp.int32)
    if not _usable(mesh, n_full):
        if backend == "kernel":
            if slab_in:
                # zero-copy: the kernel DMAs each (chunk, lane) window out
                # of the packed slab itself — no dense stream rebuild
                return kops.rans_decode_chunked(
                    n_symbols=n_symbols, tbl=tbl, chunk_size=chunk_size,
                    prob_bits=prob_bits, predictor=predictor,
                    candidates=candidates, from_container=chunks)
            return kops.rans_decode_chunked(
                chunks, n_symbols, tbl, chunk_size, prob_bits=prob_bits,
                predictor=predictor, candidates=candidates)
        if slab_in:
            chunks = bitstream.slab_to_chunked(chunks)
        return coder.decode_chunked(chunks, n_symbols, tbl, chunk_size,
                                    prob_bits=prob_bits, use_lut=use_lut,
                                    predictor=predictor,
                                    candidates=candidates)
    if slab_in:
        # sharded path: rebuild the dense chunk slab with one device-side
        # gather so the shard_map below sees the usual (n_chunks, lanes,
        # cap) layout (host right-align copy still never runs)
        chunks = bitstream.slab_to_chunked(chunks)

    per_position = coder.is_per_position(tbl, n_symbols)
    sub = jax.tree.map(lambda a: a[:n_full], chunks)
    n_loc = n_full // mesh.shape["chunks"]
    out_specs = (P("chunks"), P("chunks"), P("chunks"))

    def _decode_one(enc, tb, n=chunk_size, cand=None, flags=False):
        """One chunk decode.  ``flags=True`` threads the per-lane stream
        exhaustion flag out instead of raising — required inside traced
        shard_map/vmap bodies, where the host-level
        ``StreamExhaustedError`` cannot fire (checked after the mesh
        program returns)."""
        if backend == "kernel":
            return kops.rans_decode(enc, n, tb, prob_bits=prob_bits,
                                    predictor=predictor, candidates=cand,
                                    exhausted_flags=flags)
        return coder.decode(enc, n, tb, prob_bits,
                            predictor=predictor, use_lut=use_lut,
                            candidates=cand, return_exhausted=flags)

    def _slab_decode(enc_loc, tbl_loc, chunk_major: bool, cand_loc=None):
        """Decode the local (n_loc, lanes, cap) chunk slab.  ``tbl_loc`` is
        chunk-major ``(n_loc, chunk_size, ...)`` when ``chunk_major`` else a
        replicated static/shared TableSet; ``cand_loc`` is the local
        chunk-major ``(n_loc, chunk_size, lanes, topk)`` candidate slab.
        Returns ``(sym3, per_chunk_probes, under)`` with ``under`` the
        per-(chunk, lane) exhaustion flags."""
        if backend == "kernel":
            # one pallas_call for the whole local slab: the kernel's chunk
            # grid axis decodes every local chunk in a single launch (the
            # candidate rows ride the chunk grid axis with the tables)
            lanes = enc_loc.buf.shape[1]
            tbl_flat = (jax.tree.map(
                lambda a: a.reshape((n_loc * chunk_size,) + a.shape[2:]),
                tbl_loc) if chunk_major else tbl_loc)
            cand_flat = (cand_loc.reshape((n_loc * chunk_size,)
                                          + cand_loc.shape[2:])
                         if cand_loc is not None else None)
            sym, _, cpro, cund = kops.rans_decode_chunked(
                enc_loc, n_loc * chunk_size, tbl_flat, chunk_size,
                prob_bits=prob_bits, predictor=predictor,
                candidates=cand_flat, chunk_probes=True,
                exhausted_flags=True)
            sym3 = sym.reshape(lanes, n_loc, chunk_size).swapaxes(0, 1)
            per_chunk = (jnp.sum(cpro.astype(jnp.float32), axis=1)
                         / (lanes * chunk_size))
            return sym3, per_chunk, cund
        # coder path: batch the local chunk slab through one vmapped scan
        if chunk_major:
            if cand_loc is not None:
                return jax.vmap(
                    lambda e, tb, cd: _decode_one(
                        EncodedLanes(*e), TableSet(*tb), cand=cd,
                        flags=True))(enc_loc, tbl_loc, cand_loc)
            return jax.vmap(
                lambda e, tb: _decode_one(EncodedLanes(*e), TableSet(*tb),
                                          flags=True))(enc_loc, tbl_loc)
        if cand_loc is not None:
            return jax.vmap(
                lambda e, cd: _decode_one(EncodedLanes(*e), tbl_loc,
                                          cand=cd, flags=True))(
                enc_loc, cand_loc)
        return jax.vmap(
            lambda e: _decode_one(EncodedLanes(*e), tbl_loc, flags=True))(
            enc_loc)

    # the candidate rows of the full-size chunks, chunk-major, sharded on
    # the same "chunks" axis as the stream slab
    cand_full = (candidates[:n_full * chunk_size].reshape(
        (n_full, chunk_size) + candidates.shape[1:])
        if candidates is not None else None)
    extra_args, extra_specs = [], []
    if cand_full is not None:
        extra_args.append(cand_full)
        extra_specs.append(P("chunks"))

    if per_position:
        tbl_full = coder.chunk_tables(tbl, n_full, chunk_size)

        def body(enc_loc, tbl_loc, *cand):
            return _slab_decode(ChunkedLanes(*enc_loc), TableSet(*tbl_loc),
                                True, cand[0] if cand else None)

        sym_full, probes_full, under_full = shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("chunks"), sub),
                      _chunked_table_specs(tbl, sharded=True),
                      *extra_specs),
            out_specs=out_specs, check_vma=False)(sub, tbl_full,
                                                  *extra_args)
    else:
        def body(enc_loc, tbl_rep, *cand):
            return _slab_decode(ChunkedLanes(*enc_loc), TableSet(*tbl_rep),
                                False, cand[0] if cand else None)

        sym_full, probes_full, under_full = shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("chunks"), sub),
                      _chunked_table_specs(tbl, sharded=False),
                      *extra_specs),
            out_specs=out_specs, check_vma=False)(sub, tbl, *extra_args)

    coder._check_exhausted(under_full, "parallel.decode_chunked")
    lanes = sym_full.shape[1]
    syms = [sym_full.swapaxes(0, 1).reshape(lanes, n_full * chunk_size)]
    probe_sums = [jnp.sum(probes_full) * chunk_size]
    if tail_len:
        tbl_tail = (coder.slice_tables(tbl, n_full * chunk_size, n_symbols)
                    if per_position else tbl)
        sym_tail, probes_tail = _decode_one(
            coder.chunk_encoded(chunks, n_full), tbl_tail, n=tail_len,
            cand=(candidates[n_full * chunk_size:]
                  if candidates is not None else None))
        syms.append(sym_tail)
        probe_sums.append(probes_tail * tail_len)
    out = jnp.concatenate(syms, axis=1)
    return out, sum(probe_sums) / n_symbols
