"""Serving engine: prefill/generate plus the batched multi-stream service.

Two layers live here:

* the seed-era single-stream primitives — ``make_serve_step`` is the
  function the decode-shape dry-runs lower (one new token against a
  ring-buffered cache of ``max_len``: a cache shorter than the sequence
  wraps, slot = pos % max_len, older entries age out — the wrap is pinned
  logit-level in tests/test_serve_engine.py), and ``prefill`` /
  ``generate`` drive the same step function for the runnable examples;

* :class:`BatchEngine` — the request-level continuous-batching engine
  (DESIGN.md §11).  Concurrent compress/decompress requests are admitted
  into ``slots`` of ONE traced chunk program: the batch axis is
  ``slots * lanes`` rows (shardable over a ``("lanes",)`` mesh), every
  slot owns ``lanes`` rows of one shared ring-buffered model cache with
  its own per-row positions, and the per-row rANS ``DecState`` rides the
  same ``lax.scan`` carry.  Requests join and retire at chunk boundaries
  without retracing (row masks, not new programs); the host double-buffers
  container parse/pack and chunk encode against the in-flight launch.
  Every per-request output is byte-identical to the single-request
  ``serve.compress`` paths — the engine is a scheduler, not a new coder.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitstream, coder, constants as C
from repro.core.predictors import model_topk_candidates
from repro.serve.trace import EngineTrace, hlo_scopes
from repro.models import (ModelConfig, PrefillUnsupportedError, can_prefill,
                          decode_step, init_state, prefill_chunk,
                          recurrent_state_tree, ring_length, state_spec,
                          wrap_length)


def make_serve_step(cfg: ModelConfig):
    """Returns serve_step(params, cache, token, pos, memory=None)."""

    def serve_step(params, cache, token, pos, memory=None):
        return decode_step(params, cache, token, pos, cfg, memory=memory)

    return serve_step


def teacher_forced_scan(params, cfg: ModelConfig, tokens: jax.Array,
                        max_len: int, memory: jax.Array | None = None,
                        step_fn=None):
    """Scan ``decode_step`` over ``tokens`` (B, S), teacher-forced.

    The single shared teacher-forced core of the serve layer: ``prefill``
    consumes it for generation, and ``serve.compress.collect_tables``
    consumes it to drive the SPC (so the cache evolution that prices the
    bitstream is the *same code* that serves the model — the determinism
    contract of LM-driven lossless compression).  ``step_fn(logits, t)``
    optionally maps each step's logits before stacking; default stacks the
    raw logits.  Returns ``(cache, stacked outputs)``.

    ``max_len`` < S is a real ring: positions wrap (slot = pos % max_len)
    and entries older than ``max_len`` age out of attention, so the scan
    conditions on a sliding window of the last ``max_len`` tokens —
    logit-identical to ``forward`` with ``sliding_window=max_len`` (the
    regression test in tests/test_serve_engine.py pins this).
    """
    b, s = tokens.shape
    cache = init_state(cfg, b, max_len)

    def body(carry, t):
        cache = carry
        lg, cache = decode_step(params, cache, tokens[:, t][:, None],
                                t, cfg, memory=memory)
        return cache, (lg if step_fn is None else step_fn(lg, t))

    return jax.lax.scan(body, cache, jnp.arange(s))


def prefill(params, cfg: ModelConfig, tokens: jax.Array,
            max_len: int, memory: jax.Array | None = None):
    """Teacher-forced scan of decode_step over the prompt.

    Returns (cache, last_logits).  Using the decode path for prefill keeps
    serving numerics identical to stepwise decode — the property LM-driven
    lossless compression depends on (serve/compress.py).
    """
    cache, all_logits = teacher_forced_scan(params, cfg, tokens, max_len,
                                            memory)
    return cache, all_logits[-1]


def generate(params, cfg: ModelConfig, prompt: jax.Array, n_new: int,
             max_len: int, memory: jax.Array | None = None,
             temperature: float = 0.0, key: jax.Array | None = None,
             return_logits: bool = False):
    """Greedy (or sampled) generation; returns (B, n_new) new tokens.

    ``return_logits``: also return the per-step logits ``(B, n_new, Vpad)``
    that produced each token — the testable position contract (a cache
    off-by-one perturbs logits long before it flips an argmax).
    """
    b, s = prompt.shape
    cache, last = prefill(params, cfg, prompt, max_len, memory)

    def pick(lg, k):
        lg = lg[:, :cfg.vocab_size]
        if temperature <= 0.0:
            return jnp.argmax(lg, -1).astype(jnp.int32)
        return jax.random.categorical(k, lg / temperature).astype(jnp.int32)

    def body(carry, i):
        cache, tok, k = carry
        k, sub = jax.random.split(k)
        lg, cache = decode_step(params, cache, tok[:, None], s + i, cfg,
                                memory=memory)
        nxt = pick(lg, sub)
        return (cache, nxt, k), (nxt, lg)

    k0 = key if key is not None else jax.random.PRNGKey(0)
    first = pick(last, k0)
    # prefill consumed positions [0, s), so the first generated token is
    # consumed at position s: scan i = 0..n_new-2 (NOT 1..n_new-1, which
    # would skip cache slot s and attend over a never-written row)
    (_, _, _), (rest, lgs) = jax.lax.scan(
        body, (cache, first, k0), jnp.arange(n_new - 1))
    out = jnp.concatenate([first[:, None], rest.T], axis=1)
    if return_logits:
        logits = jnp.concatenate([last[:, None], lgs.swapaxes(0, 1)], axis=1)
        return out, logits
    return out


# ---------------------------------------------------------------------------
# batched multi-stream engine (continuous batching over slots x lanes rows)
# ---------------------------------------------------------------------------

BOS = 0
MODE_IDLE, MODE_COMPRESS, MODE_DECOMPRESS = 0, 1, 2
# jax.named_scope names of the cycle programs' layers (BatchEngine.scope_maps)
SCOPES = ("model", "spc", "topk", "decode_kernel")


class EngineQueueFullError(RuntimeError):
    """Admission queue at capacity — the graceful-degradation backstop."""


class RequestOverflowError(RuntimeError):
    """A request's per-request byte budget (cap) overflowed mid-stream."""


@dataclasses.dataclass
class RequestResult:
    """Terminal state of one engine request.

    ``ok`` requests carry ``blob`` (compress) or ``tokens`` (decompress);
    failed requests carry the named ``error`` instead — a failure retires
    its slot and NEVER perturbs co-batched streams (their rows are
    independent end to end; the isolation test pins byte-exactness of the
    neighbours).  ``probes`` is the request's total CDF-probe count
    (decompress; the Fig. 4(b) accounting, summed over its rows).
    """
    rid: int
    kind: str
    ok: bool
    blob: bytes | None = None
    tokens: np.ndarray | None = None
    error: Exception | None = None
    n_symbols: int = 0
    probes: int = 0
    slot: int = -1
    arrival: float = 0.0
    admitted_at: float = 0.0
    completed_at: float = 0.0


@dataclasses.dataclass
class _Req:
    rid: int
    kind: str                       # "compress" | "decompress"
    arrival: float
    n_symbols: int
    cap: int                        # per-request byte budget (compress)
    tokens: np.ndarray | None = None            # (lanes, T) compress input
    slab: bitstream.ContainerSlab | None = None  # decompress input
    # live-slot state
    slot: int = -1
    admitted_at: float = 0.0
    pos: int = 0                    # symbols dispatched so far
    enc_chunks: list = dataclasses.field(default_factory=list)
    out_syms: list = dataclasses.field(default_factory=list)
    probes: int = 0


def _row_reset(mask, a):
    """Zero the masked rows of a (reps, rows, ...) cache leaf."""
    return jnp.where(mask.reshape((1, -1) + (1,) * (a.ndim - 2)),
                     jnp.zeros_like(a), a)


def _chunk_body(params, cache, tok, fresh, pos0, mode, n_valid, tf, buf,
                start, *, cfg, chunk_size, prob_bits, topk, backend):
    """One continuous-batching cycle: ``chunk_size`` steps over all rows.

    Rows are the flattened ``slots * lanes`` batch axis; all per-slot
    quantities arrive rows-form (``(B,)``/``(B, ...)``) so the body is
    row-local — shardable over a ``("lanes",)`` mesh with no collectives.

      fresh   (B,) bool  — admit boundary: zero the row's cache, tok=BOS
                           (matching ``init_state`` zeros, so the row's
                           evolution equals a fresh single-request scan)
      pos0    (B,) int32 — the row's absolute position at cycle start
      mode    (B,) int32 — MODE_COMPRESS / MODE_DECOMPRESS / MODE_IDLE
      n_valid (B,) int32 — active steps this cycle (ragged join/retire:
                           rows past their request freeze via masks)
      tf      (B, chunk_size) — teacher-forced next-tokens (compress rows)
      buf     (B, cap) uint8 / start (B,) — this cycle's standalone rANS
                           chunk streams (decompress rows; the per-row
                           ``DecState`` re-initializes here each cycle,
                           exactly like the single-request chunk decode)

    Each step runs the shared ``decode_step`` (per-row ring positions),
    quantizes through the single-source ``serve.compress.step_tables``,
    ranks model-top-k candidates and pops one symbol per row
    (``kernels.ops.rans_decode_step_rows``).  Freezing a row past its
    request is per state class (``repro.models.recurrent_state_tree``):
    *ring* leaves need no select — the frozen row clamps its position to
    ``pos0 + n_valid``, so the write lands in the slot the next cycle's
    first step overwrites before attending; *recurrent* leaves (ssm/rec
    ``(h, conv)``) mutate on EVERY step, so frozen rows explicitly keep
    their old leaves (``jnp.where`` on the active mask — for ring-only
    configs the select tree is empty and the traced program is unchanged).
    Returns ``(cache', tok', tables, syms, probes)`` with scan-stacked
    ``(chunk_size, B, ...)`` outputs.
    """
    from repro.kernels.ops import rans_decode_step_rows
    from repro.serve.compress import step_tables
    with jax.named_scope("model"):
        cache = jax.tree.map(functools.partial(_row_reset, fresh), cache)
        tok = jnp.where(fresh[:, None], jnp.int32(BOS), tok)
    rec_tree = recurrent_state_tree(cache)      # static (trace-time) bools
    dec0 = coder.decoder_init(coder.EncodedLanes(
        buf=buf, start=start, length=jnp.zeros_like(start), overflow=None))
    buf_t = buf.T                   # (cap, B): transposed once, not per step

    def _freeze(active, new_cache, old_cache):
        def sel(rec, new, old):
            if not rec:
                return new
            m = active.reshape((1, -1) + (1,) * (new.ndim - 2))
            return jnp.where(m, new, old)
        return jax.tree.map(sel, rec_tree, new_cache, old_cache)

    def body(carry, t):
        cache, s, ptr, tok = carry
        active = t < n_valid
        pos = pos0 + jnp.minimum(t, n_valid)
        with jax.named_scope("model"):
            lg, new_cache = decode_step(params, cache, tok, pos, cfg)
            cache = _freeze(active, new_cache, cache)
        with jax.named_scope("spc"):
            tbl = step_tables(lg, cfg.vocab_size, prob_bits)
        with jax.named_scope("topk"):
            cands = model_topk_candidates(lg[:, :cfg.vocab_size], topk)
        with jax.named_scope("decode_kernel"):
            s2, p2, sym, probes, u = rans_decode_step_rows(
                buf_t, s, ptr, tbl, prob_bits=prob_bits, candidates=cands,
                backend=backend)
        s = jnp.where(active, s2, s)
        ptr = jnp.where(active, p2, ptr)
        und = (active & (u > 0)).astype(jnp.int32)
        nxt = jnp.where(mode == MODE_COMPRESS, tf[:, t],
                        sym.astype(jnp.int32))
        tok = jnp.where(active[:, None], nxt[:, None], tok)
        return (cache, s, ptr, tok), (tbl, sym, probes, und)

    (cache, _, _, tok), (tables, syms, probes, unders) = jax.lax.scan(
        body, (cache, dec0.s, dec0.ptr, tok), jnp.arange(chunk_size))
    return cache, tok, tables, syms, probes, unders


def _prefill_body(params, cache, tok, fresh, pos0, mode, n_valid, tf, buf,
                  start, *, cfg, chunk_size, prob_bits, topk, backend):
    """All-compress fast cycle: same signature and outputs as
    :func:`_chunk_body`, but ONE teacher-forced block pass over the chunk
    instead of ``chunk_size`` sequential decode steps (the serving-engine
    prefill/decode phase split — compress rows are fully known up front,
    so there is nothing sequential about them).

    Dispatched per cycle by :class:`BatchEngine` only when every live slot
    is an unwrapped compress request and :func:`can_prefill` holds; any
    decompress (symbols feed back step to step) or wrapped row falls back
    to the step program.  Bit-identity with the step cycle is structural:
    ``prefill_chunk`` is pinned bit-identical to the ``decode_step`` scan,
    and the per-position ``step_tables`` runs at the step path's exact
    (B, V) shape under ``lax.map``.  ``syms``/``probes`` are placeholder
    zeros — finalize never reads them for compress rows.
    """
    from repro.serve.compress import step_tables
    del mode, buf, start, topk, backend  # step-path-only inputs
    with jax.named_scope("model"):
        cache = jax.tree.map(functools.partial(_row_reset, fresh), cache)
        tok = jnp.where(fresh[:, None], jnp.int32(BOS), tok)
        # the step body feeds the PREVIOUS token at each step: inputs are
        # the carried token (BOS if fresh) followed by all but the last tf
        # token
        inputs = jnp.concatenate([tok, tf[:, :chunk_size - 1]], axis=1)
        lgs, cache = prefill_chunk(params, cache, inputs, pos0, n_valid,
                                   cfg)
    with jax.named_scope("spc"):
        tables = jax.lax.map(
            lambda lg: step_tables(lg, cfg.vocab_size, prob_bits),
            jnp.moveaxis(lgs, 1, 0))
    idx = jnp.clip(n_valid - 1, 0, chunk_size - 1)
    last = jnp.take_along_axis(tf, idx[:, None], axis=1)
    tok = jnp.where((n_valid > 0)[:, None], last, tok)
    zeros = jnp.zeros((chunk_size, tok.shape[0]), jnp.int32)
    return cache, tok, tables, zeros, zeros, zeros


@functools.partial(jax.jit, static_argnames=("cap",))
def _encode_rows(symbols, tbl, cap: int):
    """Encode one slot's chunk against its per-(position, lane) tables."""
    return coder.encode(symbols, tbl, cap=cap)


@functools.lru_cache(maxsize=None)
def _compiled_program(body, cfg, chunk_size, prob_bits, topk, backend):
    """Process-wide program cache: engines with the same traced geometry
    share ONE jitted executable per cycle body (a fresh ``jax.jit``
    wrapper per engine would recompile per instance — the retrace the
    engine exists to avoid)."""
    return jax.jit(functools.partial(
        body, cfg=cfg, chunk_size=chunk_size, prob_bits=prob_bits,
        topk=topk, backend=backend))


class BatchEngine:
    """Continuous-batching compress/decompress service over one step program.

    ``slots`` concurrent requests of ``lanes`` rANS lanes each share ONE
    jitted chunk program (:func:`_chunk_body`): a single model cache of
    ``slots * lanes`` rows ring-buffered at ``max_len``, per-row positions,
    per-row coder state.  Requests join (``fresh`` row reset) and retire at
    chunk boundaries via masks — no retracing, any slot occupancy runs the
    same executable.  The run loop keeps ONE cycle in flight: cycle ``k+1``
    is dispatched before cycle ``k``'s outputs are fetched, so the host
    half (container parse / right-align windows, chunk encode, ``pack``)
    overlaps the device half (async dispatch) — the double-buffer pipeline.

    Byte-identity contract: a request of T <= ``max_len`` symbols produces
    output byte-identical to ``lm_compress_chunked`` /
    ``lm_decompress_chunked`` at the same ``chunk_size``/``prob_bits``/
    ``topk`` regardless of co-batched traffic.  (Rows are independent in
    every model op; a ring of length >= T never wraps and its unwritten
    slots contribute exactly-zero attention mass; recurrent state is
    position-free and frozen rows keep their leaves by explicit select;
    the per-chunk coder math is the identical ``core`` single source.)
    The length guard is state-spec-driven (``repro.models.wrap_length``):
    pure-recurrent configs (mamba2) accept ANY length — their O(1) state
    never wraps; windowed configs (recurrentgemma, mixtral) accept any
    length once ``max_len >=`` the native window — both the engine ring
    (``min(max_len, window)``) and the single-request ring saturate at
    the window, byte-identically; only a ring that would wrap *shorter
    than the single-request path's* is rejected with a named error unless
    ``allow_wrap=True`` (wrapped requests condition on a sliding window
    of ``max_len`` tokens and round-trip through this engine).

    Admission: FIFO by ``(arrival, rid)``, at most ``max_queue`` waiting
    requests (``submit_*`` raises :class:`EngineQueueFullError` beyond —
    reject-at-the-door, never corrupt in-flight streams).  Failures
    (e.g. per-request cap overflow) retire their slot with a named error
    in the result; co-batched rows are untouched.

    ``mesh``: optional ``("lanes",)`` mesh (``parallel.chunked.lane_mesh``)
    sharding the program's row axis; indivisible row counts degrade to the
    single-device program bit-exactly (shared routing contract —
    ``parallel.chunked.lane_mesh_usable``).

    ``prefill``: ``"auto"`` (default) dispatches cycles whose live slots
    are all unwrapped compress requests to the block-parallel prefill
    program (:func:`_prefill_body` — the engine's throughput lever: one
    teacher-forced pass replaces ``chunk_size`` sequential steps, bit
    -identically), stepping down cleanly to the step program for families
    without ``prefill_chunk`` (recurrent/hybrid state is sequential —
    ``repro.models.can_prefill``); ``"off"`` forces every cycle onto the
    step program (the byte-identity oracle the tests compare against);
    ``"force"`` raises :class:`repro.models.PrefillUnsupportedError` at
    construction when the family cannot prefill — the named-error guard
    against silently assuming attention state for recurrent families.
    ``prefill_cycles`` counts fast-path dispatches.

    ``trace`` (:class:`repro.serve.trace.EngineTrace`) records the engine's
    host spans, its counters (``cycles.step``/``.prefill``,
    ``requests.admitted``/``.retired``/``.failed``/``.rejected``) and the
    last admissions ``(rid, slot, cycle)``; :meth:`scope_maps` names the
    layer (:data:`SCOPES`) of each instruction of the cycle programs.
    """

    def __init__(self, params, cfg: ModelConfig, *, slots: int = 4,
                 lanes: int = 8, chunk_size: int = 64,
                 max_len: int | None = None, cap: int | None = None,
                 prob_bits: int = C.PROB_BITS, topk: int = 4,
                 max_queue: int = 64, step_backend: str = "coder",
                 mesh=None,
                 prefill: str = "auto"):
        if step_backend not in ("coder", "kernel"):
            raise ValueError(f"unknown step backend {step_backend!r}")
        if prefill not in ("auto", "off", "force"):
            raise ValueError(f"unknown prefill policy {prefill!r} "
                             "(expected 'auto', 'off' or 'force')")
        if prefill == "force" and not can_prefill(cfg):
            raise PrefillUnsupportedError(
                f"prefill='force' on config {cfg.name!r} (family "
                f"{cfg.family!r}, kinds {state_spec(cfg).kinds}): this "
                "family carries sequential state and has no block-parallel "
                "prefill — use prefill='auto' (steps down to the step "
                "program) or 'off'")
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.lanes = lanes
        self.rows = slots * lanes
        self.chunk_size = chunk_size
        self.max_len = 4 * chunk_size if max_len is None else max_len
        # stream window of the traced program: every decompress chunk cell
        # must fit (validated at submit) — compress caps are per-request
        # and unconstrained (encode runs outside the program)
        self.cap = coder.default_cap(chunk_size) if cap is None else cap
        self.prob_bits = prob_bits
        self.topk = topk
        self.max_queue = max_queue
        self.step_backend = step_backend
        # state-spec-driven geometry: what the shared state actually is
        # (ring vs recurrent), how many ring slots init_state allocated,
        # and past which length a request's conditioning would diverge
        # from the single-request path (None = never — see wrap_length)
        self.state_spec = state_spec(cfg)
        self.ring_len = ring_length(cfg, self.max_len)
        self._wrap_len = wrap_length(cfg, self.max_len)
        self._cache = init_state(cfg, self.rows, self.max_len)
        self._tok = jnp.full((self.rows, 1), BOS, jnp.int32)
        self._slots: list[_Req | None] = [None] * slots
        self._queue: list[_Req] = []
        self._next_rid = 0
        self.trace = EngineTrace()
        self.prefill_cycles = 0      # cycles served by the prefill program
        # program -> (jitted, argument shapes) at its first launch, and the
        # scope maps built from them (scope_maps)
        self._launched: dict[str, tuple] = {}
        self._scope_maps: dict[str, dict[str, str | None]] = {}
        self._wall = False           # run(clock="wall") is running
        self._prog = self._build_program(mesh)
        self._prog_prefill = (self._build_program(mesh, body=_prefill_body)
                              if prefill in ("auto", "force")
                              and can_prefill(cfg) else None)

    # -- program ----------------------------------------------------------

    def _build_program(self, mesh, body=_chunk_body):
        from repro.parallel.chunked import lane_mesh_usable, state_row_specs
        if not lane_mesh_usable(mesh, self.rows,
                                what="batched engine (its slots x lanes rows)"):
            return _compiled_program(
                body, self.cfg, self.chunk_size, self.prob_bits, self.topk,
                self.step_backend)
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        core = functools.partial(
            body, cfg=self.cfg, chunk_size=self.chunk_size,
            prob_bits=self.prob_bits, topk=self.topk,
            backend=self.step_backend)
        rows, rows2 = P("lanes"), P("lanes", None)
        # arbitrary state pytrees shard by the protocol's row-axis pin
        # (axis 1 on every leaf — ring or recurrent alike)
        carry = state_row_specs(self._cache)
        pspec = jax.tree.map(lambda _: P(), self.params)
        core = shard_map(
            core, mesh=mesh,
            in_specs=(pspec, carry, rows2, rows, rows, rows, rows,
                      rows2, rows2, rows),
            out_specs=(carry, rows2, P(None, "lanes"), P(None, "lanes"),
                       P(None, "lanes"), P(None, "lanes")),
            check_vma=False)
        return jax.jit(core)

    def scope_maps(self) -> dict[str, dict[str, str | None]]:
        """``{program: {HLO instruction: scope}}`` for each cycle program
        launched so far (``"step"``, ``"prefill"``); a scope is one of
        :data:`SCOPES`, or None for what no scope names.

        Built on the first call per program by compiling the kept jitted
        program again at the shapes of its first launch (a hit where the
        persistent compilation cache is on) and reading its optimized HLO,
        whose instruction names are the names the profiler gives the
        program's device operations.  Needs neither the engine's params nor
        its program attributes, so it works after they are dropped.  It
        compiles: call it outside any timed window.
        """
        for name, (prog, shapes) in self._launched.items():
            if name not in self._scope_maps:
                text = prog.lower(*shapes).compile().as_text()
                self._scope_maps[name] = hlo_scopes(text, SCOPES)
        return dict(self._scope_maps)

    # -- admission --------------------------------------------------------

    def _submit(self, req: _Req) -> int:
        if len(self._queue) >= self.max_queue:
            self.trace.count("requests.rejected")
            raise EngineQueueFullError(
                f"engine admission queue is full ({self.max_queue} waiting "
                "requests): drain with run() or raise max_queue — rejecting "
                "at the door keeps in-flight streams untouched")
        self._queue.append(req)
        return req.rid

    def _check_len(self, t_len: int, allow_wrap: bool, what: str):
        if t_len < 1:
            raise ValueError(f"{what} must cover at least 1 symbol")
        # state-spec-driven: pure-recurrent state never wraps (any length
        # is byte-identical to the single-request path), a window-bounded
        # ring with max_len >= window saturates identically at any length;
        # only a ring the single-request path would have sized LARGER can
        # diverge (repro.models.wrap_length)
        if self._wrap_len is not None and t_len > self._wrap_len \
                and not allow_wrap:
            raise ValueError(
                f"request of {t_len} symbols exceeds the engine ring "
                f"({self.ring_len} slots at max_len={self.max_len}): the "
                "shared cache would wrap and condition on a sliding window "
                "narrower than the single-request path's — pass "
                "allow_wrap=True to accept windowed conditioning "
                "(round-trips through this engine, but is no longer "
                "byte-identical to the single-request path), or build the "
                "engine with a larger max_len")

    def submit_compress(self, tokens, arrival: float = 0.0,
                        cap: int | None = None,
                        allow_wrap: bool = False) -> int:
        """Queue a compress request: tokens (lanes, T) -> container blob.

        ``cap`` is the per-request per-(chunk, lane) byte budget (default
        ``coder.default_cap`` of the chunk length — the single-request
        default, so default-cap blobs are byte-identical to
        ``lm_compress_chunked``).  An undersized cap fails ONLY this
        request (:class:`RequestOverflowError` in its result).
        """
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim != 2 or tokens.shape[0] != self.lanes:
            raise ValueError(
                f"compress tokens must be (lanes={self.lanes}, T), got "
                f"{tokens.shape}: the engine's traced program is shaped "
                "for slots x lanes rows")
        t_len = int(tokens.shape[1])
        self._check_len(t_len, allow_wrap, "a compress request")
        cap = (coder.default_cap(min(self.chunk_size, t_len))
               if cap is None else int(cap))
        rid = self._next_rid
        self._next_rid += 1
        return self._submit(_Req(rid=rid, kind="compress", arrival=arrival,
                                 n_symbols=t_len, cap=cap, tokens=tokens))

    def submit_decompress(self, blob: bytes, arrival: float = 0.0,
                          allow_wrap: bool = False) -> int:
        """Queue a decompress request: container v2 blob -> tokens.

        The blob is parsed and validated here (``bitstream.parse_chunked``
        named errors surface at submit, not mid-batch) and must match the
        engine's traced geometry: same ``lanes``, ``chunk_size`` and
        ``prob_bits``, every cell within the engine's stream window.
        """
        with self.trace.span("engine.submit.parse"):
            slab = bitstream.parse_chunked(blob)
        meta = slab.meta
        if meta.lanes != self.lanes:
            raise ValueError(
                f"container has {meta.lanes} lanes but the engine is "
                f"shaped for lanes={self.lanes}: the slots x lanes row "
                "grid is traced into the step program")
        if meta.chunk_size != self.chunk_size:
            raise ValueError(
                f"container chunk_size {meta.chunk_size} != engine "
                f"chunk_size {self.chunk_size}: the engine decodes at its "
                "traced chunk granularity — build a matching engine")
        if meta.prob_bits != self.prob_bits:
            raise ValueError(
                f"container prob_bits {meta.prob_bits} != engine "
                f"prob_bits {self.prob_bits}")
        max_cell = int(np.max(slab.length)) if slab.length.size else 0
        if max_cell > self.cap:
            raise ValueError(
                f"container cell of {max_cell} bytes exceeds the engine's "
                f"stream window (cap={self.cap}): build the engine with "
                f"cap >= {max_cell}")
        self._check_len(int(meta.n_symbols), allow_wrap,
                        "a decompress request")
        rid = self._next_rid
        self._next_rid += 1
        return self._submit(_Req(rid=rid, kind="decompress", arrival=arrival,
                                 n_symbols=int(meta.n_symbols), cap=self.cap,
                                 slab=slab))

    # -- one scheduling cycle --------------------------------------------

    def _admit(self, now: float, cycle: int):
        with self.trace.span("engine.admit"):
            self._queue.sort(key=lambda r: (r.arrival, r.rid))
            for s in range(self.slots):
                if self._slots[s] is not None:
                    continue
                pick = next((r for r in self._queue if r.arrival <= now),
                            None)
                if pick is None:
                    break
                self._queue.remove(pick)
                pick.slot, pick.admitted_at = s, now
                self._slots[s] = pick
                self.trace.admissions.append((pick.rid, s, cycle))
                self.trace.count("requests.admitted")

    def _build_cycle(self):
        """Host half of a cycle: rows-form inputs for every live slot.

        For decompress slots this is the zero-copy container side — the
        chunk's per-lane spans are right-aligned straight out of the
        parsed payload slab into the program's stream window (the only
        byte copy on the path).  Returns (spec, arrays) or None when no
        slot has steps to run.
        """
        with self.trace.span("engine.build"):
            B, S, cap = self.rows, self.chunk_size, self.cap
            fresh = np.zeros(B, bool)
            pos0 = np.zeros(B, np.int32)
            mode = np.zeros(B, np.int32)
            n_valid = np.zeros(B, np.int32)
            tf = np.zeros((B, S), np.int32)
            buf = np.zeros((B, cap), np.uint8)
            start = np.zeros(B, np.int32)
            spec = []
            prefillable = self._prog_prefill is not None
            for s, req in enumerate(self._slots):
                if req is None or req.pos >= req.n_symbols:
                    continue
                # decompress rows feed decoded symbols back step to step, and
                # rows wrapping the ALLOCATED ring (ring_len = min(max_len,
                # window), not max_len) overwrite slots still visible to
                # in-chunk queries — both force the cycle onto the step
                # program (attn_prefill requires pos0 + S <= ring slots)
                if req.kind != "compress" or req.n_symbols > self.ring_len:
                    prefillable = False
                r0, r1 = s * self.lanes, (s + 1) * self.lanes
                n_c = min(S, req.n_symbols - req.pos)
                c = req.pos // S
                fresh[r0:r1] = req.pos == 0
                pos0[r0:r1] = req.pos
                n_valid[r0:r1] = n_c
                if req.kind == "compress":
                    mode[r0:r1] = MODE_COMPRESS
                    tf[r0:r1, :n_c] = req.tokens[:, req.pos:req.pos + n_c]
                else:
                    mode[r0:r1] = MODE_DECOMPRESS
                    slab = req.slab
                    payload = np.asarray(slab.slab, np.uint8)
                    off = np.asarray(slab.offset)
                    ln = np.asarray(slab.length)
                    for l in range(self.lanes):
                        o, n = int(off[c, l]), int(ln[c, l])
                        buf[r0 + l, cap - n:] = payload[o:o + n]
                        start[r0 + l] = cap - n
                spec.append((req.rid, s, c, n_c,
                             req.pos + n_c >= req.n_symbols))
                req.pos += n_c
            if not spec:
                return None
            return spec, prefillable, (jnp.asarray(fresh), jnp.asarray(pos0),
                                       jnp.asarray(mode), jnp.asarray(n_valid),
                                       jnp.asarray(tf), jnp.asarray(buf),
                                       jnp.asarray(start))

    def _launch(self, built):
        """Device half: dispatch the cycle program asynchronously — the
        prefill fast path when every live slot is an unwrapped compress
        request, the step program otherwise."""
        spec, prefillable, arrays = built
        name, prog = (("prefill", self._prog_prefill) if prefillable
                      else ("step", self._prog))
        args = (self.params, self._cache, self._tok) + tuple(arrays)
        if name not in self._launched:
            self._launched[name] = (prog, jax.tree.map(_shape_of, args))
        with self.trace.span(f"engine.launch.{name}"):
            self._cache, self._tok, tables, syms, probes, unders = prog(*args)
        if prefillable:
            self.prefill_cycles += 1
        self.trace.count(f"cycles.{name}")
        return spec, tables, syms, probes, unders

    def _finalize(self, inflight, now: float, results: dict):
        """Harvest a finished cycle: encode/pack/collect per-slot outputs.

        Runs while the NEXT cycle is already in flight — ``np.asarray``
        here blocks on this cycle's program only.  A cap overflow retires
        its request with :class:`RequestOverflowError`; the slot frees, the
        (at most one) already-dispatched follow-up chunk of the failed
        request is discarded at its own finalize, and no other row is
        touched.  Its span counts the chunks harvested: ``symbols`` (all
        lanes), ``decoded`` (decompress symbols), their CDF ``probes``, and
        speculation ``hits``: decoded symbols found among the model's top-k
        candidates, i.e. with at most ``topk`` probes (``core.search``
        charges a hit at rank r r probes, a miss topk plus its search).
        """
        spec, tables, syms, probes, unders = inflight
        span = self.trace.span
        with span("engine.finalize.cycle") as fin:
            n_sym = n_dec = n_probes = n_hits = 0
            for rid, s, c, n_c, last in spec:
                req = self._slots[s]
                if req is None or req.rid != rid or rid in results:
                    continue    # retired mid-flight (failed upstream chunk)
                r0, r1 = s * self.lanes, (s + 1) * self.lanes
                if req.kind == "compress":
                    with span("engine.finalize.encode"):
                        tbl_c = jax.tree.map(lambda a: a[:n_c, r0:r1],
                                             tables)
                        sym_c = jnp.asarray(
                            req.tokens[:, c * self.chunk_size:
                                       c * self.chunk_size + n_c])
                        enc = _encode_rows(sym_c, tbl_c, cap=req.cap)
                    with span("engine.finalize.wait"):
                        enc = coder.EncodedLanes(*map(np.asarray, enc))
                    if enc.overflow.any():
                        cells = np.nonzero(enc.overflow)[0].tolist()
                        self._retire(req, now, results,
                                     error=RequestOverflowError(
                            f"request {rid}: encode overflow in chunk {c} "
                            f"(lanes {cells}): the per-request byte budget "
                            f"(cap={req.cap}) truncated the stream — "
                            "resubmit with a larger cap"))
                        continue
                    req.enc_chunks.append(enc)
                else:
                    with span("engine.finalize.wait"):
                        und = np.asarray(unders[:n_c, r0:r1])
                    if und.any():
                        cells = np.nonzero(und.any(axis=0))[0].tolist()
                        self._retire(req, now, results,
                                     error=coder.StreamExhaustedError(
                            f"request {rid}: decode over-read in chunk {c} "
                            f"(lanes {cells}): a lane's stream ran out of "
                            "bytes mid-decode — the container is truncated "
                            "or was produced with a different geometry"))
                        continue
                    with span("engine.finalize.wait"):
                        sym = np.asarray(syms[:n_c, r0:r1])
                        prb = np.asarray(probes[:n_c, r0:r1])
                    req.out_syms.append(sym.T.astype(np.int32))
                    p = int(prb.sum())
                    req.probes += p
                    n_dec += prb.size
                    n_probes += p
                    n_hits += int((prb <= self.topk).sum())
                n_sym += n_c * self.lanes
                if last:
                    self._retire(req, now, results)
            fin.attrs.update(symbols=n_sym, decoded=n_dec, probes=n_probes,
                             hits=n_hits)

    def _retire(self, req: _Req, now: float, results: dict,
                error: Exception | None = None):
        res = RequestResult(rid=req.rid, kind=req.kind, ok=error is None,
                            error=error, n_symbols=req.n_symbols,
                            probes=req.probes, slot=req.slot,
                            arrival=req.arrival, admitted_at=req.admitted_at)
        if error is None:
            with self.trace.span("engine.retire.pack", rid=req.rid):
                if req.kind == "compress":
                    ch = jax.tree.map(lambda *xs: np.stack(xs),
                                      *req.enc_chunks)
                    res.blob = bitstream.pack_chunked(
                        ch.buf, ch.start, ch.length, ch.overflow,
                        chunk_size=self.chunk_size, n_symbols=req.n_symbols,
                        prob_bits=self.prob_bits)
                else:
                    res.tokens = np.concatenate(req.out_syms, axis=1)
        # stamped once the result is whole, pack included
        res.completed_at = (time.perf_counter() - self.trace.t0
                            if self._wall else now)
        self.trace.count("requests.retired")
        if error is not None:
            self.trace.count("requests.failed")
        results[req.rid] = res
        self._slots[req.slot] = None

    # -- run loop ---------------------------------------------------------

    def run(self, *, clock: str = "virtual") -> dict[int, RequestResult]:
        """Drain the queue; returns {rid: RequestResult} for every request.

        ``clock="virtual"``: time is the cycle counter — fully
        deterministic (the seeded-admission test contract): arrivals are
        in cycle units and the loop jumps idle gaps.  ``clock="wall"``:
        arrivals are seconds relative to run start (``time.perf_counter``,
        the trace's clock); the loop sleeps through idle gaps and stamps
        real latencies (the bench contract).

        One cycle: admit -> build inputs (host) -> dispatch (async device)
        -> finalize the PREVIOUS cycle (host pack/encode, blocking only on
        the already-retired launch).  Keeping exactly one cycle in flight
        double-buffers host container work against the device program.
        Each iteration is one ``engine.cycle`` span, idle sleeps outside.
        """
        if clock not in ("virtual", "wall"):
            raise ValueError(f"unknown clock {clock!r}")
        results: dict[int, RequestResult] = {}
        wall = clock == "wall"
        t0 = self.trace.t0 = time.perf_counter()
        self._wall = wall
        vnow, cycle = 0.0, 0
        inflight = None
        while self._queue or any(self._slots) or inflight is not None:
            self.trace.cycle = cycle
            with self.trace.span("engine.cycle", rids=[]) as cyc:
                now = time.perf_counter() - t0 if wall else vnow
                if inflight is not None and self._queue \
                        and any(last for _, _, _, _, last in inflight[0]):
                    # a retire is pending and successors are waiting: sync
                    # the in-flight cycle NOW so the freed slot refills
                    # this cycle instead of idling one (steady-state chunks
                    # keep the launch-before-finalize double-buffer).
                    self._finalize(inflight, now, results)
                    inflight = None
                self._admit(now, cycle)
                built = self._build_cycle()
                nxt = None
                if built is not None:
                    cyc.attrs["rids"] = [rid for rid, *_ in built[0]]
                    nxt = self._launch(built)
                if inflight is not None:
                    now = time.perf_counter() - t0 if wall else vnow
                    self._finalize(inflight, now, results)
                inflight = nxt
            if nxt is None and inflight is None and self._queue:
                gap = min(r.arrival for r in self._queue)
                if wall:
                    time.sleep(max(0.0, gap - (time.perf_counter() - t0)))
                else:
                    vnow = max(vnow + 1.0, gap)
            else:
                vnow += 1.0
            cycle += 1
        return results


def _shape_of(a):
    """A launch argument's shape, dtype, sharding and weak type."""
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding,
                                weak_type=a.weak_type)
