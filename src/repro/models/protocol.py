"""The model-state protocol: one serve surface for the whole zoo.

The paper treats the probability generator as a pluggable component of the
rANS pipeline; this module is that plug.  Every architecture family in the
registry (dense / moe / ssm / hybrid / vlm / audio) exposes the same four
entry points behind :func:`get_protocol`:

    init_state(cfg, batch, max_len)   -> state pytree (all-zeros leaves)
    decode_step(params, state, token, pos, cfg, memory=None)
                                      -> (logits (B, Vpad), state')
    prefill_chunk(params, state, tokens, pos0, n_valid, cfg)
                                      -> (logits (B, S, Vpad), state')
                                         [optional — see can_prefill]
    state_spec(cfg)                   -> StateSpec

so ``serve.compress``, ``serve.engine`` and ``parallel.chunked`` never
import an architecture module — they carry an *arbitrary state pytree*
whose only contract is:

* every leaf is shaped ``(reps, rows, ...)`` — the row axis is axis 1 on
  every leaf (the engine's slots x lanes batch axis, the lane-mesh shard
  axis: ``parallel.chunked.state_row_specs``);
* a fresh stream's state is all-zeros (``init_state`` zero-initializes
  both KV rings and recurrent state, so the engine's per-slot reset mask
  — zeroing the retiring slot's rows — IS a fresh admit);
* :class:`StateSpec` classifies the leaves: **ring** state (KV caches —
  position-addressed, a bounded window of history, raggedness handled by
  per-row positions) versus **recurrent** state (Mamba2's ``(h, conv)``,
  rGLRU's — position-free, every step mutates it, so frozen rows need an
  explicit select; see ``serve.engine._chunk_body``).

Today every family shares one assembler (``models.transformer`` composes
attn/attn_moe/cross/dec/ssm/rec blocks from the layer-kind pattern), so
the per-family protocols all delegate to it — the protocol's value is the
explicit dispatch + capability surface, and the door it leaves open for a
family with a genuinely different assembler (the probabilistic-circuits
direction in PAPERS.md) to slot in without touching serve/.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax

from repro.models import transformer as _tf
from repro.models.config import ModelConfig


class PrefillUnsupportedError(RuntimeError):
    """The family's state is sequential — no block-parallel prefill.

    Raised (instead of the assembler's bare ``KeyError``) when a caller
    asks for ``prefill_chunk`` on a config whose pattern contains a
    recurrent / cross / enc-dec kind: those blocks carry state or memory
    the teacher-forced block pass does not model, so the only bit-exact
    program is the sequential ``decode_step`` scan.  The engine's
    ``prefill="auto"`` steps down silently; ``prefill="force"`` surfaces
    this error.
    """


# layer kinds whose per-block state is a position-addressed KV ring vs a
# position-free recurrence ("cross" caches nothing: it attends a
# precomputed memory)
_RING_KINDS = ("attn", "attn_moe", "dec")
_RECURRENT_KINDS = ("ssm", "rec")


class StateSpec(NamedTuple):
    """Static classification of a config's serving state.

    ``kinds``       — deduped layer kinds, stage order.
    ``ring``        — any position-addressed KV-ring leaves.
    ``recurrent``   — any position-free recurrent leaves (ssm/rec): these
                      mutate on EVERY step, so engine-frozen rows need an
                      explicit old/new select (ring leaves don't — their
                      writes land at a clamped position the next live step
                      overwrites before attending).
    ``ring_window`` — 0: no ring at all; > 0: the ring is bounded at this
                      window regardless of stream length (local/sliding
                      attention — ``init_state`` allocates
                      ``min(max_len, window)`` slots); -1: unbounded full
                      attention (the ring is ``max_len`` and wrapping it
                      changes the conditioning).
    """
    kinds: tuple[str, ...]
    ring: bool
    recurrent: bool
    ring_window: int


def state_spec(cfg: ModelConfig) -> StateSpec:
    kinds = tuple(dict.fromkeys(k for pat, _ in cfg.stages for k in pat))
    ring = any(k in _RING_KINDS for k in kinds)
    recurrent = any(k in _RECURRENT_KINDS for k in kinds)
    if not ring:
        window = 0
    else:
        window = (cfg.local_window or cfg.sliding_window) or -1
    return StateSpec(kinds=kinds, ring=ring, recurrent=recurrent,
                     ring_window=window)


def ring_length(cfg: ModelConfig, max_len: int) -> int:
    """Actual allocated ring slots of ``init_state(cfg, _, max_len)``.

    Windowed archs only ever allocate a window-sized ring
    (``models.attention.init_kv_cache`` via ``init_block_cache``), so the
    ring a serving loop must reason about is ``min(max_len, window)`` —
    NOT ``max_len``.  Pure-recurrent configs have no ring; their
    "ring length" is reported as ``max_len`` for convenience (nothing
    wraps — see :func:`wrap_length`).
    """
    spec = state_spec(cfg)
    if spec.ring_window > 0:
        return min(max_len, spec.ring_window)
    return max_len


def wrap_length(cfg: ModelConfig, max_len: int) -> int | None:
    """Stream length above which serving diverges from the single-request
    path (the ring wraps a shorter-than-native window), or ``None`` when
    no length does:

    * no ring (pure ssm/rglru): recurrent state is O(1) in stream length —
      nothing ever wraps;
    * bounded window with ``max_len >= window``: both the engine ring
      (``min(max_len, window) == window``) and the single-request ring
      (``min(T, window)``) saturate at the native window, and the attend
      core's reductions are ring-length-invariant — byte-identical at any
      stream length;
    * bounded window with ``max_len < window``: streams longer than
      ``max_len`` wrap an under-sized ring — windowed conditioning
      narrower than the arch's native window;
    * unbounded full attention: streams longer than ``max_len`` wrap and
      condition on a sliding window the full-context path never sees.
    """
    spec = state_spec(cfg)
    if not spec.ring:
        return None
    if spec.ring_window > 0:
        return None if max_len >= spec.ring_window else max_len
    return max_len


class ModelProtocol(NamedTuple):
    """One family's serving entry points (``prefill_chunk`` optional)."""
    family: str
    init_state: Callable
    decode_step: Callable
    prefill_chunk: Callable | None
    state_spec: Callable[[ModelConfig], StateSpec]


def _shared(family: str, prefillable: bool) -> ModelProtocol:
    return ModelProtocol(
        family=family,
        init_state=_tf.init_cache,
        decode_step=_tf.decode_step,
        prefill_chunk=_tf.prefill_chunk if prefillable else None,
        state_spec=state_spec,
    )


# every current family composes the shared assembler; prefill_chunk is
# advertised only by the families whose patterns CAN be all-attention
# (the per-config gate stays can_prefill — e.g. a vlm config with cross
# layers steps down even though the family advertises prefill)
FAMILY_PROTOCOLS: dict[str, ModelProtocol] = {
    "dense": _shared("dense", prefillable=True),
    "moe": _shared("moe", prefillable=True),
    "vlm": _shared("vlm", prefillable=True),
    "audio": _shared("audio", prefillable=False),   # enc-dec memory
    "ssm": _shared("ssm", prefillable=False),
    "hybrid": _shared("hybrid", prefillable=False),
}


def get_protocol(cfg: ModelConfig) -> ModelProtocol:
    try:
        return FAMILY_PROTOCOLS[cfg.family]
    except KeyError:
        raise KeyError(
            f"no model protocol registered for family {cfg.family!r} "
            f"(config {cfg.name!r}): known families are "
            f"{sorted(FAMILY_PROTOCOLS)}") from None


def can_prefill(cfg: ModelConfig) -> bool:
    """True when the teacher-forced block pass is bit-identical to the
    sequential step scan for this config (all-self-attention patterns)."""
    return (get_protocol(cfg).prefill_chunk is not None
            and _tf.can_prefill(cfg))


# ---------------------------------------------------------------------------
# the dispatching module-level surface (what serve/ imports)
# ---------------------------------------------------------------------------

def init_state(cfg: ModelConfig, batch: int, max_len: int):
    """Fresh all-zeros serving state: ``(reps, batch, ...)`` leaves."""
    return get_protocol(cfg).init_state(cfg, batch, max_len)


def decode_step(params, state, token, pos, cfg: ModelConfig, memory=None):
    """One serving step: token (B, 1) -> (logits (B, Vpad), state')."""
    return get_protocol(cfg).decode_step(params, state, token, pos, cfg,
                                         memory=memory)


def prefill_chunk(params, state, tokens, pos0, n_valid, cfg: ModelConfig):
    """Teacher-forced block chunk — named error when the family can't."""
    if not can_prefill(cfg):
        raise PrefillUnsupportedError(
            f"config {cfg.name!r} (family {cfg.family!r}, kinds "
            f"{state_spec(cfg).kinds}) carries sequential state: "
            "prefill_chunk would not be bit-identical to the decode_step "
            "scan — run the sequential step program instead")
    return get_protocol(cfg).prefill_chunk(params, state, tokens, pos0,
                                           n_valid, cfg)


def recurrent_state_tree(state):
    """Bool pytree over ``state``: True on recurrent leaves, False on ring.

    Classification is by state *pytree path*, not by config: the block
    caches key their recurrent leaves under ``"ssm"`` / ``"rec"`` dicts
    (``models.transformer.init_block_cache``), and KV rings under
    ``"kv"``.  The engine maps this tree against old/new state to freeze
    inactive rows' recurrent leaves (ring leaves keep the zero-cost
    clamped-position trick — see ``serve.engine._chunk_body``), and
    ``models.transformer.stage_decode`` splits a stage's state by it: ring
    leaves ride the layer scan's carry, recurrent leaves its ``xs``.
    """
    from jax.tree_util import DictKey, tree_map_with_path

    def classify(path, _leaf):
        return any(isinstance(k, DictKey) and k.key in _RECURRENT_KINDS
                   for k in path)

    return tree_map_with_path(classify, state)


def has_recurrent_state(state) -> bool:
    return any(jax.tree.leaves(recurrent_state_tree(state)))
