"""The step program's KV-ring write (``models.attention._ring_write``).

Per-row positions (the serve engine's step program) scatter one slot per
row into the ring the layer and step scans carry; a scalar position keeps
the masked select over the layer's slots.  These tests pin the two writes
to each other bit for bit, across a ring wrap, and pin the structure that
makes the per-row write cheap: inside the compiled step program's loops no
operation copies, selects, broadcasts or slab-writes the stacked ring.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import coder
from repro.models import abstract_model, decode_step, init_model, init_state
from repro.serve.engine import _chunk_body, _compiled_program

# a plain full-attention ring and a sliding-window ring (attn_moe blocks)
RING_CFGS = {"ras-pimc": 8, "mixtral-8x22b": 32}      # arch -> max_len
BATCH, EXTRA = 3, 6                                   # steps past the wrap


def _ring_len(state) -> int:
    return jax.tree.leaves(state)[0].shape[2]


def _bits(a) -> bytes:
    return np.asarray(a).tobytes()


@pytest.fixture(scope="module", params=sorted(RING_CFGS))
def model(request):
    cfg = get_smoke_config(request.param)
    params = init_model(cfg, jax.random.PRNGKey(5))
    step = jax.jit(lambda p, s, tok, pos: decode_step(p, s, tok, pos, cfg))
    state = init_state(cfg, BATCH, RING_CFGS[request.param])
    n_steps = _ring_len(state) + EXTRA
    toks = jax.random.randint(jax.random.PRNGKey(7), (n_steps, BATCH, 1), 0,
                              cfg.vocab_size, jnp.int32)
    return step, params, state, toks


def test_scalar_and_per_row_writes_bit_identical(model):
    """The same position as a scalar and as a (B,) vector: the same logits
    and the same ring, bit for bit, at every step through the wrap."""
    step, params, state, toks = model
    s_scalar = s_rows = state
    for t in range(toks.shape[0]):
        lg_s, s_scalar = step(params, s_scalar, toks[t], jnp.int32(t))
        lg_r, s_rows = step(params, s_rows, toks[t],
                            jnp.full((BATCH,), t, jnp.int32))
        assert _bits(lg_s) == _bits(lg_r), f"logits differ at step {t}"
        for a, b in zip(jax.tree.leaves(s_scalar), jax.tree.leaves(s_rows)):
            assert _bits(a) == _bits(b), f"ring differs at step {t}"


def test_rows_at_different_slots_match_scalar_runs(model):
    """Rows at different positions (their rings wrap at different steps)
    in one per-row run: each row equals that row of a scalar run at its
    own positions."""
    step, params, state, toks = model
    offs = np.arange(BATCH, dtype=np.int32) * 3
    n = toks.shape[0]
    rows_lg, s = [], state
    for t in range(n):
        lg, s = step(params, s, toks[t], jnp.asarray(offs + t))
        rows_lg.append(lg)
    for r, off in enumerate(offs):
        ref = state
        for t in range(n):
            lg, ref = step(params, ref, toks[t], jnp.int32(off + t))
            assert _bits(lg[r]) == _bits(rows_lg[t][r]), (r, t)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(s)):
            assert _bits(a[:, r]) == _bits(b[:, r]), f"row {r} ring differs"


# -- structure of the compiled step program ---------------------------------

_COMP = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INST = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+\[[\d,]*\])\S* ([\w-]+)\(")
_CALLS = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.-]+)")
_BODY = re.compile(r"body=%([\w.-]+)")


def _computations(text: str) -> dict[str, list[str]]:
    comps, name = {}, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None and line.strip() not in ("", "}"):
            comps[name].append(line)
    return comps


def _loop_computations(comps: dict[str, list[str]]) -> set[str]:
    """Every computation a while body runs, nested loops and fusions too."""
    todo = [b for lines in comps.values() for ln in lines
            for b in _BODY.findall(ln)]
    seen: set[str] = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        todo += [d for ln in comps[c] for d in _CALLS.findall(ln)]
    return seen


def _step_program_hlo():
    """The engine's step program for ras-pimc SMOKE at 8 rows, max_len 64,
    chunk 16, compiled for this backend; returns (HLO text, ring shape)."""
    cfg = get_smoke_config("ras-pimc")
    rows, max_len, chunk = 8, 64, 16
    cache = jax.eval_shape(lambda: init_state(cfg, rows, max_len))
    i32, sds = jnp.int32, jax.ShapeDtypeStruct
    args = (abstract_model(cfg), cache, sds((rows, 1), i32),
            sds((rows,), bool), sds((rows,), i32), sds((rows,), i32),
            sds((rows,), i32), sds((rows, chunk), i32),
            sds((rows, coder.default_cap(chunk)), jnp.uint8),
            sds((rows,), i32))
    prog = _compiled_program(_chunk_body, cfg, chunk, 14, 4, "kernel")
    ring = jax.tree.leaves(cache)[0]
    shape = f"f32[{','.join(map(str, ring.shape))}]"
    return prog.lower(*args).compile().as_text(), shape


def test_step_program_writes_ring_only_by_scatter():
    """Inside the step program's loops, the only operations that make a
    value of the stacked ring's shape are the K and V scatters (and the
    fusions that wrap them): no copy of the carry, no whole-ring select,
    no broadcast of a fresh ring, no slab written into a restacked one."""
    text, ring = _step_program_hlo()
    comps = _computations(text)
    loops = _loop_computations(comps)
    assert loops, "no loop in the step program"
    made, roots = [], {}
    for c in loops:
        for ln in comps[c]:
            m = _INST.match(ln)
            if m and ln.lstrip().startswith("ROOT"):
                roots[c] = m.group(3)
            if m and m.group(2) == ring:
                made.append((c, m.group(1), m.group(3), ln))
    assert made, f"no {ring} operation in the loops"
    for c, name, op, ln in made:
        assert op not in ("copy", "select", "broadcast",
                          "dynamic-update-slice"), f"{c}: {name} = {op}"
        assert op in ("parameter", "get-tuple-element", "bitcast",
                      "scatter", "fusion"), f"{c}: {name} = {op}"
        if op == "fusion":
            called = _CALLS.search(ln).group(1)
            assert roots.get(called) == "scatter", \
                f"{c}: {name} fuses a {roots.get(called)} into the ring"
    scatters = [n for _, n, op, _ in made if op == "scatter"]
    assert len(scatters) == 2, scatters           # one for K, one for V
