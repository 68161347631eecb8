"""The batched engine's own trace (``repro.serve.trace``): host spans that
nest as documented, counts that add up to the results, a bounded ring,
named scopes in the compiled cycle programs, and bytes that do not change
when a profile is active."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.predictors import model_topk_candidates
from repro.data.pipeline import token_stream
from repro.models import init_model
from repro.serve.engine import (BOS, SCOPES, BatchEngine,
                                EngineQueueFullError, teacher_forced_scan)
from repro.serve.trace import EngineTrace, hlo_scopes

CFG = get_smoke_config("ras-pimc")
LANES, CHUNK, MAX_LEN, TOPK = 4, 8, 32, 4
LENGTHS = (20, 13, 9)

# the parent of each span the run loop records
PARENT = {"engine.cycle": None, "engine.admit": "engine.cycle",
          "engine.build": "engine.cycle",
          "engine.launch.step": "engine.cycle",
          "engine.launch.prefill": "engine.cycle",
          "engine.finalize.cycle": "engine.cycle",
          "engine.finalize.wait": "engine.finalize.cycle",
          "engine.finalize.encode": "engine.finalize.cycle",
          "engine.retire.pack": "engine.finalize.cycle",
          "engine.submit.parse": None}


@pytest.fixture(scope="module")
def params():
    return init_model(CFG, jax.random.PRNGKey(2))


def _tokens(t_len, seed):
    return np.asarray(token_stream(CFG.vocab_size, (LANES, t_len),
                                   seed=seed), np.int32)


def _engine(params, **kw):
    return BatchEngine(params, CFG, slots=2, lanes=LANES, chunk_size=CHUNK,
                       max_len=MAX_LEN, topk=TOPK, **kw)


def _roundtrip(eng, toks):
    """Compress ``toks`` (prefill program), then decompress the blobs (step
    program), on the wall clock; returns both results and the spans of
    each run."""
    rids = [eng.submit_compress(t) for t in toks]
    comp = eng.run(clock="wall")
    t0_comp = eng.trace.t0
    split = max(s.sid for s in eng.trace.spans)
    blobs = [comp[r].blob for r in rids]
    drids = [eng.submit_decompress(b) for b in blobs]
    dec = eng.run(clock="wall")
    spans = list(eng.trace.spans)
    return dict(rids=rids, comp=comp, drids=drids, dec=dec,
                comp_spans=[s for s in spans if s.sid <= split],
                dec_spans=[s for s in spans if s.sid > split],
                t0_comp=t0_comp, t0_dec=eng.trace.t0)


@pytest.fixture(scope="module")
def traced(params):
    eng = _engine(params)
    toks = [_tokens(n, 3 + i) for i, n in enumerate(LENGTHS)]
    out = _roundtrip(eng, toks)
    for r in out["rids"]:
        assert out["comp"][r].ok, out["comp"][r].error
    for r, t in zip(out["drids"], toks):
        assert out["dec"][r].ok, out["dec"][r].error
        np.testing.assert_array_equal(out["dec"][r].tokens, t)
    return eng, toks, out


def test_span_tree_nests_as_specified(traced):
    eng, _, out = traced
    spans = out["comp_spans"] + out["dec_spans"]
    by_sid = {s.sid: s for s in spans}
    names = {s.name for s in spans}
    assert names == set(PARENT)
    # none takes a name of the benchmark's own spans around the engine
    assert not names & {"engine.build_cycle", "engine.launch",
                        "engine.finalize"}
    for s in spans:
        assert s.name.startswith("engine.") and s.end >= s.start
        want = PARENT[s.name]
        if want is None:
            assert s.parent is None, s
            continue
        parent = by_sid[s.parent]
        assert parent.name == want, (s, parent)
        assert parent.start <= s.start and s.end <= parent.end
        assert s.cycle == parent.cycle
    # each cycle that launched names the requests it serves, and every
    # request is served by some cycle of its run
    for run, rids in (("comp_spans", out["rids"]),
                      ("dec_spans", out["drids"])):
        served = set()
        for c in (s for s in out[run] if s.name == "engine.cycle"):
            kids = [k for k in out[run] if k.parent == c.sid]
            launched = any(k.name.startswith("engine.launch.")
                           for k in kids)
            assert bool(c.attrs["rids"]) == launched
            served |= set(c.attrs["rids"])
        assert served == set(rids)
    assert {s.name for s in out["comp_spans"]} >= {"engine.launch.prefill"}
    assert {s.name for s in out["dec_spans"]} >= {"engine.launch.step"}


def test_admissions_and_counters(traced):
    eng, _, out = traced
    admitted = [rid for rid, _slot, _cyc in eng.trace.admissions]
    assert admitted == out["rids"] + out["drids"]
    # the third request of each run waits for a freed slot
    cycles = [cyc for _rid, _slot, cyc in eng.trace.admissions]
    assert cycles[:2] == [0, 0] and cycles[2] > 0
    n = 2 * len(LENGTHS)
    c = eng.trace.counters
    assert c["requests.admitted"] == c["requests.retired"] == n
    assert c["requests.failed"] == c["requests.rejected"] == 0
    assert c["cycles.prefill"] == eng.prefill_cycles > 0
    assert c["cycles.step"] == sum(
        1 for s in out["dec_spans"] if s.name == "engine.launch.step")


def test_span_counts_sum_to_the_results(traced):
    _, _, out = traced
    for run, res, rids in (("comp_spans", "comp", "rids"),
                           ("dec_spans", "dec", "drids")):
        fin = [s for s in out[run] if s.name == "engine.finalize.cycle"]
        results = [out[res][r] for r in out[rids]]
        assert sum(s.attrs["symbols"] for s in fin) == \
            LANES * sum(r.n_symbols for r in results)
        assert sum(s.attrs["probes"] for s in fin) == \
            sum(r.probes for r in results)
    fin = [s for s in out["comp_spans"] if s.name == "engine.finalize.cycle"]
    assert sum(s.attrs["decoded"] + s.attrs["hits"] for s in fin) == 0
    fin = [s for s in out["dec_spans"] if s.name == "engine.finalize.cycle"]
    assert sum(s.attrs["decoded"] for s in fin) == \
        LANES * sum(LENGTHS)


def test_speculation_hits_equal_a_direct_count(params, traced):
    """A hit (at most ``topk`` probes) is a decoded symbol among the model's
    top-k candidates: count those from the model's own logits."""
    _, toks, out = traced
    want = 0
    for t in toks:
        inputs = jnp.asarray(np.concatenate(
            [np.full((LANES, 1), BOS, np.int32), t[:, :-1]], axis=1))
        _, logits = teacher_forced_scan(params, CFG, inputs, MAX_LEN)
        cands = jax.vmap(lambda lg: model_topk_candidates(
            lg[:, :CFG.vocab_size], TOPK))(logits)      # (T, lanes, topk)
        want += int((np.asarray(cands) == t.T[:, :, None]).any(-1).sum())
    fin = [s for s in out["dec_spans"] if s.name == "engine.finalize.cycle"]
    assert sum(s.attrs["hits"] for s in fin) == want > 0


def test_completed_at_is_stamped_after_the_pack(traced):
    _, _, out = traced
    for run, res, t0 in (("comp_spans", "comp", "t0_comp"),
                         ("dec_spans", "dec", "t0_dec")):
        packs = {s.attrs["rid"]: s for s in out[run]
                 if s.name == "engine.retire.pack"}
        assert set(packs) == set(out[res])
        for rid, sp in packs.items():
            assert out[res][rid].completed_at + out[t0] >= sp.end


def test_failures_and_rejections_are_counted(params):
    eng = _engine(params, max_queue=2)
    eng.submit_compress(_tokens(16, 30), cap=5)
    eng.submit_compress(_tokens(16, 31))
    with pytest.raises(EngineQueueFullError):
        eng.submit_compress(_tokens(16, 32))
    res = eng.run()
    assert sum(not r.ok for r in res.values()) == 1
    c = eng.trace.counters
    assert (c["requests.failed"], c["requests.rejected"],
            c["requests.retired"]) == (1, 1, 2)
    # a failed request is packed never
    assert sum(s.name == "engine.retire.pack" for s in eng.trace.spans) == 1


def test_ring_stays_bounded(params):
    tr = EngineTrace(maxlen=4)
    for i in range(10):
        with tr.span("engine.x", i=i):
            pass
    assert [s.attrs["i"] for s in tr.spans] == [6, 7, 8, 9]
    eng = _engine(params)
    eng.trace = EngineTrace(maxlen=16)
    rids = [eng.submit_compress(_tokens(n, 40 + n)) for n in LENGTHS]
    res = eng.run()
    assert all(res[r].ok for r in rids)
    assert len(eng.trace.spans) == 16 and eng.trace.spans[-1].sid > 16
    assert eng.trace.counters["requests.retired"] == len(rids)


def test_profile_active_leaves_bytes_identical(params, traced, tmp_path):
    """A profile around the engine records its spans and changes no byte."""
    _, toks, out = traced
    eng = _engine(params)
    with jax.profiler.trace(str(tmp_path)):
        prof = _roundtrip(eng, toks)
    for r, rp in zip(out["rids"], prof["rids"]):
        assert prof["comp"][rp].blob == out["comp"][r].blob
    for r, rp in zip(out["drids"], prof["drids"]):
        np.testing.assert_array_equal(prof["dec"][rp].tokens,
                                      out["dec"][r].tokens)
    from jax.profiler import ProfileData
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events}
    assert {"engine.cycle", "engine.finalize.wait",
            "engine.launch.step"} <= names


def test_scope_maps_name_each_layer_after_release(params, traced):
    eng, _, _ = traced
    fresh = _engine(params)
    rid = fresh.submit_compress(_tokens(12, 60))
    blob = fresh.run()[rid].blob
    fresh.submit_decompress(blob)
    fresh.run()
    # works with the params and programs dropped, as after a benchmark
    del fresh._prog, fresh._prog_prefill, fresh._cache
    fresh.params = None
    maps = fresh.scope_maps()
    assert set(maps) == {"step", "prefill"}
    assert set(maps["step"].values()) == set(SCOPES) | {None}
    assert set(maps["prefill"].values()) == {"model", "spc", None}
    assert fresh.scope_maps() == maps


def test_hlo_scopes_reads_metadata_and_fused_roots():
    text = """HloModule jit__unknown, entry_computation_layout={()->f32[]}

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %exp.1 = f32[4]{0} exponential(%p), metadata={op_name="jit(f)/while/body/closed_call/spc/exp"}
}

%body (c: f32[4]) -> f32[4] {
  %c = f32[4]{0} parameter(0)
  %dot.2 = f32[4]{0} dot(%c, %c), metadata={op_name="jit(f)/while/body/model/attn/dot_general"}
  %fusion.3 = f32[4]{0} fusion(%dot.2), kind=kLoop, calls=%fused_computation
  ROOT %copy.4 = f32[4]{0} copy(%fusion.3)
}

ENTRY %main.5 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  ROOT %while.6 = f32[4]{0} while(%a), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
}
"""
    m = hlo_scopes(text, SCOPES)
    assert m["dot.2"] == "model"
    assert m["fusion.3"] == "spc"         # from its fused computation's root
    assert m["copy.4"] is None and m["while.6"] is None
    assert m["exp.1"] == "spc"
