"""The readers of the program's own trace (``bench/scopes.py`` and the nine
metrics on it): the join of trace operations to the engine's scope map,
self time at the innermost operation, the host spans, and the readers on
the tiny cells and on a program that records none of this."""

from __future__ import annotations

import random
import types

import pytest

import common
import scopes
import trace_reduce
from conftest import BENCH, run_tiny

NEW = {"pimc-restore": ["model_ms.restore", "spc_ms.restore",
                        "unscoped_ms.restore", "host_self_ms.restore",
                        "spec_hit_share.restore"],
       "pimc-ingest": ["model_ms.ingest", "spc_ms.ingest",
                       "unscoped_ms.ingest", "host_self_ms.ingest"]}


def _ev(name, start, end, **stats):
    return trace_reduce.Event(name, float(start), float(end - start), stats)


def _nested_trace():
    """One run of the step program holding a ``while`` over its body, an
    encode program whose instruction shares a name with the step's, and an
    operation the scope map does not know."""
    ops = [_ev("%while.1 = (s32[]) while(%t)", 100, 590),
           _ev("%fusion.2 = f32[8] fusion(%a)", 120, 220),
           _ev("%copy.3 = f32[8] copy(%b)", 220, 260),
           _ev('%rans_decode_step.4 = (u32[1,8]) custom-call(%c), '
               'custom_call_target="tpu_custom_call"', 300, 320),
           _ev("%fusion.5 = f32[8] fusion(%d)", 330, 400),
           _ev("%call.7 = f32[8] call(%e)", 400, 500),
           _ev("%fusion.8 = s32[8,4] fusion(%f)", 420, 480),
           _ev("%mystery.9 = f32[8] add(%g)", 590, 592),
           _ev("%fusion.2 = u8[8] fusion(%h)", 710, 790)]
    modules = [_ev("jit__unknown(7)", 100, 600),
               _ev("jit__encode_rows(9)", 700, 800)]
    return trace_reduce.Trace(ops=ops, modules=modules, host=[],
                              window=(0.0, 1000.0), n_devices=1)


SCOPE_MAP = {"while.1": None, "fusion.2": "model", "copy.3": None,
             "rans_decode_step.4": "decode_kernel", "fusion.5": "spc",
             "call.7": None, "fusion.8": "topk"}


def test_instruction_names():
    assert scopes.instruction("%fusion.247 = f32[4] fusion(%x)") == \
        "fusion.247"
    assert scopes.instruction("wrapped_reduce-window.1") == \
        "wrapped_reduce-window.1"
    assert scopes.instruction("") == ""


def test_self_time_counts_each_nanosecond_once_at_the_innermost_op():
    assert scopes.self_times([(0, 10), (2, 4), (5, 15)]) == [3, 2, 10]
    rng = random.Random(3)
    iv = [(a, a + rng.uniform(0, 30)) for a in
          (rng.uniform(0, 100) for _ in range(200))]
    union = sum(b - a for a, b in trace_reduce.merge(iv))
    assert sum(scopes.self_times(iv)) == pytest.approx(union)


def test_scope_join_on_a_nested_trace():
    secs, unscoped = scopes.scope_seconds(_nested_trace(), SCOPE_MAP)
    ns = {k: round(v * 1e9) for k, v in secs.items()}
    # the while's self time is what its body's operations leave: 490 less
    # 100 + 40 + 20 + 70 + 100; the call's, 100 less its fusion's 60; the
    # encode program's fusion.2 is not the step's
    assert ns == {None: 160 + 40 + 40, "model": 100, "decode_kernel": 20,
                  "spc": 70, "topk": 60, scopes.UNMAPPED: 2}
    assert {k: round(v * 1e9) for k, v in unscoped.items()} == \
        {"while.1": 160, "copy.3": 40, "call.7": 40}


def test_scope_join_reads_cpu_ops_by_module_name():
    tr = _nested_trace()
    tr.ops = [_ev("fusion.2", 0, 50, hlo_module="jit__unknown"),
              _ev("copy.3", 50, 60, hlo_module="jit__unknown"),
              _ev("fusion.2", 60, 90, hlo_module="jit__encode_rows")]
    secs, _ = scopes.scope_seconds(tr, SCOPE_MAP)
    assert {k: round(v * 1e9) for k, v in secs.items()} == \
        {"model": 50, None: 10}


class _Eng:
    def __init__(self, maps=None, trace=None):
        self._maps = maps
        self.trace = trace

    def scope_maps(self):
        return self._maps


def _ctx(eng, trace=None, **counters):
    probe = common.Probe()
    probe.t_open, probe.t_close = 10.0, 20.0
    return types.SimpleNamespace(counters=dict(counters), trace=trace,
                                 system=types.SimpleNamespace(eng=eng),
                                 probe=probe)


def test_cycle_split_per_cycle_and_its_silences():
    ctx = _ctx(_Eng({"step": SCOPE_MAP}), _nested_trace(),
               **{"window.cycles": 2, "window.step_launches": 2})
    split = scopes.cycle_split_ms(ctx, "step")
    assert split["model"] == pytest.approx(100e-9 / 2 * 1e3)
    assert ctx.counters["scope.step.topk_ms"] == pytest.approx(60e-9 / 2e-3)
    assert ctx.counters["scope.step.unscoped_top.while.1_ms"] == \
        pytest.approx(160e-9 / 2e-3)
    # both cycle programs ran in the window: they share one module name
    ctx = _ctx(_Eng({"step": SCOPE_MAP}), _nested_trace(),
               **{"window.cycles": 2, "window.step_launches": 1,
                  "window.prefill_launches": 1})
    assert scopes.cycle_split_ms(ctx, "step") is None
    # a map that does not describe what ran
    ctx = _ctx(_Eng({"step": {"fusion.2": "model"}}), _nested_trace(),
               **{"window.cycles": 2})
    assert scopes.cycle_split_ms(ctx, "step") is None


def _span(name, sid, parent, start, end, **attrs):
    from repro.serve.trace import Span
    return Span(name, start, end, sid, parent, 0, attrs)


def _host_trace(maxlen=64):
    from repro.serve.trace import EngineTrace
    tr = EngineTrace(maxlen=maxlen)
    tr.spans.extend([
        # a cycle that ended before the window
        _span("engine.finalize.wait", 1, 2, 1.0, 8.0),
        _span("engine.finalize.cycle", 2, 0, 0.9, 8.1, decoded=99,
              hits=99),
        _span("engine.cycle", 0, None, 0.5, 8.2, rids=[0]),
        # one that ended in it: 11.0 s of wall, 10.0 s waiting
        _span("engine.build", 4, 3, 9.0, 9.2),
        _span("engine.finalize.wait", 6, 5, 9.5, 15.5),
        _span("engine.finalize.wait", 7, 5, 15.6, 19.6),
        _span("engine.finalize.cycle", 5, 3, 9.4, 19.8, decoded=40,
              hits=10),
        _span("engine.cycle", 3, None, 9.0, 20.0, rids=[0]),
    ])
    return tr


def test_host_self_time_and_hit_share_from_the_engine_spans():
    ctx = _ctx(_Eng(trace=_host_trace()), **{"window.cycles": 1})
    assert scopes.host_self_ms(ctx) == pytest.approx(1000.0)
    assert scopes.spec_hit_share(ctx) == pytest.approx(25.0)
    # the ring dropped spans of the window: nothing is read
    ctx = _ctx(_Eng(trace=_host_trace(maxlen=4)), **{"window.cycles": 1})
    assert scopes.host_self_ms(ctx) is None
    assert scopes.spec_hit_share(ctx) is None


@pytest.mark.parametrize("cell", sorted(NEW))
def test_readers_are_silent_on_a_program_without_the_trace(cell):
    ctx = _ctx(types.SimpleNamespace(), _nested_trace(),
               **{"window.cycles": 2, "window.step_launches": 2,
                  "window.prefill_launches": 0, "window.symbols": 8})
    for name in NEW[cell]:
        reader = common.load_module(BENCH / "metrics" / f"{name}.py")
        assert reader.read(ctx) is None, name


@pytest.fixture(scope="module")
def tiny_traced(tiny_root):
    return {cell: run_tiny(tiny_root, cell, trace=True) for cell in NEW}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_the_new_readers_read_on_the_tiny_cells(tiny_root, tiny_traced,
                                                cell):
    import run
    r = tiny_traced[cell]
    assert r["correct"], r["checks"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(NEW[cell]) <= set(m)
    assert not set(NEW[cell]) & set(r["per_layer_missing"])
    sfx = cell.split("-")[1]
    assert m[f"host_self_ms.{sfx}"] > 0
    if sfx == "restore":
        assert 0 <= m["spec_hit_share.restore"] <= 100
    # the split closes: every scope, unscoped time included, adds up to
    # the cycle program's busy time per cycle
    counters = r["_notes"]["counters"]
    program = "step" if sfx == "restore" else "prefill"
    split = {k: v for k, v in counters.items()
             if k.startswith(f"scope.{program}.") and "unscoped_top" not in k}
    assert split[f"scope.{program}.model_ms"] == m[f"model_ms.{sfx}"]
    assert split[f"scope.{program}.unscoped_ms"] == m[f"unscoped_ms.{sfx}"]
    tr = trace_reduce.load(trace_reduce.find_xplane(
        str(run.TRACE_DIR / cell)))
    busy = sum(b - a for a, b in trace_reduce.merge(trace_reduce.clip(
        scopes.program_ops(tr), tr.window))) * 1e-6
    assert sum(split.values()) == pytest.approx(
        busy / counters["window.cycles"], rel=1e-6)
