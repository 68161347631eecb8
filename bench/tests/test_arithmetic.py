"""The benchmark's own arithmetic: windows, orders, costs,
and the trace reduction's interval bookkeeping."""

from __future__ import annotations

import hashlib
import types

import numpy as np
import pytest

import common
import costs
import engine_probe
import generator
import trace_reduce as T


def _launches(times, seconds):
    """Feed launch times to a window; returns it and the launches inside."""
    win = common.LaunchWindow(common.Probe(), seconds)
    inside = []
    for t in times:
        if win.launch(t):
            return win, inside
        inside.append(t)
    raise ValueError("the launches end before the window closes")


def test_launch_window_counts_the_launches_before_the_closing_one():
    # a cycle every 4 s; opens at t=1, closes at the first launch at or
    # after 1 + 10 = 11, i.e. t=13, which is not made: 3 cycles in 12 s
    win, inside = _launches([1.0, 5.0, 9.0, 13.0, 17.0], 10.0)
    assert inside == [1.0, 5.0, 9.0]
    assert (win.probe.t_open, win.probe.t_close) == (1.0, 13.0)
    win.work = 10 * len(inside)
    assert win.rate() == pytest.approx(30 / 12)


def test_launch_window_stall_lowers_rate():
    win, inside = _launches([0.0, 1.0, 2.0, 10.0], 3.0)
    assert inside == [0.0, 1.0, 2.0] and win.probe.t_close == 10.0
    win.work = 5 * len(inside)
    assert win.rate() == pytest.approx(15 / 10)


def test_closed_order_same_seed_same_order():
    t = {"pool": 4}
    a = generator.closed_order(t, 2**31 + 9)
    b = generator.closed_order(t, 2**31 + 9)
    first = [next(a) for _ in range(8)]
    assert first == [next(b) for _ in range(8)]
    assert sorted(first[:4]) == [0, 1, 2, 3] and first[:4] == first[4:]


@pytest.mark.parametrize("t,shape,dtype", [
    ({"pool": 2, "image": [16, 8, 3]}, (16, 8, 3), np.uint8),
    ({"pool": 2, "tokens": {"length": 96, "zipf": 1.0}}, (96,), np.int32),
], ids=["image", "tokens"])
def test_inputs_depend_on_seed_only(t, shape, dtype):
    a = generator.inputs(t, 5, 512)
    assert all(np.array_equal(x, y)
               for x, y in zip(a, generator.inputs(t, 5, 512)))
    assert not np.array_equal(a[0], generator.inputs(t, 6, 512)[0])
    assert not np.array_equal(a[0], a[1])
    assert a[0].shape == shape and a[0].dtype == dtype


# sha256 of the pool of each image mix at two seeds, as the generator made
# them before token mixes existed: the image cells read the same inputs
IMAGE_POOLS = {
    1: "2159c51296b8c91728e269e616f47b0d7199aad6a2213d6d26ea90d63229918b",
    2**31 + 5:
        "df3201369f18eae76dba03be9e0b28dc86bec772194141ebafce23debf6a6ef8",
}


@pytest.mark.parametrize("seed", sorted(IMAGE_POOLS))
@pytest.mark.parametrize("mix", ["restore", "ingest"])
def test_image_inputs_are_pinned(mix, seed):
    t = common.load_json(common.BENCH / "traffic" / f"{mix}.json")
    h = hashlib.sha256()
    # the alphabet is the image model's; image mixes ignore it
    for x in generator.inputs(t, seed, 256):
        h.update(str(x.shape).encode())
        h.update(str(x.dtype).encode())
        h.update(x.tobytes())
    assert h.hexdigest() == IMAGE_POOLS[seed]


@pytest.mark.parametrize("alphabet", [2, 512, 100_352])
def test_token_ids_lie_in_the_alphabet(alphabet):
    t = {"pool": 3, "tokens": {"length": 4096, "zipf": 0.7}}
    for x in generator.inputs(t, 2**33 + 1, alphabet):
        assert x.shape == (4096,) and x.dtype == np.int32
        assert 0 <= x.min() and x.max() < alphabet


@pytest.mark.parametrize("zipf", [0.8, 1.0, 1.3])
def test_top_ranked_share_follows_zipf(zipf):
    alphabet, n = 512, 4 * 8192
    t = {"pool": 4, "tokens": {"length": n // 4, "zipf": zipf}}
    ids = np.concatenate(generator.inputs(t, 2**31 + 11, alphabet))
    counts = np.bincount(ids, minlength=alphabet)
    p = generator.zipf_probs(alphabet, zipf)[0]
    assert p == pytest.approx(
        1 / sum(r ** -zipf for r in range(1, alphabet + 1)))
    share = counts.max() / n
    # the most frequent id is the top-ranked one (rank 2 has p / 2**zipf);
    # its share lies within five standard errors of the law's
    assert abs(share - p) < 5 * np.sqrt(p * (1 - p) / n)
    # every seed's ranking is its own permutation of the alphabet
    other = np.concatenate(generator.inputs(t, 2**31 + 12, alphabet))
    assert np.bincount(other, minlength=alphabet).argmax() != counts.argmax()


# a length law by its quantiles: median 192, a tail to 4,096
LAW = {"quantiles": [[0, 16], [0.5, 192], [0.9, 1024], [1, 4096]]}


def test_lengths_follow_the_law_and_the_multiple():
    assert generator.lengths(100, 3, 8) == [104, 104, 104]
    assert generator.lengths(96, 2, 8) == [96, 96]
    sizes = generator.lengths(LAW, 10)
    # item i at quantile (i + 0.5) / 10: 0.05 lies a tenth of the way from
    # 16 to 192 in log, 0.5 is 192, 0.95 halfway from 1,024 to 4,096
    assert sizes[0] == round(16 * (192 / 16) ** 0.1)
    assert sizes[5] == round(192 * (1024 / 192) ** 0.125)
    assert sizes[9] == 2048
    assert sizes == sorted(sizes) and len(set(sizes)) == 10
    assert generator.lengths(LAW, 10, 4) == [-(-n // 4) * 4 for n in sizes]


def test_token_inputs_take_their_lengths_from_the_law():
    t = {"pool": 10, "tokens": {"length": LAW, "zipf": 1.0, "source": "t"}}
    for seed in (3, 2**31 + 3):
        got = [x.size for x in generator.inputs(t, seed, 512, 4)]
        # the same sizes for every seed; only the ids and the order differ
        assert got == generator.lengths(LAW, 10, 4)


BASE_MIX = {"loop": "closed", "kind": "compress", "pool": 1, "clients": 1}


def _tokens(**kw):
    return {"tokens": dict({"zipf": 1.0, "length": 64, "source": "test"},
                           **kw)}


@pytest.mark.parametrize("inputs", [
    {"image": [8, 8, 3]},
    _tokens(length=1),
    _tokens(length=4096, zipf=0.05),
    _tokens(length=LAW),
    _tokens(length={"quantiles": [[0, 64], [1.0, 64]]}),
], ids=["image", "tokens-short", "tokens-flat", "tokens-law",
        "tokens-law-flat"])
def test_check_takes_one_input_kind(inputs):
    generator.check(dict(BASE_MIX, **inputs))


def _without(key):
    t = _tokens()
    del t["tokens"][key]
    return t


@pytest.mark.parametrize("inputs,why", [
    ({}, "exactly one input kind"),
    (dict(_tokens(), image=[8, 8, 3]), "exactly one input kind"),
    (_tokens(length=0), "length"),
    (_tokens(length=-8), "length"),
    (_tokens(length=64.0), "length"),
    (_tokens(length=True), "length"),
    (_tokens(length="64"), "length"),
    (_without("length"), "length"),
    (_tokens(length={"quantiles": [[0, 64]]}), "quantiles"),
    (_tokens(length={"quantiles": [[0.1, 64], [1, 128]]}), "quantiles"),
    (_tokens(length={"quantiles": [[0, 64], [0.9, 128]]}), "quantiles"),
    (_tokens(length={"quantiles": [[0, 64], [0.5, 96], [0.5, 128],
                                   [1, 256]]}), "quantiles"),
    (_tokens(length={"quantiles": [[0, 128], [1, 64]]}), "quantiles"),
    (_tokens(length={"quantiles": [[0, 0], [1, 64]]}), "quantiles"),
    (_tokens(length={"quantiles": [[0, 64], [1, 64.5]]}), "quantiles"),
    (_tokens(length={"quantiles": [[0, 64, 1], [1, 128]]}), "quantiles"),
    (_tokens(length={"median": 64}), "quantiles"),
    (_tokens(zipf=0), "zipf"),
    (_tokens(zipf=-1.0), "zipf"),
    (_tokens(zipf=float("nan")), "zipf"),
    (_without("zipf"), "zipf"),
    (_without("source"), "source"),
    (_tokens(source=""), "source"),
    (_tokens(source="two\nlines"), "source"),
    ({"tokens": [64, 1.0]}, "zipf"),
], ids=["neither", "both", "length-0", "length-negative", "length-float",
        "length-bool", "length-str", "length-missing", "law-one-point",
        "law-from-above-0", "law-to-below-1", "law-q-repeats",
        "law-n-falls", "law-n-0", "law-n-float", "law-row-of-3",
        "law-no-quantiles", "zipf-0", "zipf-negative", "zipf-nan",
        "zipf-missing", "source-missing", "source-empty",
        "source-two-lines", "tokens-list"])
def test_check_refuses_a_malformed_input_kind(inputs, why):
    with pytest.raises(ValueError, match=why):
        generator.check(dict(BASE_MIX, **inputs))


RAS_PIMC = {"model": {"n_layers": 4, "d_model": 256, "n_heads": 4,
                      "n_kv_heads": 4, "head_dim": 64, "d_ff": 512,
                      "vocab_size": 256}}


def test_model_flops_hand_count():
    # per layer: q, k, v, o 4 x 256 x 256 = 262,144 + MLP 3 x 256 x 512 =
    # 393,216; 4 layers = 2,621,440; tied logits 256 x 256 = 65,536
    assert costs.model_matmul_params(RAS_PIMC) == 2_686_976
    # position 0: 2 x 2,686,976 + 4 layers x 4 x 256 x 1 key
    assert costs.model_flops(RAS_PIMC, 0, 1) == 5_373_952 + 4_096
    # position 767 (768 keys): 8.5 MFLOP a symbol
    assert costs.model_flops(RAS_PIMC, 767, 1) == 8_519_680
    # a run of positions is the sum of single ones
    assert costs.model_flops(RAS_PIMC, 256, 256) == sum(
        costs.model_flops(RAS_PIMC, p, 1) for p in range(256, 512))


def _flops_of(model, positions):
    """``EngineSystem._flops`` over one finished cycle at ``positions``."""
    cfg = dict(RAS_PIMC, engine={"lanes": 4, "chunk_size": 16,
                                 "prob_bits": 14})
    sys_ = engine_probe.EngineSystem(cfg, {"kind": "compress"}, 1, 1.0,
                                     None, model)
    sys_.cycles = [{"positions": positions}]
    return cfg, sys_._flops()


def test_engine_flops_are_the_dense_count_without_a_hook():
    # ras-pimc defines no count of its own: mfu.* read costs.model_flops
    mod = common.load_module(common.BENCH / "configs" / "ras-pimc.py")
    assert not hasattr(mod, "model_flops")
    model = types.SimpleNamespace(model_config=lambda cfg, dtype: None)
    cfg, flops = _flops_of(model, [(0, 16), (256, 3)])
    assert flops == 4 * (costs.model_flops(cfg, 0, 16)
                         + costs.model_flops(cfg, 256, 3))


def test_engine_flops_use_the_configuration_hook():
    model = types.SimpleNamespace(model_config=lambda cfg, dtype: None,
                                  model_flops=lambda cfg, p0, n: n * p0 + 7)
    _, flops = _flops_of(model, [(0, 16), (256, 3)])
    assert flops == 4 * (0 + 7 + 3 * 256 + 7)


def test_kernel_bytes_hand_counts():
    # step kernel, 128 rows, K = 256, cap 520, top-4: window 66,560 +
    # states 1,024 + freq 131,072 + cdf 131,584 + candidates 2,048 in,
    # five (1, 128) int32 rows out
    assert costs.decode_step_bytes(128, 256, 520, 4) == 332_288 + 2_560
    assert costs.default_cap(256) == 520


def _ev(name, start, dur, **stats):
    return T.Event(name, float(start), float(dur), stats)


def _trace(ops, host, window=(0, 100), modules=()):
    win = _ev(T.WINDOW_SPAN, window[0], window[1] - window[0])
    return T.Trace(ops=ops, modules=list(modules), host=host + [win],
                   window=window, n_devices=1)


def test_busy_is_the_union_clipped_to_the_window():
    ops = [_ev("a", -10, 20), _ev("b", 5, 10), _ev("c", 50, 10),
           _ev("d", 95, 20)]
    tr = _trace(ops, [])
    # [0, 15) + [50, 60) + [95, 100) = 30 ns
    assert T.busy_seconds(tr) == pytest.approx(30e-9)
    secs = T.op_seconds(tr)
    assert secs["a"] == pytest.approx(10e-9)
    assert secs["b"] == pytest.approx(10e-9)
    assert secs["d"] == pytest.approx(5e-9)


def test_gaps_take_the_innermost_host_span():
    ops = [_ev("a", 0, 10), _ev("b", 40, 10), _ev("c", 90, 10)]
    host = [_ev("engine.finalize", 5, 40), _ev("bench.parse", 15, 10),
            _ev("engine.build_cycle", 55, 50)]
    gaps = T.idle_gaps(_trace(ops, host))
    # [10, 40): middle 25 lies in both spans, parse is the inner one;
    # [50, 90): middle 70 in build_cycle
    assert gaps == [("engine.build_cycle", pytest.approx(40e-9)),
                    ("bench.parse", pytest.approx(30e-9))]


STEP = ('%closed_call.19 = (u32[1,128]{1,0:T(1,128)S(1)}) custom-call('
        'u8[520,128]{1,0:T(8,128)(4,1)} %copy-done.7), '
        'custom_call_target="tpu_custom_call"')


def test_programs_and_kernels_by_name():
    modules = [_ev("jit__unknown(123)", -20, 60),
               _ev("jit__encode_rows(9)", 40, 7),
               _ev("jit__encode_rows(9)", 200, 7)]
    ops = [_ev("%fusion.1 = f32[128] fusion(f32[128] %a)", 0, 10),
           _ev(STEP, 10, 5), _ev(STEP, 98, 5),
           _ev(STEP.replace("520", "1032"), 20, 5)]
    tr = _trace(ops, [], modules=modules)
    assert T.program_seconds(tr, "jit__unknown") == pytest.approx(40e-9)
    assert T.program_seconds(tr, "jit__encode_rows") == pytest.approx(7e-9)
    got = T.kernel_events(tr, "tpu_custom_call", "u8[520,128]")
    assert [e.start for e in got] == [10.0]        # the other one overruns
    assert T.top_ops(tr)[0] == (
        "%fusion.1 = f32[128] fusion(f32[128] %a)", pytest.approx(10e-9))


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5 lite: a 0.3 s window of
    the static-histogram image codec serving 6 frames through the slab
    decode kernel (committed beside this file)."""
    from pathlib import Path
    tr = T.load(str(Path(__file__).parent / "data" /
                    "hist_read_small.xplane.pb"))
    assert tr.n_devices == 1
    assert tr.window_s == pytest.approx(0.304704351)
    busy = T.busy_seconds(tr)
    assert busy == pytest.approx(0.219150651)
    # the six slab decodes: one kernel run of about 36.5 ms each, and one
    # program run each around it
    kern = T.kernel_events(tr, "%rans_decode_slab", "tpu_custom_call")
    assert len(kern) == 6
    assert all(36.4e6 < e.dur < 36.7e6 for e in kern)
    assert sum(e.dur for e in kern) * 1e-9 == pytest.approx(0.219080442)
    assert T.program_seconds(tr, "jit_rans_decode_slab(") == pytest.approx(
        0.219141333)
    # busy is the union: no more than the window, no less than the kernels
    assert sum(e.dur for e in kern) * 1e-9 <= busy <= tr.window_s
    # the idle gaps lie in the server's own spans
    gaps = T.idle_gaps(tr)
    assert gaps[0] == ("bench.wait", pytest.approx(0.038274958))
    assert {g for g, _ in gaps} <= {"bench.wait", "bench.fetch",
                                    "bench.parse", "bench.decode"}
    assert sum(s for _, s in gaps) <= tr.window_s - busy + 1e-9
    assert T.top_ops(tr)[0][0].startswith("%rans_decode_slab.1 = ")
