"""The whole run of each kind of cell on the CPU, at a small size: the
harness's look for a chip is skipped, the rest runs as on the chip.  Then
the same runs with the timed path broken underneath, which ``correct``
must catch."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import generator
from conftest import BENCH, TINY_CELLS, make_tiny_root, run_tiny


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_cell_runs_and_is_correct(tiny_root, cell):
    r = run_tiny(tiny_root, cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(r["metrics"]) == want
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "_notes" and list(r)[-2] == "checks"
    # every program the window runs was compiled before it opened
    assert r["_notes"]["counters"]["window.compiles"] == 0
    assert r["_notes"]["counters"]["window.cycles"] >= 1
    assert 0 <= r["readings"]["table_mismatch_share_highest"] <= 1


# On the CPU the trace holds no TPU custom call and no device program
# events: the readers of kernels and of device time per program find
# nothing, and the result names them.
CPU_SILENT = {"pimc-restore": {"decode_step_roofline.restore",
                               "cycle_device_ms.restore"},
              "pimc-ingest": {"cycle_device_ms.ingest",
                              "encode_device_ms.ingest"}}


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_traced_run_reports_its_per_layer_metrics(tiny_root, cell):
    r = run_tiny(tiny_root, cell, trace=True)
    assert r["correct"], r["checks"]
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in bench["per_layer"]
              if cell in m["workloads"]}
    assert set(r["per_layer_missing"]) == CPU_SILENT[cell]
    assert set(r["metrics"]) == listed - CPU_SILENT[cell]
    assert r["breakdown"]["device_ops"]


def test_new_files_add_a_cell_and_a_metric(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files and entries, with no existing file edited."""
    root = make_tiny_root(tmp_path)
    (root / "bench/traffic/one-client.json").write_text(json.dumps(dict(
        loop="closed", kind="compress", clients=1, pool=1,
        image=[8, 8, 3])))
    shutil.copy(root / "bench/configs/tiny-pimc.py",
                root / "bench/configs/wide-pimc.py")
    cfg = json.loads((root / "bench/configs/tiny-pimc.json").read_text())
    cfg["engine"]["lanes"] = 8
    (root / "bench/configs/wide-pimc.json").write_text(json.dumps(cfg))
    (root / "bench/metrics/cycles_in_window.wide.py").write_text(
        "def read(ctx):\n    return ctx.counters['window.cycles']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name="wide-pimc", source="test",
                                 file="bench/configs/wide-pimc.json",
                                 reduced=[], why="test"))
    bench["workloads"].append(dict(name="wide-ingest", config="wide-pimc",
                                   traffic="one-client", chips=1,
                                   why="test"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    e2e["compress_sym_s"]["workloads"].append("wide-ingest")
    bench["per_layer"].append(dict(
        name="cycles_in_window.wide", unit="cycles", better="higher",
        source="program_counter", layer="engine host half",
        moves="compress_sym_s", workloads=["wide-ingest"]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    plain = run_tiny(root, "wide-ingest")
    assert plain["correct"] and "compress_sym_s" in plain["metrics"]
    assert plain["_notes"]["counters"]["window.compiles"] == 0
    traced = run_tiny(root, "wide-ingest", trace=True)
    assert traced["metrics"]["cycles_in_window.wide"]["value"] >= 1


# the throwaway text model's own FLOP count, 5 a symbol whatever the
# position, so that the window's total shows which count it took; its
# build hands the count on beside the parts it shares with tiny-pimc
TEXT_MODEL = """

def model_flops(cfg, pos0, n):
    return 5.0 * n


def build(cfg, traffic, seed, seconds, probe, dtype=None):
    import types
    model = types.SimpleNamespace(model_config=model_config,
                                  make_weights=make_weights,
                                  reference_logits=reference_logits,
                                  model_flops=model_flops)
    return engine_probe.EngineSystem(cfg, traffic, seed, seconds, probe,
                                     model, dtype=dtype)
"""

# request lengths by a quantile law over 4 inputs: 112, 144, 216 and 384
# ids, 28 to 96 a lane in 4 lanes; three end in a short chunk of a length
# of its own (12, 4, 6), one in a whole chunk
TEXT_LAW = {"quantiles": [[0, 96], [0.5, 160], [1, 512]]}


@pytest.mark.parametrize("kind", ["compress", "decompress"])
def test_new_files_add_a_text_cell(tmp_path, kind):
    """A language-model cell added as new files and entries only: a tiny
    dense configuration over 512 token ids whose module counts its own
    FLOPs, and a token-stream mix whose requests' lengths follow a law."""
    root = make_tiny_root(tmp_path)
    cfg = json.loads((root / "bench/configs/tiny-pimc.json").read_text())
    cfg["name"] = "tiny-text"
    cfg["model"]["vocab_size"] = 512
    cfg["engine"]["max_len"] = 128
    (root / "bench/configs/tiny-text.json").write_text(json.dumps(cfg))
    (root / "bench/configs/tiny-text.py").write_text(
        (root / "bench/configs/tiny-pimc.py").read_text() + TEXT_MODEL)
    (root / f"bench/traffic/text-{kind}.json").write_text(json.dumps(dict(
        loop="closed", kind=kind, clients=3, pool=4,
        tokens=dict(zipf=1.0, length=TEXT_LAW, source="test"))))
    cell = f"text-{kind}"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name="tiny-text", source="test",
                                 file="bench/configs/tiny-text.json",
                                 reduced=[], why="test"))
    bench["workloads"].append(dict(name=cell, config="tiny-text",
                                   traffic=f"text-{kind}", chips=1,
                                   why="test"))
    rate = f"{kind}_sym_s"
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    e2e[rate]["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert [n // 4 % 16 for n in generator.lengths(TEXT_LAW, 4, 4)] \
        == [12, 4, 6, 0]
    r = run_tiny(root, cell, seconds=2.0)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {rate, "setup_s"}
    counters = r["_notes"]["counters"]
    # the short last chunks ran in set-up: nothing compiles in the window
    assert counters["window.compiles"] == 0
    assert counters["window.symbols"] > 0
    assert counters["window.model_flops"] == 5.0 * counters["window.symbols"]


def test_off_the_chip_the_command_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pimc-restore",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=BENCH.parent, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip().endswith("}")


def test_without_the_program_the_command_exits_nonzero(tmp_path):
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pimc-restore",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and not p.stdout.strip()


# -- faults planted in the timed path ------------------------------------


@pytest.fixture
def fresh_programs():
    """Drop every compiled program, so a patched function is traced anew."""
    import jax
    from repro.serve import engine
    engine._compiled_program.cache_clear()
    jax.clear_caches()
    yield
    engine._compiled_program.cache_clear()
    jax.clear_caches()


def _break(monkeypatch, module, name, wrap):
    real = getattr(module, name)
    monkeypatch.setattr(module, name, wrap(real))


def test_fault_decoded_symbol_altered(tiny_root, monkeypatch,
                                      fresh_programs):
    from repro.kernels import ops

    def wrap(real):
        def f(*a, **kw):
            s, p, sym, probes, u = real(*a, **kw)
            return s, p, (sym + 1) % 256, probes, u
        return f
    _break(monkeypatch, ops, "rans_decode_step_rows", wrap)
    r = run_tiny(tiny_root, "pimc-restore")
    assert not r["correct"]
    # a wrong symbol fed back derails the rest of its chunk: the stream
    # runs out and the request fails, or the symbols differ
    assert r["checks"]["decode_mismatch"]["value"] > 0 \
        or r["checks"]["failed_requests"]["value"] > 0


def test_fault_half_the_rows_left_out(tiny_root, monkeypatch,
                                      fresh_programs):
    from repro.kernels import ops

    def wrap(real):
        def f(*a, **kw):
            s, p, sym, probes, u = real(*a, **kw)
            half = sym.shape[0] // 2
            return s, p, sym.at[half:].set(0), probes, u
        return f
    _break(monkeypatch, ops, "rans_decode_step_rows", wrap)
    r = run_tiny(tiny_root, "pimc-restore")
    assert not r["correct"]
    # a wrong symbol fed back derails the rest of its chunk: the stream
    # runs out and the request fails, or the symbols differ
    assert r["checks"]["decode_mismatch"]["value"] > 0 \
        or r["checks"]["failed_requests"]["value"] > 0


def test_fault_step_returns_its_state_unchanged(tiny_root, monkeypatch,
                                                fresh_programs):
    from repro.serve import engine

    def wrap(real):
        def f(params, cache, *a, **kw):
            lg, _ = real(params, cache, *a, **kw)
            return lg, cache
        return f
    _break(monkeypatch, engine, "decode_step", wrap)
    r = run_tiny(tiny_root, "pimc-restore")
    assert not r["correct"]
    assert r["checks"]["table_mismatch_share"]["value"] > 0.05


def test_fault_prefill_returns_its_state_unchanged(tiny_root, monkeypatch,
                                                   fresh_programs):
    from repro.serve import engine

    def wrap(real):
        def f(params, cache, *a, **kw):
            lg, _ = real(params, cache, *a, **kw)
            return lg, cache
        return f
    _break(monkeypatch, engine, "prefill_chunk", wrap)
    r = run_tiny(tiny_root, "pimc-ingest")
    assert not r["correct"]
    assert r["checks"]["table_mismatch_share"]["value"] > 0.05


def test_fault_compressed_byte_altered(tiny_root, monkeypatch,
                                       fresh_programs):
    from repro.serve import engine

    def wrap(real):
        def f(symbols, tbl, cap):
            enc = real(symbols, tbl, cap=cap)
            return enc._replace(buf=enc.buf.at[0, -1].add(1))
        return f
    _break(monkeypatch, engine, "_encode_rows", wrap)
    r = run_tiny(tiny_root, "pimc-ingest")
    assert not r["correct"]
    assert r["checks"]["chunk_byte_mismatch"]["value"] > 0


def test_fault_container_byte_altered(tiny_root, monkeypatch):
    from repro.core import bitstream

    def wrap(real):
        def f(*a, **kw):
            blob = bytearray(real(*a, **kw))
            blob[-1] ^= 0x01
            return bytes(blob)
        return f
    _break(monkeypatch, bitstream, "pack_chunked", wrap)
    r = run_tiny(tiny_root, "pimc-ingest")
    assert not r["correct"]
    assert r["checks"]["container_mismatch"]["value"] > 0
