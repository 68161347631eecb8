"""Launch/analysis substrate tests (no 512-device init — that is dryrun-only)."""

from pathlib import Path

import numpy as np
import pytest

from repro.analysis.hlo import collective_stats, op_histogram, _shape_bytes
from repro.analysis.roofline import model_flops, scan_multiplier
from repro.configs import ARCH_IDS, SHAPES, get_config, grid, shape_applicable


def test_grid_covers_40_cells():
    cells = list(grid())
    assert len(cells) == 40
    runnable = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    assert len(skipped) == 8           # 8 full-attention archs x long_500k
    assert all(s == "long_500k" for _, s, ok, _ in cells if not ok)


def test_exact_assigned_configs():
    """The pool configs must match the assignment sheet exactly."""
    want = {
        "llama-3.2-vision-11b": (40, 4096, 32, 8, 14336, 128256),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "qwen3-32b": (64, 5120, 64, 8, 25600, 151936),
        "llama3-405b": (126, 16384, 128, 8, 53248, 128256),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064),
        "mamba2-130m": (24, 768, 0, 0, 0, 50280),
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
    }
    for arch, (nl, d, h, kv, ff, v) in want.items():
        c = get_config(arch)
        got = (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
               c.vocab_size)
        assert got == (nl, d, h, kv, ff, v), (arch, got)


def test_moe_flags():
    assert get_config("mixtral-8x22b").n_experts == 8
    assert get_config("mixtral-8x22b").sliding_window == 4096
    assert get_config("phi3.5-moe-42b-a6.6b").n_experts == 16
    assert get_config("mamba2-130m").ssm_state == 128
    assert get_config("recurrentgemma-2b").block_pattern == \
        ("rec", "rec", "attn")


def test_stage_layer_counts():
    for arch in ARCH_IDS:
        if arch == "ras-pimc":
            continue
        cfg = get_config(arch)
        total = sum(len(pat) * reps for pat, reps in cfg.stages)
        assert total == cfg.n_layers, (arch, total)


def test_scan_multiplier():
    cfg = get_config("llama3-405b")
    assert scan_multiplier(cfg, SHAPES["train_4k"]) == 126 * cfg.grad_accum
    assert scan_multiplier(cfg, SHAPES["decode_32k"]) == 126


def test_model_flops_train_matches_6nd():
    cfg = get_config("qwen3-4b")
    mf = model_flops(cfg, SHAPES["train_4k"])
    n = cfg.param_count_estimate()
    assert abs(mf - 6 * n * 256 * 4096) / mf < 1e-9


def test_hlo_collective_parser():
    hlo = """
ENTRY %main (p0: f32[8,16]) -> f32[8,16] {
  %p0 = f32[8,16] parameter(0)
  %ar = f32[8,16] all-reduce(%p0), replica_groups={}
  ROOT %out = f32[8,16] add(%ar, %p0)
}
%body (x: bf16[4]) -> bf16[4] {
  %x = bf16[4] parameter(0)
  %ag = bf16[16] all-gather(%x), dimensions={0}
  ROOT %r = bf16[4] slice(%ag)
}
"""
    st = collective_stats(hlo)
    assert st["all-reduce"]["count"] == 1
    assert st["all-reduce"]["bytes"] == 8 * 16 * 4
    assert st["all-gather"]["count"] == 1
    assert st["entry_bytes"] == 8 * 16 * 4   # all-reduce in ENTRY
    assert st["body_bytes"] == 8             # all-gather operand in %body
    assert _shape_bytes("(f32[2,2], bf16[4])") == 16 + 8
    hist = op_histogram(hlo)
    assert any(op == "parameter" for op, _ in hist)


def test_mesh_helpers():
    from repro.launch.mesh import make_mesh_for
    # on 1 CPU device only shape (1,1) is constructible
    m = make_mesh_for(1)
    assert m.devices.size == 1


def test_compile_cache_rule(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, decides alone; otherwise every
    entry point shares one fixed directory inside the checkout."""
    import jax
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.setup_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = compile_cache.setup_compile_cache()
        assert fixed == str(compile_cache.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == fixed
        # inside the checkout: the repo root holds tests/ and the cache
        assert compile_cache.CACHE_DIR.parent == (
            Path(__file__).resolve().parents[1])
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
