"""The control, the program one step below the precision or the
fixed-point width its configuration states, comes out not correct."""

from __future__ import annotations

import json
import time

import pytest

import common
from conftest import BENCH, CPU, PEAKS, TINY_CELLS, run_tiny


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_control_is_not_correct(tiny_root, cell):
    import run
    c = run.Cell(common.load_benchmark(tiny_root), cell, root=tiny_root)
    if "model" in c.cfg:
        # bfloat16 shows at the configured widths, not at the tiny ones
        c.cfg["model"] = json.loads(
            (BENCH / "configs" / "ras-pimc.json").read_text())["model"]
    c.cfg, dtype = common.load_module(c.config_code).control(c.cfg)
    r = run.run_cell(c, 2**31 + 77, 1.0, False, CPU, PEAKS,
                     t_start=time.perf_counter(), dtype=dtype)
    assert not r["correct"], r["checks"]
    if "model" in c.cfg:
        t = r["checks"]["table_mismatch_share"]
        assert t["value"] > t["limit"]


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_program_is_correct_on_the_control_seed(tiny_root, cell):
    assert run_tiny(tiny_root, cell, seed=2**31 + 77)["correct"]
