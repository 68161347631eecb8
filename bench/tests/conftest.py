"""Benchmark tests run on the CPU, at small sizes, without a chip."""

from __future__ import annotations

import os
import sys
from pathlib import Path

if "JAX_PLATFORMS" not in os.environ:
    os.environ["JAX_PLATFORMS"] = "cpu"

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"bf16_flops_s": 1e12, "hbm_bytes_s": 1e11}

TINY_TRAFFIC = {
    "restore": dict(loop="closed", kind="decompress", clients=3, pool=2,
                    image=[8, 8, 3]),
    "ingest": dict(loop="closed", kind="compress", clients=3, pool=2,
                   image=[8, 8, 3]),
}
TINY_CELLS = {"pimc-restore": ("tiny-pimc", "restore"),
              "pimc-ingest": ("tiny-pimc", "ingest")}


def make_tiny_root(root: Path) -> Path:
    """A checkout-like tree whose cells are the benchmark's, cut to CPU
    size: new configuration and traffic files and ``BENCHMARK.json``
    entries, beside the benchmark's own code and metric readers."""
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", root / "bench" / "metrics")
    cfg = json.loads((BENCH / "configs" / "ras-pimc.json").read_text())
    cfg["name"] = "tiny-pimc"
    cfg["model"].update(n_layers=2, d_model=64, d_ff=128, head_dim=16)
    cfg["engine"].update(slots=2, lanes=4, chunk_size=16, max_len=48)
    (root / "bench/configs/tiny-pimc.json").write_text(json.dumps(cfg))
    shutil.copy(BENCH / "configs" / "ras-pimc.py",
                root / "bench/configs/tiny-pimc.py")
    for name, t in TINY_TRAFFIC.items():
        (root / f"bench/traffic/{name}.json").write_text(json.dumps(t))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [
        dict(name="tiny-pimc", source="test",
             file="bench/configs/tiny-pimc.json", reduced=[], why="test")]
    bench["workloads"] = [
        dict(name=w, config=c, traffic=t, chips=1, why="test")
        for w, (c, t) in TINY_CELLS.items()]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("tiny"))


def run_tiny(root: Path, cell: str, seed: int = 2**31 + 5,
             seconds: float = 1.0, trace: bool = False,
             dtype: str | None = None) -> dict:
    import time
    import common
    import run
    c = run.Cell(common.load_benchmark(root), cell, root=root)
    if trace:
        run.TRACE_DIR = root / ".bench" / "trace"
    return run.run_cell(c, seed, seconds, trace, CPU, PEAKS,
                        t_start=time.perf_counter(), dtype=dtype)
