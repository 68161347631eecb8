"""Readings that set the limits of ``correct``: the program on a dozen seeds
or more, and the control (the program one step below the precision the
configuration states) on three or more, at the cell's own size, in one
process.  The benchmark's own runs never run the control.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... \\
        [--control-seeds 4 5 6] [--seconds 1] [--out file.jsonl]

Prints one JSON line per run: which side, the seed, ``correct`` and every
number compared.  Off a TPU it exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import common  # noqa: E402
import run  # noqa: E402


def reading(cell, side, seed, seconds, info, peaks, dtype=None) -> dict:
    r = run.run_cell(cell, seed, seconds, False, info, peaks,
                     t_start=time.perf_counter(), dtype=dtype)
    notes = r.pop("_notes")
    return {"side": side, "cell": cell.name, "seed": seed,
            "correct": r["correct"], "failed": r["failed"],
            "checks": {k: v["value"] for k, v in r["checks"].items()},
            "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            "setup_s": notes["setup_s"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    run.compile_cache()
    try:
        info = run.require_chips(1)
    except run.NoChip as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 3
    peaks = run.peak_row(info["kind"])
    bench = common.load_benchmark()
    out = open(args.out, "a") if args.out else None
    for side, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        for seed in seeds:
            cell = run.Cell(bench, args.workload)
            dtype = None
            if side == "control":
                mod = common.load_module(cell.config_code)
                cell.cfg, dtype = mod.control(cell.cfg)
            rec = reading(cell, side, seed, args.seconds, info, peaks, dtype)
            line = json.dumps(rec)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
