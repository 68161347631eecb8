"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig4a_speed,...]

Prints ``name,us_per_call,derived`` CSV (us_per_call column holds the
figure's primary value when the metric is not a latency).
"""

from __future__ import annotations

import argparse
import sys
import time

# suites import lazily so one missing optional dep (e.g. bench_ratio's
# zstandard baseline) cannot take down the others
SUITES = {
    "fig4a_speed": "bench_speed",
    "fig4b_search": "bench_search",
    "fig4c_ratio": "bench_ratio",
    "lanes": "bench_lanes",
    "spc": "bench_spc",
    "chunked": "bench_chunked",
    "serve": "bench_serve",
}


def _load(mod_name: str):
    import importlib
    return importlib.import_module(f"benchmarks.{mod_name}").main


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")

    def emit(name, value, derived=""):
        print(f"{name},{value:.4f},{derived}", flush=True)

    failures = 0
    for name, mod_name in SUITES.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            _load(mod_name)(emit)
            print(f"# suite {name} done in {time.time()-t0:.1f}s",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"# suite {name} FAILED: {e}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
