"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  ``BENCHMARK.json`` names the cell's
configuration and traffic mix; every part is a file found by its name:
``bench/configs/<config>.json`` (the sizes as run) and ``<config>.py`` (what
builds the system under test and its plain reference),
``bench/traffic/<traffic>.json`` (read by ``bench/generator.py``) and
``bench/metrics/<metric>.py`` (one reader per per-layer metric).

Set-up (weights and inputs from the seed, every program the window uses
compiled or loaded from the cache) is timed as ``setup_s``; then the window
runs for ``--seconds``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from the
profiler's trace of the window and from the benchmark's spans and counters.
A traced window is asked for ``TRACE_SECONDS`` (it closes at the first
launch at or after that: one step cycle, two prefill cycles): a step cycle
of the engine alone leaves over a million device events in the trace.
After the window, the program's state is freed and its outputs are compared
with the plain reference: that decides ``correct``.

The last line of standard output is one JSON object.  Off a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import common  # noqa: E402
import costs  # noqa: E402
import generator  # noqa: E402
import trace_reduce  # noqa: E402

TRACE_DIR = ROOT / ".bench" / "trace"
TRACE_SECONDS = 5.0


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Cell:
    """A cell of ``BENCHMARK.json`` with its configuration, mix and metrics."""

    def __init__(self, bench: dict, name: str, root: Path = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        conf = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.cfg = common.load_json(root / conf["file"])
        self.config_code = (root / conf["file"]).with_suffix(".py")
        self.traffic = common.load_json(
            root / "bench" / "traffic" / f"{self.entry['traffic']}.json")
        generator.check(self.traffic)
        self.e2e = [m for m in bench["end_to_end"]
                    if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m["workloads"]]
        self.metric_dir = root / "bench" / "metrics"


def compile_cache() -> str:
    """The program's rule for the cache directory (``JAX_COMPILATION_CACHE_DIR``
    or a fixed directory in the checkout), and every program kept however
    quickly it compiled: a one-second threshold would leave the small
    programs of a cell to compile again in every run."""
    import jax
    from repro.launch.compile_cache import setup_compile_cache
    cache = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def device_info():
    import jax
    devs = jax.devices()
    return devs, {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}


def require_chips(chips: int):
    devs, info = device_info()
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {info['platform']!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has "
                     f"{len(devs)}")
    return info


def peak_row(kind: str) -> dict:
    peaks = common.load_json(BENCH / "peaks.json")
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


class Tracer:
    """Starts the profiler when the window opens and stops it when it
    closes, around one host span named ``bench.window``."""

    def __init__(self, directory: Path):
        self.dir = directory
        self.ann = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.dir))
        self.ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self.ann.__enter__()

    def stop(self):
        import jax
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


def per_layer_metrics(cell: Cell, ctx) -> tuple[dict, list[str]]:
    """The cell's per-layer metrics, and the names of those whose reader
    found nothing to read: they are left out of the metrics, never read as
    0, and named in the result so that a lost kernel or program shows."""
    out, missing = {}, []
    for m in cell.per_layer:
        reader = common.load_module(cell.metric_dir / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is None:
            missing.append(m["name"])
        else:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out, missing


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             info: dict, peaks: dict, t_start: float = T_START,
             dtype: str | None = None) -> dict:
    """Set up, measure, check; returns the result object (no printing)."""
    import jax
    devs = jax.devices()
    clock = common.CompileClock()
    probe = common.Probe()
    tracer = None
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
        tracer = Tracer(TRACE_DIR / cell.name)
        probe.on_open.append(tracer.start)
        probe.on_close.append(tracer.stop)
    system = common.load_module(cell.config_code).build(
        cell.cfg, cell.traffic, seed, seconds, probe, dtype=dtype)
    system.setup()
    report = system.run()
    setup_s = probe.t_open - t_start
    probe.counters["window.compiles"] = sum(
        1 for t in clock.times if probe.t_open <= t <= probe.t_close)
    probe.counters["window.cache_loads"] = sum(
        1 for t in clock.loads if probe.t_open <= t <= probe.t_close)
    probe.counters["setup.compile_s"] = clock.seconds
    mem = memory_peak(devs)
    system.release()
    checks = system.check(cell.cfg.get("limits", {}))
    correct = all(c.ok for c in checks) and report.failed == 0
    result = {"correct": bool(correct), "attempted": int(report.attempted),
              "failed": int(report.failed), "metrics": {},
              "device": dict(info, memory_peak_bytes=mem)}
    if not trace:
        for m in cell.e2e:
            if m["name"] == "setup_s":
                value = setup_s
            elif m["name"] in report.e2e:
                value = report.e2e[m["name"]]
            else:
                raise KeyError(f"the cell reports no {m['name']!r}")
            result["metrics"][m["name"]] = {"value": float(value),
                                            "unit": m["unit"]}
    else:
        t0 = time.perf_counter()
        tr = trace_reduce.load(trace_reduce.find_xplane(str(tracer.dir)))
        probe.counters["trace.load_s"] = time.perf_counter() - t0
        ctx = types.SimpleNamespace(
            cell=cell, cfg=cell.cfg, traffic=cell.traffic, probe=probe,
            counters=probe.counters, trace=tr, peaks=peaks, costs=costs,
            tr=trace_reduce, report=report, system=system)
        result["metrics"], result["per_layer_missing"] = \
            per_layer_metrics(cell, ctx)
        result["device"]["busy_s"] = trace_reduce.busy_seconds(tr)
        result["device"]["window_s"] = tr.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in trace_reduce.top_ops(tr)],
            "idle_gaps": [[n, s] for n, s in trace_reduce.idle_gaps(tr)]}
    result["readings"] = dict(system.readings)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    result["_notes"] = {"setup_s": setup_s, "counters": probe.counters,
                        "e2e": report.e2e}
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    root_src = ROOT / "src"
    if not (root_src / "repro").is_dir():
        print(f"bench/run.py: no program under {root_src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(root_src))
    cell = Cell(common.load_benchmark(ROOT), args.workload)
    cache = compile_cache()
    try:
        info = require_chips(cell.entry["chips"])
        peaks = peak_row(info["kind"])
    except (NoChip, KeyError) as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    print(f"cell {cell.name}: config {cell.entry['config']}, traffic "
          f"{cell.entry['traffic']}, seed {args.seed}, {args.seconds} s, "
          f"trace {args.trace}; device {info}; compile cache {cache}",
          flush=True)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), info,
                      peaks)
    notes = result.pop("_notes")
    print(f"setup_s {notes['setup_s']:.6f}; end to end {notes['e2e']}",
          flush=True)
    for k, v in sorted(notes["counters"].items()):
        print(f"counter {k} = {v}", flush=True)
    for name in result.get("per_layer_missing", []):
        print(f"error: per-layer metric {name} found nothing to read",
              file=sys.stderr, flush=True)
    for name, v in result["readings"].items():
        print(f"reading {name}: {v} (printed, not compared)",
              file=sys.stderr, flush=True)
    checks = result["checks"]
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
