"""Smoke run of the compression service on one TPU chip.

    python chip_smoke.py

Run from the root of a checkout.  One process drives the system's main path
once, through the entry points a user calls, with every Pallas kernel
compiled for the chip; times are printed as a smoke run, not a benchmark.

  (a) device check: the first JAX device must be a TPU (there is no CPU
      fallback — anywhere else the script exits non-zero);
  (b) integer datapath across platforms: the frozen blobs under
      ``tests/golden_vectors/`` decode on the chip, through the compiled
      kernels, to exactly the symbols they were frozen with;
  (c) static-histogram image codec (the paper's Fig. 4a path): one
      Kodak-sized 768x512x3 frame over 128 lanes, chunked encode in-kernel,
      ``pack_chunked`` / ``parse_chunked``, zero-copy slab decode in-kernel;
      the blob must equal ``coder.encode_chunked``'s and the decode the
      input;
  (d) LM compression service: ``ras-pimc`` at its registered width in
      ``BatchEngine(step_backend="kernel")``; 8 compress requests of
      128x128x3 image tiles (prefill program), then a mixed queue of their
      8 blobs as decompress requests plus the 8 tiles compressed again
      (step program).  Every blob must equal the single-request
      ``lm_compress_chunked(backend="kernel")`` blob, and every decompress
      must return its tile exactly.

A failed phase exits non-zero.  The last line of standard output is one
JSON object naming the device.
"""

from __future__ import annotations

import importlib.util
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def _import_repo():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        sys.exit(f"chip_smoke.py: no repro package under {src}; run it from "
                 "the root of a checkout")
    sys.path.insert(0, str(src))


def _golden_module():
    path = ROOT / "tests" / "golden_vectors" / "generate.py"
    spec = importlib.util.spec_from_file_location("golden_generate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileClock:
    """Seconds the backend spent compiling in this process, summed from
    JAX's monitoring events (persistent-cache hits compile nothing)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event == self.EVENT:
            self.seconds += secs


def _timed(fn):
    """(result, seconds) of ``fn()`` with every output array ready."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def phase_device():
    import jax
    devs = jax.devices()
    dev = devs[0]
    print(f"[a] device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX's first device is {dev.platform!r}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def phase_golden():
    """Every frozen container and stack blob decodes to its symbols."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import bitstream, coder, stack
    from repro.kernels import ops
    golden = _golden_module()
    for case in golden.CASES:
        tbl, syms = golden.build_case(case)
        with open(golden.blob_path(case), "rb") as f:
            blob = f.read()
        if case["fmt"] == "v1":
            buf, start, _ = bitstream.unpack(blob)
            enc = coder.EncodedLanes(jnp.asarray(buf), jnp.asarray(start),
                                     jnp.asarray(buf.shape[1] - start))
            got, _ = ops.rans_decode(enc, case["t"], tbl)
        else:
            got, _ = ops.rans_decode_chunked(
                n_symbols=case["t"], tbl=tbl, chunk_size=case["chunk_size"],
                from_container=bitstream.parse_chunked(blob))
        check(np.array_equal(np.asarray(got), syms),
              f"golden {case['name']}: kernel decode differs from the "
              "frozen symbols")
    for case in golden.STACK_CASES:
        _, _, aux = golden.run_stack_case(case)
        with open(golden.blob_path(case), "rb") as f:
            buf, start, _ = bitstream.unpack(f.read())
        enc = coder.EncodedLanes(jnp.asarray(buf), jnp.asarray(start),
                                 jnp.asarray(buf.shape[1] - start))
        _, got = golden.pop_stack_case(case, stack.stack_open(enc), aux,
                                       backend="kernel")
        want = aux["x"]
        if case["name"] == "stack_bitsback":
            got, want = (got["x"], got["k"]), (aux["x"], aux["k"])
        got = got if isinstance(got, tuple) else (got,)
        want = want if isinstance(want, tuple) else (want,)
        check(all(np.array_equal(g, w) for g, w in zip(got, want)),
              f"golden {case['name']}: per-step kernel pops differ from the "
              "frozen symbols")
    n = len(golden.CASES) + len(golden.STACK_CASES)
    print(f"[b] golden vectors: {n} frozen blobs decode exactly through the "
          "compiled kernels", flush=True)


def phase_image(h: int = 512, w: int = 768, lanes: int = 128,
                chunk: int = 1024, seed: int = 0):
    """Static-histogram codec on one Kodak-sized frame (Fig. 4a path)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import bitstream, coder, spc
    from repro.data.pipeline import synthetic_image
    from repro.kernels import ops
    planes = [synthetic_image(h, w, seed=seed + c) for c in range(3)]
    img = np.stack(planes, axis=-1)                      # (h, w, 3) uint8
    syms_np = img.reshape(lanes, -1).astype(np.int32)
    t_len = syms_np.shape[1]
    tbl = jax.tree.map(jnp.asarray, spc.tables_from_counts_np(
        np.bincount(syms_np.ravel(), minlength=256)))
    syms = jnp.asarray(syms_np)

    def encode():
        return ops.rans_encode_chunked(syms, tbl, chunk)
    ch, t_first = _timed(encode)
    ch, t_warm = _timed(encode)
    ref = coder.encode_chunked(syms, tbl, chunk)
    blob = bitstream.pack_chunked(*map(np.asarray, ch), chunk_size=chunk,
                                  n_symbols=t_len)
    ref_blob = bitstream.pack_chunked(*map(np.asarray, ref),
                                      chunk_size=chunk, n_symbols=t_len)
    check(blob == ref_blob, "image: kernel blob differs from "
          "coder.encode_chunked's")
    cs = bitstream.parse_chunked(blob)

    def decode():
        return ops.rans_decode_chunked(n_symbols=t_len, tbl=tbl,
                                       chunk_size=chunk, from_container=cs)
    (dec, probes), d_first = _timed(decode)
    (dec, probes), d_warm = _timed(decode)
    check(np.array_equal(np.asarray(dec), syms_np),
          "image: zero-copy decode differs from the input frame")
    print(f"[c] image {w}x{h}x3 = {syms_np.size} symbols over {lanes} "
          f"lanes, chunk {chunk}: blob {len(blob)} B (CR "
          f"{syms_np.size / len(blob):.4f}), byte-identical to the coder, "
          f"decoded exactly, {float(probes):.4f} probes/symbol", flush=True)
    print(f"    encode first call {t_first:.3f} s, warm {t_warm:.3f} s; "
          f"decode first call {d_first:.3f} s, warm {d_warm:.3f} s",
          flush=True)


def phase_engine(arch: str = "ras-pimc", full: bool = True, slots: int = 4,
                 lanes: int = 32, chunk: int = 256, tile: int = 128,
                 n_req: int = 8, seed: int = 0):
    """ras-pimc in BatchEngine: compress, then a mixed queue."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config, get_smoke_config
    from repro.core import bitstream
    from repro.data.pipeline import synthetic_image
    from repro.models import init_model
    from repro.serve.compress import lm_compress_chunked
    from repro.serve.engine import BatchEngine
    cfg = (get_config if full else get_smoke_config)(arch)
    params = init_model(cfg, jax.random.PRNGKey(seed))
    tiles = []
    for i in range(n_req):
        t = np.stack([synthetic_image(tile, tile, seed=100 + 3 * i + c)
                      for c in range(3)], axis=-1)
        tiles.append(t.reshape(lanes, -1).astype(np.int32))
    t_len = tiles[0].shape[1]
    eng = BatchEngine(params, cfg, slots=slots, lanes=lanes,
                      chunk_size=chunk, max_len=t_len, step_backend="kernel",
                      prefill="auto")
    cache_bytes = sum(a.nbytes for a in jax.tree.leaves(eng._cache))
    print(f"[d] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads, K={cfg.vocab_size}, {cfg.dtype}; engine "
          f"{slots} slots x {lanes} lanes = {eng.rows} rows, chunk {chunk}, "
          f"{t_len} symbols/lane; ring cache {cache_bytes} B", flush=True)

    rids = [eng.submit_compress(t) for t in tiles]
    res, t_comp = _timed(lambda: eng.run(clock="virtual"))
    prefill = eng.prefill_cycles
    check(prefill > 0, "engine: compress-only cycles never took the prefill "
          "program")
    blobs = []
    for rid in rids:
        check(res[rid].ok, f"engine compress {rid} failed: {res[rid].error}")
        blobs.append(res[rid].blob)

    def single(t):
        st = lm_compress_chunked(params, cfg, jnp.asarray(t),
                                 chunk_size=chunk, backend="kernel")
        ch = jax.tree.map(np.asarray, st.chunks)
        return bitstream.pack_chunked(ch.buf, ch.start, ch.length,
                                      ch.overflow, chunk_size=chunk,
                                      n_symbols=t_len)
    t0 = time.perf_counter()
    for i, t in enumerate(tiles):
        check(single(t) == blobs[i], f"engine blob {i} differs from the "
              "single-request lm_compress_chunked(backend='kernel') blob")
    t_single = time.perf_counter() - t0

    # mixed queue: every blob back as a decompress request, interleaved
    # with the same tiles compressed again (these cycles run the step
    # program, so prefill and step compress must agree byte for byte)
    back, again = [], []
    for i, t in enumerate(tiles):
        back.append(eng.submit_decompress(blobs[i], arrival=float(i)))
        again.append(eng.submit_compress(t, arrival=float(i)))
    step_cycles0 = eng.prefill_cycles
    res2, t_mixed = _timed(lambda: eng.run(clock="virtual"))
    for i, (rb, ra) in enumerate(zip(back, again)):
        check(res2[rb].ok, f"engine decompress {rb} failed: "
              f"{res2[rb].error}")
        check(np.array_equal(res2[rb].tokens, tiles[i]),
              f"engine decompress of blob {i} differs from its tile")
        check(res2[ra].ok and res2[ra].blob == blobs[i],
              f"engine step-program compress of tile {i} differs from its "
              "prefill-program blob")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"    {n_req} blobs byte-identical to the single-request path; "
          f"{n_req} decompress requests exact; step-program compress == "
          f"prefill compress; prefill cycles {prefill} then "
          f"{eng.prefill_cycles - step_cycles0} in the mixed run; mean "
          f"{sum(len(b) for b in blobs) / n_req:.1f} B/blob", flush=True)
    print(f"    compress run {t_comp:.3f} s, single-request references "
          f"{t_single:.3f} s, mixed run {t_mixed:.3f} s (first runs, "
          "compilation included); device peak bytes in use "
          f"{stats.get('peak_bytes_in_use', 'n/a')}", flush=True)


def main() -> int:
    _import_repo()
    import jax
    from repro.launch.compile_cache import setup_compile_cache
    cache = setup_compile_cache()
    clock = CompileClock()
    try:
        device = phase_device()
    except SmokeFailure as e:
        print(f"chip_smoke.py: {e}", file=sys.stderr)
        return 1
    print(f"compile cache: {cache}", flush=True)
    t0 = time.perf_counter()
    for name, phase in (("golden", phase_golden), ("image", phase_image),
                        ("engine", phase_engine)):
        c0, w0 = clock.seconds, time.perf_counter()
        try:
            phase()
        except SmokeFailure as e:
            print(f"chip_smoke.py: phase {name} failed: {e}",
                  file=sys.stderr)
            return 1
        print(f"    {name}: wall {time.perf_counter() - w0:.3f} s, of which "
              f"compile {clock.seconds - c0:.3f} s (smoke run, not a "
              "benchmark)", flush=True)
    print(f"all phases passed in {time.perf_counter() - t0:.3f} s, compile "
          f"{clock.seconds:.3f} s (jax {jax.__version__})", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
