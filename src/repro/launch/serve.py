"""Serving / compression launcher (the paper's deployment direction).

    python -m repro.launch.serve --arch ras-pimc --mode compress --lanes 8 \
        --symbols 256
    python -m repro.launch.serve --mode engine --streams 6 --slots 2 \
        --arrival-rate 0.5
    python -m repro.launch.serve --full --mode engine --lanes 32 \
        --symbols 1536 --chunk-size 256 --slots 4 --streams 8

``--mode compress`` runs one stream end to end: SPC + multi-lane rANS
encode, prediction-guided decode, bit-exactness check — the full Fig. 2
datapath.  ``--mode engine`` drives the batched multi-stream engine
instead: ``--streams`` requests with seeded Poisson arrivals
(``--arrival-rate`` per virtual tick) are continuously batched into
``--slots`` slots of one traced step program, every blob is verified
byte-identical to the single-request path and decompressed again through
the engine, and per-request latency (admission wait included) is
reported.  ``--full`` serves the arch's registered full-width config
instead of its smoke-sized cut.  Pallas kernels compile on a TPU and run
in the interpreter elsewhere.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import bitstream
from repro.data.pipeline import token_stream
from repro.launch.compile_cache import setup_compile_cache
from repro.models import init_model, state_spec
from repro.serve.compress import lm_compress, lm_decompress
from repro.serve.engine import generate
from repro.train import checkpoint


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="ras-pimc", metavar="ARCH",
                    help="any registered arch id (configs.registry.ARCH_IDS)"
                         " — the serve stack is family-agnostic behind the "
                         "model-state protocol: SSM / rGLRU / MoE smoke "
                         "configs all run the same datapath")
    ap.add_argument("--full", action="store_true",
                    help="serve the arch's registered full-width config "
                         "instead of its smoke-sized cut")
    ap.add_argument("--mode", choices=["compress", "generate", "engine"],
                    default="compress",
                    help="compress = one stream end to end; generate = "
                         "sampled rollout; engine = batched multi-stream "
                         "serving (continuous batching, Poisson arrivals)")
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--symbols", type=int, default=256)
    ap.add_argument("--streams", type=int, default=6,
                    help="[engine] number of concurrent compress requests")
    ap.add_argument("--slots", type=int, default=2,
                    help="[engine] co-batched request slots in the shared "
                         "step program (rows = slots * lanes)")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="[engine] Poisson arrival rate per virtual tick "
                         "(one tick ~= one chunk cycle)")
    ap.add_argument("--chunk-size", type=int, default=16,
                    help="[engine] symbols per lane per scheduling chunk")
    ap.add_argument("--seed", type=int, default=0,
                    help="[engine] arrival-process seed (schedules are "
                         "deterministic per seed)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--topk", type=int, default=4)
    ap.add_argument("--backend", choices=["coder", "kernel", "two_pass"],
                    default="kernel",
                    help="rANS datapath: 'kernel' = Pallas encode + the "
                         "FUSED serve decode (one program: model step + SPC "
                         "+ per-step decode kernel; the engine's step "
                         "program runs the same kernel); 'coder' = pure-JAX "
                         "lane coder (the reference); 'two_pass' = Pallas "
                         "encode + the collect-then-replay reference decode")
    args = ap.parse_args(argv)

    if args.arch not in ARCH_IDS:
        ap.error(f"unknown --arch {args.arch!r}; registered ids: "
                 f"{', '.join(ARCH_IDS)}")
    setup_compile_cache()
    cfg = (get_config if args.full else get_smoke_config)(args.arch)
    spec = state_spec(cfg)
    state_kind = ("ring+recurrent" if spec.ring and spec.recurrent
                  else "recurrent" if spec.recurrent else "ring")
    print(f"arch={args.arch} config={cfg.name} family={cfg.family} "
          f"kinds={spec.kinds} state={state_kind} "
          f"device={jax.devices()[0].device_kind}")
    params = init_model(cfg, jax.random.PRNGKey(0))
    if args.ckpt:
        step = checkpoint.latest_step(args.ckpt)
        if step is not None:
            from repro.train.train_loop import init_train_state
            state = checkpoint.restore(args.ckpt, step,
                                       init_train_state(params))
            params = state.params
            print(f"restored checkpoint step {step}")

    if args.mode == "engine":
        from repro.serve.compress import lm_compress_chunked
        from repro.serve.engine import BatchEngine
        rng = np.random.default_rng(args.seed)
        arrivals = np.cumsum(rng.exponential(1.0 / args.arrival_rate,
                                             size=args.streams))
        streams = [np.asarray(token_stream(cfg.vocab_size,
                                           (args.lanes, args.symbols),
                                           seed=100 + i), np.int32)
                   for i in range(args.streams)]
        backend = "coder" if args.backend == "coder" else "kernel"
        eng = BatchEngine(params, cfg, slots=args.slots, lanes=args.lanes,
                          chunk_size=args.chunk_size,
                          max_len=args.symbols, step_backend=backend)
        rids = [eng.submit_compress(s, arrival=float(a))
                for s, a in zip(streams, arrivals)]
        t0 = time.time()
        res = eng.run(clock="virtual")
        wall = time.time() - t0
        lat = []
        for rid, toks in zip(rids, streams):
            r = res[rid]
            assert r.ok, r.error
            stats = lm_compress_chunked(params, cfg, jnp.asarray(toks),
                                        chunk_size=args.chunk_size,
                                        backend=backend)
            enc = jax.tree.map(np.asarray, stats.chunks)
            ref = bitstream.pack_chunked(enc.buf, enc.start, enc.length,
                                         enc.overflow,
                                         chunk_size=args.chunk_size,
                                         n_symbols=args.symbols)
            assert r.blob == ref, f"request {rid}: engine blob diverged"
            lat.append(r.completed_at - r.arrival)
        lat = np.sort(np.asarray(lat))
        back = [eng.submit_decompress(res[rid].blob) for rid in rids]
        t0 = time.time()
        res_back = eng.run(clock="virtual")
        wall_back = time.time() - t0
        for rid, toks in zip(back, streams):
            r = res_back[rid]
            assert r.ok, r.error
            assert np.array_equal(r.tokens, toks), (
                f"request {rid}: engine decompress diverged")
        print(f"engine: {args.streams} streams x {args.lanes} lanes x "
              f"{args.symbols} symbols through {args.slots} slots "
              f"(step backend {backend})")
        print(f"  compress wall {wall:.2f}s (prefill cycles "
              f"{eng.prefill_cycles})  decompress wall {wall_back:.2f}s, "
              "first runs include compilation")
        print(f"  virtual latency (ticks): p50 {np.percentile(lat, 50):.1f} "
              f" p99 {np.percentile(lat, 99):.1f}")
        print(f"  all {args.streams} blobs byte-identical to the "
              "single-request path and decompressed exactly by the engine")
        return

    if args.mode == "generate":
        prompt = jnp.asarray(
            token_stream(cfg.vocab_size, (2, 16), seed=1), jnp.int32)
        out = generate(params, cfg, prompt, 32, max_len=64)
        print("generated:", np.asarray(out))
        return

    toks = jnp.asarray(token_stream(cfg.vocab_size,
                                    (args.lanes, args.symbols), seed=7),
                       jnp.int32)
    t0 = time.time()
    enc_backend = "coder" if args.backend == "coder" else "kernel"
    stats = lm_compress(params, cfg, toks, backend=enc_backend)
    jax.block_until_ready(stats.enc.buf)
    t_enc = time.time() - t0
    blob = bitstream.pack(*map(np.asarray, stats.enc),
                          n_symbols=args.symbols)
    t0 = time.time()
    dec, probes = lm_decompress(params, cfg, stats.enc, args.symbols,
                                topk=args.topk, backend=args.backend)
    jax.block_until_ready(dec)
    t_dec = time.time() - t0
    exact = bool(np.array_equal(np.asarray(dec), np.asarray(toks)))
    raw = args.lanes * args.symbols
    print(f"lanes={args.lanes} symbols/lane={args.symbols} "
          f"backend={args.backend}")
    print(f"  bits/symbol     : {float(stats.bits_per_symbol):.3f} "
          f"(model bound {float(stats.model_xent_bits):.3f})")
    print(f"  container bytes : {len(blob)} (raw {raw})  "
          f"CR={raw/len(blob):.3f}")
    print(f"  encode {t_enc:.2f}s  decode {t_dec:.2f}s  "
          f"avg CDF probes/symbol {float(probes):.2f}")
    print(f"  bit-exact roundtrip: {exact}")
    assert exact


if __name__ == "__main__":
    main()
