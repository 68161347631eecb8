"""Drives the program's batched engine (``repro.serve.engine.BatchEngine``)
through a traffic mix, with the benchmark's own spans around it.

The engine has no public per-cycle hook yet, so :func:`probed_engine`
subclasses it and wraps three private methods: ``_build_cycle`` and
``_launch`` (host spans) and ``_finalize`` (host span, then the window's
bookkeeping: symbols and probes of every finished chunk).  This is a
stopgap; a public hook in the engine should replace it.

The window drives the engine's own ``run(clock="wall")`` in a closed loop:
a request is resubmitted whenever one retires.  It is aligned on launches
(``common.LaunchWindow``): it opens at the first launch after the loop's
first cycle has been finalized (so every program the loop runs has run
once) and closes at the first launch at or after the opening plus
``seconds``.  That launch is not made: the cycle in flight, already ended
on the device, is finalized and the run stops without draining the queue.
The rate is the symbols of the cycles launched in the window over the time
between the two launches.

Finalize times would not do as bounds.  ``_finalize`` reads a cycle's
outputs through device slices that queue behind the cycle launched after
it, so a cycle's symbols reach the host when the next cycle ends, except
where a request retires and the engine finalizes at once: there two
cycles' symbols arrive together after a gap of two cycles, and a window
bounded by finalizes counts 7 or 8 cycles' work in the same 8 cycles of
time, by where the retirements fall in it.
"""

from __future__ import annotations

import collections
import gc
import time

import numpy as np

import common
import costs
import generator
import refcoder


def probed_engine(hooks):
    """A ``BatchEngine`` subclass reporting to ``hooks``."""
    from repro.serve.engine import BatchEngine

    class ProbedEngine(BatchEngine):
        def __init__(self, *a, **kw):
            self.reqs = {}
            self.flight = collections.deque()   # (outputs, in window)
            super().__init__(*a, **kw)

        def _submit(self, req):
            self.reqs[req.rid] = req
            return super()._submit(req)

        def _build_cycle(self):
            with hooks.probe.span("engine.build_cycle"):
                return super()._build_cycle()

        def _launch(self, built):
            if hooks.launching():
                # the window has closed: finish the cycle still in flight
                # (it has ended on the device) and launch nothing more
                if self.flight:
                    self._finalize(self.flight[0][0], 0.0, {})
                raise common.WindowClosed
            n = self.prefill_cycles
            with hooks.probe.span("engine.launch"):
                out = super()._launch(built)
            inside = hooks.probe.in_window(time.perf_counter())
            if inside:
                hooks.probe.count("window.prefill_launches"
                                  if self.prefill_cycles > n
                                  else "window.step_launches")
            self.flight.append((out, inside))
            return out

        def _finalize(self, inflight, now, results):
            out, inside = self.flight.popleft()
            assert out is inflight, "cycles finalized out of launch order"
            spec = inflight[0]
            before = {rid: self.reqs[rid].probes for rid, *_ in spec}
            with hooks.probe.span("engine.finalize"):
                super()._finalize(inflight, now, results)
            hooks.finalized(self, inflight, before, inside)

        def _retire(self, req, now, results, error=None):
            super()._retire(req, now, results, error)
            hooks.retired(self, req, results[req.rid])

    return ProbedEngine


class EngineSystem:
    """One engine configuration under one traffic mix.

    ``model`` holds what the configuration's ``build`` hands on: the
    program's model config (``model_config``), the weights
    (``make_weights``), the plain reference forward (``reference_logits``)
    and, where the dense count of ``costs.model_flops`` does not fit the
    architecture, its own FLOP count (``model_flops``).  Inputs of either kind of mix run as ``(lanes, L)``
    int32 streams over the model's vocabulary.
    """

    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float,
                 probe, model, dtype: str | None = None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self._seconds = seconds
        self.probe, self.model = probe, model
        self.mcfg = model.model_config(cfg, dtype)
        self.lanes = cfg["engine"]["lanes"]
        self.chunk = cfg["engine"]["chunk_size"]
        self.prob_bits = cfg["engine"]["prob_bits"]
        self.kind = traffic["kind"]
        self.rng = np.random.default_rng([seed % (1 << 64), 7])
        self.cycles: list[dict] = []     # finished cycles in the window
        self.kept: list[dict] = []       # cycles sampled for the checks
        self.item_of: dict[int, int] = {}
        self.results: dict = {}
        self.window = None
        self.readings: dict[str, float] = {}   # printed, not compared

    # -- set-up ------------------------------------------------------------

    def setup(self):
        import jax
        # the reference always gets the weights the configuration states;
        # a lower-precision program (the control) gets them cast
        self.params = self.model.make_weights(
            self.model.model_config(self.cfg), self.seed)
        prog_params = jax.tree.map(
            lambda a: a.astype(self.mcfg.dtype), self.params)
        items = generator.inputs(self.traffic, self.seed,
                                 self.mcfg.vocab_size, self.lanes)
        self.streams = [x.reshape(self.lanes, -1).astype(np.int32)
                        for x in items]
        t_len = max(x.shape[1] for x in self.streams)
        e = self.cfg["engine"]
        depth = self.traffic["clients"] + len(self.streams)
        self.eng = probed_engine(self)(
            prog_params, self.mcfg, slots=e["slots"], lanes=self.lanes,
            chunk_size=self.chunk, max_len=e["max_len"],
            prob_bits=self.prob_bits, topk=e["topk"],
            step_backend=e["step_backend"], prefill=e["prefill"],
            max_queue=max(64, depth, len(self.streams) * e["slots"]))
        if t_len > e["max_len"]:
            raise ValueError(f"inputs of {t_len} symbols per lane exceed "
                             f"max_len {e['max_len']}")
        self.blobs = None
        if self.kind == "decompress":
            rids = [self.eng.submit_compress(t) for t in self.streams]
            res = self.eng.run(clock="virtual")
            self.blobs = [res[r].blob for r in rids]
            if not all(res[r].ok for r in rids):
                raise RuntimeError("set-up compress failed")
        self._warm_ragged()
        jax.block_until_ready(self.eng._cache)
        self.eng.reqs.clear()
        self.results.clear()

    def _warm_ragged(self):
        """Run, in every slot, each last chunk shorter than ``chunk`` that
        the inputs end in.  Finalizing such a chunk slices the cycle's
        outputs, and a compress encodes it, at shapes of their own that no
        whole chunk compiles; unwarmed, they first come, and compile,
        inside the window.  One set-up cycle per length (two where the mix
        decompresses): a request of just that many symbols a lane, under
        the byte budget that the inputs get, in each slot at once."""
        from repro.core.coder import default_cap
        tails = {}
        for x in self.streams:
            tails.setdefault(x.shape[1] % self.chunk, x)
        tails.pop(0, None)
        if not tails:
            return
        rids = []
        for r, x in sorted(tails.items()):
            cap = default_cap(min(self.chunk, x.shape[1]))
            rids += [self.eng.submit_compress(x[:, :r], cap=cap)
                     for _ in range(self.cfg["engine"]["slots"])]
        res = self.eng.run(clock="virtual")
        if self.kind == "decompress":
            for rid in rids:
                self.eng.submit_decompress(res[rid].blob)
            res.update(self.eng.run(clock="virtual"))
        if not all(r.ok for r in res.values()):
            raise RuntimeError("set-up of the short last chunks failed")

    def _submit(self, item: int):
        if self.kind == "compress":
            rid = self.eng.submit_compress(self.streams[item])
        else:
            rid = self.eng.submit_decompress(self.blobs[item])
        self.item_of[rid] = item
        return rid

    # -- hooks called from inside the engine ---------------------------------

    def launching(self) -> bool:
        """Before each launch: opens the window at the first launch after a
        cycle of the measured loop has been finalized; True when this
        launch closes it."""
        if self.window is None or not self.warm:
            return False
        return self.window.launch()

    def finalized(self, eng, inflight, before, inside):
        if self.window is None:
            return
        self.warm = True
        if not inside:
            return
        spec, tables = inflight[0], inflight[1]
        t = time.perf_counter()
        syms = 0
        probes = 0
        positions = []
        for rid, s, c, n_c, last in spec:
            req = eng.reqs[rid]
            syms += n_c * self.lanes
            probes += req.probes - before[rid]
            positions.append((c * self.chunk, n_c))
        self.window.work += syms
        cyc = {"t": t, "spec": list(spec), "symbols": syms,
               "probes": probes, "positions": positions}
        self.cycles.append(cyc)
        last = self.probe.t_close is not None
        if len(self.kept) < 2 and (last or self.rng.random() < 0.25):
            self.kept.append(dict(cyc, freq=tables.freq))

    def retired(self, eng, req, res):
        if self.window is None:
            return
        self.results[req.rid] = res
        self._submit(next(self.order))

    # -- the window ------------------------------------------------------------

    def run(self) -> common.RunReport:
        import jax
        self.window = common.LaunchWindow(self.probe, self._seconds)
        self.warm = False
        self.order = generator.closed_order(self.traffic, self.seed)
        for _ in range(self.traffic["clients"]):
            self._submit(next(self.order))
        try:
            self.eng.run(clock="wall")
            raise RuntimeError("the engine drained before the window closed")
        except common.WindowClosed:
            pass
        jax.block_until_ready(self.eng._cache)
        win = self.window
        sym = win.work
        self.probe.counters.update({
            "window.cycles": len(self.cycles),
            "window.symbols": sym,
            "window.seconds": win.elapsed,
            "window.probes": sum(c["probes"] for c in self.cycles),
            "window.model_flops": self._flops(),
        })
        for n in ("engine.build_cycle", "engine.launch", "engine.finalize"):
            self.probe.counters[f"window.{n}_s"] = sum(
                self.probe.window_spans(n))
            self.probe.counters[f"window.{n}_cpu_s"] = sum(
                self.probe.window_spans(n, cpu=True))
        failed = sum(1 for r in self.results.values() if not r.ok)
        name = "compress_sym_s" if self.kind == "compress" \
            else "decompress_sym_s"
        return common.RunReport(
            e2e={name: win.rate()},
            attempted=sum(len(c["spec"]) for c in self.cycles),
            failed=failed)

    def _flops(self) -> float:
        """Model FLOPs of the window's finished symbols: the configuration's
        own ``model_flops(cfg, pos0, n)`` where its module defines one,
        else the dense count of ``costs.model_flops``."""
        count = getattr(self.model, "model_flops", costs.model_flops)
        return float(sum(self.lanes * count(self.cfg, p0, n)
                         for c in self.cycles for p0, n in c["positions"]))

    # -- correct -----------------------------------------------------------------

    def release(self):
        """Free the program's state before the reference runs."""
        import jax
        for k in self.kept:
            k["freq"] = np.asarray(jax.device_get(k["freq"]))
        del self.eng._cache, self.eng._prog, self.eng._prog_prefill
        self.eng.params = None
        gc.collect()

    def check(self, limits: dict) -> list[common.Check]:
        checks = [common.Check(
            "failed_requests",
            sum(1 for r in self.results.values() if not r.ok), 0)]
        S, L = self.chunk, self.lanes
        if self.kind == "decompress":
            bad = 0
            for rid, req in self.eng.reqs.items():
                stream = self.streams[self.item_of[rid]]
                for c, out in enumerate(req.out_syms):
                    want = stream[:, c * S:c * S + out.shape[1]]
                    bad += int((out != want).sum()) + abs(
                        want.size - out.size)
            checks.append(common.Check("decode_mismatch", bad, 0))
        # the compared reference computes the weight products at the
        # precision the program runs them at; a second, at HIGHEST, is
        # printed beside it and not compared, so that a change to that
        # precision shows
        precisions = {"": self.cfg["assumed"]["matmul_precision"],
                      "_highest": "highest"}
        logits = {}
        entries = 0
        differ = dict.fromkeys(precisions, 0)
        byte_bad = 0
        for kept in self.kept:
            for rid, s, c, n_c, last in kept["spec"]:
                item = self.item_of[rid]
                prog = kept["freq"][:n_c, s * L:(s + 1) * L].astype(np.int64)
                for key, precision in precisions.items():
                    if (item, key) not in logits:
                        logits[item, key] = np.asarray(
                            self.model.reference_logits(
                                self.params, self.mcfg, self.streams[item],
                                precision))
                    lg = logits[item, key][:, c * S:c * S + n_c,
                                           :self.mcfg.vocab_size]
                    ref = refcoder.quantize(refcoder.softmax(lg),
                                            self.prob_bits).swapaxes(0, 1)
                    differ[key] += int((prog != ref).sum())
                entries += prog.size
                if self.kind == "compress":
                    byte_bad += self._chunk_bytes_gap(rid, c, n_c, prog)
        self.readings = {"table_mismatch_share_highest":
                         differ["_highest"] / max(entries, 1)}
        checks.append(common.Check(
            "table_mismatch_share", differ[""] / max(entries, 1),
            limits["table_mismatch_share"]))
        if self.kind == "compress":
            checks.append(common.Check("chunk_byte_mismatch", byte_bad, 0))
            checks.append(common.Check(
                "container_mismatch", self._container_gap(), 0))
        return checks

    def _chunk_bytes_gap(self, rid, c, n_c, prog_freq) -> int:
        req = self.eng.reqs[rid]
        enc = req.enc_chunks[c]
        stream = self.streams[self.item_of[rid]]
        ref = refcoder.encode_streams(
            stream[:, c * self.chunk:c * self.chunk + n_c], prog_freq,
            self.prob_bits)
        gap = 0
        for lane, want in enumerate(ref):
            o, n = int(enc.start[lane]), int(enc.length[lane])
            gap += refcoder.byte_gap(enc.buf[lane, o:o + n].tobytes(), want)
        return gap

    def _container_gap(self) -> int:
        gap = 0
        for rid, res in self.results.items():
            if not res.ok or res.blob is None:
                continue
            req = self.eng.reqs[rid]
            cells = [[e.buf[l, int(e.start[l]):int(e.start[l])
                            + int(e.length[l])].tobytes()
                      for l in range(self.lanes)] for e in req.enc_chunks]
            ref = refcoder.pack_v2(cells, lanes=self.lanes,
                                   n_symbols=req.n_symbols,
                                   chunk_size=self.chunk,
                                   prob_bits=self.prob_bits)
            gap += refcoder.byte_gap(res.blob, ref)
        return gap
