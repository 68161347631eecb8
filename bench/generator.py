"""The one traffic generator.  A traffic mix is a data file under
``bench/traffic/`` that this module reads; it holds no code.

Keys of a mix:

``loop``        ``"closed"``: ``clients`` requests outstanding at all times;
                one completes, the next is sent.
``kind``        ``"compress"`` or ``"decompress"``.
``pool``        how many distinct inputs the run makes from its seed; requests
                draw from them in a seeded order.
``clients``     requests outstanding.
``why``         one line on what the mix exercises.

and exactly one input kind:

``image``       ``[height, width, channels]`` of every input: 8-bit images,
                a smooth random field per channel plus noise.
``tokens``      ``{"zipf": s, "length": ..., "source": "..."}``: token ids
                over the configuration's alphabet ``[0, V)``.

``tokens`` keys:

``zipf``        the ids are drawn independently from a Zipf law of exponent
                ``s > 0`` over a seeded permutation of ``[0, V)``: the id of
                rank ``r`` (1-based) comes with probability
                ``r**-s / sum_k k**-s``.
``length``      symbols a request: a positive integer, every request that
                long, or ``{"quantiles": [[q, n], ...]}``, a length law given
                by its quantile function: ``q`` rising from 0 to 1, ``n``
                positive integers, never falling, log-linear in between.
                Input ``i`` of the pool takes the quantile
                ``(i + 0.5) / pool``.
``source``      the public source of the exponent and of the length law: the
                trace or tokenized corpus that the mix stands for, measured
                with the configuration's own tokenizer.  Zipf's law of word
                frequencies (an exponent near 1) is not a measurement of
                subword tokens, and one length hides the batching and the
                padding of requests whose lengths vary.

Ids follow one another independently: with weights random from the seed, the
model has no structure of the text to find, so what the law sets is how the
ids fall among the model's tables.  The system passes ``V`` (its model's
vocabulary) and the multiple that a request's length is rounded up to (its
lanes) to :func:`inputs`; image mixes ignore both.  Every seed gets the same
work: the same number and sizes of inputs, asked for in a seeded order.
"""

from __future__ import annotations

import itertools
import math
import numbers

import numpy as np

KINDS = ("image", "tokens")


def _rng(seed: int, *keys: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *keys])


def image(h: int, w: int, c: int, seed: int, item: int) -> np.ndarray:
    """``(h, w, c)`` uint8: per channel, a separable random walk over rows
    and columns around 128 plus uniform noise in [-4, 4]."""
    planes = []
    for ch in range(c):
        rng = _rng(seed, item, ch)
        rows = np.cumsum(rng.integers(-2, 3, (h, 1)), axis=0)
        cols = np.cumsum(rng.integers(-2, 3, (1, w)), axis=1)
        noise = rng.integers(-4, 5, (h, w))
        planes.append(np.clip(128 + rows + cols + noise, 0, 255))
    return np.stack(planes, axis=-1).astype(np.uint8)


def zipf_probs(alphabet: int, zipf: float) -> np.ndarray:
    """Probability of each rank ``1..alphabet`` under the Zipf law."""
    p = np.arange(1, alphabet + 1, dtype=np.float64) ** -float(zipf)
    return p / p.sum()


def lengths(length, pool: int, multiple: int = 1) -> list[int]:
    """Symbols of each of the pool's inputs under the law ``length`` (see
    the module's docstring), each rounded up to a multiple of ``multiple``:
    the same for every seed."""
    if isinstance(length, dict):
        q, n = np.asarray(length["quantiles"], np.float64).T
        at = (np.arange(pool) + 0.5) / pool
        raw = np.rint(np.exp(np.interp(at, q, np.log(n)))).astype(int)
    else:
        raw = np.full(pool, length)
    return [int(-(-x // multiple) * multiple) for x in raw]


def tokens(length: int, zipf: float, alphabet: int, seed: int,
           item: int) -> np.ndarray:
    """``(length,)`` int32 ids in ``[0, alphabet)``, i.i.d. Zipf over ranks;
    rank ``r`` is the id ``perm[r - 1]`` of one permutation per seed."""
    perm = _rng(seed, 1 << 21).permutation(alphabet)
    cdf = np.cumsum(zipf_probs(alphabet, zipf))
    cdf /= cdf[-1]
    u = _rng(seed, item, 1 << 21).random(length)
    return perm[np.searchsorted(cdf, u, side="right")].astype(np.int32)


def inputs(traffic: dict, seed: int, alphabet: int,
           multiple: int = 1) -> list[np.ndarray]:
    """The pool's inputs: ``(h, w, c)`` uint8 images, or 1-D int32 token ids
    over ``[0, alphabet)``, each a multiple of ``multiple`` long."""
    pool = range(traffic["pool"])
    if "image" in traffic:
        h, w, c = traffic["image"]
        return [image(h, w, c, seed, i) for i in pool]
    t = traffic["tokens"]
    sizes = lengths(t["length"], traffic["pool"], multiple)
    return [tokens(n, t["zipf"], alphabet, seed, i)
            for i, n in zip(pool, sizes)]


def closed_order(traffic: dict, seed: int):
    """Endless item indices for a closed loop: a seeded permutation of the
    pool, repeated."""
    perm = _rng(seed, 1 << 20).permutation(traffic["pool"])
    return itertools.cycle(perm.tolist())


def check(traffic: dict) -> None:
    """Refuse a mix that lacks what its loop needs."""
    missing = {"loop", "kind", "pool", "clients"} - set(traffic)
    if missing:
        raise ValueError(f"traffic mix lacks {sorted(missing)}")
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    if traffic["kind"] not in ("compress", "decompress"):
        raise ValueError(f"unknown kind {traffic['kind']!r}")
    kinds = [k for k in KINDS if k in traffic]
    if len(kinds) != 1:
        raise ValueError(f"traffic mix needs exactly one input kind of "
                         f"{list(KINDS)}, has {kinds}")
    if "tokens" in traffic:
        _check_tokens(traffic["tokens"])


def _positive_int(x) -> bool:
    return isinstance(x, int) and not isinstance(x, bool) and x >= 1


def _check_tokens(t) -> None:
    t = t if isinstance(t, dict) else {}
    zipf, source = t.get("zipf"), t.get("source")
    if isinstance(zipf, bool) or not isinstance(zipf, numbers.Real) \
            or not math.isfinite(zipf) or zipf <= 0:
        raise ValueError(f"tokens.zipf must be a number > 0, not {zipf!r}")
    if not isinstance(source, str) or not source.strip() or "\n" in source:
        raise ValueError(f"tokens.source must name the public source of the "
                         f"exponent and the length law, not {source!r}")
    length = t.get("length")
    if isinstance(length, dict):
        table = length.get("quantiles")
        ok = (isinstance(table, list) and len(table) >= 2
              and all(isinstance(r, list) and len(r) == 2 for r in table))
        if ok:
            q = [r[0] for r in table]
            n = [r[1] for r in table]
            ok = (all(isinstance(x, numbers.Real) and not isinstance(x, bool)
                      for x in q)
                  and q[0] == 0 and q[-1] == 1
                  and all(a < b for a, b in zip(q, q[1:]))
                  and all(_positive_int(x) for x in n)
                  and all(a <= b for a, b in zip(n, n[1:])))
        if not ok:
            raise ValueError(
                f"tokens.length.quantiles must be [[q, n], ...] with q rising "
                f"from 0 to 1 and n positive integers that never fall, not "
                f"{table!r}")
    elif not _positive_int(length):
        raise ValueError(f"tokens.length must be a positive integer or "
                         f"{{'quantiles': ...}}, not {length!r}")
