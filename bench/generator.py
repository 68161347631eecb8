"""The one traffic generator.  A traffic mix is a data file under
``bench/traffic/`` that this module reads; it holds no code.

Keys of a mix:

``loop``        ``"closed"``: ``clients`` requests outstanding at all times;
                one completes, the next is sent.
``kind``        ``"compress"`` or ``"decompress"``.
``pool``        how many distinct inputs the run makes from its seed; requests
                draw from them in a seeded order.
``image``       ``[height, width, channels]`` of every input: 8-bit images,
                a smooth random field per channel plus noise.
``clients``     requests outstanding.
``why``         one line on what the mix exercises.

Every seed gets the same work: the same number and sizes of inputs, asked
for in a seeded order.
"""

from __future__ import annotations

import itertools

import numpy as np


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


def inputs(traffic: dict, seed: int) -> list[np.ndarray]:
    h, w, c = traffic["image"]
    return [image(h, w, c, seed, i) for i in range(traffic["pool"])]


def closed_order(traffic: dict, seed: int):
    """Endless item indices for a closed loop: a seeded permutation of the
    pool, repeated."""
    perm = _rng(seed, 1 << 20).permutation(traffic["pool"])
    return itertools.cycle(perm.tolist())


def check(traffic: dict) -> None:
    """Refuse a mix that lacks what its loop needs."""
    missing = {"loop", "kind", "pool", "image", "clients"} - set(traffic)
    if missing:
        raise ValueError(f"traffic mix lacks {sorted(missing)}")
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    if traffic["kind"] not in ("compress", "decompress"):
        raise ValueError(f"unknown kind {traffic['kind']!r}")
